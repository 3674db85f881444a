"""Mixture-of-Experts layer: dropless top-k routing over the experts HELD
HERE, as one grouped matrix product.

The reference has no MoE (SURVEY §2.3 EP row: "Absent") — this is a
new-capability component designed TPU-first.  The layer is told which
experts it holds (``first .. first + count`` of ``num_experts``): it routes
every position over ALL ``num_experts`` (router product and scores in
float32: a softmax over the experts, or a sigmoid an expert as DeepSeek-V3
has it; top-k, optionally chosen under a SELECTION bias that never enters
a weight, renormalised, times a routing scale), sorts the assignments
that picked a held
expert into expert order, runs the held experts as ``jax.lax.ragged_dot``
grouped products, and adds each position's weighted results back.  What the
absent experts would have added is left out: with ``count == num_experts``
(the default) that is the whole layer; with fewer it is one chip's share
under expert parallelism, and the shares of all the chips add up to the
whole layer (pinned by tests/chipbench/test_sdar_chipbench.py).  A SHARED
expert (``shared_hidden``) is one more gated expert that every position
passes through, unrouted and unweighted, under the scope ``mx.moe.shared``:
every share holds it whole, so the shares add up to the whole layer with
the shared expert counted once.

**No assignment is ever dropped.**  Shapes are static, so the sorted buffer
has ``positions x min(top_k, count)`` rows: a position picks ``top_k``
distinct experts, so at most ``min(top_k, count)`` held ones, and that is
the only bound that holds for every routing.  Rows past the last real one
belong to no group: the grouped product skips them (XLA's TPU lowering
walks the tiles the groups fill) and nothing reads them.

**The buffer has the bound's rows; moving rows costs by the real ones.**
``rows``, the count the router produced, is known only on the device, so a
movement of rows into a buffer is a loop over fixed-size chunks of the
sorted assignment list whose trip count is ``ceil(rows / chunk)``
(``_rows_of``; the chunk is a rule of the static shape, ``chunk_rows``): a
turn gathers one chunk of rows into its place.  The chunks past the last
real row are never written, so they hold whatever the memory held.  What
reads a buffer is a grouped product, which walks the real rows only, or
selects by ``real``: nothing MULTIPLIES an unwritten row by zero to be rid
of it (0 x NaN is NaN), so the expert biases and the weights past the last
real row come and go through ``jnp.where``.  Nothing else passes over the
unbiased layer's ``R x d`` (an elementwise pass costs twice what gathering
all of it does): nothing selects, scales or converts there.  Rows go into
expert order by such a loop (``_to_rows``).  Its transpose, adding buffer
rows into their positions (``_to_positions``), moves the real rows once
more, into POSITION order, where the rows of a tile of 128 positions are
one stretch, and sums them by a grouped product
(``jax.lax.ragged_dot_general``, the ragged dimension contracted) with each
row's one-hot place in its tile, accumulated in float32: no
``(positions, top_k, d)`` float32 intermediate is gathered and summed over
the slots.  Each is the other's backward, as two ``custom_vjp`` functions
whose rules nobody differentiates again.  No scatter of rows and no gather
or scatter of single elements anywhere: a TPU runs those one by one.

**Which movement loops is a rule of the static shape** (``loops``): a turn
of the loop moves a row at a seventh of a plain gather's rate, so the loop
is taken where the share of the buffer a balanced router fills,
``top_k x count / num_experts`` of a position's ``min(top_k, count)`` rows,
is under the measured crossover of that movement, and one plain gather of
the whole buffer (the form before PR 31) elsewhere.  A layer that holds all
its experts has ``rows == R`` whatever the routing and never loops.

**Rounding.**  The routing weight multiplies the experts' HIDDEN rows, in
float32 inside the fusion that makes them (the second product is linear),
and the product is rounded to the rows' dtype before the second grouped
product; before PR 31 the weight multiplied that product's results in
float32 on their way into the float32 sum over a position's slots.  In
bf16 that is one rounding more on the way to the output, and the weights'
gradient is a reduction over ``hidden`` rounded ``dh`` instead of
``<y, dout>`` in float32 (bounded against the older form by
tests/python/unittest/test_moe_dropless.py at the widths of the benchmark's
cell).  The weights reach the buffer's order and their gradients leave it
as the payload of a sort.

``parallel.moe_apply`` (capacity-limited ``all_to_all`` dispatch over an
``ep`` mesh axis) takes the ungated, biased form of this block with all
experts held; with capacity to spare it agrees with ``forward`` exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ... import telemetry as _tel, trace as _trace
from ...base import MXNetError
from ..block import HybridBlock
from ..parameter import Parameter

__all__ = ["MoE"]

_ACTIVATIONS = {"relu": jax.nn.relu, "gelu": jax.nn.gelu,
                "silu": jax.nn.silu}


# positions a tile of `_sum_tiles`' grouped product covers: the MXU's width
_TILE = 128


_SCORES = {"softmax": functools.partial(jax.nn.softmax, axis=-1),
           "sigmoid": jax.nn.sigmoid}


def route(x, gate, top_k, first, count, norm_topk=True, score="softmax",
          scale=1.0, bias=None):
    """Route ``x`` (N, d) over all the router's experts; lay out the
    assignments that picked a held expert in expert order.

    ``score``: how the router's float32 logits become scores, ``softmax``
    over the experts or ``sigmoid`` an expert; the ``top_k`` largest are a
    position's experts.  ``bias`` (experts,): a SELECTION bias
    (DeepSeek-V3's ``noaux_tc``): the experts are the ``top_k`` largest of
    ``score + bias``, and a chosen expert's weight is made from its score
    alone.  Returns ``layout``'s dict and ``weights`` (N, k) float32: the
    chosen scores, renormalised over the top-k when ``norm_topk``, times
    ``scale``."""
    logits = jnp.einsum("td,ed->te", x, gate,
                        preferred_element_type=jnp.float32)
    probs = _SCORES[score](logits.astype(jnp.float32))
    if bias is None:
        top_p, top_e = jax.lax.top_k(probs, top_k)
    else:
        _, top_e = jax.lax.top_k(probs + bias.astype(jnp.float32), top_k)
        # the chosen experts' own scores, by a select over the experts: no
        # gather of single elements, forward or backward
        chosen = top_e[..., None] == jnp.arange(probs.shape[-1],
                                                dtype=top_e.dtype)
        top_p = jnp.sum(jnp.where(chosen, probs[:, None, :], 0), axis=-1)
    weights = top_p / jnp.sum(top_p, -1, keepdims=True) if norm_topk \
        else top_p
    if scale != 1.0:
        weights = weights * scale
    return dict(layout(top_e, weights, first, count), weights=weights)


@jax.custom_vjp
def _by_expert(key, weights):
    """``key`` (N * k,) sorted (stable), each entry's index and weight
    riding the sort: (sorted key, order, weights in that order)."""
    return jax.lax.sort(
        (key, jnp.arange(key.shape[0], dtype=jnp.int32), weights),
        num_keys=1, is_stable=True)


def _by_expert_fwd(key, weights):
    out = _by_expert.fun(key, weights)
    return out, out[1]


def _by_expert_bwd(order, cts):
    # `order` is a permutation: sorting by it undoes the expert order
    with jax.named_scope("mx.moe.route"):
        return None, jax.lax.sort((order, cts[2]), num_keys=1)[1]


_by_expert.defvjp(_by_expert_fwd, _by_expert_bwd)


def layout(top_e, weights, first, count):
    """The sorted buffer of the assignments ``top_e`` (N, k), a position's
    distinct experts, with routing weights ``weights`` (N, k): ``held``
    (N, k) bool; ``order`` (N * k,) the assignments (``position * k +
    slot``) in expert order, those of absent experts last; ``row_token`` /
    ``row_slot`` (R,) the position and top-k slot a row of the buffer came
    from, ``real`` (R,) whether an assignment fills the row and ``row_w``
    (R,) float32 its weight (0 where none does);
    ``group_sizes`` (count,) rows per held expert, ``rows`` their sum;
    ``R = N * min(k, count)``.  The real rows again in POSITION order (a
    position's rows adjacent): ``tm_row`` (R,) the buffer row (row 0 past
    the last real one: whatever is gathered by it is a real row) and
    ``tm_flat`` (R,) its ``position * k + slot`` (``N * k`` past it).
    What is reordered rides the two sorts as a payload: no gather or
    scatter of single elements, forward or backward."""
    n, k = top_e.shape
    local = top_e - first
    held = (local >= 0) & (local < count)
    # absent experts sort last, under the sentinel `count`
    key, order, w = _by_expert(
        jnp.where(held, local, count).astype(jnp.int32).reshape(-1),
        weights.astype(jnp.float32).reshape(-1))
    r = n * min(k, count)
    group_sizes = jnp.sum(
        key[:, None] == jnp.arange(count, dtype=jnp.int32)[None, :],
        axis=0, dtype=jnp.int32)
    rows = jnp.sum(group_sizes)
    at = jnp.arange(r, dtype=jnp.int32)
    real = at < rows
    tm_flat, tm_row = jax.lax.sort(
        (jnp.where(real, order[:r], n * k), at), num_keys=1)
    return {"held": held, "order": order, "row_token": order[:r] // k,
            "row_slot": order[:r] % k, "real": real,
            "row_w": jnp.where(real, w[:r], 0),
            "row_expert": jnp.minimum(key[:r], count - 1),
            "group_sizes": group_sizes, "rows": rows,
            "tm_row": jnp.where(real, tm_row, 0), "tm_flat": tm_flat}


def chunk_rows(r):
    """Rows one turn of ``_rows_of`` moves into a buffer of ``r`` rows.
    A turn costs by its rows whatever the chunk (4,096, 8,192 and 16,384
    read the same 45 ns a 4 KB row alone on the chip, 23 in the step), so
    the chunk only sets what a movement wastes, half a chunk on average,
    against the number of turns: at 8,192 two or three turns of 0.2 ms
    move the SDAR cell's 14-24 thousand real rows where one gather over
    the buffer's 131,072 takes 1.2-2.2 ms (benchmark/moe_bench.py rows;
    PERF.md section 6, PR 31).

    The chunk is 3/32 of the buffer, at most 8,192 rows.  The loops are
    taken at balanced fills under 0.14 (``loops``), in the benchmark's
    cells an eighth (16 of 128, 32 of 256 experts held at top-8): 4/32 of
    the buffer, so the balanced expectation lies in the middle of the
    second chunk and a routing that sends a quarter fewer rows or half as
    many more takes the same two turns.  With the expectation ON a chunk's
    boundary the trip count flips with the routing's noise and a step's
    time with it: a buffer of 65,536 rows in chunks of 8,192 (8,192 rows
    expected) spread the Laguna cell's median step by 0.84% across three
    seeds, in chunks of 6,144 by 0.12% (PERF.md section 6, PR 32).  The
    cap keeps the chunk that PR 31 measured at 131,072 rows.  A multiple
    of the grouped products' tile of rows (512).  A buffer of up to 8,192
    rows is one chunk."""
    if r <= 8192:
        return r
    return min(8192, 3 * r // 32 // 512 * 512)


# The share of a buffer's rows below which `_rows_of`'s loop over the real
# chunks beats one plain gather of all of them, by where the rows come from
# (benchmark/moe_bench.py rows, alone on the chip at R = 131,072 rows of 2,048
# bf16; PERF.md section 6, PR 31).  From the POSITIONS (x, dout: 64 MB, which
# XLA prefetches) a plain gather moves a row in 6.3 ns, a turn of the loop in
# 45: the loop wins under 18 thousand rows (inside the benchmark's step a
# plain gather takes 9.5 ns a row and a turn 23, so it goes on winning up to
# 0.4; between the two the form before PR 31 stays).  From the BUFFER itself
# (512 MB) a plain gather and the grouped product after it take 6.1-6.6 ms,
# the loop 0.75 + 0.052 a thousand rows: it wins under 100 thousand.
_LOOPS_BELOW = {"positions": 0.14, "buffer": 0.75}


def loops(top_k, count, experts):
    """Which movements of a layer that holds ``count`` of ``experts`` go as
    loops over the real chunks: {"positions": bool, "buffer": bool}, from
    the share of its buffer a balanced router fills."""
    fill = top_k * count / (experts * min(top_k, count))
    return {src: fill < below for src, below in _LOOPS_BELOW.items()}


# a buffer nobody has written (the tests make it NaN: off the TPU
# `lax.empty` gives zeros, which would hide a read of an unwritten row)
_blank = jax.lax.empty


def _rows_of(src, idx, rows):
    """(R, d) whose row ``r`` is ``src[idx[r]]`` in every chunk that holds
    one of the first ``rows`` rows; the chunks past them are NOT WRITTEN,
    and nothing may read them (the grouped products walk the tiles their
    groups fill; whatever else meets the buffer selects by ``real``).  The trip count follows ``rows``: moving rows costs by
    the real ones, not by the bound.  Where the chunk does not divide R
    the last chunk overlaps the one before it and writes the same rows
    again.  ``rows`` None: one gather moves the buffer's every row (see
    ``loops``)."""
    if rows is None:
        return src[idx]
    r = idx.shape[0]
    chunk = chunk_rows(r)

    def turn(i, buf):
        start = jnp.minimum(i * chunk, r - chunk)
        return jax.lax.dynamic_update_slice(
            buf, src[jax.lax.dynamic_slice(idx, (start,), (chunk,))],
            (start, 0))

    return jax.lax.fori_loop(0, (rows + chunk - 1) // chunk, turn,
                             _blank((r, src.shape[1]), src.dtype))


def _sum_tiles(tm, held, tm_flat):
    """``out[t] = sum of the rows of tm that belong to position t`` (N, d),
    accumulated in float32; ``tm`` holds the real rows in position order,
    where the rows of a tile of ``_TILE`` positions are one stretch: a
    grouped product with each row's one-hot place in its tile, one group a
    tile, which walks the real rows only (the rest of ``tm`` may be
    unwritten)."""
    n, k = held.shape
    tiles = -(-n // _TILE)
    sizes = jnp.sum(jnp.pad(jnp.sum(held, axis=1, dtype=jnp.int32),
                            (0, tiles * _TILE - n)).reshape(tiles, _TILE),
                    axis=1)
    place = ((tm_flat // k) % _TILE)[:, None] \
        == jnp.arange(_TILE, dtype=jnp.int32)[None, :]
    out = jax.lax.ragged_dot_general(
        place.astype(tm.dtype), tm, sizes,
        jax.lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(((0,), (0,)), ((), ())),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[]),
        # float32 rows stay float32 on the MXU (bf16 rows are exact there)
        precision=jax.lax.Precision.HIGHEST
        if tm.dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32)
    return out.reshape(tiles * _TILE, -1)[:n].astype(tm.dtype)


@jax.custom_vjp
def _to_rows(x, rows_p, rows_b, row_token, held, tm_row, tm_flat):
    """Rows of ``x`` in expert order: ``xs[r] = x[row_token[r]]``.
    ``rows_p`` / ``rows_b``: ``rows`` where the gathers from the positions
    / from the buffer go as loops, else None."""
    return _rows_of(x, row_token, rows_p)


def _to_rows_fwd(x, rows_p, rows_b, row_token, held, tm_row, tm_flat):
    return _rows_of(x, row_token, rows_p), (rows_b, held, tm_row, tm_flat)


def _to_rows_bwd(res, dxs):
    rows_b, held, tm_row, tm_flat = res
    with jax.named_scope("mx.moe.route"):
        return (_sum_tiles(_rows_of(dxs, tm_row, rows_b), held, tm_flat),) \
            + (None,) * 6


_to_rows.defvjp(_to_rows_fwd, _to_rows_bwd)


@jax.custom_vjp
def _to_positions(y, rows_p, rows_b, row_token, held, tm_row, tm_flat):
    """The transpose of ``_to_rows``: ``out[t] = sum of the real rows of y
    that came from position t``."""
    return _sum_tiles(_rows_of(y, tm_row, rows_b), held, tm_flat)


def _to_positions_fwd(y, rows_p, rows_b, row_token, held, tm_row, tm_flat):
    return _sum_tiles(_rows_of(y, tm_row, rows_b), held, tm_flat), \
        (rows_p, row_token)


def _to_positions_bwd(res, dout):
    rows_p, row_token = res
    with jax.named_scope("mx.moe.route"):
        return (_rows_of(dout, row_token, rows_p),) + (None,) * 6


_to_positions.defvjp(_to_positions_fwd, _to_positions_bwd)


def moe_forward(x, gate, w1, w2, wg=None, b1=None, b2=None, shared_w1=None,
                shared_wg=None, shared_w2=None, *, top_k, first,
                select_bias=None, activation="relu", norm_topk=True,
                score="softmax", scale=1.0):
    """The layer as a pure function of (N, d) positions; see the module's
    docstring.  ``w1`` (count, d, hidden), ``wg`` the gate projection of a
    gated expert (``act(x wg) * (x w1)``), ``w2`` (count, hidden, units);
    ``shared_*`` the shared expert's three, without the leading dim;
    ``select_bias`` the router's selection bias (``route``)."""
    count = w1.shape[0]
    act = _ACTIVATIONS[activation]
    with jax.named_scope("mx.moe.route"):
        r = route(x, gate, top_k, first, count, norm_topk, score, scale,
                  select_bias)
        loop = loops(top_k, count, gate.shape[0])
        moves = (r["rows"] if loop["positions"] else None,
                 r["rows"] if loop["buffer"] else None,
                 r["row_token"], r["held"], r["tm_row"], r["tm_flat"])
        xs = _to_rows(x, *moves)
    with jax.named_scope("mx.moe.experts"):
        gs, w = r["group_sizes"], r["row_w"][:, None]

        def bias(b):
            # by a select, forward and backward: past the last real row a
            # buffer and its gradient hold anything, NaN too
            return jnp.where(r["real"][:, None], b[r["row_expert"]], 0)

        h = jax.lax.ragged_dot(xs, w1, gs)
        if b1 is not None:
            h = h + bias(b1)
        h = act(jax.lax.ragged_dot(xs, wg, gs)) * h if wg is not None \
            else act(h)
        # the routing weight goes onto the hidden rows, in float32 inside
        # the fusion that makes them (the second product is linear), not
        # onto its wider results: no pass over the buffer for it
        h = (h.astype(jnp.float32) * w).astype(h.dtype)
        y = jax.lax.ragged_dot(h, w2, gs)
        if b2 is not None:
            y = y + (w * bias(b2)).astype(y.dtype)
    with jax.named_scope("mx.moe.route"):
        out = _to_positions(y, *moves)
    if shared_w1 is None:
        return out
    with jax.named_scope("mx.moe.shared"):
        return out + (act(x @ shared_wg) * (x @ shared_w1)) @ shared_w2


class MoE(HybridBlock):
    """Top-k routed mixture of FFN experts, dropless.

    Parameters
    ----------
    num_experts : int
        Experts E the router chooses among (its width).
    hidden_size : int
        Expert FFN hidden width.
    units : int
        Output width (and input width unless ``in_units`` given).
    top_k : int
        Experts per position.
    activation : str
        'relu' / 'gelu' / 'silu'.
    gated : bool
        Experts are ``w2(act(wg x) * (w1 x))`` instead of ``w2(act(w1 x))``.
    use_bias : bool
        Expert biases ``b1``/``b2`` (never with ``gated``-style models).
    first, count : int
        This block holds experts ``first .. first + count`` of the E
        (default: all of them).  It still routes over all E and computes
        the part of the result its own experts give.
    norm_topk : bool
        Renormalise the top-k scores to sum to 1.
    score : str
        'softmax' over the experts or 'sigmoid' an expert (``route``).
    scale : float
        Factor on the routing weights, after the renormalisation.
    shared_hidden : int
        Hidden width of a shared gated expert that every position passes
        through, added unweighted (0: none; needs ``gated``).
    select_bias : bool
        Hold a selection bias ``select_bias`` (num_experts,): the experts
        are chosen by ``score + bias``, the weights made from the scores
        alone (``route``).  No gradient reaches it (``grad_req="null"``):
        whoever balances the load sets it between steps.
    """

    def __init__(self, num_experts, hidden_size, units, top_k=2,
                 in_units=0, activation="relu", gated=False, use_bias=True,
                 first=0, count=None, norm_topk=True, score="softmax",
                 scale=1.0, shared_hidden=0, select_bias=False, **kwargs):
        super().__init__()
        if top_k < 1 or top_k > num_experts:
            raise MXNetError("top_k must be in [1, num_experts]")
        count = num_experts if count is None else int(count)
        if count < 1 or first < 0 or first + count > num_experts:
            raise MXNetError("experts %d..%d are not among %d"
                             % (first, first + count, num_experts))
        if activation not in _ACTIVATIONS:
            raise MXNetError("unknown MoE activation %r" % (activation,))
        if score not in _SCORES:
            raise MXNetError("unknown MoE router score %r" % (score,))
        if shared_hidden and not gated:
            raise MXNetError("a shared expert is a gated expert")
        self._E = int(num_experts)
        self._hidden = int(hidden_size)
        self._units = int(units)
        self._k = int(top_k)
        self._act = activation
        self._first, self._count = int(first), count
        self._gated, self._norm_topk = bool(gated), bool(norm_topk)
        self._score, self._scale = score, float(scale)
        in_units = int(in_units) or int(units)
        self._in_units = in_units
        self._laid_out = set()
        # held experts stacked on a leading dim sharded over 'ep'
        self.w1 = Parameter("w1", shape=(count, in_units, hidden_size),
                            sharding=("ep", None, None))
        self.w2 = Parameter("w2", shape=(count, hidden_size, units),
                            sharding=("ep", None, None))
        self.wg = Parameter("wg", shape=(count, in_units, hidden_size),
                            sharding=("ep", None, None)) if gated else None
        self.b1 = Parameter("b1", shape=(count, hidden_size), init="zeros",
                            sharding=("ep", None)) if use_bias else None
        self.b2 = Parameter("b2", shape=(count, units), init="zeros",
                            sharding=("ep", None)) if use_bias else None
        self.gate = Parameter("gate", shape=(self._E, in_units))
        shared = int(shared_hidden)
        self.shared_w1 = Parameter("shared_w1", shape=(in_units, shared)) \
            if shared else None
        self.shared_wg = Parameter("shared_wg", shape=(in_units, shared)) \
            if shared else None
        self.shared_w2 = Parameter("shared_w2", shape=(shared, units)) \
            if shared else None
        self.select_bias = Parameter(
            "select_bias", shape=(self._E,), init="zeros",
            grad_req="null") if select_bias else None

    def _activation(self, jnp_, h):  # parallel.moe_apply's hook
        return _ACTIVATIONS[self._act](h)

    def buffer_rows(self, positions):
        """Rows of the sorted buffer: the dropless bound."""
        return positions * min(self._k, self._count)

    def _layout(self, positions):
        if positions not in self._laid_out:
            self._laid_out.add(positions)
            r = self.buffer_rows(positions)
            _trace.instant("mx.moe.layout", args={
                "experts": self._E, "held": self._count,
                "first": self._first, "top_k": self._k, "buffer_rows": r,
                "chunk_rows": chunk_rows(r),
                "chunks": -(-r // chunk_rows(r)), "score": self._score,
                "scale": self._scale,
                "shared": 0 if self.shared_w1 is None
                else self.shared_w1.shape[1],
                "bias": self.select_bias is not None})

    def forward(self, x):
        from ...ops.registry import apply_op

        lead = x.shape[:-1]
        if x.ndim != 2:
            x = x.reshape((-1, x.shape[-1]))
        self._layout(x.shape[0])
        # the optional weights this layer has, by moe_forward's keyword
        extra = {n: getattr(self, n).data()
                 for n in ("wg", "b1", "b2", "shared_w1", "shared_wg",
                           "shared_w2", "select_bias")
                 if getattr(self, n) is not None}
        fn = functools.partial(moe_forward, top_k=self._k, first=self._first,
                               activation=self._act,
                               norm_topk=self._norm_topk, score=self._score,
                               scale=self._scale)

        def moe(x_, gate_, w1_, w2_, *rest):
            return fn(x_, gate_, w1_, w2_, **dict(zip(extra, rest)))

        out = apply_op(moe, x, self.gate.data(), self.w1.data(),
                       self.w2.data(), *extra.values())
        if lead != out.shape[:-1]:
            out = out.reshape(lead + (out.shape[-1],))
        return out

    def load(self, x):
        """Rows each held expert would get from ``x``, eagerly, as a list
        of ints (diagnosis and tests; the step program never calls it).
        Feeds the gauges ``moe_expert_rows{expert}`` and
        ``moe_rows_walked_share``: the share of the sorted buffer that the
        chunk loops walk for this ``x`` (1.0: every chunk)."""
        from ...ndarray.ndarray import NDArray

        xv = x._data if isinstance(x, NDArray) else jnp.asarray(x)
        xv = xv.reshape(-1, xv.shape[-1])
        sizes = route(xv, self.gate.data()._data, self._k, self._first,
                      self._count, self._norm_topk, self._score,
                      self._scale,
                      None if self.select_bias is None
                      else self.select_bias.data()._data)["group_sizes"]
        sizes = [int(s) for s in jax.device_get(sizes)]
        if _tel.ENABLED:
            g = _tel.gauge("moe_expert_rows",
                           "rows routed to a held expert by MoE.load",
                           ("expert",))
            for i, s in enumerate(sizes):
                g.labels(expert=str(self._first + i)).set(s)
            r = self.buffer_rows(xv.shape[0])
            chunk = chunk_rows(r)
            _tel.gauge("moe_rows_walked_share",
                       "share of the sorted buffer's rows the chunk loops "
                       "walk, by MoE.load").set(
                min(r, -(-sum(sizes) // chunk) * chunk) / r)
        return sizes

    def __repr__(self):
        return "MoE(experts=%d, held=%d..%d, hidden=%d, units=%d, top_k=%d)" \
            % (self._E, self._first, self._first + self._count, self._hidden,
               self._units, self._k)
