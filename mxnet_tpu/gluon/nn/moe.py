"""Mixture-of-Experts layer: dropless top-k routing over the experts HELD
HERE, as one grouped matrix product.

The reference has no MoE (SURVEY §2.3 EP row: "Absent") — this is a
new-capability component designed TPU-first.  The layer is told which
experts it holds (``first .. first + count`` of ``num_experts``): it routes
every position over ALL ``num_experts`` (router product and softmax in
float32, renormalised top-k), sorts the assignments that picked a held
expert into expert order, runs the held experts as ``jax.lax.ragged_dot``
grouped products, and adds each position's weighted results back.  What the
absent experts would have added is left out: with ``count == num_experts``
(the default) that is the whole layer; with fewer it is one chip's share
under expert parallelism, and the shares of all the chips add up to the
whole layer (pinned by tests/chipbench/test_sdar_chipbench.py).

**No assignment is ever dropped.**  Shapes are static, so the sorted buffer
has ``positions x min(top_k, count)`` rows: a position picks ``top_k``
distinct experts, so at most ``min(top_k, count)`` held ones, and that is
the only bound that holds for every routing.  Rows past the last real one
belong to no group: the grouped product skips them (XLA's TPU lowering
walks the tiles the groups fill) and nothing reads them.

Every movement of rows is a GATHER, forward and backward (the sort is a
permutation, so the transpose of "gather rows into expert order" is "gather
them back"), written as two ``custom_vjp`` functions: autodiff alone would
emit scatter-adds, which a TPU runs row by row.

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


def route(x, gate, top_k, first, count, norm_topk=True):
    """Route ``x`` (N, d) over all the router's experts; lay out the
    assignments that picked a held expert in expert order.

    Returns a dict: ``weights`` (N, k) float32 (renormalised over the top-k
    when ``norm_topk``), ``held`` (N, k) bool, ``pos`` (N, k) the row of each
    assignment in the sorted buffer (meaningful where ``held``),
    ``row_token`` / ``row_slot`` (R,) the position and top-k slot a row came
    from, ``group_sizes`` (count,) rows per held expert, ``rows`` their sum;
    ``R = N * min(k, count)``."""
    n, k = x.shape[0], top_k
    logits = jnp.einsum("td,ed->te", x, gate,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    weights = top_p / jnp.sum(top_p, -1, keepdims=True) if norm_topk \
        else top_p
    local = top_e - first
    held = (local >= 0) & (local < count)
    # absent experts sort last, under the sentinel `count`
    key = jnp.where(held, local, count).astype(jnp.int32).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    r = n * min(k, count)
    rank = jnp.zeros((n * k,), jnp.int32).at[order].set(
        jnp.arange(n * k, dtype=jnp.int32), unique_indices=True)
    group_sizes = jnp.sum(
        key[:, None] == jnp.arange(count, dtype=jnp.int32)[None, :],
        axis=0, dtype=jnp.int32)
    rows = jnp.sum(group_sizes)
    order = order[:r]
    return {"weights": weights, "held": held, "pos": rank.reshape(n, k),
            "row_token": order // k, "row_slot": order % k,
            "row_expert": jnp.minimum(key[order], count - 1),
            "group_sizes": group_sizes, "rows": rows}


def _picked(y, pos, held):
    """(N, k, d) float32: each held slot's row of ``y``, 0 elsewhere."""
    return jnp.where(held[..., None], y[pos].astype(jnp.float32), 0)


@jax.custom_vjp
def _dispatch(x, row_token, pos, held):
    """Rows of ``x`` in expert order: ``xs[r] = x[row_token[r]]``."""
    return x[row_token]


def _dispatch_fwd(x, row_token, pos, held):
    return x[row_token], (pos, held)


def _dispatch_bwd(res, dxs):
    pos, held = res
    with jax.named_scope("mx.moe.route"):
        return jnp.sum(_picked(dxs, pos, held), axis=1).astype(dxs.dtype), \
            None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y, weights, pos, held, row_token, row_slot, rows):
    """``out[t] = sum_s weights[t, s] * y[pos[t, s]]`` over the held slots."""
    return jnp.sum(_picked(y, pos, held) * weights[..., None],
                   axis=1).astype(y.dtype)


def _combine_fwd(*args):
    return _combine.fun(*args), args


def _combine_bwd(res, dout):
    y, weights, pos, held, row_token, row_slot, rows = res
    with jax.named_scope("mx.moe.route"):
        real = jnp.arange(y.shape[0], dtype=jnp.int32) < rows
        row_w = weights[row_token, row_slot]
        dy = jnp.where(real[:, None],
                       dout[row_token].astype(jnp.float32) * row_w[:, None],
                       0).astype(y.dtype)
        dw = jnp.sum(_picked(y, pos, held)
                    * dout.astype(jnp.float32)[:, None, :], axis=-1)
        return dy, dw.astype(weights.dtype), None, None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def moe_forward(x, gate, w1, w2, wg=None, b1=None, b2=None, *, top_k, first,
                activation="relu", norm_topk=True):
    """The layer as a pure function of (N, d) positions; see the module's
    docstring.  ``w1`` (count, d, hidden), ``wg`` the gate projection of a
    gated expert (``act(x wg) * (x w1)``), ``w2`` (count, hidden, units)."""
    count = w1.shape[0]
    act = _ACTIVATIONS[activation]
    with jax.named_scope("mx.moe.route"):
        r = route(x, gate, top_k, first, count, norm_topk)
        xs = _dispatch(x, r["row_token"], r["pos"], r["held"])
    with jax.named_scope("mx.moe.experts"):
        gs = r["group_sizes"]
        h = jax.lax.ragged_dot(xs, w1, gs)
        if b1 is not None:
            h = h + b1[r["row_expert"]]
        h = act(jax.lax.ragged_dot(xs, wg, gs)) * h if wg is not None \
            else act(h)
        y = jax.lax.ragged_dot(h, w2, gs)
        if b2 is not None:
            y = y + b2[r["row_expert"]]
    with jax.named_scope("mx.moe.route"):
        return _combine(y, r["weights"].astype(jnp.float32), r["pos"],
                        r["held"], r["row_token"], r["row_slot"], r["rows"])


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
        Renormalise the top-k probabilities to sum to 1.
    """

    def __init__(self, num_experts, hidden_size, units, top_k=2,
                 in_units=0, activation="relu", gated=False, use_bias=True,
                 first=0, count=None, norm_topk=True, **kwargs):
        super().__init__()
        if top_k < 1 or top_k > num_experts:
            raise MXNetError("top_k must be in [1, num_experts]")
        count = num_experts if count is None else int(count)
        if count < 1 or first < 0 or first + count > num_experts:
            raise MXNetError("experts %d..%d are not among %d"
                             % (first, first + count, num_experts))
        if activation not in _ACTIVATIONS:
            raise MXNetError("unknown MoE activation %r" % (activation,))
        self._E = int(num_experts)
        self._hidden = int(hidden_size)
        self._units = int(units)
        self._k = int(top_k)
        self._act = activation
        self._first, self._count = int(first), count
        self._gated, self._norm_topk = bool(gated), bool(norm_topk)
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

    def _activation(self, jnp_, h):  # parallel.moe_apply's hook
        return _ACTIVATIONS[self._act](h)

    def buffer_rows(self, positions):
        """Rows of the sorted buffer: the dropless bound."""
        return positions * min(self._k, self._count)

    def _layout(self, positions):
        if positions not in self._laid_out:
            self._laid_out.add(positions)
            _trace.instant("mx.moe.layout", args={
                "experts": self._E, "held": self._count,
                "first": self._first, "top_k": self._k,
                "buffer_rows": self.buffer_rows(positions)})

    def forward(self, x):
        from ...ops.registry import apply_op

        lead = x.shape[:-1]
        if x.ndim != 2:
            x = x.reshape((-1, x.shape[-1]))
        self._layout(x.shape[0])
        # the optional weights this layer has, by moe_forward's keyword
        extra = {n: getattr(self, n).data() for n in ("wg", "b1", "b2")
                 if getattr(self, n) is not None}
        fn = functools.partial(moe_forward, top_k=self._k, first=self._first,
                               activation=self._act,
                               norm_topk=self._norm_topk)

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
        Feeds the gauge ``moe_expert_rows{expert}``."""
        from ...ndarray.ndarray import NDArray

        xv = x._data if isinstance(x, NDArray) else jnp.asarray(x)
        xv = xv.reshape(-1, xv.shape[-1])
        sizes = route(xv, self.gate.data()._data, self._k, self._first,
                      self._count, self._norm_topk)["group_sizes"]
        sizes = [int(s) for s in jax.device_get(sizes)]
        if _tel.ENABLED:
            g = _tel.gauge("moe_expert_rows",
                           "rows routed to a held expert by MoE.load",
                           ("expert",))
            for i, s in enumerate(sizes):
                g.labels(expert=str(self._first + i)).set(s)
        return sizes

    def __repr__(self):
        return "MoE(experts=%d, held=%d..%d, hidden=%d, units=%d, top_k=%d)" \
            % (self._E, self._first, self._first + self._count, self._hidden,
               self._units, self._k)
