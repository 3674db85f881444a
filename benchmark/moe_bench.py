"""Expert-layer microbenchmark: the movements of rows between positions and
the sorted buffer of ``gluon.nn.MoE``, alone on the chip.

    python benchmark/moe_bench.py rows [--rows N ...] [--chunks C ...]
        the four movements of a layer at the shape the cell
        ``sdar_30b_a3b_bd4k`` sends them (16,384 positions, top-8, 2,048
        wide bf16, 16 of 128 experts held: a buffer of 131,072 rows), each
        as ONE program whose device time is read from a profiler trace by
        the program's name:
          dispatch       rows of x into expert order
          combine        weighted buffer rows added into their positions
          combine_bwd    dy and dweights from dout
          dispatch_bwd   buffer rows added into their positions
        in these forms:
          layer     what ``gluon/nn/moe.py`` does: a loop over chunks of
                    the real rows that leaves the rest of the buffer
                    unwritten; the slot side by a grouped product in position
                    order.  The routing weight is not in these programs: it
                    multiplies the experts' hidden rows, so ``combine``
                    takes rows that hold it already (``chunked`` too) and
                    ``combine_bwd`` is the gather of ``dout`` alone
          whole     the layer before PR 31: the slot side a gather of
                    N x k rows in float32 and a sum over the k slots
          gather    the layer when it holds every expert (every row is
                    real): one plain gather a movement
          poisoned  ``layer`` with its buffers starting as NaN: agrees only
                    if nothing reads what the loops do not write
        (the forms PR 31 measured and did not take, zeros past the real
        rows and a row scatter-add for the slot side, are in PERF.md
        section 6, PR 31, with their numbers.)
        ``--rows``: how many of the buffer's rows are real (the routing is
        made to hold exactly that many); ``--chunks``: rows a turn of the
        loops moves.  One JSON line a program and a count of rows: ms a
        call, and whether its result agrees with the ``whole`` form's
        (buffer side: bit for bit).
        Then the WHOLE layer (``moe.moe_forward`` at the cell's widths,
        768 hidden, gated, 16 of 128 experts held, with and without the
        experts' biases): the gradients of a loss by all its operands with
        every buffer starting as NaN, against the same with every movement
        one plain gather (nothing unwritten): ``finite`` and the largest
        norm of a difference over the norm.

    python benchmark/moe_bench.py rows --rehearse
        the same programs at a tiny shape on whatever backend JAX has:
        shows that they run and agree, reports no time.
"""
from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import re
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from mxnet_tpu.gluon.nn import moe  # noqa: E402

HIDDEN = {2048: 768, 32: 24}              # an expert's, by the width
CELL = {"positions": 16384, "top_k": 8, "width": 2048, "held": 16,
        "experts": 128}
TINY = {"positions": 200, "top_k": 4, "width": 32, "held": 8, "experts": 64}


# ------------------------------------------------ the forms before PR 31
def _picked(y, lay):
    pos = jnp.zeros(lay["order"].shape, jnp.int32).at[lay["order"]].set(
        jnp.arange(lay["order"].shape[0], dtype=jnp.int32),
        unique_indices=True).reshape(lay["held"].shape)
    return jnp.where(lay["held"][..., None], y[pos].astype(jnp.float32), 0)


def whole_dispatch(x, lay, w):
    return x[lay["row_token"]]


def whole_combine(y, lay, w):
    return jnp.sum(_picked(y, lay) * w[..., None], axis=1).astype(y.dtype)


def whole_combine_bwd(y, dout, lay, w):
    real = jnp.arange(y.shape[0], dtype=jnp.int32) < lay["rows"]
    row_w = w[lay["row_token"], lay["row_slot"]]
    dy = jnp.where(real[:, None], dout[lay["row_token"]].astype(jnp.float32)
                   * row_w[:, None], 0).astype(y.dtype)
    dw = jnp.sum(_picked(y, lay) * dout.astype(jnp.float32)[:, None, :],
                 axis=-1)
    return dy, dw


def whole_dispatch_bwd(dxs, lay, w):
    return jnp.sum(_picked(dxs, lay), axis=1).astype(dxs.dtype)


# ------------------------------------------------------ the layer's forms
def _moves(lay, every_row=False):
    rows = None if every_row else lay["rows"]
    return (rows, rows, lay["row_token"], lay["held"], lay["tm_row"],
            lay["tm_flat"])


def layer_dispatch(x, lay, w, **kw):
    return moe._to_rows(x, *_moves(lay, **kw))


def layer_combine(yw, lay, w, **kw):
    return moe._to_positions(yw, *_moves(lay, **kw))


def layer_combine_bwd(y, dout, lay, w, **kw):
    moves = _moves(lay, **kw)
    return moe._to_positions_bwd((moves[0], moves[2]), dout)[0]


def layer_dispatch_bwd(dxs, lay, w, **kw):
    moves = _moves(lay, **kw)
    return moe._to_rows_bwd(moves[1:2] + moves[3:], dxs)[0]


def _poisoned(fn):
    """``fn`` with every buffer of the layer's starting as NaN: what the
    loops do not write must not reach a result."""
    def run(*args):
        blank, moe._blank = moe._blank, lambda shape, dtype: jnp.full(
            shape, jnp.nan, dtype)
        try:
            return fn(*args)
        finally:
            moe._blank = blank
    return run


for _move in ("dispatch", "combine", "combine_bwd", "dispatch_bwd"):
    globals()["gather_" + _move] = functools.partial(
        globals()["layer_" + _move], every_row=True)
    globals()["poisoned_" + _move] = _poisoned(globals()["layer_" + _move])


def routing(shape, rows, seed):
    """``moe.layout`` of assignments made so that exactly ``rows`` of them
    picked a held expert (at most ``min(top_k, held)`` a position), and
    routing weights."""
    n, k = shape["positions"], shape["top_k"]
    held, experts = shape["held"], shape["experts"]
    slots = min(k, held)
    if not 0 <= rows <= n * slots or experts < held + k:
        raise SystemExit("moe_bench: %d rows do not fit %d positions x %d"
                         % (rows, n, slots))
    rs = np.random.RandomState(seed)
    top_e = np.tile(held + np.arange(k), (n, 1))          # all absent
    pick = rs.permutation(n * slots)[:rows]
    # slot s of a position picks held expert s, or s + slots where held
    top_e[pick // slots, pick % slots] = pick % slots + slots * (
        rs.randint(0, 2, rows) * (2 * slots <= held))
    w = rs.uniform(0.05, 1.0, (n, k)).astype(np.float32)
    w = jnp.asarray(w / w.sum(-1, keepdims=True))
    return moe.layout(jnp.asarray(top_e, jnp.int32), w, 0, held), w


def programs(chunks):
    """{(movement, form, chunk): function of the movement's operands}."""
    out = {}
    for move in ("dispatch", "combine", "combine_bwd", "dispatch_bwd"):
        for form, cs in (("whole", [0]), ("layer", chunks), ("gather", [0]),
                         ("poisoned", [0])):
            for c in cs:
                out[move, form, c] = globals()["%s_%s" % (form, move)]
    return out


def module_ms(trace_dir):
    """{program name: [device ms of each execution]} from the ``XLA
    Modules`` line of the first TPU plane of the profiler's trace."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                for e in line.events:
                    name = re.sub(r"\(\d+\)$", "", e.name)
                    found.setdefault(name, []).append(e.duration_ns / 1e6)
        break
    return found


def bench_rows(shape, rows_list, chunks, iters, timed):
    n, d = shape["positions"], shape["width"]
    r = n * min(shape["top_k"], shape["held"])
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(keys[0], (n, d), jnp.float32).astype(jnp.bfloat16)
    y0 = jax.random.normal(keys[1], (r, d), jnp.float32)
    dout = jax.random.normal(keys[2], (n, d), jnp.float32) \
        .astype(jnp.bfloat16)
    rule, jitted = moe.chunk_rows, {}
    for i, ((move, form, c), fn) in enumerate(programs(chunks).items()):
        name = "mb_%s_%s_%d" % (move, form, c)
        # its own number as a second result: two forms that trace to one
        # program would otherwise share an executable, and its name
        fn = (lambda f, i: lambda *a: (f(*a), jnp.int32(i)))(fn, i)
        fn.__name__ = fn.__qualname__ = name
        jitted[move, form, c] = (name, jax.jit(fn))
    for rows in rows_list:
        lay, w = routing(shape, rows, seed=rows % 1000)
        assert int(lay["rows"]) == rows
        real = jnp.arange(r) < rows
        # rows past the last real one hold what a grouped product may
        # leave there: nothing may read them
        y = jnp.where(real[:, None], y0, jnp.nan).astype(jnp.bfloat16)
        yw = (y.astype(jnp.float32) * lay["row_w"][:, None]).astype(
            jnp.bfloat16)
        operands = {"dispatch": (x,), "combine": (y,), "combine_bwd":
                    (y, dout), "dispatch_bwd": (y,)}
        want, agrees, calls = {}, {}, []
        for (move, form, c), (name, fn) in jitted.items():
            # rows a turn moves: read when a program is traced
            moe.chunk_rows = (lambda c: lambda r: min(r, c))(c) if c \
                else rule
            args = operands[move] + (lay, w)
            if move == "combine" and form != "whole":
                args = (yw,) + args[1:]
            out = jax.block_until_ready(fn(*args))[0]
            if form == "whole":             # a movement's first program
                want = {move: out}
            else:
                agrees[move, form, c] = _agrees(move, want[move], out, real,
                                                lay["row_w"])
            calls.append((name, fn, args))
        moe.chunk_rows = rule
        ms = {}
        if timed:
            with tempfile.TemporaryDirectory() as tmp:
                jax.profiler.start_trace(tmp)
                for name, fn, args in calls:
                    for _ in range(iters):
                        out = fn(*args)
                    jax.block_until_ready(out)
                jax.profiler.stop_trace()
                ms = module_ms(tmp)
        for (move, form, c), (name, _) in jitted.items():
            row = {"movement": move, "form": form, "chunk_rows": c,
                   "rows": rows, "buffer_rows": r}
            if timed:
                took = sorted(ms.get("jit_" + name, []))
                if len(took) != iters:
                    raise SystemExit("moe_bench: %d executions of %s in the "
                                     "trace, not %d" % (len(took), name,
                                                        iters))
                row["ms"] = round(took[len(took) // 2], 4)
            if form != "whole":
                row["agrees"] = agrees[move, form, c]
            print(json.dumps(row), flush=True)


def bench_layer(shape, iters, timed):
    """The whole layer, forward and backward: one JSON line a form."""
    n, d, k = shape["positions"], shape["width"], shape["top_k"]
    held, hidden = shape["held"], HIDDEN[shape["width"]]
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))

    def draw(*dims, scale=1.0):
        return (scale * jax.random.normal(next(keys), dims, jnp.float32)) \
            .astype(jnp.bfloat16)

    co = draw(n, d)
    operands = [draw(n, d), draw(shape["experts"], d, scale=d ** -0.5),
                draw(held, d, hidden, scale=d ** -0.5),
                draw(held, hidden, d, scale=hidden ** -0.5),
                draw(held, d, hidden, scale=d ** -0.5),
                draw(held, hidden), draw(held, d)]
    rows = int(moe.route(operands[0], operands[1], k, 0, held)["rows"])
    assert all(moe.loops(k, held, shape["experts"]).values())

    def loss(*ops):
        out = moe.moe_forward(*ops, top_k=k, first=0, activation="silu")
        return jnp.sum(co.astype(jnp.float32) * out.astype(jnp.float32))

    nan = lambda s, dtype: jnp.full(s, jnp.nan, dtype)      # noqa: E731
    never = dict.fromkeys(moe._LOOPS_BELOW, 0.0)
    calls, got = [], {}
    for biased in (False, True):
        ops = operands[:7 if biased else 5]
        for form, blank, below in (("gather", moe._blank, never),
                                   ("layer", moe._blank, moe._LOOPS_BELOW),
                                   ("poisoned", nan, moe._LOOPS_BELOW)):
            name = "mb_grad_%s_%d" % (form, biased)
            fn = (lambda *a: jax.grad(loss, range(len(a)))(*a))
            fn.__name__ = fn.__qualname__ = name
            fn = jax.jit(fn)
            # read when the program is traced
            keep = moe._blank, moe._LOOPS_BELOW
            moe._blank, moe._LOOPS_BELOW = blank, below
            try:
                out = jax.block_until_ready(fn(*ops))
            finally:
                moe._blank, moe._LOOPS_BELOW = keep
            got[form, biased] = [np.asarray(g, np.float32) for g in out]
            calls.append((form, biased, name, fn, ops))
    ms = {}
    if timed:
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            for _, _, name, fn, ops in calls:
                for _ in range(iters):
                    out = fn(*ops)
                jax.block_until_ready(out)
            jax.profiler.stop_trace()
            ms = module_ms(tmp)
    for form, biased, name, _, _ in calls:
        row = {"movement": "layer_grad", "form": form, "biased": biased,
               "rows": rows, "buffer_rows": n * min(k, held),
               "finite": all(bool(np.isfinite(g).all())
                             for g in got[form, biased])}
        if timed:
            took = sorted(ms["jit_" + name])
            row["ms"] = round(took[len(took) // 2], 4)
        if form != "gather":
            row["off"] = float(max(
                np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30)
                for g, w in zip(got[form, biased], got["gather", biased])))
            row["agrees"] = row["finite"] and row["off"] <= 2.0 ** -7
        print(json.dumps(row), flush=True)


def _agrees(move, want, got, real, row_w):
    """Buffer side: the real rows bit for bit (the layer's ``dy`` before
    the weight, which it applies elsewhere); slot side (sums in another
    order, terms rounded to the rows' dtype): to a few units of bf16.
    Compared on the device: the buffers are a gigabyte each in float32."""
    want = jax.tree_util.tree_leaves(want)[0].astype(jnp.float32)
    got = jax.tree_util.tree_leaves(got)[0].astype(jnp.float32)
    if move == "combine_bwd":
        ok = jnp.abs(want - got * row_w[:, None]) <= 2.0 ** -7 * jnp.abs(want)
        return bool(jnp.all(ok | ~real[:, None]))
    if move == "dispatch":
        return bool(jnp.all((want == got) | ~real[:, None]))
    scale = jnp.maximum(jnp.max(jnp.abs(want)), 1e-6)
    return bool(jnp.max(jnp.abs(want - got)) <= 2.0 ** -6 * scale)


def main():
    from mxnet_tpu.compile import jax_cache_dir

    if sys.argv[1:2] != ["rows"]:
        raise SystemExit(__doc__)
    ap = argparse.ArgumentParser(prog="moe_bench.py rows")
    ap.add_argument("--rows", type=int, nargs="+",
                    default=[0, 8192, 16384, 32768, 65536, 131072])
    ap.add_argument("--chunks", type=int, nargs="+",
                    default=[4096, 8192, 16384])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(sys.argv[2:])
    if args.rehearse:
        bench_rows(TINY, [0, 100, 101, 300, 800], [50, 100], 1, timed=False)
        bench_layer(TINY, 1, timed=False)
        print(json.dumps({"rehearsal": True,
                          "backend": jax.default_backend()}))
        return
    if jax.default_backend() != "tpu":
        raise SystemExit("moe_bench rows: the times come from a TPU's "
                         "trace; the backend here is %r (--rehearse runs "
                         "the programs at a tiny shape, without times)"
                         % jax.default_backend())
    jax_cache_dir()
    bench_rows(CELL, args.rows, args.chunks, 5, timed=True)
    bench_layer(CELL, 5, timed=True)


if __name__ == "__main__":
    main()
