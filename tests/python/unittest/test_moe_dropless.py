"""gluon.nn.MoE rebuilt: dropless routing over the experts held here as
grouped products; the decoder layer built on it; recomputation; the zoo's
SDAR model through FusedTrainer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, telemetry, trace
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo import sdar
from mxnet_tpu.gluon.nn import moe as moe_mod


def _dense_moe(moe, x):
    """Every held expert on every position, weighted by its routing weight
    or 0: what the grouped path must equal."""
    p = {n: v.data().asnumpy() for n, v in moe.collect_params().items()}
    logits = x @ p["gate"].T
    probs = jax.nn.sigmoid(logits) if moe._score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, moe._k)
    top_p = moe._scale * top_p / top_p.sum(-1, keepdims=True)
    weight = (jax.nn.one_hot(top_e, moe._E) * top_p[..., None]).sum(1)
    out = 0
    if "shared_w1" in p:    # every position, unrouted and unweighted
        out = (jax.nn.silu(x @ p["shared_wg"]) * (x @ p["shared_w1"])) \
            @ p["shared_w2"]
    for e in range(moe._count):
        h = x @ p["w1"][e]
        if "b1" in p:
            h = h + p["b1"][e]
        h = jax.nn.silu(x @ p["wg"][e]) * h if "wg" in p \
            else jnp.maximum(h, 0)
        y = h @ p["w2"][e] + (p["b2"][e] if "b2" in p else 0)
        out = out + weight[:, moe._first + e, None] * y
    return np.asarray(out)


@pytest.mark.parametrize("kw", [
    dict(),                                                    # the old form
    dict(gated=True, use_bias=False, activation="silu"),
    dict(gated=True, use_bias=False, activation="silu", first=2, count=3),
    dict(first=6, count=2, top_k=3),         # top_k over the experts held
    dict(gated=True, use_bias=False, activation="silu", score="sigmoid",
         scale=2.5),                         # sigmoid scores, scaled weights
    dict(gated=True, use_bias=False, activation="silu", score="sigmoid",
         scale=2.5, shared_hidden=12, first=2, count=3),   # a shared expert
    dict(gated=True, use_bias=False, activation="silu", shared_hidden=4),
])
def test_grouped_forward_equals_every_expert_on_every_position(kw):
    mx.random.seed(5)
    kw = dict(dict(top_k=2), **kw)
    moe = nn.MoE(num_experts=8, hidden_size=16, units=8, **kw)
    moe.initialize()
    for name in ("b1", "b2"):
        if getattr(moe, name) is not None:
            getattr(moe, name).set_data(nd.array(
                np.random.RandomState(1).randn(*getattr(moe, name).shape)))
    x = np.random.RandomState(0).randn(3, 10, 8).astype(np.float32)
    y = moe(nd.array(x))
    assert y.shape == (3, 10, 8)
    np.testing.assert_allclose(y.asnumpy().reshape(30, 8),
                               _dense_moe(moe, x.reshape(30, 8)), atol=2e-5)
    assert moe.buffer_rows(30) == 30 * min(kw["top_k"], moe._count)


def _layer(n, experts, count, seed=2, c=12, hidden=10):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    return {"x": jax.random.normal(k[0], (n, c)),
            "gate": jax.random.normal(k[1], (experts, c)),
            "w1": 0.3 * jax.random.normal(k[2], (count, c, hidden)),
            "wg": 0.3 * jax.random.normal(k[3], (count, c, hidden)),
            "w2": 0.3 * jax.random.normal(k[4], (count, hidden, c)),
            "co": jax.random.normal(k[5], (n, c)),
            "b1": jax.random.normal(k[6], (count, hidden)),
            "b2": jax.random.normal(k[7], (count, c))}


def _losses(p, top_k, first, recompute=False, biased=False):
    """The layer's loss as the grouped path computes it and as every held
    expert on every position does, functions of the five operands (seven
    with the experts' biases)."""
    co, count, experts = p["co"], p["w1"].shape[0], p["gate"].shape[0]

    def grouped(x, gate, w1, wg, w2, *b):
        def layer(x):
            return moe_mod.moe_forward(x, gate, w1, w2, wg, *b, top_k=top_k,
                                       first=first, activation="silu")

        return jnp.sum(co * (jax.checkpoint(layer) if recompute
                             else layer)(x))

    def dense(x, gate, w1, wg, w2, b1=None, b2=None):
        prob = jax.nn.softmax(x @ gate.T, -1)
        tp, te = jax.lax.top_k(prob, top_k)
        weight = (jax.nn.one_hot(te, experts)
                  * (tp / tp.sum(-1, keepdims=True))[..., None]).sum(1)
        out = 0
        for e in range(count):
            out = out + weight[:, first + e, None] * (
                (jax.nn.silu(x @ wg[e])
                 * (x @ w1[e] + (0 if b1 is None else b1[e])))
                @ w2[e] + (0 if b2 is None else b2[e]))
        return jnp.sum(co * out)

    return grouped, dense, tuple(p[n] for n in (
        "x", "gate", "w1", "wg", "w2") + ("b1", "b2") * biased)


def _biased(p, first, count, winners, picking):
    """The router made to send the first ``picking`` positions to the held
    experts ``winners`` (all of them) and no position to another held
    expert: ``len(winners) * picking`` real rows."""
    n = p["x"].shape[0]
    x = p["x"].at[:, 0].set(1.0).at[:, 1].set(
        jnp.where(jnp.arange(n) < picking, 1.0, -1.0))
    gate = p["gate"].at[first:first + count, 0].set(-50.0).at[:, 1].set(0.0)
    for e in winners:
        gate = gate.at[e, 0].set(0.0).at[e, 1].set(50.0)
    return dict(p, x=x, gate=gate)


# routings the chunk loops and the tiles can get wrong: (positions, experts,
# top_k, first, count, the held experts every picking position picks,
# picking positions (None: the router as drawn), real rows), at 16 rows a
# chunk
_ROUTINGS = {
    "no_held_expert_picked": (40, 8, 2, 2, 4, [3], 0, 0),
    "rows_a_multiple_of_the_chunk": (40, 8, 2, 2, 4, [3, 4], 16, 32),
    "rows_one_past_a_multiple": (40, 8, 2, 2, 4, [3], 33, 33),
    "every_assignment_to_one_expert": (40, 8, 2, 2, 4, [5], 40, 40),
    "every_expert_held": (25, 8, 2, 0, 8, [], None, 50),
    "top_k_over_the_held_experts": (40, 8, 3, 6, 2, [6, 7], 21, 42),
    "the_chunk_does_not_divide_the_buffer": (25, 8, 2, 2, 4, [2, 3], 23,
                                             46),
    "a_full_a_cut_and_an_empty_tile": (300, 8, 2, 2, 4, [2, 3], 200, 400),
    "biased_experts_under_unwritten_rows": (40, 8, 3, 6, 2, [6, 7], 21, 42),
    "biased_experts_and_no_real_row": (40, 8, 2, 2, 4, [3], 0, 0),
    "only_the_buffer_side_loops": (40, 8, 2, 2, 4, [3], 33, 33),
}
# the layer's own rule (half the experts held: plain gathers from the
# positions, loops from the buffer); the others loop wherever they can
_OWN_RULE = {"only_the_buffer_side_loops"}


@pytest.mark.parametrize("recompute", [False, True],
                         ids=["kept", "recomputed"])
@pytest.mark.parametrize("routing", sorted(_ROUTINGS))
def test_chunked_movements_equal_the_whole_expressions(routing, recompute,
                                                       monkeypatch):
    n, experts, top_k, first, count, winners, picking, rows = \
        _ROUTINGS[routing]
    monkeypatch.setattr(moe_mod, "chunk_rows", lambda r: min(r, 16))
    if routing not in _OWN_RULE:
        monkeypatch.setattr(moe_mod, "_LOOPS_BELOW",
                            {"positions": 1.0, "buffer": 1.0})
    # what no turn wrote shows wherever it is read (off the TPU lax.empty
    # gives zeros)
    monkeypatch.setattr(moe_mod, "_blank",
                        lambda shape, dtype: jnp.full(shape, jnp.nan, dtype))
    p = _layer(n, experts, count)
    if picking is not None:
        p = _biased(p, first, count, winners, picking)
    lay = moe_mod.route(p["x"], p["gate"], top_k, first, count)
    r = n * min(top_k, count)
    assert int(lay["rows"]) == rows and lay["row_token"].shape == (r,)
    real = np.arange(r) < rows
    np.testing.assert_array_equal(lay["real"], real)
    moves = (lay["rows"], lay["rows"], lay["row_token"], lay["held"],
             lay["tm_row"], lay["tm_flat"])
    w = lay["weights"]
    # what rides the sort is what a gather would fetch; 0 past the rows
    np.testing.assert_array_equal(
        lay["row_w"], jnp.where(real, w[lay["row_token"], lay["row_slot"]],
                                0))
    # into expert order: the real rows bit for bit, and past them only
    # the last real row's chunk is written
    xs = np.asarray(moe_mod._to_rows(p["x"], *moves))
    np.testing.assert_array_equal(xs[real],
                                  np.asarray(p["x"][lay["row_token"]])[real])
    walked = min(r, -(-rows // 16) * 16)
    assert np.isnan(xs[walked:]).all() and (
        rows == 0 or not np.isnan(xs[max(0, min(walked, r) - 16):walked])
        .any())
    # back: the sum over a position's held slots, whatever the rows past
    # the last real one hold (a grouped product leaves NaNs there)
    y = jnp.where(real[:, None],
                  jax.random.normal(jax.random.PRNGKey(7), xs.shape), jnp.nan)
    pos = jnp.zeros((n * top_k,), jnp.int32).at[lay["order"]].set(
        jnp.arange(n * top_k)).reshape(n, top_k)
    picked = jnp.where(lay["held"][..., None], y[pos], 0)
    np.testing.assert_allclose(moe_mod._to_positions(y, *moves),
                               jnp.sum(picked, 1), atol=2e-5)
    # each is the other's backward
    np.testing.assert_array_equal(
        np.asarray(jax.vjp(lambda v: moe_mod._to_positions(v, *moves), y)[1](
            p["co"])[0])[real], np.asarray(p["co"][lay["row_token"]])[real])
    np.testing.assert_allclose(
        jax.vjp(lambda v: moe_mod._to_rows(v, *moves), p["x"])[1](y)[0],
        jnp.sum(picked, 1), atol=2e-5)
    # the layer: output and all its gradients against the dense form
    grouped, dense, args = _losses(p, top_k, first, recompute,
                                   biased=routing.startswith("biased"))
    np.testing.assert_allclose(grouped(*args), dense(*args), rtol=2e-5)
    for g, d in zip(jax.grad(grouped, range(len(args)))(*args),
                    jax.grad(dense, range(len(args)))(*args)):
        # sums over 300 positions round in another order
        np.testing.assert_allclose(g, d, rtol=1e-4, atol=2e-5)


def _movements(jaxpr, in_loop=False):
    """(primitive, the moved array's aval, index rows, inside a loop body)
    of every gather and scatter in ``jaxpr``, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather":
            yield name, eqn.outvars[0].aval, eqn.invars[1].aval.shape[0], \
                in_loop
        elif name.startswith("scatter"):
            yield name, eqn.invars[2].aval, eqn.invars[1].aval.shape[0], \
                in_loop
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _movements(sub, in_loop or name == "while")


def test_no_movement_of_rows_costs_by_the_bound(monkeypatch):
    """What the layer may not do, forward or backward: move rows at the
    cost of the bound where a loop is cheaper (there every gather of rows
    sits in a loop body and takes a chunk of them, in the rows' own dtype;
    before PR 31 they were gathers of the buffer's 80 rows, the slot side
    (positions, top_k, width) in float32 and summed over the slots),
    scatter ROWS, or gather or scatter single elements by the buffer's
    count (a TPU runs those one by one; the sorts carry them).  top-k's own
    backward scatters elements of the (40, experts) probabilities.  Which
    of the four movements loop follows the share of its experts a layer
    holds (``loops``): an eighth, all four; half, the two from the buffer;
    all, none, and the four are one gather of the buffer each."""
    monkeypatch.setattr(moe_mod, "chunk_rows", lambda r: min(r, 16))
    for experts, count, first, looping in ((32, 4, 2, 4), (8, 4, 2, 2),
                                           (8, 8, 0, 0)):
        assert sum(moe_mod.loops(2, count, experts).values()) * 2 == looping
        grouped, _, args = _losses(_layer(40, experts, count), 2, first)
        moves = list(_movements(
            jax.make_jaxpr(jax.grad(grouped, range(5)))(*args).jaxpr))
        rows = [m for m in moves
                if m[1].ndim > 1 and m[1].shape[-1] in (12, 10)]
        # into expert order, into position order, and their two backwards
        assert len(rows) == 4 and {m[0] for m in rows} == {"gather"}, rows
        assert sorted((m[1].shape, str(m[1].dtype), m[3]) for m in rows) \
            == [((16, 12), "float32", True)] * looping \
            + [((80, 12), "float32", False)] * (4 - looping)
        assert not [m for m in moves if m not in rows and m[2] >= 80
                    and m[1].shape != (40, 2)], moves


@pytest.mark.parametrize("share", ["an_eighth_held", "all_held"])
def test_bf16_layer_stays_beside_the_float32_weighted_form(share):
    """PR 31 moved the routing weight from the second product's results
    (float32, into the float32 sum over a position's slots) onto the hidden
    rows, rounded to bf16 before that product.  At the widths of the cell
    ``sdar_30b_a3b_bd4k`` (2,048 wide, 768 hidden, top-8) the bf16 layer
    stays as near the float32 layer as the older form does: output and
    every gradient, by the norm of the difference over the norm."""
    experts, count, first, top_k = \
        {"an_eighth_held": (32, 4, 8), "all_held": (8, 8, 0)}[share] + (8,)
    p = _layer(96, experts, count, seed=11, c=2048, hidden=768)
    for n in ("w1", "wg", "w2"):
        p[n] = p[n] / 0.3 * p[n].shape[1] ** -0.5
    names = ("x", "gate", "w1", "wg", "w2")

    def older(x, gate, w1, wg, w2):
        # every held expert on every position in the operands' dtype (as
        # the grouped products round), weighted and summed in float32
        prob = jax.nn.softmax(jnp.einsum(
            "td,ed->te", x, gate, preferred_element_type=jnp.float32), -1)
        tp, te = jax.lax.top_k(prob, top_k)
        weight = (jax.nn.one_hot(te, experts)
                  * (tp / tp.sum(-1, keepdims=True))[..., None]).sum(1)
        out = 0
        for e in range(count):
            y = (jax.nn.silu(x @ wg[e]) * (x @ w1[e])) @ w2[e]
            out = out + weight[:, first + e, None] * y.astype(jnp.float32)
        return out.astype(x.dtype)

    def layer(x, gate, w1, wg, w2):
        return moe_mod.moe_forward(x, gate, w1, w2, wg, top_k=top_k,
                                   first=first, activation="silu")

    def read(fn, dtype):
        # the bf16 operands, and the same numbers in float32: the output
        # and the five gradients of its product with ``co``
        args = [p[n].astype(jnp.bfloat16).astype(dtype) for n in names]

        def loss(*a):
            out = fn(*a).astype(jnp.float32)
            return jnp.sum(p["co"] * out), out

        (_, out), grads = jax.value_and_grad(loss, range(5), has_aux=True)(
            *args)
        return [np.asarray(v, np.float64) for v in (out,) + grads]

    exact = read(older, jnp.float32)
    for name, want, was, now in zip(("out",) + names, exact,
                                    read(older, jnp.bfloat16),
                                    read(layer, jnp.bfloat16)):
        off = [np.linalg.norm(v - want) / np.linalg.norm(want)
               for v in (was, now)]
        # read on the CPU: 0.0037-0.0054 the older form, 0.0024-0.0056 the
        # layer, at most 1.06 times the older form's
        assert off[1] < 1.25 * off[0] and off[1] < 0.008, (name, off)


def test_load_counts_rows_per_held_expert_and_feeds_the_gauge():
    mx.random.seed(6)
    moe = nn.MoE(8, 16, 8, top_k=2, first=2, count=4)
    moe.initialize()
    x = nd.array(np.random.RandomState(3).randn(50, 8))
    rows = moe.load(x)
    assert len(rows) == 4 and 0 < sum(rows) <= 100
    r = moe_mod.route(x._data, moe.gate.data()._data, 2, 2, 4)
    assert rows == [int(v) for v in r["group_sizes"]]
    g = telemetry.get_metric("moe_expert_rows")
    assert [g.labels(expert=str(e)).value for e in range(2, 6)] == rows
    # one chunk holds this buffer (100 rows): the loops walk all of it
    assert telemetry.get_metric("moe_rows_walked_share").value == 1.0


def test_walked_share_follows_the_real_rows(monkeypatch):
    monkeypatch.setattr(moe_mod, "chunk_rows", lambda r: min(r, 10))
    mx.random.seed(6)
    moe = nn.MoE(8, 16, 8, top_k=2, first=2, count=4)
    moe.initialize()
    rows = sum(moe.load(nd.array(np.random.RandomState(3).randn(50, 8))))
    assert 0 < rows < 90
    assert telemetry.get_metric("moe_rows_walked_share").value \
        == -(-rows // 10) * 10 / 100


def test_layout_instant_is_written_when_the_layer_meets_a_shape(monkeypatch):
    moe = nn.MoE(8, 16, 8, top_k=2, first=2, count=4)
    moe.initialize()
    trace.clear()
    moe(nd.array(np.ones((6, 8), "float32")))
    moe(nd.array(np.ones((6, 8), "float32")))       # the same shape: once
    got = [e for e in trace.events() if e.get("name") == "mx.moe.layout"]
    assert len(got) == 1
    assert got[0]["args"] == {"experts": 8, "held": 4, "first": 2,
                              "top_k": 2, "buffer_rows": 12,
                              "chunk_rows": 12, "chunks": 1,
                              "score": "softmax", "scale": 1.0, "shared": 0,
                              "bias": False}
    monkeypatch.setattr(moe_mod, "chunk_rows", lambda r: min(r, 8))
    moe(nd.array(np.ones((10, 8), "float32")))
    assert [e for e in trace.events() if e.get("name") == "mx.moe.layout"][
        -1]["args"]["chunks"] == 3                    # 20 rows by eights


def test_chunk_puts_the_balanced_expectation_inside_a_chunk():
    """3/32 of the buffer, at most 8,192, in whole tiles of 512; a buffer
    of up to 8,192 rows is one chunk.  An eighth of the buffer (the fill of
    the benchmark's MoE cells) is 1.33 chunks where the cap does not bind:
    not on a boundary, where the trip count would flip with the routing."""
    assert [moe_mod.chunk_rows(r) for r in (12, 100, 8192)] == [12, 100, 8192]
    assert moe_mod.chunk_rows(65536) == 6144        # laguna_xs2_t8k
    assert moe_mod.chunk_rows(131072) == 8192       # sdar_30b_a3b_bd4k
    assert moe_mod.chunk_rows(16384) == 1536
    assert moe_mod.chunk_rows(10 ** 7) == 8192
    for r in (16384, 32768, 65536, 81920):
        chunk = moe_mod.chunk_rows(r)
        assert chunk % 512 == 0 and 1.3 < (r / 8) / chunk < 1.4


def test_constructor_refuses_experts_outside_the_router():
    with pytest.raises(MXNetError):
        nn.MoE(8, 16, 8, first=6, count=4)
    with pytest.raises(MXNetError):
        nn.MoE(8, 16, 8, activation="tanh")
    with pytest.raises(MXNetError, match="score"):
        nn.MoE(8, 16, 8, score="tanh")
    with pytest.raises(MXNetError, match="shared"):
        nn.MoE(8, 16, 8, shared_hidden=4)       # ungated


def test_sigmoid_routing_weights_are_the_scaled_share_of_the_chosen_scores():
    """DeepSeek-V3's router: a sigmoid an expert (the scores do not compete),
    the k largest, normalised over the chosen ones, times the scale."""
    rs = np.random.RandomState(2)
    x, gate = rs.randn(20, 6).astype("float32"), \
        rs.randn(8, 6).astype("float32")
    r = moe_mod.route(jnp.asarray(x), jnp.asarray(gate), 3, 0, 8,
                      score="sigmoid", scale=2.5)
    s = 1.0 / (1.0 + np.exp(-(x @ gate.T)))
    chosen = np.sort(s, axis=-1)[:, ::-1][:, :3]
    np.testing.assert_allclose(r["weights"],
                               2.5 * chosen / chosen.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(r["weights"]).sum(-1), 2.5,
                               rtol=1e-5)
    # softmax scores pick the same experts (both are monotone in the
    # logit) and weigh them differently
    soft = moe_mod.route(jnp.asarray(x), jnp.asarray(gate), 3, 0, 8)
    assert (np.asarray(soft["held"]) == np.asarray(r["held"])).all()
    assert int(soft["rows"]) == int(r["rows"]) == 60
    assert np.abs(np.asarray(soft["weights"]) * 2.5
                  - np.asarray(r["weights"])).max() > 1e-3


def test_shared_expert_takes_gradients_and_load_follows_the_sigmoid_router():
    mx.random.seed(8)
    moe = nn.MoE(8, 16, 8, top_k=2, gated=True, use_bias=False,
                 activation="silu", first=2, count=4, score="sigmoid",
                 scale=2.5, shared_hidden=6)
    moe.initialize()
    x = nd.array(np.random.RandomState(4).randn(25, 8))
    for p in moe.collect_params().values():
        p.grad_req = "write"
    with mx.autograd.record():
        loss = (moe(x) ** 2).sum()
    loss.backward()
    for name in ("shared_w1", "shared_wg", "shared_w2", "gate", "w1"):
        assert float(np.abs(getattr(moe, name).grad().asnumpy()).max()) > 0
    rows = moe.load(x)
    r = moe_mod.route(x._data, moe.gate.data()._data, 2, 2, 4,
                      score="sigmoid", scale=2.5)
    assert rows == [int(n) for n in r["group_sizes"]] and sum(rows) > 0


def test_moe_apply_refuses_a_share_or_gated_experts():
    mesh = parallel.make_mesh({"ep": 2}) if len(jax.devices()) >= 2 else None
    if mesh is None:
        pytest.skip("needs 2 devices")
    moe = nn.MoE(4, 8, 4, top_k=1, gated=True, use_bias=False)
    moe.initialize()
    with pytest.raises(MXNetError, match="ungated"):
        parallel.moe_apply(moe, nd.array(np.ones((8, 4), "float32")),
                           mesh=mesh)


def _tiny_sdar(recompute):
    mx.random.seed(9)
    net = sdar.SDARMoE(64, 32, 2, 4, 2, 8, 8, 16, 2, block_length=4,
                       first_expert=2, experts_held=4, recompute=recompute)
    net.initialize()
    return net


def _batch(seq=16, vocab=64, seed=0):
    rs = np.random.RandomState(seed)
    x0 = rs.randint(0, vocab - 1, (2, seq)).astype("int32")
    t = rs.uniform(0.1, 1, (2, seq // 4)).repeat(4, 1)
    masked = rs.uniform(size=(2, seq)) < t
    xt = np.where(masked, vocab - 1, x0).astype("int32")
    return (jnp.asarray(xt), jnp.asarray(x0)), \
        (jnp.asarray(x0), jnp.asarray((masked / t).astype("float32")))


def test_recomputed_layers_give_the_same_step_as_kept_ones():
    """recompute=True changes what the backward keeps, not a number."""
    x, y = _batch()
    losses = {}
    for recompute in (False, True):
        net = _tiny_sdar(recompute)
        tr = parallel.FusedTrainer(
            net, loss_fn=sdar.block_diffusion_loss, optimizer="adam",
            optimizer_params={"learning_rate": 1e-3})
        losses[recompute] = [float(tr.step(x, y).asnumpy())
                             for _ in range(3)]
        text = tr._lower(x, y).as_text()     # jax.checkpoint's barrier
        assert ("optimization_barrier" in text) == recompute
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-6)
    assert losses[True][2] < losses[True][0]


def test_sdar_logits_come_from_the_noisy_half_and_see_the_clean_past():
    net = _tiny_sdar(False)
    (xt, x0), _ = _batch()
    base = net(nd.NDArray(xt), nd.NDArray(x0)).asnumpy()
    assert base.shape == (2, 16, 64)
    # a clean token of block 2 moves the logits of later blocks only
    x0b = x0.at[:, 9].set((x0[:, 9] + 1) % 63)
    moved = np.abs(net(nd.NDArray(xt), nd.NDArray(x0b)).asnumpy()
                   - base).max(-1)
    assert (moved[:, :12] == 0).all() and (moved[:, 12:] > 0).all()
    # a noisy token moves its own block only
    xtb = xt.at[:, 9].set((xt[:, 9] + 1) % 63)
    moved = np.abs(net(nd.NDArray(xtb), nd.NDArray(x0)).asnumpy()
                   - base).max(-1)
    assert (moved[:, 8:12] > 0).all()
    assert (moved[:, :8] == 0).all() and (moved[:, 12:] == 0).all()
