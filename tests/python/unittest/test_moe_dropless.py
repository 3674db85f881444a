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
    probs = jax.nn.softmax(x @ p["gate"].T, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, moe._k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    weight = (jax.nn.one_hot(top_e, moe._E) * top_p[..., None]).sum(1)
    out = 0
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


def test_gradients_flow_by_gathers_and_match_autodiff_of_the_dense_form():
    k = jax.random.split(jax.random.PRNGKey(2), 6)
    x = jax.random.normal(k[0], (40, 12))
    gate = jax.random.normal(k[1], (8, 12))
    w1, wg = (0.3 * jax.random.normal(k[i], (4, 12, 10)) for i in (2, 3))
    w2 = 0.3 * jax.random.normal(k[4], (4, 10, 12))
    co = jax.random.normal(k[5], (40, 12))

    def grouped(x, gate, w1, wg, w2):
        return jnp.sum(co * moe_mod.moe_forward(
            x, gate, w1, w2, wg=wg, top_k=2, first=2, activation="silu"))

    def dense(x, gate, w1, wg, w2):
        p = jax.nn.softmax(x @ gate.T, -1)
        tp, te = jax.lax.top_k(p, 2)
        weight = (jax.nn.one_hot(te, 8) * (tp / tp.sum(-1, keepdims=True))
                  [..., None]).sum(1)
        out = 0
        for e in range(4):
            out = out + weight[:, 2 + e, None] * (
                (jax.nn.silu(x @ wg[e]) * (x @ w1[e])) @ w2[e])
        return jnp.sum(co * out)

    args = (x, gate, w1, wg, w2)
    for g, r in zip(jax.grad(grouped, range(5))(*args),
                    jax.grad(dense, range(5))(*args)):
        np.testing.assert_allclose(g, r, atol=2e-5)
    # no scatter of ROWS (12 or 10 wide) in either direction: the sort is a
    # permutation, so rows move by gathers (top-k's own backward scatters
    # elements of the (40, 8) probabilities)
    jaxpr = str(jax.make_jaxpr(jax.grad(grouped, range(5)))(*args))
    scatters = [line for line in jaxpr.splitlines() if "scatter" in line]
    assert scatters and not [line for line in scatters
                             if ",12]" in line or ",10]" in line]


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


def test_layout_instant_is_written_when_the_layer_meets_a_shape():
    moe = nn.MoE(8, 16, 8, top_k=2, first=2, count=4)
    moe.initialize()
    trace.clear()
    moe(nd.array(np.ones((6, 8), "float32")))
    moe(nd.array(np.ones((6, 8), "float32")))       # the same shape: once
    got = [e for e in trace.events() if e.get("name") == "mx.moe.layout"]
    assert len(got) == 1
    assert got[0]["args"] == {"experts": 8, "held": 4, "first": 2,
                              "top_k": 2, "buffer_rows": 12}


def test_constructor_refuses_experts_outside_the_router():
    with pytest.raises(MXNetError):
        nn.MoE(8, 16, 8, first=6, count=4)
    with pytest.raises(MXNetError):
        nn.MoE(8, 16, 8, activation="tanh")


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
