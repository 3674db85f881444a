"""What the Laguna family asks of the layers: rotary positions over a
leading part of the head and with YaRN's frequencies, against a direct
complex-number form; attention without QK-norm and with a per-head output
gate; a decoder layer whose feed-forward is handed in; the zoo's model
through FusedTrainer."""
import cmath
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, parallel
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo import laguna

YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}


def _complex_rotary(x, positions, inv_freq, factor=1.0):
    """Pair (i, i + R/2) of the first R = 2 len(inv_freq) dimensions as the
    complex number x_i + j x_{i+R/2}, times factor * exp(j pos inv_freq_i);
    the other dimensions pass.  x (T, H, D)."""
    out = np.array(x, dtype=np.float64)
    half = len(inv_freq)
    for t, pos in enumerate(positions):
        for i, f in enumerate(inv_freq):
            turn = factor * cmath.exp(1j * pos * f)
            z = (x[t, :, i] + 1j * x[t, :, i + half]) * turn
            out[t, :, i], out[t, :, i + half] = z.real, z.imag
    return out


def test_yarn_frequencies_follow_the_published_formula():
    """dim 64, base 500,000, factor 64 over 4,096 original positions: the
    first dimensions keep the plain frequency (they turn more than 64
    times), the last are divided by 64 (less than once), a ramp between."""
    dim = 64
    freq = laguna.yarn_inv_freq(dim, **YARN)
    plain = [500000 ** (-2.0 * i / dim) for i in range(dim // 2)]

    def c(n):
        return dim * math.log(4096 / (2 * math.pi * n)) \
            / (2 * math.log(500000))

    low, high = math.floor(c(64)), math.ceil(c(1))
    assert (low, high) == (5, 16)
    assert len(freq) == 32
    for i in range(32):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want = plain[i] / 64 * ramp + plain[i] * (1 - ramp)
        assert freq[i] == pytest.approx(want, rel=1e-12)
    assert freq[:6] == pytest.approx(plain[:6])
    assert freq[16:] == pytest.approx([p / 64 for p in plain[16:]])
    assert plain[10] / 64 < freq[10] < plain[10]
    assert YARN["attention_factor"] == pytest.approx(0.1 * math.log(64) + 1)


@pytest.mark.parametrize("kw", [
    dict(theta=10000.0),                                # the whole head
    dict(theta=100.0, rotary_dim=8),                    # a leading part
    dict(rotary_dim=8, inv_freq=laguna.yarn_inv_freq(8, **YARN),
         factor=YARN["attention_factor"]),              # partial YaRN
], ids=["whole", "partial", "partial_yarn"])
def test_rotary_against_a_complex_number_form(kw):
    d = 16
    x = np.random.RandomState(0).randn(7, 3, d).astype("float32")
    positions = np.array([0, 1, 2, 5, 11, 40, 4095])
    r = kw.get("rotary_dim", d)
    inv_freq = kw.get("inv_freq") or [
        kw["theta"] ** (-2.0 * i / r) for i in range(r // 2)]
    got = nd.rotary_embedding(nd.array(x), nd.array(positions, dtype="int32"),
                              **kw).asnumpy()
    want = _complex_rotary(x, positions, inv_freq, kw.get("factor", 1.0))
    np.testing.assert_allclose(got, want, atol=2e-4)
    if r < d:       # the other dimensions pass through, bit for bit
        assert (got[..., r:] == x[..., r:]).all()
        assert np.abs(got[1:, :, :r] - x[1:, :, :r]).max() > 0.1
    # position 0 turns nothing; the factor still scales the rotated part
    np.testing.assert_allclose(got[0, :, :r],
                               kw.get("factor", 1.0) * x[0, :, :r], rtol=1e-6)


def _attention(**kw):
    mx.random.seed(4)
    attn = nn.GroupedQueryAttention(32, 4, 2, 8, rope_theta=100.0, **kw)
    attn.initialize()
    return attn


def test_attention_without_qk_norm_and_with_a_head_gate():
    """Against a hand-written form: no norm on q and k, causal scores, and
    ``sigmoid(x W_g)`` a head on that head's output before the output
    projection."""
    attn = _attention(qk_norm=False, gate=True, causal=True)
    assert attn.query_norm is None and attn.key_norm is None
    assert attn.gate_proj.weight.shape == (4, 32)
    names = set(attn.collect_params())
    assert names == {"query_proj.weight", "key_proj.weight",
                     "value_proj.weight", "out_proj.weight",
                     "gate_proj.weight"}
    rs = np.random.RandomState(1)
    x = rs.randn(2, 6, 32).astype("float32")
    pos = np.arange(6)
    got = attn(nd.array(x), nd.array(pos, dtype="int32")).asnumpy()
    p = {n: v.data().asnumpy() for n, v in attn.collect_params().items()}
    inv = [100.0 ** (-2.0 * i / 8) for i in range(4)]

    def rows(name, n, rope):
        h = (x @ p[name + "_proj.weight"].T).reshape(2, 6, n, 8)
        return np.stack([_complex_rotary(b, pos, inv) for b in h]) \
            if rope else h

    q, k, v = rows("query", 4, True), rows("key", 2, True), \
        rows("value", 2, False)
    k, v = np.repeat(k, 2, axis=2), np.repeat(v, 2, axis=2)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(8)
    s = np.where(np.tril(np.ones((6, 6), bool)), s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    o = np.einsum("bhqk,bkhd->bqhd", w, v)
    g = 1 / (1 + np.exp(-(x @ p["gate_proj.weight"].T)))       # (2, 6, 4)
    o = (o * g[..., None]).reshape(2, 6, 32)
    np.testing.assert_allclose(got, o @ p["out_proj.weight"].T, atol=2e-5)
    # the gate matters: the ungated layer on the same weights differs
    plain = _attention(qk_norm=False, causal=True)
    for n, v_ in plain.collect_params().items():
        v_.set_data(nd.array(p[n]))
    assert np.abs(plain(nd.array(x), nd.array(pos, dtype="int32")).asnumpy()
                  - got).max() > 1e-3


def test_a_layers_rule_is_its_own_window_or_causal_and_names_its_scope():
    x = nd.array(np.random.RandomState(2).randn(1, 12, 32))
    pos = nd.array(np.arange(12), dtype="int32")
    causal, window = _attention(causal=True), _attention(window=4)
    for name, p in causal.collect_params().items():
        window.collect_params()[name].set_data(p.data())
    a, b = causal(x, pos).asnumpy(), window(x, pos).asnumpy()
    # the first 4 queries see the same keys under both rules
    np.testing.assert_allclose(a[:, :4], b[:, :4], atol=1e-6)
    assert np.abs(a[:, 4:] - b[:, 4:]).max() > 1e-4
    # a later token never moves an earlier position under either rule; under
    # the window it moves only the next 3
    x2 = x.asnumpy().copy()
    x2[:, 5] += 1.0
    for attn, upto in ((causal, 12), (window, 9)):
        moved = np.abs(attn(nd.array(x2), pos).asnumpy()
                       - attn(x, pos).asnumpy()).max(-1)[0]
        assert (moved[:5] == 0).all() and (moved[5:upto] > 0).all()
        assert (moved[upto:] == 0).all()
    for attn, scope in ((causal, "mx.attn.causal"),
                        (window, "mx.attn.window")):
        text = jax.jit(lambda a, attn=attn: attn(
            nd.NDArray(a), pos)._data).lower(x._data).as_text(debug_info=True)
        assert scope in text


def test_decoder_layer_takes_its_feed_forward_under_a_name():
    attn = nn.GroupedQueryAttention(32, 4, 2, 8, qk_norm=False, causal=True)
    dense = nn.DecoderLayer(32, attn, mlp=nn.GatedMLP(32, 48))
    assert {"mlp.gate_proj.weight", "mlp.up_proj.weight",
            "mlp.down_proj.weight", "input_norm.gamma", "post_norm.gamma",
            "attention.out_proj.weight"} <= set(dense.collect_params())
    with pytest.raises(MXNetError, match="one feed-forward"):
        nn.DecoderLayer(32, attn)
    with pytest.raises(MXNetError, match="one feed-forward"):
        nn.DecoderLayer(32, attn, mlp=nn.GatedMLP(32, 48),
                        moe=nn.MoE(4, 8, 32))
    dense.initialize()
    x = nd.array(np.random.RandomState(3).randn(2, 5, 32))
    pos = nd.array(np.arange(5), dtype="int32")
    out = dense(x, pos).asnumpy()
    p = {n: v.data().asnumpy() for n, v in dense.collect_params().items()}

    def rms(h, g):
        return h / np.sqrt((h ** 2).mean(-1, keepdims=True) + 1e-6) * g

    h = x.asnumpy() + dense.attention(
        nd.array(rms(x.asnumpy(), p["input_norm.gamma"])), pos).asnumpy()
    m = rms(h, p["post_norm.gamma"])
    gate = m @ p["mlp.gate_proj.weight"].T
    want = h + (gate / (1 + np.exp(-gate)) * (m @ p["mlp.up_proj.weight"].T)) \
        @ p["mlp.down_proj.weight"].T
    np.testing.assert_allclose(out, want, atol=2e-5)
    # SDAR's layer is this layer with QK-norm and a softmax mixture
    sdar_layer = nn.MoEDecoderLayer(32, 4, 2, 8, 8, 16, 2)
    assert isinstance(sdar_layer, nn.DecoderLayer)
    assert {"moe.gate", "attention.query_norm.gamma"} \
        <= set(sdar_layer.collect_params())


def _tiny_cfg():
    return {"vocab_size": 96, "hidden_size": 32, "intermediate_size": 48,
            "num_key_value_heads": 2, "head_dim": 8, "rms_norm_eps": 1e-6,
            "num_experts": 4, "router_experts": 8, "first_expert": 2,
            "num_experts_per_tok": 2, "moe_intermediate_size": 16,
            "shared_expert_intermediate_size": 16, "gating": True,
            "sliding_window": 6, "moe_routed_scaling_factor": 2.5,
            "rope_parameters": {
                "full_attention": YARN,
                "sliding_attention": {"rope_type": "default",
                                      "rope_theta": 10000,
                                      "partial_rotary_factor": 1}},
            "layer_types": ["full_attention", "sliding_attention",
                            "full_attention"],
            "mlp_layer_types": ["dense", "sparse", "sparse"],
            "num_attention_heads_per_layer": [4, 6, 4]}


def test_the_zoos_model_is_built_from_the_configs_keys():
    mx.random.seed(7)
    net = laguna.LagunaForCausalLM(_tiny_cfg())
    net.initialize()
    shapes = {n: p.shape for n, p in net.collect_params().items()}
    assert shapes["layers.0.attention.query_proj.weight"] == (32, 32)
    assert shapes["layers.1.attention.query_proj.weight"] == (48, 32)
    assert shapes["layers.1.attention.gate_proj.weight"] == (6, 32)
    assert shapes["layers.0.mlp.up_proj.weight"] == (48, 32)
    assert shapes["layers.1.moe.gate"] == (8, 32)
    assert shapes["layers.2.moe.w1"] == (4, 32, 16)
    assert shapes["layers.2.moe.shared_w2"] == (16, 32)
    assert not any("norm.gamma" in n and "attention" in n for n in shapes)
    assert "layers.0.moe.gate" not in shapes
    full, slide = net.layers[0].attention, net.layers[1].attention
    assert slide._window.block == 6 and full._window is None
    assert full._rotary["rotary_dim"] == 4 \
        and len(full._rotary["inv_freq"]) == 2 \
        and full._rotary["factor"] == YARN["attention_factor"]
    assert slide._rotary == {"rotary_dim": 8, "theta": 10000}
    ids = np.random.RandomState(0).randint(0, 96, (2, 16)).astype("int32")
    base = net(nd.array(ids, dtype="int32")).asnumpy()
    assert base.shape == (2, 16, 96)
    # causal: a token moves its own and later positions only
    ids2 = ids.copy()
    ids2[:, 9] = (ids2[:, 9] + 1) % 96
    moved = np.abs(net(nd.array(ids2, dtype="int32")).asnumpy()
                   - base).max(-1)
    assert (moved[:, :9] == 0).all() and (moved[:, 9:] > 0).all()


def test_recomputed_layers_give_the_same_step_as_kept_ones_and_train():
    rs = np.random.RandomState(1)
    ids = rs.randint(0, 96, (2, 17)).astype("int32")
    x, y = jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])
    losses = {}
    for recompute in (False, True):
        mx.random.seed(9)
        net = laguna.LagunaForCausalLM(_tiny_cfg(), recompute=recompute)
        net.initialize()
        tr = parallel.FusedTrainer(
            net, loss_fn=laguna.next_token_loss, optimizer="adam",
            optimizer_params={"learning_rate": 1e-3})
        losses[recompute] = [float(tr.step(x, y).asnumpy())
                             for _ in range(3)]
        text = tr._lower(x, y).as_text()     # jax.checkpoint's barrier
        assert ("optimization_barrier" in text) == recompute
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-6)
    assert losses[True][2] < losses[True][0]
    # the loss is the mean cross-entropy of position t against token t + 1
    assert abs(losses[True][0] - math.log(96)) < 0.5
