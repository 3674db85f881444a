"""What GLM-4.7-Flash's architecture asks of the layers: multi-head latent
attention (the expanded form) against a dense float32 einsum, through the
flash kernels at heads of 256 (interpreted) and through the dense path; a
selection bias in the router that chooses and does not weigh; the zoo's
model with its multi-token prediction module through FusedTrainer."""
import cmath
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, trace
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo import glm_moe_lite
from mxnet_tpu.gluon.nn import moe
from mxnet_tpu.ops import pallas_attention as pa


# ------------------------------------------------------- latent attention
def _rotated(x, theta):
    """Rotate-half rotary positions 0..T-1 over all of x's (T, H, R) last
    dimension, as complex numbers x_i + j x_{i+R/2}."""
    out = np.array(x, dtype=np.float64)
    half = x.shape[-1] // 2
    for t in range(x.shape[0]):
        for i in range(half):
            z = (x[t, :, i] + 1j * x[t, :, i + half]) \
                * cmath.exp(1j * t * theta ** (-2.0 * i / (2 * half)))
            out[t, :, i], out[t, :, i + half] = z.real, z.imag
    return out


def _rms(h, g, eps):
    return h / np.sqrt((h ** 2).mean(-1, keepdims=True) + eps) * g


def _latent_by_hand(p, x, heads, kv_rank, nope, theta, eps):
    """The block's equations in float64 numpy: a dense causal softmax over
    keys and values expanded from the latent, ONE rotary key for all
    heads, rotary on the last dimensions of a head."""
    b, t, _ = x.shape
    c_q = _rms(x @ p["q_a_proj.weight"].T, p["q_a_norm.gamma"], eps)
    q = (c_q @ p["q_b_proj.weight"].T).reshape(b, t, heads, -1)
    kv_a = x @ p["kv_a_proj.weight"].T
    c_kv = _rms(kv_a[..., :kv_rank], p["kv_a_norm.gamma"], eps)
    kv = (c_kv @ p["kv_b_proj.weight"].T).reshape(b, t, heads, -1)
    k_r = np.stack([_rotated(row[:, None, :], theta)
                    for row in kv_a[..., kv_rank:]])       # (b, t, 1, rope)
    q_r = np.stack([_rotated(row, theta) for row in q[..., nope:]])
    q = np.concatenate([q[..., :nope], q_r], -1)
    k = np.concatenate([kv[..., :nope],
                        np.repeat(k_r, heads, axis=2)], -1)
    v = kv[..., nope:]
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    o = np.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, t, -1)
    return o @ p["out_proj.weight"].T


def _latent(units, heads, q_rank, kv_rank, nope, rope, seed=4):
    mx.random.seed(seed)
    attn = nn.LatentAttention(units, heads, q_rank, kv_rank, nope, rope,
                              nope + rope, rope_theta=1e4, epsilon=1e-5)
    attn.initialize()
    # gains away from 1, so that a norm in the wrong place shows
    rs = np.random.RandomState(seed)
    for name in ("q_a_norm", "kv_a_norm"):
        g = getattr(attn, name).gamma
        g.set_data(nd.array(1 + 0.3 * rs.randn(*g.shape)))
    return attn


@pytest.mark.parametrize("t,flash", [(12, False), (256, True)],
                         ids=["dense_path", "flash_kernels_at_256"])
def test_latent_attention_against_a_dense_float32_einsum(t, flash):
    """At T = 256 and a head of 192 + 64 = 256 the call goes to the flash
    kernels (interpreted here: two 128-lane tiles a head); at T = 12 to the
    dense path.  Both are the hand-written equations."""
    heads, q_rank, kv_rank, nope, rope = (2, 24, 16, 192, 64) if flash \
        else (4, 24, 16, 24, 8)
    assert pa.use_flash(t, t, nope + rope, False, 4) == flash
    attn = _latent(64, heads, q_rank, kv_rank, nope, rope)
    assert {n: p.shape for n, p in attn.collect_params().items()} == {
        "q_a_proj.weight": (q_rank, 64), "q_a_norm.gamma": (q_rank,),
        "q_b_proj.weight": (heads * (nope + rope), q_rank),
        "kv_a_proj.weight": (kv_rank + rope, 64),
        "kv_a_norm.gamma": (kv_rank,),
        "kv_b_proj.weight": (heads * (nope + nope + rope), kv_rank),
        "out_proj.weight": (64, heads * (nope + rope))}
    x = np.random.RandomState(1).randn(2, t, 64).astype("float32")
    pos = nd.array(np.arange(t), dtype="int32")
    got = attn(nd.array(x), pos).asnumpy()
    p = {n: v.data().asnumpy().astype(np.float64)
         for n, v in attn.collect_params().items()}
    want = _latent_by_hand(p, x.astype(np.float64), heads, kv_rank, nope,
                           1e4, 1e-5)
    np.testing.assert_allclose(got, want, atol=3e-5)
    assert float(np.abs(want).max()) > 1e-2
    # causal: a later token never moves an earlier position
    x2 = x.copy()
    x2[:, 7] += 1.0
    moved = np.abs(attn(nd.array(x2), pos).asnumpy() - got).max(-1)
    assert (moved[:, :7] == 0).all() and (moved[:, 7:] > 0).all()


def test_latent_attention_names_its_scope_and_the_rules_within_it():
    """Everything between the block's input and its output projection
    carries ``mx.attn.mla``, the low-rank projections too; the rule's own
    scope stands within it, which is how the benchmark's readers tell this
    model's kernel calls from another's."""
    attn = _latent(64, 2, 24, 16, 192, 64)
    x = jnp.zeros((1, 256, 64), jnp.float32)
    pos = nd.array(np.arange(256), dtype="int32")
    text = jax.jit(lambda a: attn(nd.NDArray(a), pos)._data) \
        .lower(x).as_text(debug_info=True)
    assert "mx.attn.mla/mx.attn.causal" in text
    # q_a, q_b, kv_a, kv_b inside; the output projection outside
    assert text.count('jit(<lambda>)/mx.attn.mla/dot_general"') == 4
    assert text.count('jit(<lambda>)/dot_general"') == 1


def test_latent_attention_raises_on_unequal_head_sizes():
    with pytest.raises(MXNetError, match="one head size"):
        nn.LatentAttention(64, 2, 24, 16, 192, 64, 128)     # DeepSeek-V3's


def test_tiles_instant_carries_the_head_size(monkeypatch):
    seen = []
    monkeypatch.setattr(trace, "instant",
                        lambda name, args=None: seen.append((name, args)))
    pa._TILES_NOTED.clear()
    q = jnp.zeros((1, 512, 2, 256), jnp.float32)
    pa.flash_attention(q, q, q, causal=True, interpret=True)
    (name, args), = [s for s in seen if s[0] == "mx.attn.tiles"]
    assert args["head_dim"] == 256 and args["kind"] == "causal"
    assert args["layout"] == "heads" and args["heads_per_step"] == 1
    # the cell's call: 8,192 positions in tiles of 512
    assert pa.tile_counts(8192, 8192, 512, 512, True) == (136, 120, 16)


# ------------------------------------------------------ the selection bias
def _router(n=64, d=16, experts=8, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    return jax.random.normal(k[0], (n, d)), \
        0.5 * jax.random.normal(k[1], (experts, d))


def test_selection_bias_chooses_and_does_not_weigh():
    """A bias that flips a choice changes WHICH experts run; the chosen
    experts' weights are made from the scores alone."""
    x, gate = _router()
    plain = moe.route(x, gate, 2, 0, 8, True, "sigmoid", 1.8)
    # a large bias on expert 5: every position now picks it
    bias = jnp.zeros(8).at[5].set(10.0)
    biased = moe.route(x, gate, 2, 0, 8, True, "sigmoid", 1.8, bias)
    assert int(plain["group_sizes"][5]) < 64 == int(biased["group_sizes"][5])
    assert int(biased["rows"]) == int(plain["rows"]) == 128
    scores = jax.nn.sigmoid(x @ gate.T)
    # by hand: the top 2 of score + bias, weights 1.8 s / sum of chosen s
    top_e = np.argsort(-np.asarray(scores + bias), axis=-1)[:, :2]
    top_s = np.take_along_axis(np.asarray(scores), top_e, axis=-1)
    want = 1.8 * top_s / top_s.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(biased["weights"]), want,
                               rtol=1e-6)
    assert (top_e[:, 0] == 5).all()
    # nowhere does the bias enter a weight: they are convex x 1.8
    np.testing.assert_allclose(np.asarray(biased["weights"]).sum(-1), 1.8,
                               rtol=1e-6)
    # a bias of zeros chooses as no bias does
    zero = moe.route(x, gate, 2, 0, 8, True, "sigmoid", 1.8, jnp.zeros(8))
    np.testing.assert_array_equal(np.asarray(zero["order"]),
                                  np.asarray(plain["order"]))
    np.testing.assert_allclose(np.asarray(zero["weights"]),
                               np.asarray(plain["weights"]), rtol=1e-6)
    # a small bias flips only near ties, and the flipped position's weights
    # are still its chosen experts' scores
    small = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (8,))
    nudged = moe.route(x, gate, 2, 0, 8, True, "sigmoid", 1.8, small)
    e0 = np.argsort(-np.asarray(scores), -1)[:, :2]
    e1 = np.argsort(-np.asarray(scores + small), -1)[:, :2]
    flipped = (np.sort(e0, -1) != np.sort(e1, -1)).any(-1)
    assert 0 < flipped.sum() < 32
    s1 = np.take_along_axis(np.asarray(scores), e1, axis=-1)
    np.testing.assert_allclose(np.asarray(nudged["weights"]),
                               1.8 * s1 / s1.sum(-1, keepdims=True),
                               rtol=1e-6)


def test_the_bias_gets_no_gradient_and_the_scores_get_theirs():
    x, gate = _router()
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (8,))

    def f(gate, bias):
        return jnp.sum(moe.route(x, gate, 2, 0, 8, True, "sigmoid", 1.8,
                                 bias)["weights"] ** 2)

    d_gate, d_bias = jax.grad(f, argnums=(0, 1))(gate, bias)
    assert float(jnp.abs(d_bias).max()) == 0.0
    assert float(jnp.abs(d_gate).max()) > 1e-4
    # the select over the experts is the gather's gradient
    def g(gate):
        s = jax.nn.sigmoid(x @ gate.T)
        _, e = jax.lax.top_k(s + bias, 2)
        top = jnp.take_along_axis(s, e, axis=-1)
        return jnp.sum((1.8 * top / top.sum(-1, keepdims=True)) ** 2)

    np.testing.assert_allclose(d_gate, jax.grad(g)(gate), atol=1e-6)


@pytest.mark.parametrize("model", ["sdar", "laguna"])
def test_without_a_bias_the_layer_is_the_parents_program(model):
    """``bias=None`` adds nothing to SDAR's and Laguna's layers: the traced
    layer holds no add of a bias and no select over the experts, and is the
    jaxpr of the same call with the keyword left out."""
    kw = dict(top_k=2, first=0, activation="silu") if model == "sdar" else \
        dict(top_k=2, first=0, activation="silu", score="sigmoid", scale=2.5)
    k = jax.random.split(jax.random.PRNGKey(1), 8)
    x, gate = jax.random.normal(k[0], (32, 16)), \
        jax.random.normal(k[1], (8, 16))
    w = [0.3 * jax.random.normal(k[2 + i], s) for i, s in enumerate(
        [(4, 16, 8), (4, 8, 16), (4, 16, 8)])]
    shared = {} if model == "sdar" else {
        "shared_w1": w[0][0], "shared_wg": w[2][0], "shared_w2": w[1][0]}

    def layer(x, **extra):
        return moe.moe_forward(x, gate, w[0], w[1], wg=w[2], **shared, **kw,
                               **extra)

    plain = str(jax.make_jaxpr(layer)(x))
    assert plain == str(jax.make_jaxpr(
        lambda x: layer(x, select_bias=None))(x))
    biased = str(jax.make_jaxpr(
        lambda x: layer(x, select_bias=jnp.zeros(8)))(x))
    assert biased != plain and len(biased) > len(plain)


def test_moe_block_holds_the_bias_as_a_frozen_parameter(monkeypatch):
    mx.random.seed(2)
    layer = nn.MoE(8, 16, 32, top_k=2, in_units=32, activation="silu",
                   gated=True, use_bias=False, first=0, count=4,
                   score="sigmoid", scale=1.8, shared_hidden=16,
                   select_bias=True)
    layer.initialize()
    p = layer.collect_params()
    assert p["select_bias"].shape == (8,) \
        and p["select_bias"].grad_req == "null"
    assert "select_bias" not in nn.MoE(8, 16, 32).collect_params()
    x = nd.array(np.random.RandomState(0).randn(2, 24, 32))
    seen = []
    monkeypatch.setattr(trace, "instant",
                        lambda name, args=None: seen.append((name, args)))
    base = layer(x).asnumpy()
    assert [a["bias"] for n, a in seen if n == "mx.moe.layout"] == [True]
    before = layer.load(x)
    # held experts 0..3: a bias on expert 0 sends every position there, and
    # MoE.load and the forward both choose by it
    p["select_bias"].set_data(nd.array(
        np.array([10, 0, 0, 0, 0, 0, 0, 0], "float32")))
    after = layer.load(x)
    assert after[0] == 48 > before[0] and sum(after) >= 48
    assert np.abs(layer(x).asnumpy() - base).max() > 1e-3


# ----------------------------------------------------------- the zoo's model
def _tiny_cfg(**over):
    cfg = {"vocab_size": 96, "hidden_size": 32, "intermediate_size": 48,
           "num_hidden_layers": 3, "first_k_dense_replace": 1,
           "num_attention_heads": 4, "num_key_value_heads": 4,
           "q_lora_rank": 12, "kv_lora_rank": 8, "qk_nope_head_dim": 12,
           "qk_rope_head_dim": 4, "v_head_dim": 16, "rope_theta": 1e6,
           "rms_norm_eps": 1e-5, "n_routed_experts": 4, "router_experts": 8,
           "first_expert": 2, "num_experts_per_tok": 2,
           "moe_intermediate_size": 16, "n_shared_experts": 1,
           "routed_scaling_factor": 1.8, "norm_topk_prob": True,
           "topk_method": "noaux_tc", "num_nextn_predict_layers": 1}
    cfg.update(over)
    return cfg


def test_the_zoos_model_is_built_from_the_configs_keys():
    mx.random.seed(7)
    net = glm_moe_lite.GlmMoeLiteForCausalLM(_tiny_cfg())
    net.initialize()
    shapes = {n: p.shape for n, p in net.collect_params().items()}
    assert shapes["layers.0.attention.q_b_proj.weight"] == (4 * 16, 12)
    assert shapes["layers.0.attention.kv_a_proj.weight"] == (8 + 4, 32)
    assert shapes["layers.0.attention.kv_b_proj.weight"] == (4 * 28, 8)
    assert shapes["layers.0.mlp.up_proj.weight"] == (48, 32)
    assert "layers.0.moe.gate" not in shapes
    assert shapes["layers.1.moe.gate"] == (8, 32)
    assert shapes["layers.2.moe.w1"] == (4, 32, 16)
    assert shapes["layers.2.moe.shared_w2"] == (16, 32)
    assert shapes["layers.2.moe.select_bias"] == (8,)
    assert shapes["mtp.proj.weight"] == (32, 64)
    assert shapes["mtp.layer.moe.select_bias"] == (8,)
    assert {"mtp.hidden_norm.gamma", "mtp.embed_norm.gamma",
            "mtp.norm.gamma"} <= set(shapes)
    # ONE embedding and ONE head, used by both paths
    assert [n for n in shapes if n.endswith("embed.weight")] \
        == ["embed.weight"]
    assert [n for n in shapes if "head" in n] == ["head.weight"]
    ids = np.random.RandomState(0).randint(0, 96, (2, 17)).astype("int32")
    main, module = (o.asnumpy() for o in net(nd.array(ids, dtype="int32")))
    assert main.shape == module.shape == (2, 16, 96)
    # causal: the main logits at t see ids up to t, the module's up to t + 1
    ids2 = ids.copy()
    ids2[:, 9] = (ids2[:, 9] + 1) % 96
    main2, module2 = (o.asnumpy()
                      for o in net(nd.array(ids2, dtype="int32")))
    moved, moved_m = np.abs(main2 - main).max(-1), \
        np.abs(module2 - module).max(-1)
    assert (moved[:, :9] == 0).all() and (moved[:, 9:] > 0).all()
    assert (moved_m[:, :8] == 0).all() and (moved_m[:, 8:] > 0).all()
    # without a module the model is the main model
    plain = glm_moe_lite.GlmMoeLiteForCausalLM(
        _tiny_cfg(num_nextn_predict_layers=0))
    assert not any(n.startswith("mtp.") for n in plain.collect_params())
    with pytest.raises(MXNetError, match="prediction modules"):
        glm_moe_lite.GlmMoeLiteForCausalLM(
            _tiny_cfg(num_nextn_predict_layers=2))


def test_the_loss_has_two_terms_over_the_same_positions():
    rs = np.random.RandomState(0)
    main, module = rs.randn(2, 5, 7), rs.randn(2, 5, 7)
    labels = rs.randint(0, 7, (2, 6))

    def ce(logits, y):
        logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
        return -np.take_along_axis(logp, y[..., None], -1).mean()

    got = float(glm_moe_lite.mtp_loss(
        (jnp.asarray(main), jnp.asarray(module)), jnp.asarray(labels)))
    want = ce(main, labels[:, :-1]) + 0.1 * ce(module, labels[:, 1:])
    assert got == pytest.approx(want, rel=1e-5)
    assert glm_moe_lite.MTP_LOSS_WEIGHT == 0.1
    heavy = float(glm_moe_lite.mtp_loss(
        (jnp.asarray(main), jnp.asarray(module)), jnp.asarray(labels),
        weight=0.3))
    assert heavy == pytest.approx(
        ce(main, labels[:, :-1]) + 0.3 * ce(module, labels[:, 1:]), rel=1e-5)


def test_recomputed_layers_give_the_same_step_as_kept_ones_and_train(
        monkeypatch):
    rs = np.random.RandomState(1)
    ids = rs.randint(0, 96, (2, 18)).astype("int32")
    x, y = jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])
    seen = []
    monkeypatch.setattr(trace, "instant",
                        lambda name, args=None: seen.append((name, args)))
    losses, biases, drawn = {}, {}, 0.02 * rs.randn(8)
    for recompute in (False, True):
        mx.random.seed(9)
        net = glm_moe_lite.GlmMoeLiteForCausalLM(_tiny_cfg(),
                                                 recompute=recompute)
        net.initialize()
        bias = net.layers[1].moe.select_bias
        bias.set_data(nd.array(drawn))
        biases[recompute] = bias.data().asnumpy().copy()
        tr = parallel.FusedTrainer(
            net, loss_fn=glm_moe_lite.mtp_loss, optimizer="adam",
            optimizer_params={"learning_rate": 1e-3})
        losses[recompute] = [float(tr.step(x, y).asnumpy())
                             for _ in range(3)]
        text = tr._lower(x, y).as_text(debug_info=True)
        assert ("optimization_barrier" in text) == recompute
        assert "mx.mtp" in text and "mx.attn.mla" in text
        # the bias is not trained: no optimizer state, no change
        state = tr.state_dict()
        assert not any("select_bias" in n for n in state["opt_state"])
        np.testing.assert_array_equal(
            np.asarray(state["params"]["layers.1.moe.select_bias"]),
            biases[recompute])
        assert "embed.weight" in state["opt_state"] \
            and "head.weight" in state["opt_state"]
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-6)
    assert losses[True][2] < losses[True][0]
    # two cross-entropies near log(vocabulary), the second weighing 0.1
    assert abs(losses[True][0] - 1.1 * math.log(96)) < 0.6
    layouts = [a for n, a in seen if n == "mx.mtp.layout"]
    assert layouts and layouts[0] == {"depth": 1, "weight": 0.1}
    mla = [a for n, a in seen if n == "mx.mla.layout"]
    assert mla and mla[0] == {"heads": 4, "q_rank": 12, "kv_rank": 8,
                              "nope": 12, "rope": 4, "v": 16,
                              "form": "expanded"}


def test_embedding_and_head_gradients_are_sums_of_two_paths():
    """With the module's loss weighing 0 its path adds nothing; with the
    main loss cut off (its logits stopped) the main path adds nothing to the
    head; the whole gradient is the sum of the two."""
    mx.random.seed(5)
    net = glm_moe_lite.GlmMoeLiteForCausalLM(_tiny_cfg())
    net.initialize()
    ids = np.random.RandomState(2).randint(0, 96, (2, 18)).astype("int32")
    x, y = jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])
    apply_fn, params = net.export_pure(training=True)
    key = jax.random.PRNGKey(0)

    def grads(main_w, module_w):
        def loss(p):
            (main, module), _ = apply_fn(p, key, x)
            return main_w * glm_moe_lite.next_token_loss((main,), y[:, :-1]) \
                + module_w * glm_moe_lite.next_token_loss((module,),
                                                          y[:, 1:])

        return jax.grad(loss)(params)

    both, main_only, module_only = grads(1.0, 0.1), grads(1.0, 0.0), \
        grads(0.0, 0.1)
    for name in ("embed.weight", "head.weight"):
        a, b = np.asarray(main_only[name]), np.asarray(module_only[name])
        assert np.abs(a).max() > 1e-5 and np.abs(b).max() > 1e-6
        np.testing.assert_allclose(np.asarray(both[name]), a + b, atol=1e-6)
    # the module's own weights see the module's loss alone
    assert float(jnp.abs(main_only["mtp.proj.weight"]).max()) == 0.0
    assert float(jnp.abs(module_only["mtp.proj.weight"]).max()) > 0.0
