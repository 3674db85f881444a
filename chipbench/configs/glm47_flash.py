"""Builder of the ``glm47_flash`` configuration: weights and batches from a
key, the program's trainer, and the counts of model operations.

Only ``make_trainer`` touches the program (``mxnet_tpu``).  Weights are
named as the program names its parameters, because that is how they are
handed to it; the plain reference reads the same dict by the same names.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

UNIT = "tokens"


def _layer_shapes(cfg, prefix, sparse):
    """One decoder layer's {name: (shape, kind)}: latent attention and a
    dense MLP or the experts held here with router, selection bias and
    shared expert."""
    c, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    a = prefix + "attention."
    out = {prefix + "input_norm.gamma": ((c,), "ones"),
           a + "q_a_proj.weight": ((rq, c), "normal"),
           a + "q_a_norm.gamma": ((rq,), "ones"),
           a + "q_b_proj.weight": ((h * (nope + rope), rq), "normal"),
           a + "kv_a_proj.weight": ((rkv + rope, c), "normal"),
           a + "kv_a_norm.gamma": ((rkv,), "ones"),
           a + "kv_b_proj.weight": ((h * (nope + dv), rkv), "normal"),
           a + "out_proj.weight": ((c, h * dv), "normal"),
           prefix + "post_norm.gamma": ((c,), "ones")}
    if not sparse:
        wide = cfg["intermediate_size"]
        out[prefix + "mlp.gate_proj.weight"] = ((wide, c), "normal")
        out[prefix + "mlp.up_proj.weight"] = ((wide, c), "normal")
        out[prefix + "mlp.down_proj.weight"] = ((c, wide), "normal")
        return out
    held, ff = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    s = cfg["n_shared_experts"] * ff
    m = prefix + "moe."
    out.update({m + "gate": ((cfg["router_experts"], c), "normal"),
                m + "select_bias": ((cfg["router_experts"],), "bias"),
                m + "w1": ((held, c, ff), "normal"),
                m + "wg": ((held, c, ff), "normal"),
                m + "w2": ((held, ff, c), "normal"),
                m + "shared_w1": ((c, s), "normal"),
                m + "shared_wg": ((c, s), "normal"),
                m + "shared_w2": ((s, c), "normal")})
    return out


def weight_shapes(cfg):
    """{program parameter name: (shape, kind)}; kind is how it is drawn:
    ``normal`` N(0, initializer_range), ``ones``, ``bias`` (a selection
    bias: N(0, select_bias_std), not trained)."""
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed.weight": ((v, c), "normal")}
    for i in range(cfg["num_hidden_layers"]):
        out.update(_layer_shapes(cfg, "layers.%d." % i,
                                 i >= cfg["first_k_dense_replace"]))
    out["norm.gamma"] = ((c,), "ones")
    out["head.weight"] = ((v, c), "normal")
    out["mtp.hidden_norm.gamma"] = ((c,), "ones")
    out["mtp.embed_norm.gamma"] = ((c,), "ones")
    out["mtp.proj.weight"] = ((c, 2 * c), "normal")
    out.update(_layer_shapes(cfg, "mtp.layer.", True))
    out["mtp.norm.gamma"] = ((c,), "ones")
    return out


def make_weights(cfg, key):
    """All weights in float32 (the masters the optimizer keeps) from one
    key; traced inside the harness's one jitted set-up call."""
    std = {"normal": cfg["initializer_range"],
           "bias": cfg["select_bias_std"]}
    out = {}
    for i, (name, (shape, kind)) in enumerate(weight_shapes(cfg).items()):
        if kind == "ones":
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            out[name] = std[kind] * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
    return out


def make_batch(cfg, traffic, key):
    """One batch ``(x, y)``: ``T + 2`` ids a sequence, uniform over the
    vocabulary slice; x the first ``T + 1`` (the main model's ``T`` inputs
    and, shifted by one, the prediction module's), y the ``T + 1`` that
    follow the first (the main model's labels and, shifted by one, the
    module's)."""
    ids = jax.random.randint(key, (traffic["batch"], traffic["seq"] + 2), 0,
                             cfg["vocab_size"], jnp.int32)
    return ids[:, :-1], ids[:, 1:]


def allowed_pairs(seq):
    """Query-key pairs a head that the causal rule allows, of ``seq**2``."""
    return seq * (seq + 1) // 2


def forward_ops_per_position(cfg, traffic, sparse):
    """Forward operations (multiply-adds x 2) of one decoder layer on one
    position: the latent block's five projections, the ALLOWED attention
    pairs at a head's 256 dimensions, and the feed-forward: the dense
    gated MLP, or the router, the shared expert and the EXPECTED rows of
    this chip's share of the experts (``top_k * held / router_experts`` a
    position)."""
    c, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    proj = 2 * (c * rq + rq * h * (nope + rope) + c * (rkv + rope)
                + rkv * h * (nope + dv) + h * dv * c)
    pairs = allowed_pairs(traffic["seq"]) / traffic["seq"]
    attn = 2 * h * (nope + rope + dv) * pairs
    if not sparse:
        return proj + attn + 3 * 2 * c * cfg["intermediate_size"]
    ff = cfg["moe_intermediate_size"]
    rows = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["router_experts"]
    return proj + attn + 2 * c * cfg["router_experts"] \
        + (rows + cfg["n_shared_experts"]) * 3 * 2 * c * ff


def ops_per_step(cfg, traffic):
    """Model operations of one training step (forward x 3 for forward,
    input gradients and weight gradients; nothing recomputed): the decoder
    layers, the prediction module (``W_eh`` and one sparse layer) and the
    head TWICE, once a set of logits.

    The expert rows are the EXPECTATION under a balanced router (4,096 a
    sparse layer at the cell's size), as for ``sdar_30b_a3b``: the count is
    made from the cell's files alone and cannot see a batch."""
    positions = traffic["batch"] * traffic["seq"]
    c = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"]
    layers = dense * forward_ops_per_position(cfg, traffic, False) \
        + (cfg["num_hidden_layers"] - dense + 1) \
        * forward_ops_per_position(cfg, traffic, True)
    module = 2 * 2 * c * c
    heads = 2 * 2 * c * cfg["vocab_size"]
    return 3.0 * positions * (layers + module + heads)


def units_per_step(cfg, traffic):
    """Tokens a step, counted once: the prediction module predicts a
    second token a position and moves no more of them."""
    return traffic["batch"] * traffic["seq"]


def attention_calls(cfg, traffic, chips):
    """What the attention kernels' calls work on, by the name of the scope
    the program's latent block opens (``mx.attn.mla``): batch on one chip,
    heads (as many KV heads: the expanded form groups nothing), sequence
    length, a head's size, the pairs a head that the causal rule allows,
    and how many layers make such a call (the prediction module's is one
    of them)."""
    return {"mla": {
        "batch": traffic["batch"] // chips,
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "seq": traffic["seq"],
        "head_dim": cfg["v_head_dim"], "window": None,
        "layers": cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"],
        "pairs": allowed_pairs(traffic["seq"])}}


def make_trainer(cfg, weights, mesh):
    """The program under test: the zoo's GlmMoeLiteForCausalLM under
    FusedTrainer with the two-term loss, holding the harness's weights."""
    from mxnet_tpu import parallel
    try:
        from mxnet_tpu.gluon.model_zoo import glm_moe_lite
    except ImportError:
        raise SystemExit("glm47_flash: this program has no "
                         "gluon.model_zoo.glm_moe_lite; it cannot run the "
                         "configuration") from None

    net = glm_moe_lite.GlmMoeLiteForCausalLM(
        cfg, recompute=cfg["recompute_layers"])
    params = net.collect_params()
    if set(params) != set(weights):
        raise SystemExit("glm47_flash: the program's parameters are not the "
                         "ones this builder makes: %s"
                         % sorted(set(params) ^ set(weights))[:6])
    for name, p in params.items():
        p.set_data(weights[name])
    opt = dict(cfg["optimizer"])
    return parallel.FusedTrainer(
        net, loss_fn=functools.partial(glm_moe_lite.mtp_loss,
                                       weight=cfg["mtp_loss_weight"]),
        optimizer=opt.pop("name"), optimizer_params=opt,
        dtype=cfg["compute_dtype"], mesh=mesh)


def program_names(weights):
    """weight name -> the trainer's parameter name."""
    return {n: n for n in weights}


def first_gradient(cfg, state_leaf):
    """The gradient the optimizer was given in step 1, from its state after
    that step: Adam's first moment is (1 - beta1) * g."""
    m, _v = state_leaf
    return m / (1.0 - cfg["optimizer"]["beta1"])
