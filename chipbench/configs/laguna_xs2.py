"""Builder of the ``laguna_xs2`` configuration: weights and batches from a
key, the program's trainer, and the counts of model operations.

Only ``make_trainer`` touches the program (``mxnet_tpu``).  Weights are
named as the program names its parameters, because that is how they are
handed to it; the plain reference reads the same dict by the same names.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

UNIT = "tokens"
# attention rule of a layer type, as the program's scopes and the readers
# name it (``mx.attn.<kind>``)
KINDS = {"full_attention": "causal", "sliding_attention": "window"}


def weight_shapes(cfg):
    """{program parameter name: (shape, kind)}; kind is how it is drawn."""
    c, v, d = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    kv, held = cfg["num_key_value_heads"], cfg["num_experts"]
    ff, s = cfg["moe_intermediate_size"], \
        cfg["shared_expert_intermediate_size"]
    out = {"embed.weight": ((v, c), "normal")}
    for i, (h, ffn) in enumerate(zip(cfg["num_attention_heads_per_layer"],
                                     cfg["mlp_layer_types"])):
        p = "layers.%d." % i
        out[p + "input_norm.gamma"] = ((c,), "ones")
        out[p + "attention.query_proj.weight"] = ((h * d, c), "normal")
        out[p + "attention.key_proj.weight"] = ((kv * d, c), "normal")
        out[p + "attention.value_proj.weight"] = ((kv * d, c), "normal")
        out[p + "attention.out_proj.weight"] = ((c, h * d), "normal")
        out[p + "attention.gate_proj.weight"] = ((h, c), "normal")
        out[p + "post_norm.gamma"] = ((c,), "ones")
        if ffn == "dense":
            wide = cfg["intermediate_size"]
            out[p + "mlp.gate_proj.weight"] = ((wide, c), "normal")
            out[p + "mlp.up_proj.weight"] = ((wide, c), "normal")
            out[p + "mlp.down_proj.weight"] = ((c, wide), "normal")
        else:
            out[p + "moe.gate"] = ((cfg["router_experts"], c), "normal")
            out[p + "moe.w1"] = ((held, c, ff), "normal")
            out[p + "moe.wg"] = ((held, c, ff), "normal")
            out[p + "moe.w2"] = ((held, ff, c), "normal")
            out[p + "moe.shared_w1"] = ((c, s), "normal")
            out[p + "moe.shared_wg"] = ((c, s), "normal")
            out[p + "moe.shared_w2"] = ((s, c), "normal")
    out["norm.gamma"] = ((c,), "ones")
    out["head.weight"] = ((v, c), "normal")
    return out


def make_weights(cfg, key):
    """All weights in float32 (the masters the optimizer keeps) from one
    key; traced inside the harness's one jitted set-up call."""
    std = cfg["initializer_range"]
    out = {}
    for i, (name, (shape, kind)) in enumerate(weight_shapes(cfg).items()):
        if kind == "normal":
            out[name] = std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        else:
            out[name] = jnp.ones(shape, jnp.float32)
    return out


def make_batch(cfg, traffic, key):
    """One batch ``(x, y)``: ``T + 1`` ids a sequence, uniform over the
    vocabulary slice; x the first ``T``, y the ``T`` that follow them."""
    ids = jax.random.randint(key, (traffic["batch"], traffic["seq"] + 1), 0,
                             cfg["vocab_size"], jnp.int32)
    return ids[:, :-1], ids[:, 1:]


def allowed_pairs(kind, seq, window):
    """Query-key pairs a head that one sequence's rule allows, of the
    ``seq**2``: causal ``seq (seq + 1) / 2``; a window of ``w`` keys
    ``w seq - w (w - 1) / 2`` (``w`` clipped to the sequence)."""
    w = seq if kind == "causal" else min(window, seq)
    return w * seq - w * (w - 1) // 2


def forward_ops_per_position(cfg, traffic, layer):
    """Forward operations (multiply-adds x 2) of layer ``layer`` on one
    position: projections and the per-head gate, the ALLOWED attention
    pairs only, and the layer's feed-forward: the dense gated MLP, or the
    router, the shared expert and the EXPECTED rows of this chip's share
    of the experts (``top_k * held / router_experts`` a position)."""
    c, d = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads_per_layer"][layer], \
        cfg["num_key_value_heads"]
    proj = 2 * c * (h * d + 2 * kv * d) + 2 * h * d * c + 2 * c * h
    pairs = allowed_pairs(KINDS[cfg["layer_types"][layer]], traffic["seq"],
                          cfg["sliding_window"]) / traffic["seq"]
    attn = 2 * 2 * h * d * pairs
    if cfg["mlp_layer_types"][layer] == "dense":
        return proj + attn + 3 * 2 * c * cfg["intermediate_size"]
    rows = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"]
    return proj + attn + 2 * c * cfg["router_experts"] \
        + rows * 3 * 2 * c * cfg["moe_intermediate_size"] \
        + 3 * 2 * c * cfg["shared_expert_intermediate_size"]


def ops_per_step(cfg, traffic):
    """Model operations of one training step (forward x 3 for forward,
    input gradients and weight gradients; nothing recomputed).

    The expert rows are the EXPECTATION under a balanced router (8,192 a
    sparse layer, 32,768 a step at the cell's size), as for
    ``sdar_30b_a3b``: the count is made from the cell's files alone and
    cannot see a batch, while untrained routers send more or fewer."""
    positions = traffic["batch"] * traffic["seq"]
    layers = sum(forward_ops_per_position(cfg, traffic, i)
                 for i in range(cfg["num_hidden_layers"]))
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return 3.0 * positions * (layers + head)


def units_per_step(cfg, traffic):
    return traffic["batch"] * traffic["seq"]


def attention_calls(cfg, traffic, chips):
    """What the attention kernels' calls of each rule work on, by the rule's
    name in the program's scopes: batch on one chip, query heads, KV heads,
    sequence length, head size, the window (None: causal), the pairs a head
    that the rule allows, and how many layers make such a call.  The head
    counts of a rule's layers are equal in this model."""
    out = {}
    for kind, heads in zip(cfg["layer_types"],
                           cfg["num_attention_heads_per_layer"]):
        call = out.setdefault(KINDS[kind], {
            "batch": traffic["batch"] // chips, "heads": heads,
            "kv_heads": cfg["num_key_value_heads"], "seq": traffic["seq"],
            "head_dim": cfg["head_dim"], "layers": 0,
            "window": cfg["sliding_window"]
            if KINDS[kind] == "window" else None,
            "pairs": allowed_pairs(KINDS[kind], traffic["seq"],
                                   cfg["sliding_window"])})
        if call["heads"] != heads:
            raise SystemExit("laguna_xs2: %s layers of %d and %d heads"
                             % (kind, call["heads"], heads))
        call["layers"] += 1
    return out


def make_trainer(cfg, weights, mesh):
    """The program under test: the zoo's LagunaForCausalLM under
    FusedTrainer with the next-token loss, holding the harness's weights."""
    from mxnet_tpu import parallel
    try:
        from mxnet_tpu.gluon.model_zoo import laguna
    except ImportError:
        raise SystemExit("laguna_xs2: this program has no "
                         "gluon.model_zoo.laguna; it cannot run the "
                         "configuration") from None

    net = laguna.LagunaForCausalLM(cfg, recompute=cfg["recompute_layers"])
    params = net.collect_params()
    if set(params) != set(weights):
        raise SystemExit("laguna_xs2: the program's parameters are not the "
                         "ones this builder makes: %s"
                         % sorted(set(params) ^ set(weights))[:6])
    for name, p in params.items():
        p.set_data(weights[name])
    opt = dict(cfg["optimizer"])
    return parallel.FusedTrainer(
        net, loss_fn=laguna.next_token_loss, optimizer=opt.pop("name"),
        optimizer_params=opt, dtype=cfg["compute_dtype"], mesh=mesh)


def program_names(weights):
    """weight name -> the trainer's parameter name."""
    return {n: n for n in weights}


def first_gradient(cfg, state_leaf):
    """The gradient the optimizer was given in step 1, from its state after
    that step: Adam's first moment is (1 - beta1) * g."""
    m, _v = state_leaf
    return m / (1.0 - cfg["optimizer"]["beta1"])
