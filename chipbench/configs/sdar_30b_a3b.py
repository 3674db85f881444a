"""Builder of the ``sdar_30b_a3b`` configuration: weights and batches from a
key, the program's trainer, and the counts of model operations.

Only ``make_trainer`` touches the program (``mxnet_tpu``).  Weights are
named as the program names its parameters, because that is how they are
handed to it; the plain reference reads the same dict by the same names.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

UNIT = "tokens"


def weight_shapes(cfg):
    """{program parameter name: (shape, kind)}; kind is how it is drawn."""
    c, v, d = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    held, ff = cfg["num_experts"], cfg["moe_intermediate_size"]
    out = {"embed.weight": ((v, c), "normal")}
    for i in range(cfg["num_hidden_layers"]):
        p = "layers.%d." % i
        out[p + "input_norm.gamma"] = ((c,), "ones")
        out[p + "attention.query_proj.weight"] = ((h * d, c), "normal")
        out[p + "attention.key_proj.weight"] = ((kv * d, c), "normal")
        out[p + "attention.value_proj.weight"] = ((kv * d, c), "normal")
        out[p + "attention.out_proj.weight"] = ((c, h * d), "normal")
        out[p + "attention.query_norm.gamma"] = ((d,), "ones")
        out[p + "attention.key_norm.gamma"] = ((d,), "ones")
        out[p + "post_norm.gamma"] = ((c,), "ones")
        out[p + "moe.gate"] = ((cfg["router_experts"], c), "normal")
        out[p + "moe.w1"] = ((held, c, ff), "normal")
        out[p + "moe.wg"] = ((held, c, ff), "normal")
        out[p + "moe.w2"] = ((held, ff, c), "normal")
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
    """One batch ``(x, y)``: x = (xt, x0), the noised and the clean ids;
    y = (x0, weights), the labels and ``masked / t`` a token.  ``t`` a
    block ~ U(t_min, 1]; a token is masked with probability ``t``."""
    b, seq, blk = traffic["batch"], traffic["seq"], traffic["block_length"]
    mask_id = cfg["mask_token_id"]
    k = jax.random.split(key, 3)
    x0 = jax.random.randint(k[0], (b, seq), 0, cfg["vocab_size"] - 1,
                            jnp.int32)                 # never the mask id
    t = 1.0 - jax.random.uniform(k[1], (b, seq // blk)) \
        * (1.0 - traffic["t_min"])                     # in (t_min, 1]
    t = jnp.repeat(t, blk, axis=1)
    masked = jax.random.uniform(k[2], (b, seq)) < t
    xt = jnp.where(masked, mask_id, x0).astype(jnp.int32)
    return (xt, x0), (x0, masked.astype(jnp.float32) / t)


def allowed_pairs(traffic):
    """Query-key pairs one sequence's attention rule allows, of the
    ``4 L**2``: ``L**2 + L*b``."""
    seq, blk = traffic["seq"], traffic["block_length"]
    return seq * seq + seq * blk


def forward_ops_per_position_layer(cfg, traffic):
    """Forward operations (multiply-adds x 2) of one layer on one of the
    ``2L`` positions: projections, the allowed attention pairs only, the
    router, and the EXPECTED expert rows of this chip's share
    (``top_k * held / router_experts`` a position)."""
    c, d = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    proj = 2 * c * (h * d + 2 * kv * d) + 2 * h * d * c
    pairs = allowed_pairs(traffic) / (2 * traffic["seq"])   # keys a query
    attn = 2 * 2 * h * d * pairs
    router = 2 * c * cfg["router_experts"]
    rows = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"]
    experts = rows * 3 * 2 * c * cfg["moe_intermediate_size"]
    return proj + attn + router + experts


def ops_per_step(cfg, traffic):
    """Model operations of one training step (forward x 3 for forward,
    input gradients and weight gradients; nothing recomputed).  The head
    runs on the noisy half only.

    The expert rows are the EXPECTATION under a balanced router (98,304 a
    step at the cell's size, 10.8% of the count), as ISSUE 26 defines the
    count; it is made from the cell's files alone and cannot see a batch.
    The untrained routers of this configuration send 83,581 to 142,158
    (PERF.md, PR 26), so the count, and ``step_mfu_pct.tokens`` with it,
    is between 1.6% too high and 4.8% too low, step by step."""
    b, seq = traffic["batch"], traffic["seq"]
    layers = cfg["num_hidden_layers"] * 2 * seq * b \
        * forward_ops_per_position_layer(cfg, traffic)
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"] * b * seq
    return 3.0 * (layers + head)


def units_per_step(cfg, traffic):
    """Sequence tokens, not the doubled positions."""
    return traffic["batch"] * traffic["seq"]


def attention_call(cfg, traffic, chips):
    """What one call of an attention kernel works on: batch on one chip,
    query heads, KV heads, sequence length L (the call sees 2L positions),
    block length, head size."""
    return {"batch": traffic["batch"] // chips,
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "seq": traffic["seq"],
            "block": traffic["block_length"], "head_dim": cfg["head_dim"]}


def make_trainer(cfg, weights, mesh):
    """The program under test: the zoo's SDARMoE under FusedTrainer with the
    block-diffusion loss, holding the harness's weights."""
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.model_zoo import sdar

    net = sdar.SDARMoE(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_experts=cfg["router_experts"],
        expert_hidden=cfg["moe_intermediate_size"],
        top_k=cfg["num_experts_per_tok"], block_length=cfg["block_length"],
        first_expert=cfg["first_expert"], experts_held=cfg["num_experts"],
        rope_theta=cfg["rope_theta"], epsilon=cfg["rms_norm_eps"],
        norm_topk=cfg["norm_topk_prob"], recompute=cfg["recompute_layers"])
    params = net.collect_params()
    if set(params) != set(weights):
        raise SystemExit("sdar_30b_a3b: the program's parameters are not the "
                         "ones this builder makes: %s"
                         % sorted(set(params) ^ set(weights))[:6])
    for name, p in params.items():
        p.set_data(weights[name])
    opt = dict(cfg["optimizer"])
    return parallel.FusedTrainer(
        net, loss_fn=sdar.block_diffusion_loss, optimizer=opt.pop("name"),
        optimizer_params=opt, dtype=cfg["compute_dtype"], mesh=mesh)


def program_names(weights):
    """weight name -> the trainer's parameter name."""
    return {n: n for n in weights}


def first_gradient(cfg, state_leaf):
    """The gradient the optimizer was given in step 1, from its state after
    that step: Adam's first moment is (1 - beta1) * g."""
    m, _v = state_leaf
    return m / (1.0 - cfg["optimizer"]["beta1"])
