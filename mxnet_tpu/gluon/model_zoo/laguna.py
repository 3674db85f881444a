"""Laguna: a causal language model whose layers mix full and sliding-window
attention over a mixture-of-experts decoder (poolside's ``laguna``; config
of Laguna-XS.2: https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json).

Pre-norm decoder layers, RMSNorm, no bias, untied head.  ``layer_types``
gives every layer its attention: ``full_attention`` is causal, with YaRN
rotary positions over a leading part of each head
(``partial_rotary_factor``); ``sliding_attention`` is causal over the last
``sliding_window`` keys with plain rotary positions, and has its own count
of query heads (``num_attention_heads_per_layer``) over the same KV heads.
Every layer's attention output passes a per-head sigmoid gate; there is no
QK-norm.  ``mlp_layer_types`` gives every layer its feed-forward: ``dense``
is a gated SiLU MLP, ``sparse`` a mixture of small gated experts chosen by a
sigmoid router (the chosen scores normalised, times
``moe_routed_scaling_factor``) beside one shared expert.

What the published config leaves open is read as the configuration
``chipbench/configs/laguna_xs2.json`` says under ``assumed``: the gate is
per head, the router is DeepSeek-V3's without groups or bias, the shared
expert is added unscaled.

``first_expert``/``num_experts`` against ``router_experts`` give one chip's
share of every layer's experts under expert parallelism (``gluon.nn.MoE``);
the vocabulary may be a slice (ids, logits and loss are then over it).
"""
from __future__ import annotations

import math

from .. import nn
from ..block import HybridBlock
from ... import ndarray as nd

__all__ = ["LagunaForCausalLM", "next_token_loss", "yarn_inv_freq"]


def yarn_inv_freq(dim, rope_theta, factor, original_max_position_embeddings,
                  beta_fast=32, beta_slow=1, **_other):
    """The ``dim / 2`` inverse frequencies of a YaRN-scaled rotary (Peng et
    al. arXiv:2309.00071) as ``transformers`` computes them for
    ``rope_type: yarn``: the plain frequencies where a dimension turns more
    than ``beta_fast`` times over the original context, those divided by
    ``factor`` where it turns less than ``beta_slow`` times, a linear ramp
    between.  They do not depend on the sequence's length."""
    def turns_at(n):
        return dim * math.log(original_max_position_embeddings
                              / (n * 2 * math.pi)) \
            / (2 * math.log(rope_theta))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(dim // 2):
        plain = rope_theta ** (-2.0 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(plain / factor * ramp + plain * (1.0 - ramp))
    return tuple(out)


def _rotary(rope, head_dim):
    """``GroupedQueryAttention``'s rotary keywords from one entry of the
    config's ``rope_parameters``."""
    dim = int(head_dim * rope.get("partial_rotary_factor", 1))
    if rope.get("rope_type", "default") == "default":
        return rope["rope_theta"], {"rotary_dim": dim}
    if rope["rope_type"] != "yarn":
        raise ValueError("laguna: rope_type %r" % (rope["rope_type"],))
    return rope["rope_theta"], {
        "rotary_dim": dim, "inv_freq": yarn_inv_freq(dim, **rope),
        "factor": rope["attention_factor"]}


class LagunaForCausalLM(HybridBlock):
    """forward(ids): (B, T) int ids -> logits (B, T, vocab).  ``cfg`` holds
    the published config's keys; ``num_experts`` is the count HELD here,
    of ``router_experts`` (default: all) from ``first_expert`` on.
    ``recompute`` runs every decoder layer under ``jax.checkpoint`` in a
    traced program."""

    def __init__(self, cfg, recompute=False):
        super().__init__()
        units, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self.embed = nn.Embedding(cfg["vocab_size"], units)
        self.layers = nn.HybridSequential()
        for kind, heads, ffn in zip(cfg["layer_types"],
                                    cfg["num_attention_heads_per_layer"],
                                    cfg["mlp_layer_types"]):
            theta, rotary = _rotary(cfg["rope_parameters"][kind],
                                    cfg["head_dim"])
            attention = nn.GroupedQueryAttention(
                units, heads, cfg["num_key_value_heads"], cfg["head_dim"],
                rope_theta=theta, epsilon=eps, qk_norm=False,
                gate=bool(cfg["gating"]), rotary=rotary, causal=True,
                window=cfg["sliding_window"]
                if kind == "sliding_attention" else None)
            if ffn == "dense":
                feed_forward = {"mlp": nn.GatedMLP(
                    units, cfg["intermediate_size"])}
            else:
                feed_forward = {"moe": nn.MoE(
                    cfg.get("router_experts", cfg["num_experts"]),
                    cfg["moe_intermediate_size"], units,
                    top_k=cfg["num_experts_per_tok"], in_units=units,
                    activation="silu", gated=True, use_bias=False,
                    first=cfg.get("first_expert", 0),
                    count=cfg["num_experts"], score="sigmoid",
                    scale=cfg["moe_routed_scaling_factor"],
                    shared_hidden=cfg["shared_expert_intermediate_size"])}
            self.layers.add(nn.DecoderLayer(
                units, attention, epsilon=eps, recompute=recompute,
                **feed_forward))
        self.norm = nn.RMSNorm(epsilon=eps, in_channels=units)
        self.head = nn.Dense(cfg["vocab_size"], use_bias=False,
                             flatten=False, in_units=units)

    def forward(self, ids):
        h = self.embed(ids)
        positions = nd.arange(ids.shape[1], dtype="int32")
        for layer in self.layers:
            h = layer(h, positions)
        return self.head(self.norm(h))


def next_token_loss(outs, labels):
    """``loss_fn`` for ``parallel.FusedTrainer``: the mean over the ``B T``
    positions of the cross-entropy of position ``t``'s logits against
    ``labels[:, t]``, the token that follows it; float32."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(outs[0].astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, labels[..., None].astype(jnp.int32), axis=-1))
