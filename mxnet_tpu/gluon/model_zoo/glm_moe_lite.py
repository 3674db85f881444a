"""GLM-4.7-Flash's architecture (``glm4_moe_lite``; config:
https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json): a
causal language model whose every layer attends through low-rank latents
(multi-head latent attention, ``gluon.nn.LatentAttention``, in the expanded
form), over a mixture-of-experts decoder, trained with one multi-token
prediction module (DeepSeek-V3, arXiv:2412.19437 section 2.2).

Pre-norm decoder layers, RMSNorm, no bias, untied head.  The first
``first_k_dense_replace`` layers' feed-forward is a dense gated SiLU MLP;
the others hold ``n_routed_experts`` small gated experts chosen by a
sigmoid router under a SELECTION bias (``topk_method: noaux_tc``: the
``num_experts_per_tok`` largest of ``score + bias``; the weights are the
chosen scores alone, normalised, times ``routed_scaling_factor``; the bias
gets no gradient) beside ``n_shared_experts`` shared experts, added
unscaled.

The prediction module (``num_nextn_predict_layers`` 1) sits after the main
model: ``h' = [RMSNorm(h) ; RMSNorm(Emb(next token))] W_eh``, one more
sparse decoder layer, its own final RMSNorm, and the main model's head;
``Emb`` is the main model's embedding.  Embedding and head are each ONE
parameter used twice, so their gradients are sums of two paths.

What the published config leaves open is read as the configuration
``chipbench/configs/glm47_flash.json`` says under ``assumed``: rotary in
the rotate-half form over all ``qk_rope_head_dim`` dimensions; the module
reads the main model's hidden states AFTER its final norm and concatenates
``[hidden ; embedding]``; the module's loss weighs 0.1.

``first_expert``/``n_routed_experts`` against ``router_experts`` give one
chip's share of every layer's experts under expert parallelism
(``gluon.nn.MoE``); the vocabulary may be a slice (ids, logits and losses
are then over it).
"""
from __future__ import annotations

import jax

from .. import nn
from ..block import HybridBlock
from ... import ndarray as nd, trace as _trace
from ...base import MXNetError
from .laguna import next_token_loss

__all__ = ["GlmMoeLiteForCausalLM", "mtp_loss", "MTP_LOSS_WEIGHT"]

# lambda of the prediction module's loss: the late-stage value of the
# DeepSeek-V3 report (config.json has no key for it)
MTP_LOSS_WEIGHT = 0.1


def _decoder_layer(cfg, sparse, recompute):
    units, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    attention = nn.LatentAttention(
        units, cfg["num_attention_heads"], cfg["q_lora_rank"],
        cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
        cfg["qk_rope_head_dim"], cfg["v_head_dim"],
        rope_theta=cfg["rope_theta"], epsilon=eps)
    if sparse:
        feed_forward = {"moe": nn.MoE(
            cfg.get("router_experts", cfg["n_routed_experts"]),
            cfg["moe_intermediate_size"], units,
            top_k=cfg["num_experts_per_tok"], in_units=units,
            activation="silu", gated=True, use_bias=False,
            first=cfg.get("first_expert", 0), count=cfg["n_routed_experts"],
            norm_topk=cfg["norm_topk_prob"], score="sigmoid",
            scale=cfg["routed_scaling_factor"],
            shared_hidden=cfg["n_shared_experts"]
            * cfg["moe_intermediate_size"],
            select_bias=cfg.get("topk_method") == "noaux_tc")}
    else:
        feed_forward = {"mlp": nn.GatedMLP(units, cfg["intermediate_size"])}
    return nn.DecoderLayer(units, attention, epsilon=eps,
                           recompute=recompute, **feed_forward)


class _PredictionModule(HybridBlock):
    """The parameters of one multi-token prediction module and its forward
    up to its final norm: forward(h, emb, positions), ``h`` the main
    model's hidden states and ``emb`` the embeddings of the tokens that
    follow them, both (B, T, units)."""

    def __init__(self, cfg):
        super().__init__()
        units, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self.hidden_norm = nn.RMSNorm(epsilon=eps, in_channels=units)
        self.embed_norm = nn.RMSNorm(epsilon=eps, in_channels=units)
        self.proj = nn.Dense(units, use_bias=False, flatten=False,
                             in_units=2 * units)
        self.layer = _decoder_layer(cfg, sparse=True, recompute=False)
        self.norm = nn.RMSNorm(epsilon=eps, in_channels=units)

    def forward(self, h, emb, positions):
        x = self.proj(nd.concat(self.hidden_norm(h), self.embed_norm(emb),
                                dim=-1))
        return self.norm(self.layer(x, positions))


class GlmMoeLiteForCausalLM(HybridBlock):
    """forward(ids): (B, T + 1) int ids -> ``(main logits, MTP logits)``,
    both (B, T, vocab): the main model runs over the first ``T`` ids and
    predicts each one's successor; the prediction module reads the main
    model's hidden states with the embeddings of the LAST ``T`` ids (each
    position's successor) and predicts the token after that.  Without a
    module (``num_nextn_predict_layers`` 0): (B, T) ids -> main logits.

    ``cfg`` holds the published config's keys; ``n_routed_experts`` is the
    count HELD here, of ``router_experts`` (default: all) from
    ``first_expert`` on.  ``recompute`` runs every decoder layer, and the
    prediction module as a whole, under ``jax.checkpoint`` in a traced
    program."""

    def __init__(self, cfg, recompute=False):
        super().__init__()
        units, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        depth = cfg.get("num_nextn_predict_layers", 0)
        if depth not in (0, 1):
            raise MXNetError("glm_moe_lite: %d prediction modules; one is "
                             "what this model has" % depth)
        self._recompute = bool(recompute)
        self._mtp_weight = cfg.get("mtp_loss_weight", MTP_LOSS_WEIGHT)
        self._laid_out = False
        self.embed = nn.Embedding(cfg["vocab_size"], units)
        self.layers = nn.HybridSequential()
        for i in range(cfg["num_hidden_layers"]):
            self.layers.add(_decoder_layer(
                cfg, i >= cfg["first_k_dense_replace"], recompute))
        self.norm = nn.RMSNorm(epsilon=eps, in_channels=units)
        self.head = nn.Dense(cfg["vocab_size"], use_bias=False,
                             flatten=False, in_units=units)
        self.mtp = _PredictionModule(cfg) if depth else None

    def _predict(self, h, emb, positions):
        """The prediction module's logits, under the scope ``mx.mtp``;
        recomputed in the backward like a decoder layer."""
        def module(h_, emb_, positions_):
            with jax.named_scope("mx.mtp"):
                return self.head(self.mtp(h_, emb_, positions_))

        return nn.recomputed(module, self._recompute, h, emb, positions)

    def forward(self, ids):
        if self.mtp is None:
            following = None
        else:
            ids, following = ids[:, :-1], ids[:, 1:]
            if not self._laid_out:
                self._laid_out = True
                _trace.instant("mx.mtp.layout", args={
                    "depth": 1, "weight": self._mtp_weight})
        h = self.embed(ids)
        positions = nd.arange(ids.shape[1], dtype="int32")
        for layer in self.layers:
            h = layer(h, positions)
        h = self.norm(h)
        if following is None:
            return self.head(h)
        return self.head(h), self._predict(h, self.embed(following),
                                           positions)


def mtp_loss(outs, labels, weight=MTP_LOSS_WEIGHT):
    """``loss_fn`` for ``parallel.FusedTrainer`` over ``forward``'s two
    sets of logits: ``labels`` (B, T + 1) are the ids that follow the main
    model's ``T`` inputs; the main logits are held against the first ``T``
    of them (token ``t + 1``), the module's against the last ``T`` (token
    ``t + 2``), each a mean over the ``B T`` positions
    (``laguna.next_token_loss``), the module's times ``weight``."""
    main, module = outs
    return next_token_loss((main,), labels[:, :-1]) \
        + weight * next_token_loss((module,), labels[:, 1:])
