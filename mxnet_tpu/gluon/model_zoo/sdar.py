"""SDAR-MoE: a block-diffusion language model over a mixture-of-experts
decoder (JetLM's ``sdar_moe``; config of SDAR-30B-A3B-Chat:
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json).

The decoder is the Qwen3-MoE family's: pre-norm layers of grouped-KV
attention with per-head QK-norm and rotary positions, and a top-k mixture
of gated SiLU experts without a shared expert; RMSNorm, no bias, untied
head.  What makes it SDAR is the training objective, BD3-LMs' block
diffusion (Arriola et al. arXiv:2503.09573): a sequence ``x0`` of ``L``
tokens in blocks of ``block_length``; each block draws a noise level ``t``
and has its tokens replaced by the mask id with probability ``t`` (``xt``);
the model runs ONCE over ``[xt ; x0]``, ``2L`` positions, position ``i`` at
rotary position ``i mod L``, under the block-diffusion attention rule
(``ops.pallas_attention.block_diffusion_mask``); the loss is the masked
tokens' cross-entropy weighted by ``1/t``, read from the noisy half.

``first_expert``/``experts_held`` give one chip's share of every layer's
experts under expert parallelism (``gluon.nn.MoE``); the vocabulary may be
a slice (ids, mask id, logits and loss are then over the slice).
"""
from __future__ import annotations

from ... import ndarray as nd
from ...ops.pallas_attention import block_diffusion_mask
from .. import nn
from ..block import HybridBlock

__all__ = ["SDARMoE", "block_diffusion_loss"]


class SDARMoE(HybridBlock):
    """forward(xt, x0): (B, L) int ids each -> logits (B, L, vocab) of the
    noisy half; L a multiple of ``block_length``.  ``recompute`` runs every
    decoder layer under ``jax.checkpoint`` in a traced program."""

    def __init__(self, vocab_size, units, num_layers, num_heads,
                 num_kv_heads, head_dim, num_experts, expert_hidden, top_k,
                 block_length=4, first_expert=0, experts_held=None,
                 rope_theta=1e6, epsilon=1e-6, norm_topk=True,
                 recompute=False):
        super().__init__()
        self._block = int(block_length)
        self.embed = nn.Embedding(vocab_size, units)
        self.layers = nn.HybridSequential()
        for _ in range(num_layers):
            self.layers.add(nn.MoEDecoderLayer(
                units, num_heads, num_kv_heads, head_dim, num_experts,
                expert_hidden, top_k, first=first_expert,
                count=experts_held, rope_theta=rope_theta, epsilon=epsilon,
                norm_topk=norm_topk, recompute=recompute))
        self.norm = nn.RMSNorm(epsilon=epsilon, in_channels=units)
        self.head = nn.Dense(vocab_size, use_bias=False, flatten=False,
                             in_units=units)

    def forward(self, xt, x0):
        seq = xt.shape[1]
        mask = block_diffusion_mask(seq, self._block)   # static: from shapes
        h = self.embed(nd.concat(xt, x0, dim=1))
        positions = nd.arange(2 * seq, dtype="int32") % seq
        for layer in self.layers:
            h = layer(h, positions, mask)
        return self.head(self.norm(h[:, :seq]))


def block_diffusion_loss(outs, labels, weights):
    """``loss_fn`` for ``parallel.FusedTrainer``: ``1/(B L) * sum_i w_i *
    CE(logits_i, labels_i)`` with ``w_i = masked_i / t_block(i)`` made by
    the data pipeline (0 where the token was not masked); float32."""
    import jax
    import jax.numpy as jnp

    logits = outs[0].astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return jnp.mean(nll * weights.astype(jnp.float32))
