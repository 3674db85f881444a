"""Transformer layers.

Reference coverage: the reference's transformer support is only the fused
attention GEMM ops ``_contrib_interleaved_matmul_selfatt_qk/valatt`` and
encdec variants (src/operator/contrib/transformer.cc:650-826) plus masking
utilities — users assembled blocks by hand (gluon-nlp did it downstream).
Here the block layer is first-class and TPU-native:

- the attention core is one fused einsum chain on the MXU
  (ops/nn.py multi_head_attention), with a Pallas flash-attention kernel
  for long sequences; for sequence-parallel long-context training use
  mxnet_tpu.parallel.ring_attention / ulysses_attention directly inside a
  pjit'd step (SURVEY §5.7);
- Dense weights carry tensor-parallel sharding hints (Megatron layout:
  qkv/ffn-in column-parallel over 'tp', out/ffn-out row-parallel) so a
  pjit'd trainer shards the whole block with zero user code.
"""
from __future__ import annotations

import contextlib
import math

from ... import ndarray as nd, trace as _trace
from ...base import MXNetError
from ..block import HybridBlock
from .basic_layers import Dense, Dropout, Embedding, HybridSequential, \
    LayerNorm, RMSNorm
from .moe import MoE
from ...ops import pallas_rotary
from ...ops.pallas_attention import AttnMask, rule_kind, window_mask
from ...ops.registry import apply_op

__all__ = ["MultiHeadAttention", "PositionwiseFFN",
           "TransformerEncoderCell", "TransformerEncoder",
           "PositionalEmbedding", "SinusoidalPositionalEmbedding",
           "GroupedQueryAttention", "LatentAttention", "GatedMLP",
           "DecoderLayer", "MoEDecoderLayer", "recomputed"]


class MultiHeadAttention(HybridBlock):
    """Multi-head (self/cross) attention with TP-sharded projections.

    forward(query, key=None, value=None, mask=None): key/value default to
    query (self-attention).  mask broadcasts against (B, H, Tq, Tk).
    ``dropout`` drops attention *probabilities* (the BERT recipe), active
    only in training mode; it forces the dense attention path.
    """

    def __init__(self, units, num_heads, dropout=0.0, use_bias=True,
                 causal=False, attention_impl="auto", in_units=0, **kwargs):
        super().__init__()
        if units % num_heads:
            raise MXNetError("units %d not divisible by num_heads %d"
                             % (units, num_heads))
        self._units = units
        self._num_heads = num_heads
        self._causal = causal
        self._impl = attention_impl
        self._dropout = dropout
        # column-parallel in-projections, row-parallel out-projection.
        # in_units (when the caller knows the input dim) skips deferred
        # shape resolution — no eager probe pass is needed before jit.
        self.query_proj = Dense(units, use_bias=use_bias, flatten=False,
                                in_units=in_units)
        self.key_proj = Dense(units, use_bias=use_bias, flatten=False,
                              in_units=in_units)
        self.value_proj = Dense(units, use_bias=use_bias, flatten=False,
                                in_units=in_units)
        self.out_proj = Dense(units, use_bias=use_bias, flatten=False,
                              in_units=units)
        self.out_proj.weight.sharding = (None, "tp")
        if self.out_proj.bias is not None:
            self.out_proj.bias.sharding = (None,)

    def forward(self, query, key=None, value=None, mask=None):
        from ... import autograd, random as mxrandom

        key = query if key is None else key
        value = key if value is None else value
        q = self.query_proj(query)
        k = self.key_proj(key)
        v = self.value_proj(value)
        if self._dropout > 0.0 and autograd.is_training():
            # auto-dispatch handles dropout now: long sequences ride the
            # blockwise flash path (per-block mask, no (T,T) buffer)
            attn_kwargs = dict(attn_dropout=self._dropout,
                               dropout_key=mxrandom.take_key(),
                               impl=self._impl)
        else:
            attn_kwargs = dict(impl=self._impl)
        out = nd.multi_head_attention(
            q, k, v, num_heads=self._num_heads, mask=mask,
            causal=self._causal, **attn_kwargs)
        return self.out_proj(out)


class PositionwiseFFN(HybridBlock):
    """Transformer FFN: dense -> activation -> dense (+dropout), Megatron
    TP layout (ffn-in column-parallel, ffn-out row-parallel)."""

    def __init__(self, units, hidden_size, activation="gelu", dropout=0.0,
                 use_bias=True, in_units=0, **kwargs):
        super().__init__()
        self.ffn_1 = Dense(hidden_size, use_bias=use_bias, flatten=False,
                           activation=activation, in_units=in_units)
        self.ffn_2 = Dense(units, use_bias=use_bias, flatten=False,
                           in_units=hidden_size)
        self.ffn_2.weight.sharding = (None, "tp")
        if self.ffn_2.bias is not None:
            self.ffn_2.bias.sharding = (None,)
        self.dropout = Dropout(dropout) if dropout else None

    def forward(self, x):
        out = self.ffn_2(self.ffn_1(x))
        if self.dropout is not None:
            out = self.dropout(out)
        return out


class TransformerEncoderCell(HybridBlock):
    """Pre/post-LN encoder block: MHA + FFN with residuals."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 attention_dropout=0.0, activation="gelu", pre_norm=False,
                 layer_norm_eps=1e-12, causal=False, **kwargs):
        super().__init__()
        self._pre_norm = pre_norm
        # the residual (x + h) pins the cell's input dim to units, so all
        # in_units are static — no deferred-shape probe needed
        self.attention = MultiHeadAttention(units, num_heads,
                                            dropout=attention_dropout,
                                            causal=causal, in_units=units)
        self.attn_ln = LayerNorm(epsilon=layer_norm_eps, in_channels=units)
        self.ffn = PositionwiseFFN(units, hidden_size, activation=activation,
                                   dropout=dropout, in_units=units)
        self.ffn_ln = LayerNorm(epsilon=layer_norm_eps, in_channels=units)
        self.dropout = Dropout(dropout) if dropout else None

    def forward(self, x, mask=None):
        if self._pre_norm:
            h = self.attention(self.attn_ln(x), mask=mask)
            x = x + (self.dropout(h) if self.dropout is not None else h)
            h = self.ffn(self.ffn_ln(x))
            return x + h
        h = self.attention(x, mask=mask)
        if self.dropout is not None:
            h = self.dropout(h)
        x = self.attn_ln(x + h)
        h = self.ffn(x)
        return self.ffn_ln(x + h)


class TransformerEncoder(HybridBlock):
    """Stack of encoder cells."""

    def __init__(self, num_layers, units, hidden_size, num_heads,
                 dropout=0.0, attention_dropout=0.0, activation="gelu",
                 pre_norm=False, layer_norm_eps=1e-12, causal=False,
                 **kwargs):
        super().__init__()
        self._num_layers = num_layers
        self.layers = HybridSequential()
        for _ in range(num_layers):
            self.layers.add(TransformerEncoderCell(
                units, hidden_size, num_heads, dropout=dropout,
                attention_dropout=attention_dropout, activation=activation,
                pre_norm=pre_norm, layer_norm_eps=layer_norm_eps,
                causal=causal))

    def forward(self, x, mask=None):
        for cell in self.layers:
            x = cell(x, mask=mask)
        return x


class PositionalEmbedding(HybridBlock):
    """Learned positional embedding (BERT-style)."""

    def __init__(self, max_length, units, **kwargs):
        super().__init__()
        self.embed = Embedding(max_length, units)
        self._max_length = max_length

    def forward(self, x):
        """x: (B, T, C) token embeddings -> x + pos[:T]."""
        T = x.shape[1]
        if T > self._max_length:
            raise MXNetError(
                "sequence length %d exceeds max_length %d of the learned "
                "positional table" % (T, self._max_length))
        pos = nd.arange(T)
        return x + self.embed(pos).reshape((1, T, -1))


class SinusoidalPositionalEmbedding(HybridBlock):
    """Fixed sin/cos positional encoding (Vaswani et al.)."""

    def __init__(self, units, **kwargs):
        super().__init__()
        self._units = units

    def forward(self, x):
        import jax.numpy as jnp

        T, C = x.shape[1], self._units

        def add_pe(data):
            pos = jnp.arange(T, dtype=jnp.float32)[:, None]
            dim = jnp.arange(0, C, 2, dtype=jnp.float32)[None, :]
            angle = pos / jnp.power(10000.0, dim / C)
            n_cos = C // 2  # odd units: one fewer cos slot than sin
            pe = jnp.zeros((T, C), data.dtype)
            pe = pe.at[:, 0::2].set(jnp.sin(angle).astype(data.dtype))
            pe = pe.at[:, 1::2].set(
                jnp.cos(angle[:, :n_cos]).astype(data.dtype))
            return data + pe[None]

        add_pe.__name__ = "sinusoidal_pe"
        return apply_op(add_pe, x)


def recomputed(fn, recompute, *arrays):
    """``fn(*arrays)``, NDArrays in and one out.  With ``recompute``,
    inside a traced program (``FusedTrainer``, ``hybridize``), under
    ``jax.checkpoint``: the backward keeps ``arrays`` only and runs ``fn``
    again."""
    import jax

    if not (recompute and isinstance(arrays[0]._data, jax.core.Tracer)):
        return fn(*arrays)

    def pure(*datas):
        return fn(*map(nd.NDArray, datas))._data

    return nd.NDArray(jax.checkpoint(pure)(*(a._data for a in arrays)))


def _attn_scope(kind):
    """The scope ``mx.attn.<kind>`` an attention block's own work carries
    in a traced program (``kind`` None: no scope)."""
    import jax

    return jax.named_scope("mx.attn.%s" % kind) if kind \
        else contextlib.nullcontext()


_PLACED_NOTED = set()


def _note_placed(heads, head_dim, rotary, norm, form):
    """One ``mx.attn.placed`` instant a distinct call, written where the
    call is traced: which form gives ``heads`` heads of ``head_dim`` their
    norm and rotary positions, the Pallas pass (``kernel``) or XLA
    operations (``xla``)."""
    args = {"heads": heads, "head_dim": head_dim,
            "rotary_dim": rotary.get("rotary_dim") or head_dim,
            "norm": norm is not None, "form": form}
    if tuple(args.values()) not in _PLACED_NOTED:
        _PLACED_NOTED.add(tuple(args.values()))
        _trace.instant("mx.attn.placed", args=args)


def _placed(h, heads, positions, rotary, norm=None):
    """A projection's ``(B, T, heads * D)`` rows as ``(B, T, heads, D)``
    with their per-head norm and rotary positions (``rotary``: keywords of
    ``nd.rotary_embedding``), as XLA operations."""
    b, t = h.shape[0], h.shape[1]
    h = h.reshape((b, t, heads, -1))
    _note_placed(heads, h.shape[-1], rotary, norm, "xla")
    if norm is not None:
        h = norm(h)
    return nd.rotary_embedding(h, positions, **rotary)


class GroupedQueryAttention(HybridBlock):
    """Self-attention of a modern decoder: ``num_heads`` query heads over
    ``num_kv_heads`` KV heads of ``head_dim`` (each KV head serves a group
    of query heads, never repeated in memory), rotary positions
    (rotate-half form) on q and k, no bias.  Everything is the LAYER's own:
    a model may give its layers different head counts, rotary parameters
    and rules.

    ``qk_norm``: per-head RMSNorm of q and k with learned gains, before
    the rotary positions (the Qwen3 family; False: none).  ``gate``: a
    per-head output gate, ``sigmoid(x W_g)`` (one scalar a head a position,
    from the layer's input) on that head's attention output before the
    output projection (the headwise form of Qiu et al. arXiv:2505.06708).
    ``rotary``: keywords of ``nd.rotary_embedding`` beside ``theta``
    (``rotary_dim``, ``inv_freq``, ``factor``).  ``causal``: query ``i``
    sees keys ``j <= i``; ``window``: of those only the last ``window``.

    forward(x, positions, mask=None): x (B, T, units), positions (T,) or
    (B, T); ``mask`` an array (dense path) or a static
    ``ops.pallas_attention.AttnMask`` that the flash kernels evaluate tile
    by tile, where the rule depends on the call (block diffusion).  The
    layer's rotary, attention and gate work carries the scope
    ``mx.attn.<kind>`` of its rule."""

    def __init__(self, units, num_heads, num_kv_heads=None, head_dim=None,
                 rope_theta=10000.0, epsilon=1e-6, qk_norm=True, gate=False,
                 rotary=None, causal=False, window=None):
        super().__init__()
        num_kv_heads = num_kv_heads or num_heads
        head_dim = head_dim or units // num_heads
        if num_heads % num_kv_heads:
            raise MXNetError("num_heads %d not a multiple of num_kv_heads %d"
                             % (num_heads, num_kv_heads))
        self._heads, self._kv_heads, self._dim = num_heads, num_kv_heads, \
            head_dim
        self._rotary = dict(rotary or {}, theta=rope_theta)
        self._eps = epsilon
        self._causal = bool(causal) or window is not None
        self._window = None if window is None else window_mask(window)
        self.query_proj = Dense(num_heads * head_dim, use_bias=False,
                                flatten=False, in_units=units)
        self.key_proj = Dense(num_kv_heads * head_dim, use_bias=False,
                              flatten=False, in_units=units)
        self.value_proj = Dense(num_kv_heads * head_dim, use_bias=False,
                                flatten=False, in_units=units)
        self.out_proj = Dense(units, use_bias=False, flatten=False,
                              in_units=num_heads * head_dim)
        self.out_proj.weight.sharding = (None, "tp")
        self.query_norm = RMSNorm(epsilon=epsilon, in_channels=head_dim) \
            if qk_norm else None
        self.key_norm = RMSNorm(epsilon=epsilon, in_channels=head_dim) \
            if qk_norm else None
        self.gate_proj = Dense(num_heads, use_bias=False, flatten=False,
                               in_units=units) if gate else None

    def forward(self, x, positions, mask=None):
        if mask is None:
            mask = self._window
        causal = self._causal and mask is None
        q, k, v = self.query_proj(x), self.key_proj(x), self.value_proj(x)
        norms = (self.query_norm, self.key_norm)
        with _attn_scope(rule_kind(causal, mask)):
            if pallas_rotary.serves(
                    x.shape[1], self._dim,
                    not (mask is None or isinstance(mask, AttnMask)),
                    q._data.dtype.itemsize):
                # one Pallas pass each from the projections' rows into the
                # flash kernels' view
                for heads, norm in zip((self._heads, self._kv_heads), norms):
                    _note_placed(heads, self._dim, self._rotary, norm,
                                 "kernel")
                out = apply_op(
                    pallas_rotary.placed_attention, q, k, v, positions,
                    *(n.gamma.data() for n in norms if n is not None),
                    num_heads=self._heads, num_kv_heads=self._kv_heads,
                    causal=causal, mask=mask, eps=self._eps, **self._rotary)
            else:
                out = nd.multi_head_attention(
                    _placed(q, self._heads, positions, self._rotary,
                            self.query_norm).reshape(q.shape),
                    _placed(k, self._kv_heads, positions, self._rotary,
                            self.key_norm).reshape(k.shape),
                    v, num_heads=self._heads, num_kv_heads=self._kv_heads,
                    mask=mask, causal=causal)
            if self.gate_proj is not None:
                b, t = x.shape[0], x.shape[1]
                g = nd.sigmoid(self.gate_proj(x)) \
                    .reshape((b, t, self._heads, 1))
                out = (out.reshape((b, t, self._heads, self._dim)) * g) \
                    .reshape((b, t, self._heads * self._dim))
        return self.out_proj(out)


class LatentAttention(HybridBlock):
    """Multi-head latent attention (MLA; DeepSeek-V2 arXiv:2405.04434
    section 2.1, as DeepSeek-V3 and ``glm4_moe_lite`` carry it) in its
    EXPANDED form, the form of training and prefill: queries, keys and
    values go through low-rank latents,

    - ``c_q = RMSNorm(x W_qa)`` (``q_rank``), ``q = c_q W_qb``: a head's
      ``nope + rope`` dimensions;
    - ``[c_kv ; k_r] = x W_kva`` (``kv_rank + rope``), ``c_kv =
      RMSNorm(c_kv)``; ``k_r`` is ONE rotary key for all the heads;
    - ``[k_nope ; v] = c_kv W_kvb``: a head's ``nope + v_dim`` dimensions;
    - ``q_h = [q_nope_h ; rotary(q_rope_h)]``, ``k_h = [k_nope_h ;
      rotary(k_r)]``: rotary positions (rotate-half form) on the LAST
      ``rope`` dimensions of a head only, the rotary key repeated over the
      heads; scores scaled by ``(nope + rope) ** -0.5``.

    The heads' keys and values are made whole (``(B, T, heads, D)``) and
    handed to ``nd.multi_head_attention`` like any other block's, so the
    flash kernels or the dense path are chosen as for every block; the
    kernels want ``nope + rope == v_dim`` (GLM-4.7-Flash: 192 + 64 = 256;
    unequal sizes raise: nothing is padded).  The absorbed form, in which
    decoding attends over the latents themselves, is not here.

    forward(x, positions, mask=None) as ``GroupedQueryAttention``; causal
    unless a ``mask`` says otherwise.
    Everything between the layer's input and the output projection's (the
    low-rank path IS the mechanism) carries the scope ``mx.attn.mla``;
    the rule's own ``mx.attn.<kind>`` appears within it."""

    def __init__(self, units, num_heads, q_rank, kv_rank, nope_dim,
                 rope_dim, v_dim, rope_theta=10000.0, epsilon=1e-6):
        super().__init__()
        if nope_dim + rope_dim != v_dim:
            raise MXNetError(
                "LatentAttention: a head's query/key size %d + %d and its "
                "value size %d differ; the attention kernels take one head "
                "size" % (nope_dim, rope_dim, v_dim))
        self._sizes = {"heads": num_heads, "q_rank": q_rank,
                       "kv_rank": kv_rank, "nope": nope_dim,
                       "rope": rope_dim, "v": v_dim}
        self._rotary = {"theta": rope_theta}
        self._laid_out = set()
        self.q_a_proj = Dense(q_rank, use_bias=False, flatten=False,
                              in_units=units)
        self.q_a_norm = RMSNorm(epsilon=epsilon, in_channels=q_rank)
        self.q_b_proj = Dense(num_heads * (nope_dim + rope_dim),
                              use_bias=False, flatten=False,
                              in_units=q_rank)
        self.kv_a_proj = Dense(kv_rank + rope_dim, use_bias=False,
                               flatten=False, in_units=units)
        self.kv_a_norm = RMSNorm(epsilon=epsilon, in_channels=kv_rank)
        self.kv_b_proj = Dense(num_heads * (nope_dim + v_dim),
                               use_bias=False, flatten=False,
                               in_units=kv_rank)
        self.out_proj = Dense(units, use_bias=False, flatten=False,
                              in_units=num_heads * v_dim)
        self.out_proj.weight.sharding = (None, "tp")

    def _layout(self, shape):
        if shape not in self._laid_out:
            self._laid_out.add(shape)
            _trace.instant("mx.mla.layout",
                           args=dict(self._sizes, form="expanded"))

    def forward(self, x, positions, mask=None):
        self._layout(tuple(x.shape[:2]))
        b, t = x.shape[0], x.shape[1]
        heads, kv_rank, nope, rope = (self._sizes[n] for n in (
            "heads", "kv_rank", "nope", "rope"))
        with _attn_scope("mla"):
            q = self.q_b_proj(self.q_a_norm(self.q_a_proj(x))) \
                .reshape((b, t, heads, -1))
            kv_a = self.kv_a_proj(x)
            kv = self.kv_b_proj(self.kv_a_norm(kv_a[..., :kv_rank])) \
                .reshape((b, t, heads, -1))
            # one rotary key, repeated over the heads
            k_r = _placed(kv_a[..., kv_rank:], 1, positions, self._rotary)
            q = nd.concat(q[..., :nope],
                          _placed(q[..., nope:], heads, positions,
                                  self._rotary), dim=-1)
            k = nd.concat(kv[..., :nope],
                          nd.broadcast_to(k_r, (b, t, heads, rope)), dim=-1)
            out = nd.multi_head_attention(
                q.reshape((b, t, -1)), k.reshape((b, t, -1)),
                kv[..., nope:].reshape((b, t, -1)), num_heads=heads,
                mask=mask, causal=mask is None)
        return self.out_proj(out)


class GatedMLP(HybridBlock):
    """The dense feed-forward of a modern decoder:
    ``down(silu(gate(x)) * up(x))``, no bias."""

    def __init__(self, units, hidden_size):
        super().__init__()
        self.gate_proj = Dense(hidden_size, use_bias=False, flatten=False,
                               in_units=units)
        self.up_proj = Dense(hidden_size, use_bias=False, flatten=False,
                             in_units=units)
        self.down_proj = Dense(units, use_bias=False, flatten=False,
                               in_units=hidden_size)
        self.gate_proj.weight.sharding = ("tp", None)
        self.up_proj.weight.sharding = ("tp", None)
        self.down_proj.weight.sharding = (None, "tp")

    def forward(self, x):
        return self.down_proj(
            nd.silu(self.gate_proj(x)) * self.up_proj(x))


class DecoderLayer(HybridBlock):
    """Pre-norm decoder layer, no bias anywhere:
    ``h = x + attention(RMSNorm(x), positions, mask)``,
    ``h + feed_forward(RMSNorm(h))``.  The attention block and the
    feed-forward are handed in; the feed-forward comes as ONE keyword,
    whose name its parameters carry (``mlp=GatedMLP(...)``,
    ``moe=MoE(...)``).

    ``recompute=True``: inside a traced program (``FusedTrainer``,
    ``hybridize``) the layer runs under ``jax.checkpoint``: the backward
    keeps the layer's input only and runs the layer's forward again."""

    def __init__(self, units, attention, epsilon=1e-6, recompute=False,
                 **feed_forward):
        super().__init__()
        if len(feed_forward) != 1:
            raise MXNetError("DecoderLayer takes one feed-forward block as "
                             "a keyword, got %s" % sorted(feed_forward))
        self._recompute = bool(recompute)
        self.input_norm = RMSNorm(epsilon=epsilon, in_channels=units)
        self.attention = attention
        self.post_norm = RMSNorm(epsilon=epsilon, in_channels=units)
        (self._ffn, block), = feed_forward.items()
        setattr(self, self._ffn, block)

    def _layer(self, x, positions, mask):
        h = x + self.attention(self.input_norm(x), positions, mask)
        return h + getattr(self, self._ffn)(self.post_norm(h))

    def forward(self, x, positions, mask=None):
        return recomputed(lambda x_, positions_: self._layer(
            x_, positions_, mask), self._recompute, x, positions)


class MoEDecoderLayer(DecoderLayer):
    """``DecoderLayer`` of the Qwen3-MoE family: grouped-KV attention with
    QK-norm, and a softmax-routed mixture of gated SiLU experts under the
    name ``moe``.  ``first``/``count`` say which of the ``num_experts``
    experts this chip holds (``gluon.nn.MoE``)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 num_experts, expert_hidden, top_k, first=0, count=None,
                 rope_theta=10000.0, epsilon=1e-6, norm_topk=True,
                 recompute=False):
        super().__init__(
            units, GroupedQueryAttention(
                units, num_heads, num_kv_heads, head_dim,
                rope_theta=rope_theta, epsilon=epsilon),
            epsilon=epsilon, recompute=recompute,
            moe=MoE(num_experts, expert_hidden, units, top_k=top_k,
                    in_units=units, activation="silu", gated=True,
                    use_bias=False, first=first, count=count,
                    norm_topk=norm_topk))
