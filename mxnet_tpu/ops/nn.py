"""Neural-net ops: dense, conv, pooling, normalization, activations, dropout.

Reference surface: src/operator/nn/ (31k LoC: convolution.cc,
fully_connected.cc, batch_norm.cc, layer_norm.cc, pooling.cc, softmax.cc,
dropout, activation + the cuDNN/MKLDNN dispatch trees).

TPU-native: each op is a single lax/jnp expression that XLA tiles onto the
MXU (conv/FC) or fuses into surrounding elementwise chains (activations,
norms).  The cuDNN/MKLDNN forks disappear — XLA:TPU is the one backend.
bf16 contractions rely on the MXU's native f32 accumulation — the
hardware's mixed-precision mode (see _amp_pair).
"""
# pylint: disable=redefined-builtin
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register

# ---- activations (reference nn/activation.cc, leaky_relu.cc) --------------


@register("relu")
def relu(x):
    return jnp.maximum(x, 0)


@register("sigmoid")
def sigmoid(x):
    return jax.nn.sigmoid(x)


@register("log_sigmoid")
def log_sigmoid(x):
    return jax.nn.log_sigmoid(x)


@register("softrelu")
def softrelu(x):
    return jax.nn.softplus(x)


@register("softsign")
def softsign(x):
    return jax.nn.soft_sign(x)


@register("hard_sigmoid")
def hard_sigmoid(x, alpha=0.2, beta=0.5):
    return jnp.clip(alpha * x + beta, 0.0, 1.0)


@register("leaky_relu")
def leaky_relu(x, slope=0.25):
    return jnp.where(x >= 0, x, slope * x)


@register("prelu")
def prelu(x, gamma):
    return jnp.where(x >= 0, x, gamma * x)


@register("elu")
def elu(x, alpha=1.0):
    return jnp.where(x >= 0, x, alpha * jnp.expm1(x))


@register("selu")
def selu(x):
    return jax.nn.selu(x)


@register("gelu")
def gelu(x, approximate=True):
    return jax.nn.gelu(x, approximate=approximate)


@register("silu")
def silu(x):
    return jax.nn.silu(x)


swish = silu


@register("mish")
def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


@register("softmax")
def softmax(x, axis=-1, temperature=None, length=None):
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    if length is not None:
        pos = jnp.arange(x.shape[axis])
        shape = [1] * x.ndim
        shape[axis] = x.shape[axis]
        mask = pos.reshape(shape) < length.reshape(
            length.shape + (1,) * (x.ndim - length.ndim))
        x = jnp.where(mask, x, -jnp.inf)
    return jax.nn.softmax(x, axis=axis)


@register("log_softmax")
def log_softmax(x, axis=-1, temperature=None):
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    return jax.nn.log_softmax(x, axis=axis)


@register("softmin")
def softmin(x, axis=-1):
    return jax.nn.softmax(-x, axis=axis)


# ---- dense (reference nn/fully_connected.cc; MXU GEMM) --------------------


def _amp_pair(x, weight):
    """Mixed-precision dtype alignment: when exactly one side is bf16 (AMP
    casts weights, normalization keeps f32), compute the contraction in
    bf16 — the MXU accumulates bf16 products in f32 natively, so no
    explicit preferred_element_type is needed (and requesting one breaks
    the conv/dot transpose rules under value_and_grad)."""
    if x.dtype != weight.dtype and jnp.bfloat16 in (x.dtype, weight.dtype):
        return x.astype(jnp.bfloat16), weight.astype(jnp.bfloat16)
    return x, weight


@register("fully_connected")
def fully_connected(x, weight, bias=None, num_hidden=None, flatten=True,
                    no_bias=False):
    """y = x @ W^T + b.  Weight layout (out, in) matches the reference
    (fully_connected.cc shape conventions) and feeds the MXU directly."""
    if flatten and x.ndim > 2:
        x = x.reshape(x.shape[0], -1)
    x, weight = _amp_pair(x, weight)
    # bf16 contractions accumulate in f32 on the MXU natively; an explicit
    # preferred_element_type=f32 breaks the conv/dot transpose rules under
    # value_and_grad (mixed-dtype cotangents), so rely on the hardware
    y = lax.dot_general(x, weight, (((x.ndim - 1,), (1,)), ((), ())))
    if bias is not None and not no_bias:
        y = y + bias.astype(y.dtype)
    return y


# ---- convolution (reference nn/convolution.cc / deconvolution.cc) ---------


def _conv_dims(ndim, layout):
    if layout is None:
        layout = {3: "NCW", 4: "NCHW", 5: "NCDHW"}[ndim]
    # weight layout: O I spatial... (reference convention)
    w_layout = {3: "OIW", 4: "OIHW", 5: "OIDHW"}[ndim]
    out_layout = layout
    return layout, w_layout, out_layout


@register("convolution")
def convolution(x, weight, bias=None, kernel=None, stride=None, dilate=None,
                pad=None, num_filter=None, num_group=1, no_bias=False,
                layout=None):
    nd = x.ndim
    nspatial = nd - 2
    stride = tuple(stride) if stride else (1,) * nspatial
    dilate = tuple(dilate) if dilate else (1,) * nspatial
    pad = tuple(pad) if pad else (0,) * nspatial
    dn_layout = _conv_dims(nd, layout)
    x, weight = _amp_pair(x, weight)
    # (see fully_connected) bf16 convs accumulate f32 on the MXU natively
    dn = lax.conv_dimension_numbers(
        x.shape, weight.shape, dn_layout[:2] + (dn_layout[2],))
    y = lax.conv_general_dilated(
        x, weight, window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group)
    if bias is not None and not no_bias:
        lay = dn_layout[0]
        c_axis = lay.index("C")
        shape = [1] * nd
        shape[c_axis] = bias.shape[0]
        y = y + bias.reshape(shape).astype(y.dtype)
    return y


@register("deconvolution")
def deconvolution(x, weight, bias=None, kernel=None, stride=None, dilate=None,
                  pad=None, adj=None, num_filter=None, num_group=1,
                  no_bias=False, layout=None):
    """Transposed conv (reference nn/deconvolution.cc).  Implemented as the
    gradient of convolution — lax.conv_transpose with IO-swapped weights."""
    nd = x.ndim
    nspatial = nd - 2
    stride = tuple(stride) if stride else (1,) * nspatial
    pad = tuple(pad) if pad else (0,) * nspatial
    dilate = tuple(dilate) if dilate else (1,) * nspatial
    lay, wlay, olay = _conv_dims(nd, layout)
    dn = lax.conv_dimension_numbers(
        x.shape, weight.shape, (lay, wlay.replace("O", "X").replace("I", "O")
                                .replace("X", "I"), olay))
    y = lax.conv_transpose(
        x, jnp.swapaxes(weight, 0, 1) if num_group == 1 else weight,
        strides=stride, padding=[(p, p) for p in pad],
        rhs_dilation=dilate, dimension_numbers=dn,
        transpose_kernel=True)
    if bias is not None and not no_bias:
        c_axis = lay.index("C")
        shape = [1] * nd
        shape[c_axis] = bias.shape[0]
        y = y + bias.reshape(shape)
    return y


# ---- pooling (reference nn/pooling.cc) ------------------------------------


@register("pooling")
def pooling(x, kernel=None, pool_type="max", stride=None, pad=None,
            global_pool=False, count_include_pad=True, layout=None):
    nd = x.ndim
    nspatial = nd - 2
    lay = layout or {3: "NCW", 4: "NCHW", 5: "NCDHW"}[nd]
    spatial_axes = [lay.index(c) for c in lay if c not in ("N", "C")]
    if global_pool:
        if pool_type == "max":
            return jnp.max(x, axis=tuple(spatial_axes), keepdims=True)
        return jnp.mean(x, axis=tuple(spatial_axes), keepdims=True)
    kernel = tuple(kernel)
    stride = tuple(stride) if stride else (1,) * nspatial
    pad = tuple(pad) if pad else (0,) * nspatial
    window = [1] * nd
    strides = [1] * nd
    padding = [(0, 0)] * nd
    for i, ax in enumerate(spatial_axes):
        window[ax] = kernel[i]
        strides[ax] = stride[i]
        padding[ax] = (pad[i], pad[i])
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else (
            jnp.iinfo(x.dtype).min)
        return lax.reduce_window(x, init, lax.max, window, strides, padding)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(x, 0.0, lax.add, window, strides, padding)
        if pool_type == "sum":
            return s
        if count_include_pad:
            denom = 1
            for k in kernel:
                denom *= k
            return s / denom
        ones = jnp.ones_like(x)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, padding)
        return s / cnt
    if pool_type == "lp":
        p2 = lax.reduce_window(jnp.abs(x) ** 2, 0.0, lax.add, window,
                               strides, padding)
        return jnp.sqrt(p2)
    raise ValueError("unknown pool_type %s" % pool_type)


@register("adaptive_avg_pooling")
def adaptive_avg_pooling(x, output_size=1):
    """Reference: contrib/adaptive_avg_pooling.cc (NCHW)."""
    if isinstance(output_size, int):
        output_size = (output_size, output_size)
    N, C, H, W = x.shape
    oh, ow = output_size
    # split into oh x ow near-equal windows via mean over reshaped blocks
    if H % oh == 0 and W % ow == 0:
        return x.reshape(N, C, oh, H // oh, ow, W // ow).mean(axis=(3, 5))
    hi = jnp.linspace(0, H, oh + 1).astype(jnp.int32)
    wi = jnp.linspace(0, W, ow + 1).astype(jnp.int32)
    rows = []
    for i in range(oh):
        cols = []
        for j in range(ow):
            cols.append(x[:, :, hi[i]:hi[i + 1], wi[j]:wi[j + 1]].mean(
                axis=(2, 3)))
        rows.append(jnp.stack(cols, axis=-1))
    return jnp.stack(rows, axis=-2)


# ---- normalization (reference nn/batch_norm.cc etc.) ----------------------


@register("batch_norm", num_outputs=3)
def batch_norm(x, gamma, beta, moving_mean, moving_var, eps=1e-5,
               momentum=0.9, fix_gamma=False, use_global_stats=False,
               axis=1, training=False, stat_dtype=None):
    """Returns (out, new_moving_mean, new_moving_var).

    Reference: src/operator/nn/batch_norm.cc — the running-stat update is an
    op side effect there; here it is an explicit functional output that the
    Gluon layer writes back (XLA-friendly: no hidden state in the graph).

    ``stat_dtype`` (None -> "float32") is the dtype the batch mean/var
    reduce in; "bfloat16" is the only other value.
    """
    reduce_axes = tuple(i for i in range(x.ndim) if i != axis)
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    # statistics and normalization math in f32 even under AMP (bf16 x with
    # f32 gamma/beta/running stats); output back in x's dtype
    xf = x.astype(jnp.float32) if x.dtype != jnp.float32 else x
    if training and not use_global_stats:
        if stat_dtype == "bfloat16":
            xs = xf.astype(jnp.bfloat16)
            m = jnp.mean(xs, axis=reduce_axes).astype(jnp.float32)
            v = jnp.var(xs, axis=reduce_axes).astype(jnp.float32)
        else:
            m = jnp.mean(xf, axis=reduce_axes)
            v = jnp.var(xf, axis=reduce_axes)
        new_mean = moving_mean * momentum + m.astype(moving_mean.dtype) * \
            (1 - momentum)
        new_var = moving_var * momentum + v.astype(moving_var.dtype) * \
            (1 - momentum)
    else:
        m, v = moving_mean, moving_var
        new_mean, new_var = moving_mean, moving_var
    inv = lax.rsqrt(v.astype(jnp.float32) + eps)
    out = (xf - m.reshape(shape)) * (g * inv).reshape(shape) + \
        beta.reshape(shape)
    return out.astype(x.dtype), new_mean, new_var


@register("layer_norm")
def layer_norm(x, gamma, beta, axis=-1, eps=1e-5):
    """Reference: src/operator/nn/layer_norm.cc."""
    m = jnp.mean(x, axis=axis, keepdims=True)
    v = jnp.var(x, axis=axis, keepdims=True)
    out = (x - m) * lax.rsqrt(v + eps)
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register("group_norm")
def group_norm(x, gamma, beta, num_groups=1, eps=1e-5):
    """Reference: src/operator/nn/group_norm.cc (NC+ layout)."""
    N, C = x.shape[0], x.shape[1]
    spatial = x.shape[2:]
    xg = x.reshape((N, num_groups, C // num_groups) + spatial)
    axes = tuple(range(2, xg.ndim))
    m = jnp.mean(xg, axis=axes, keepdims=True)
    v = jnp.var(xg, axis=axes, keepdims=True)
    out = ((xg - m) * lax.rsqrt(v + eps)).reshape(x.shape)
    shape = (1, C) + (1,) * len(spatial)
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register("instance_norm")
def instance_norm(x, gamma, beta, eps=1e-5):
    axes = tuple(range(2, x.ndim))
    m = jnp.mean(x, axis=axes, keepdims=True)
    v = jnp.var(x, axis=axes, keepdims=True)
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    return (x - m) * lax.rsqrt(v + eps) * gamma.reshape(shape) + \
        beta.reshape(shape)


@register("rms_norm")
def rms_norm(x, gamma, axis=-1, eps=1e-6):
    """RMSNorm — modern-transformer staple (no reference equivalent)."""
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=axis, keepdims=True)
    # the gain multiplies in float32 too; the result keeps x's dtype (a
    # float32 gamma under bf16 compute must not widen what follows)
    return (x.astype(jnp.float32) * lax.rsqrt(ms + eps)
            * gamma.astype(jnp.float32)).astype(x.dtype)


@register("l2_normalization")
def l2_normalization(x, eps=1e-10, mode="instance"):
    if mode == "instance":
        n = jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim)),
                             keepdims=True) + eps)
    elif mode == "channel":
        n = jnp.sqrt(jnp.sum(jnp.square(x), axis=1, keepdims=True) + eps)
    else:
        n = jnp.sqrt(jnp.sum(jnp.square(x)) + eps)
    return x / n


@register("lrn")
def lrn(x, alpha=1e-4, beta=0.75, knorm=2, nsize=5):
    """Local response norm over channels (reference nn/lrn.cc, NCHW)."""
    sq = jnp.square(x)
    half = nsize // 2
    pad = [(0, 0), (half, half)] + [(0, 0)] * (x.ndim - 2)
    sqp = jnp.pad(sq, pad)
    window = [1, nsize] + [1] * (x.ndim - 2)
    s = lax.reduce_window(sqp, 0.0, lax.add, window, [1] * x.ndim,
                          [(0, 0)] * x.ndim)
    return x / jnp.power(knorm + alpha * s / nsize, beta)


# ---- dropout (reference nn/dropout.cc) ------------------------------------


@register("dropout", differentiable=True)
def dropout(x, key, p=0.5, mode="training", axes=None):
    if p <= 0.0:
        return x
    shape = x.shape
    if axes:
        shape = tuple(1 if i in axes else s for i, s in enumerate(x.shape))
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, shape).astype(x.dtype) / keep
    return x * mask


# ---- resize / upsampling (reference nn/upsampling.cc, bilinear_resize) ----


@register("upsampling")
def upsampling(x, scale=2, sample_type="nearest"):
    N, C, H, W = x.shape
    if sample_type == "nearest":
        return jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
    return jax.image.resize(x, (N, C, H * scale, W * scale), "bilinear")


@register("bilinear_resize")
def bilinear_resize(x, height=None, width=None, align_corners=False):
    N, C = x.shape[:2]
    method = "bilinear"
    return jax.image.resize(x, (N, C, height, width), method)


# ---- losses as ops (reference nn/softmax_output, smooth_l1, ctc) ----------


@register("softmax_cross_entropy")
def softmax_cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    lbl = labels.astype(jnp.int32)
    nll = -jnp.take_along_axis(logp, lbl[..., None], axis=-1)[..., 0]
    return jnp.sum(nll)


@register("smooth_l1")
def smooth_l1(x, scalar=1.0):
    s2 = scalar * scalar
    return jnp.where(jnp.abs(x) < 1.0 / s2, 0.5 * s2 * jnp.square(x),
                     jnp.abs(x) - 0.5 / s2)


@register("ctc_loss")
def ctc_loss(data, label, data_lengths=None, label_lengths=None,
             blank_label="first"):
    """CTC forward-backward (reference nn/ctc_loss.cc + 3rdparty/ctc_include).

    data: (T, B, V) unnormalized activations; label: (B, L) padded with -1
    (or 0s counted via label_lengths).  Pure lax.scan dynamic program — XLA
    compiles the recurrence; no warp-ctc needed.
    """
    T, B, V = data.shape
    logp = jax.nn.log_softmax(data, axis=-1)
    blank = 0 if blank_label == "first" else V - 1
    L = label.shape[1]
    lab = label.astype(jnp.int32)
    if label_lengths is None:
        label_lengths = jnp.sum((lab >= 0) & (lab != blank) if blank_label ==
                                "first" else (lab >= 0), axis=1)
        label_lengths = jnp.sum(lab > (0 if blank_label == "first" else -1),
                                axis=1) if blank_label == "first" else \
            label_lengths
    if data_lengths is None:
        data_lengths = jnp.full((B,), T, jnp.int32)
    S = 2 * L + 1
    # extended label sequence: blank l1 blank l2 ... blank
    ext = jnp.full((B, S), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(lab)
    neg_inf = -1e30
    # alpha recursion
    same_as_prev2 = jnp.concatenate(
        [jnp.zeros((B, 2), bool), ext[:, 2:] == ext[:, :-2]], axis=1)
    pos = jnp.arange(S)

    def step(alpha, logp_t):
        a1 = jnp.concatenate([jnp.full((B, 1), neg_inf), alpha[:, :-1]],
                             axis=1)
        a2 = jnp.concatenate([jnp.full((B, 2), neg_inf), alpha[:, :-2]],
                             axis=1)
        a2 = jnp.where(same_as_prev2 | (pos[None, :] % 2 == 0), neg_inf, a2)
        m = jnp.maximum(alpha, jnp.maximum(a1, a2))
        new = m + jnp.log(
            jnp.exp(alpha - m) + jnp.exp(a1 - m) + jnp.exp(a2 - m))
        emit = jnp.take_along_axis(logp_t, ext, axis=1)
        new = new + emit
        return new, new

    alpha0 = jnp.full((B, S), neg_inf)
    alpha0 = alpha0.at[:, 0].set(logp[0, :, blank])
    alpha0 = alpha0.at[:, 1].set(
        jnp.take_along_axis(logp[0], ext[:, 1:2], axis=1)[:, 0])
    _, alphas = lax.scan(step, alpha0, logp[1:])
    alphas = jnp.concatenate([alpha0[None], alphas], axis=0)  # (T, B, S)
    # per-sample final frame: alpha at t = data_length - 1
    t_end = jnp.clip(data_lengths.astype(jnp.int32) - 1, 0, T - 1)
    alpha_T = alphas[t_end, jnp.arange(B)]                    # (B, S)
    end = 2 * label_lengths.astype(jnp.int32)
    a_end = jnp.take_along_axis(alpha_T, end[:, None], axis=1)[:, 0]
    a_end1 = jnp.take_along_axis(alpha_T, jnp.maximum(end - 1, 0)[:, None],
                                 axis=1)[:, 0]
    m = jnp.maximum(a_end, a_end1)
    ll = m + jnp.log(jnp.exp(a_end - m) + jnp.exp(a_end1 - m))
    return -ll


# ---- attention (reference contrib/transformer.cc interleaved matmuls) -----


@register("multi_head_attention")
def multi_head_attention(q, k, v, num_heads=1, mask=None, scale=None,
                         causal=False, impl="auto", attn_dropout=0.0,
                         dropout_key=None, num_kv_heads=None):
    """Batched SDPA: q (B, T, H*D); k, v (B, T, Hkv*D).  Reference
    equivalent: _contrib_interleaved_matmul_selfatt_qk/valatt
    (contrib/transformer.cc:650-826) which exist only to feed cuBLAS strided
    GEMMs; on TPU one einsum chain fuses and lands on the MXU, and the
    Pallas flash kernel (mxnet_tpu/ops/pallas_attention.py) takes over for
    long sequences.

    impl: 'auto' | 'dense' | 'flash' (blockwise scan) | 'pallas'.
    'pallas' hands the kernels these rows as they are, viewed as
    (B, T, H, D), and gets rows back: no transpose stands in this function
    on either side of the call.  At heads of 64 or 32 the kernels' blocks
    index the heads where the projections wrote them, forward and backward;
    every other shape is transposed inside ``flash_attention``
    (``pallas_attention.heads_per_step``: at heads of 128 XLA makes that
    transpose the layout of the projection's product).  The dense and
    blockwise paths run head-major and transpose here.
    attn_dropout (+ dropout_key) drops attention probabilities; every
    impl supports it — the Pallas kernel applies a per-tile PRNG mask
    inside fwd AND both backward kernels (regenerated, never stored), so
    auto-dispatch sends all long-sequence cases, dropout included, to
    'pallas'; 'flash' (blockwise) remains the pure-JAX fallback.

    ``mask`` is an array broadcast against (B, H, Tq, Tk) (dense path
    only), or a static ``pallas_attention.AttnMask`` rule, which the
    kernels evaluate tile by tile.  ``num_kv_heads`` (default: num_heads)
    KV heads each serve ``num_heads // num_kv_heads`` query heads.
    """
    from . import pallas_attention as pa

    args = (q, k, v, num_heads, mask, scale, causal, impl, attn_dropout,
            dropout_key, num_kv_heads)
    # a structured rule names its calls (kernels and the layout work around
    # them) in the program, forward and backward: mx.attn.<kind>, the
    # built-in causal rule as mx.attn.causal
    kind = pa.rule_kind(causal, mask)
    if kind is None:
        return _multi_head_attention(*args)
    with jax.named_scope("mx.attn.%s" % kind):
        return _multi_head_attention(*args)


def _multi_head_attention(q, k, v, num_heads, mask, scale, causal, impl,
                          attn_dropout, dropout_key, num_kv_heads):
    from ..base import MXNetError
    from . import pallas_attention as pa

    B, Tq, HD = q.shape
    Tk = k.shape[1]
    D = HD // num_heads
    kv_heads = num_kv_heads or num_heads
    if num_heads % kv_heads:
        raise MXNetError("num_heads %d is not a multiple of num_kv_heads %d"
                         % (num_heads, kv_heads))
    group = num_heads // kv_heads
    rule = mask if isinstance(mask, pa.AttnMask) else None
    # the projections' rows with their heads named: a free reshape
    q4 = q.reshape(B, Tq, num_heads, D)
    k4 = k.reshape(B, Tk, kv_heads, D)
    v4 = v.reshape(B, Tk, kv_heads, D)
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    if attn_dropout > 0.0 and dropout_key is None:
        raise MXNetError("attn_dropout > 0 requires dropout_key (draw one "
                         "with mxnet_tpu.random.take_key())")
    has_dropout = attn_dropout > 0.0
    if impl == "auto":
        # the Pallas kernel covers dropout too (in-kernel per-tile PRNG
        # mask, fwd + both bwd kernels regenerate it); a sequence whose
        # K/V outgrows the kernels' fast memory takes the blockwise scan,
        # never the (Tq, Tk) score matrix
        isz = q.dtype.itemsize
        array_mask = mask is not None and rule is None
        if pa.use_flash(Tq, Tk, D, array_mask, isz):
            impl = "pallas"
        elif mask is None and group == 1 \
                and pa.flash_vmem_bytes(Tq, Tk, D, isz) \
                > pa.VMEM_BUDGET_BYTES:
            impl = "flash"
        else:
            impl = "dense"
    if impl in ("pallas", "flash"):
        if mask is not None and (rule is None or impl == "flash"):
            raise MXNetError(
                "impl=%r does not support an arbitrary mask (only causal= "
                "and, for 'pallas', a static AttnMask); use impl='dense' or "
                "drop the mask" % impl)
        if impl == "pallas":
            # the kernels index the rows where they lie: no transpose here
            return pa.flash_attention(
                q4, k4, v4, causal, scale, dropout_p=attn_dropout,
                dropout_key=dropout_key, mask=rule).reshape(B, Tq, HD)
        if group > 1:
            raise MXNetError("impl='flash' (blockwise) has no grouped "
                             "KV heads; use 'pallas' or 'dense'")
        out = pa.blockwise_attention(
            *(x.transpose(0, 2, 1, 3) for x in (q4, k4, v4)), causal=causal,
            sm_scale=scale, dropout_p=attn_dropout, dropout_key=dropout_key)
        return out.transpose(0, 2, 1, 3).reshape(B, Tq, HD)
    # the dense products run head-major
    qh, kh, vh = (x.transpose(0, 2, 1, 3) for x in (q4, k4, v4))
    if rule is not None:
        mask = pa.mask_allowed(rule, jnp.arange(Tq, dtype=jnp.int32)[:, None],
                               jnp.arange(Tk, dtype=jnp.int32)[None, :])
    if group > 1:   # query heads of a group share their KV head's rows
        qh = qh.reshape(B, kv_heads, group * Tq, D)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                        preferred_element_type=jnp.float32) * scale
    scores = scores.reshape(B, num_heads, Tq, Tk)
    if causal:
        cmask = jnp.tril(jnp.ones((Tq, Tk), bool))
        scores = jnp.where(cmask, scores, -1e30)
    if mask is not None:
        scores = jnp.where(mask.astype(bool), scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(qh.dtype)
    if has_dropout:
        keep = 1.0 - attn_dropout
        dmask = jax.random.bernoulli(dropout_key, keep, w.shape)
        w = w * dmask.astype(w.dtype) / keep
    out = jnp.einsum("bhqk,bhkd->bhqd",
                     w.reshape(B, kv_heads, group * Tq, Tk), vh)
    out = out.reshape(B, num_heads, Tq, D)
    return out.transpose(0, 2, 1, 3).reshape(B, Tq, HD)


def _rotary_angles(positions, r, theta, inv_freq):
    """The angles ``(..., T, r/2)`` of a rotary of ``r`` dimensions:
    ``positions * inv_freq`` (default ``theta**(-2i/r)``), float32."""
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)) \
        if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    return positions.astype(jnp.float32)[..., None] * inv


@register("rotary_embedding")
def rotary_embedding(x, positions, theta=10000.0, rotary_dim=None,
                     inv_freq=None, factor=1.0):
    """Rotary position embedding, rotate-half form (Su et al.
    arXiv:2104.09864 as GPT-NeoX and the Qwen family write it): x
    (..., T, H, D), positions (T,) or (B, T); pair (i, i + R/2) of the
    first ``R = rotary_dim`` dimensions of every head (default: all D) is
    rotated by ``positions * theta**(-2i/R)``, the other ``D - R`` pass
    through (a partial rotary factor).  ``inv_freq`` (R/2 numbers) gives
    the frequencies instead of ``theta`` and ``factor`` multiplies cos and
    sin: what a scaled rotary (YaRN, arXiv:2309.00071) needs, computed by
    whoever knows the model's config.  Angles in float32."""
    d = x.shape[-1]
    r = d if rotary_dim is None else int(rotary_dim)
    ang = _rotary_angles(positions, r, theta, inv_freq)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[..., None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    xf = x.astype(jnp.float32)
    head = xf if r == d else xf[..., :r]
    rot = jnp.concatenate([-head[..., r // 2:], head[..., :r // 2]], -1)
    out = head * cos + rot * sin
    if r < d:
        out = jnp.concatenate([out, xf[..., r:]], -1)
    return out.astype(x.dtype)
