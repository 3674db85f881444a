"""Image op family — the reference's ``_image_*`` operators, exposed as
``mx.nd.image.*`` / ``mx.npx.image.*``.

Reference: src/operator/image/image_random.cc (_image_normalize:106,
_image_random_resized_crop:121, jitter family), image_resize.cc
(_image_resize:36), crop.cc (_image_crop:39, _image_random_crop:86),
totensor.cc (_image_to_tensor:42).

Conventions (kept from the reference):
- layout is HWC (or NHWC batched) EXCEPT normalize, which runs on the
  CHW/NCHW output of to_tensor;
- to_tensor scales uint8 [0,255] -> float32 [0,1] and moves channels
  first;
- random_* ops draw from the framework RNG stream (reference: per-device
  resource pool) and are registered non-differentiable like their
  MakeZeroGradNodes originals; deterministic ops (to_tensor, normalize,
  crop, resize) keep autograd.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as _onp

from .registry import alias, register


def _key():
    from .. import random as _random

    return _random.take_key()


def _batched(x):
    return x.ndim == 4


@register("image_to_tensor")
def image_to_tensor(data):
    """HWC uint8 [0,255] -> CHW float32 [0,1] [totensor.cc:42]."""
    x = data.astype(jnp.float32) / 255.0
    if _batched(data):
        return jnp.transpose(x, (0, 3, 1, 2))
    return jnp.transpose(x, (2, 0, 1))


@register("image_normalize")
def image_normalize(data, mean=0.0, std=1.0):
    """(x - mean) / std on CHW/NCHW float [image_random.cc:106]."""
    mean = jnp.asarray(mean, jnp.float32)
    std = jnp.asarray(std, jnp.float32)
    shape = (-1, 1, 1) if not _batched(data) else (1, -1, 1, 1)
    if mean.ndim == 0:
        mean = mean[None]
    if std.ndim == 0:
        std = std[None]
    return (data - mean.reshape(shape)) / std.reshape(shape)


@register("image_resize")
def image_resize(data, size=None, keep_ratio=False, interp=1):
    """Resize HWC/NHWC to size=(w, h) [image_resize.cc:36]."""
    w, h = (size, size) if isinstance(size, int) else tuple(size)
    method = "nearest" if interp == 0 else "bilinear"
    if _batched(data):
        out_shape = (data.shape[0], h, w, data.shape[3])
    else:
        out_shape = (h, w, data.shape[2])
    out = jax.image.resize(data.astype(jnp.float32), out_shape, method)
    return out.astype(data.dtype) if data.dtype == jnp.uint8 else out


@register("image_crop")
def image_crop(data, x=0, y=0, width=1, height=1):
    """Fixed crop at (x, y) size (width, height) [crop.cc:39]."""
    if _batched(data):
        return data[:, y:y + height, x:x + width, :]
    return data[y:y + height, x:x + width, :]


@register("image_random_crop", differentiable=False)
def image_random_crop(data, xrange=(0.0, 1.0), yrange=(0.0, 1.0), width=1,
                      height=1, interp=1):
    """Random-position crop; xrange/yrange bound the start position as
    fractions of the free space [crop.cc:86]."""
    H, W = (data.shape[1], data.shape[2]) if _batched(data) \
        else (data.shape[0], data.shape[1])
    k1, k2 = jax.random.split(_key())
    free_x, free_y = max(0, W - width), max(0, H - height)
    fx = jax.random.uniform(k1, (), minval=xrange[0], maxval=xrange[1])
    fy = jax.random.uniform(k2, (), minval=yrange[0], maxval=yrange[1])
    x0 = jnp.round(fx * free_x).astype(jnp.int32)
    y0 = jnp.round(fy * free_y).astype(jnp.int32)
    if _batched(data):
        return jax.lax.dynamic_slice(
            data, (0, y0, x0, 0),
            (data.shape[0], height, width, data.shape[3]))
    return jax.lax.dynamic_slice(data, (y0, x0, 0),
                                 (height, width, data.shape[2]))


@register("image_random_resized_crop", differentiable=False)
def image_random_resized_crop(data, size=None, scale=(0.08, 1.0),
                              ratio=(3 / 4, 4 / 3), interp=1,
                              max_trial=10):
    """Inception-style area/aspect crop then resize
    [image_random.cc:121].  Geometry is drawn host-side (static shapes
    for XLA) from the FRAMEWORK RNG stream, so mx.random.seed makes the
    pipeline reproducible; pixels flow through slice + resize."""
    import math

    import numpy as _np

    H, W = (data.shape[1], data.shape[2]) if _batched(data) \
        else (data.shape[0], data.shape[1])
    # one key -> all host-side draws this call (seeded, thread-safe)
    draws = _np.asarray(jax.random.uniform(_key(), (max_trial, 4)))
    for t in range(max_trial):
        u_area, u_ratio, u_x, u_y = draws[t]
        area = (scale[0] + u_area * (scale[1] - scale[0])) * H * W
        ar = math.exp(math.log(ratio[0]) + u_ratio *
                      (math.log(ratio[1]) - math.log(ratio[0])))
        cw = int(round(math.sqrt(area * ar)))
        ch = int(round(math.sqrt(area / ar)))
        if cw <= W and ch <= H:
            x0 = int(u_x * (W - cw + 1))
            y0 = int(u_y * (H - ch + 1))
            cropped = image_crop.fn(data, x0, y0, cw, ch)
            return image_resize.fn(cropped, size=size, interp=interp)
    # fallback: center crop of the short side
    s = min(H, W)
    cropped = image_crop.fn(data, (W - s) // 2, (H - s) // 2, s, s)
    return image_resize.fn(cropped, size=size, interp=interp)


@register("image_flip_left_right")
def image_flip_left_right(data):
    return jnp.flip(data, axis=2 if _batched(data) else 1)


@register("image_flip_top_bottom")
def image_flip_top_bottom(data):
    return jnp.flip(data, axis=1 if _batched(data) else 0)


def _maybe(data, fn, p=0.5):
    return jnp.where(jax.random.uniform(_key(), ()) < p, fn(data), data)


@register("image_random_flip_left_right", differentiable=False)
def image_random_flip_left_right(data, p=0.5):
    return _maybe(data, image_flip_left_right.fn, p)


@register("image_random_flip_top_bottom", differentiable=False)
def image_random_flip_top_bottom(data, p=0.5):
    return _maybe(data, image_flip_top_bottom.fn, p)


def _blend(a, b, alpha):
    return a * alpha + b * (1.0 - alpha)


# Plain numpy on purpose: a module-level jnp constant would trigger PJRT
# backend initialization during `import mxnet_tpu` (which must stay
# host-only).  jnp ops accept numpy operands and the constant is
# folded into the compiled program either way.
_GRAY = _onp.asarray([0.299, 0.587, 0.114], dtype=_onp.float32)


@register("image_random_brightness", differentiable=False)
def image_random_brightness(data, min_factor=1.0, max_factor=1.0):
    """x *= f, f ~ U(min_factor, max_factor) [image_random.cc
    RandomBrightness — factors are multiplicative, 1.0 = identity]."""
    f = jax.random.uniform(_key(), (), minval=min_factor,
                           maxval=max_factor)
    return data.astype(jnp.float32) * f


@register("image_random_contrast", differentiable=False)
def image_random_contrast(data, min_factor=1.0, max_factor=1.0):
    f = jax.random.uniform(_key(), (), minval=min_factor,
                           maxval=max_factor)
    x = data.astype(jnp.float32)
    lum = jnp.tensordot(x, _GRAY, axes=([-1], [0]))
    if _batched(data):  # per-image anchor, not batch-global
        gray = jnp.mean(lum, axis=(1, 2), keepdims=True)[..., None]
    else:
        gray = jnp.mean(lum)
    return _blend(x, gray, f)


@register("image_random_saturation", differentiable=False)
def image_random_saturation(data, min_factor=1.0, max_factor=1.0):
    f = jax.random.uniform(_key(), (), minval=min_factor,
                           maxval=max_factor)
    x = data.astype(jnp.float32)
    gray = jnp.tensordot(x, _GRAY, axes=([-1], [0]))[..., None]
    return _blend(x, gray, f)


@register("image_random_hue", differentiable=False)
def image_random_hue(data, min_factor=0.0, max_factor=0.0):
    """YIQ rotation (the reference's tyiq/ityiq path,
    image_random-inl.h RandomHue)."""
    import numpy as _np

    f = jax.random.uniform(_key(), (), minval=min_factor, maxval=max_factor)
    u = jnp.cos(f * _np.pi)
    w = jnp.sin(f * _np.pi)
    tyiq = jnp.asarray([[0.299, 0.587, 0.114],
                        [0.596, -0.274, -0.321],
                        [0.211, -0.523, 0.311]])
    ityiq = jnp.asarray([[1.0, 0.956, 0.621],
                         [1.0, -0.272, -0.647],
                         [1.0, -1.107, 1.705]])
    bt = jnp.stack([jnp.stack([jnp.float32(1), jnp.float32(0),
                               jnp.float32(0)]),
                    jnp.stack([jnp.float32(0), u, -w]),
                    jnp.stack([jnp.float32(0), w, u])])
    t = (ityiq @ bt @ tyiq).T
    return jnp.tensordot(data.astype(jnp.float32), t, axes=([-1], [0]))


@register("image_random_color_jitter", differentiable=False)
def image_random_color_jitter(data, brightness=0.0, contrast=0.0,
                              saturation=0.0, hue=0.0):
    x = data.astype(jnp.float32)
    if brightness > 0:
        x = image_random_brightness.fn(x, max(0.0, 1 - brightness),
                                       1 + brightness)
    if contrast > 0:
        x = image_random_contrast.fn(x, max(0.0, 1 - contrast),
                                     1 + contrast)
    if saturation > 0:
        x = image_random_saturation.fn(x, max(0.0, 1 - saturation),
                                       1 + saturation)
    if hue > 0:
        x = image_random_hue.fn(x, -hue, hue)
    return x


@register("image_adjust_lighting")
def image_adjust_lighting(data, alpha=None):
    """AlexNet PCA lighting with fixed alpha [image_random.cc
    AdjustLighting]."""
    eigval = jnp.asarray([55.46, 4.794, 1.148])
    eigvec = jnp.asarray([[-0.5675, 0.7192, 0.4009],
                          [-0.5808, -0.0045, -0.8140],
                          [-0.5836, -0.6948, 0.4203]])
    alpha = jnp.asarray(alpha, jnp.float32)
    rgb = (eigvec * alpha[None, :]) @ eigval
    return data.astype(jnp.float32) + rgb


@register("image_random_lighting", differentiable=False)
def image_random_lighting(data, alpha_std=0.05):
    alpha = jax.random.normal(_key(), (3,)) * alpha_std
    return image_adjust_lighting.fn(data, alpha=alpha)


for _ref, _ours in [
        ("_image_to_tensor", "image_to_tensor"),
        ("_image_normalize", "image_normalize"),
        ("_image_resize", "image_resize"),
        ("_image_crop", "image_crop"),
        ("_image_random_crop", "image_random_crop"),
        ("_image_random_resized_crop", "image_random_resized_crop"),
        ("_image_flip_left_right", "image_flip_left_right"),
        ("_image_flip_top_bottom", "image_flip_top_bottom"),
        ("_image_random_flip_left_right", "image_random_flip_left_right"),
        ("_image_random_flip_top_bottom", "image_random_flip_top_bottom"),
        ("_image_random_brightness", "image_random_brightness"),
        ("_image_random_contrast", "image_random_contrast"),
        ("_image_random_saturation", "image_random_saturation"),
        ("_image_random_hue", "image_random_hue"),
        ("_image_random_color_jitter", "image_random_color_jitter"),
        ("_image_adjust_lighting", "image_adjust_lighting"),
        ("_image_random_lighting", "image_random_lighting")]:
    alias(_ref, _ours)


# ---------------------------------------------------------------------------
# spatial sampling family — BilinearSampler (bilinear_sampler.cc:150),
# GridGenerator (grid_generator.cc:237), SpatialTransformer
# (spatial_transformer.cc:217).  One differentiable jnp bilinear-sample
# core serves all three (plus image.imrotate); XLA fuses the gathers.
# ---------------------------------------------------------------------------

def _bilinear_sample_core(data, grid):
    """data (N,C,H,W), grid (N,2,Ho,Wo) with grid[:,0]=x, grid[:,1]=y in
    [-1,1]; out-of-range samples read 0 (the reference's zero padding)."""
    N, C, H, W = data.shape
    x = (grid[:, 0] + 1.0) * (W - 1) / 2.0       # (N,Ho,Wo)
    y = (grid[:, 1] + 1.0) * (H - 1) / 2.0
    x0 = jnp.floor(x)
    y0 = jnp.floor(y)
    wx = x - x0
    wy = y - y0

    def take(yi, xi):
        inb = ((xi >= 0) & (xi <= W - 1) & (yi >= 0)
               & (yi <= H - 1))
        xc = jnp.clip(xi, 0, W - 1).astype(jnp.int32)
        yc = jnp.clip(yi, 0, H - 1).astype(jnp.int32)
        flat = data.reshape(N, C, H * W)
        idx = (yc * W + xc).reshape(N, 1, -1)
        vals = jnp.take_along_axis(
            flat, jnp.broadcast_to(idx, (N, C, idx.shape[-1])), axis=2)
        vals = vals.reshape(N, C, *xi.shape[1:])
        return vals * inb[:, None].astype(data.dtype)

    out = (take(y0, x0) * ((1 - wy) * (1 - wx))[:, None]
           + take(y0, x0 + 1) * ((1 - wy) * wx)[:, None]
           + take(y0 + 1, x0) * (wy * (1 - wx))[:, None]
           + take(y0 + 1, x0 + 1) * (wy * wx)[:, None])
    return out.astype(data.dtype)


@register("BilinearSampler")
def bilinear_sampler(data, grid, cudnn_off=None):
    """Reference bilinear_sampler.cc:150: sample ``data`` at ``grid``
    (normalized [-1,1] x,y), zero outside."""
    return _bilinear_sample_core(data, grid)


@register("GridGenerator")
def grid_generator(data, transform_type="affine", target_shape=(0, 0)):
    """Reference grid_generator.cc:237.

    affine: ``data`` (N,6) row-major 2x3 theta -> grid (N,2,Ho,Wo)
    warp: ``data`` (N,2,H,W) pixel offsets -> normalized grid
    """
    if transform_type == "affine":
        N = data.shape[0]
        Ho, Wo = int(target_shape[0]), int(target_shape[1])
        theta = data.reshape(N, 2, 3)
        ys, xs = jnp.meshgrid(
            jnp.linspace(-1.0, 1.0, Ho), jnp.linspace(-1.0, 1.0, Wo),
            indexing="ij")
        ones = jnp.ones_like(xs)
        base = jnp.stack([xs, ys, ones], axis=0).reshape(3, -1)  # (3,HoWo)
        out = jnp.einsum("nij,jk->nik", theta, base)             # (N,2,HoWo)
        return out.reshape(N, 2, Ho, Wo)
    if transform_type == "warp":
        N, _two, H, W = data.shape
        ys, xs = jnp.meshgrid(jnp.arange(H, dtype=data.dtype),
                              jnp.arange(W, dtype=data.dtype),
                              indexing="ij")
        x_new = (data[:, 0] + xs) * (2.0 / max(W - 1, 1)) - 1.0
        y_new = (data[:, 1] + ys) * (2.0 / max(H - 1, 1)) - 1.0
        return jnp.stack([x_new, y_new], axis=1)
    raise ValueError("GridGenerator transform_type %r" % (transform_type,))


@register("SpatialTransformer")
def spatial_transformer(data, loc, target_shape=(0, 0),
                        transform_type="affine", sampler_type="bilinear",
                        cudnn_off=None):
    """Reference spatial_transformer.cc:217 (STN): affine theta from the
    localization net + bilinear sampling in one op."""
    if transform_type != "affine" or sampler_type != "bilinear":
        raise ValueError("SpatialTransformer supports affine/bilinear")
    grid = grid_generator.fn(loc, "affine", target_shape)
    return _bilinear_sample_core(data, grid)
