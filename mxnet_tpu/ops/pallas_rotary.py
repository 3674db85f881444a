"""Per-head RMSNorm and rotary positions in one pass: a Pallas TPU kernel
between a decoder layer's q / k projections and the flash kernels.

What a layer does to q and to k between their projection and the attention
kernels (a per-head RMSNorm where the model has QK-norm, then rotary
positions in the rotate-half form) is elementwise work on ``(B, T, heads *
D)`` rows.  As XLA operations (``ops/nn.py:rms_norm`` and
``rotary_embedding``) the rotated half is a concatenate of two lane slices,
which XLA emits as a fusion of its own with a float32 result in HBM, its
backward as a ``split`` and a padded add, and every 4-D operation between a
projection and a Mosaic call decides layouts XLA then pays for: four to six
float32 passes a call, eight times the HBM bound (PERF.md section 6, PR 35).
Here it is ONE pass: a grid step reads a tile of positions of the
projection's rows as the matmul wrote them, and for every head's 128-lane
slice computes, in float32 registers,

    n = round(x * rsqrt(mean(x^2) + eps) * gain)      (where there is a gain)
    y = n * cos + partner(n) * sin

with ``partner`` a lane rotate (``pltpu.roll``) by half the rotary
dimension, and writes the head's tile where the flash kernels read it, in
their head-major view ``(B * heads, T, 1, D)``: the transpose is the out
``BlockSpec``'s index map.  The rounding points are those of the XLA form
(the norm rounds to the input's dtype before the rotation, one rounding at
the end), so the result is that form's, not a more or less precise one.

The backward is the same pass the other way: it reads the cotangent in the
kernels' view, as ``flash_bwd_dq`` / ``flash_bwd_dkv`` wrote it, applies the
transposed rotation (the same body under ``-sin``), then the norm's backward
where there is a gain, and writes ``(B, T, heads * D)`` rows for the
projection's backward; the gain's gradient leaves as one partial row a grid
step, summed by XLA.

``serves`` is the rule, from the shapes alone: heads of a multiple of the
128 lanes on a call the flash kernels take.  Everything else (rotary on
64-lane slices, test-sized heads, the dense path, ``nd.rotary_embedding`` as
an operator) keeps the XLA form.
"""
from __future__ import annotations

import contextlib
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import pallas_attention as pa

# bytes of one block of rows: tiles of 128 positions at 64 heads of 128 in
# bf16, 512 at 8 (PERF.md section 6, PR 35: the tiles measured)
BLOCK_BYTES = 2 << 20
MAX_TILE = 512


def serves(seq, head_dim, array_mask, itemsize):
    """Whether the kernel serves a layer's q and k: the head fills whole
    128-lane registers and ``multi_head_attention(impl="auto")`` would hand
    the call to the flash kernels."""
    return head_dim % 128 == 0 and pa.use_flash(seq, seq, head_dim,
                                                array_mask, itemsize)


def rotary_tables(positions, head_dim, theta=10000.0, rotary_dim=None,
                  inv_freq=None, factor=1.0):
    """The float32 tables ``(1 or B, T, 2 * head_dim)`` of a rotation
    ``rotary_embedding`` would make with the same arguments on heads of
    ``head_dim``: ``head_dim`` lanes of cos (1 on the lanes that pass
    through), then as many of the sin a lane's partner takes, signed (minus
    on the first half of the rotary dimensions, whose partner stands
    ``rotary_dim / 2`` lanes up; 0 on the lanes that pass through)."""
    from .nn import _rotary_angles

    r = head_dim if rotary_dim is None else int(rotary_dim)
    ang = _rotary_angles(positions, r, theta, inv_freq)     # (.., T, r/2)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    rest = jnp.zeros(ang.shape[:-1] + (head_dim - r,), jnp.float32)
    tables = jnp.concatenate([cos, cos, rest + 1.0, -sin, sin, rest], -1)
    return tables[None] if tables.ndim == 2 else tables


class _Cfg(NamedTuple):
    """Static (hashable) configuration of one call."""
    heads: int
    rotary_dim: int
    eps: float | None       # None: no norm
    each: bool              # a table a batch row (positions (B, T))
    interpret: bool


def _tile(seq, row_bytes):
    """Positions a grid step takes: the power of two whose block of rows
    stays within ``BLOCK_BYTES``, or the whole sequence."""
    tile = MAX_TILE
    while tile > 16 and tile * row_bytes > BLOCK_BYTES:
        tile //= 2
    return seq if seq <= tile else tile


def _split(t_ref, r, transposed=False):
    """A step's tables as ``(cos, [(lane rotate, sin its partner takes)])``,
    each ``(tile, d)``: a whole head has one partner (a rotate by half the
    head); a partial rotary two, lane ``i + r/2`` for the first half of the
    rotary dimensions and ``i - r/2`` for the second, each under the sin
    of its own lanes and 0 elsewhere.  ``transposed``: the rotation by the
    opposite angles."""
    tables = t_ref[0]
    d = tables.shape[1] // 2
    cos, sin = tables[:, :d], tables[:, d:]
    if transposed:
        sin = -sin
    if r == d:
        return cos, [(d // 2, sin)]
    lane = jax.lax.broadcasted_iota(jnp.int32, sin.shape, 1)
    first = jnp.where(lane < r // 2, sin, 0.0)
    return cos, [(d - r // 2, first), (r // 2, sin - first)]


def _turn(n, cos, partners):
    """``n * cos + sum of partner(n) * sin`` on a ``(tile, d)`` float32
    head."""
    from jax.experimental.pallas import tpu as pltpu

    y = n * cos
    for shift, sin in partners:
        y = y + pltpu.roll(n, shift, 1) * sin
    return y


def _rms(x, eps):
    """A head's ``x * rsqrt(mean(x^2) + eps)`` and the rsqrt, float32."""
    r = jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return x * r, r


def _fwd_kernel(x_ref, t_ref, *rest, heads, rotary_dim, eps):
    o_ref = rest[-1]
    d = o_ref.shape[-1]
    cos, partners = _split(t_ref, rotary_dim)
    for h in range(heads):
        x = x_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)
        if eps is not None:
            # the norm's own rounding, as ops/nn.py:rms_norm makes it
            x = (_rms(x, eps)[0] * rest[0][...]).astype(o_ref.dtype) \
                .astype(jnp.float32)
        o_ref[h] = _turn(x, cos, partners).astype(o_ref.dtype)


def _bwd_kernel(dy_ref, *rest, heads, rotary_dim, eps, seq, tile):
    if eps is None:
        t_ref, dx_ref = rest
    else:
        x_ref, t_ref, g_ref, dx_ref, dg_ref = rest
        gain = g_ref[...]
        dgain = jnp.zeros((tile, gain.shape[-1]), jnp.float32)
    d = dy_ref.shape[-1]
    cos, partners = _split(t_ref, rotary_dim, transposed=True)
    for h in range(heads):
        lanes = slice(h * d, (h + 1) * d)
        dn = _turn(dy_ref[h].astype(jnp.float32), cos, partners)
        if eps is not None:
            dn = dn.astype(dx_ref.dtype).astype(jnp.float32)
            xh, r = _rms(x_ref[0, :, lanes].astype(jnp.float32), eps)
            dgain = dgain + dn * xh
            u = dn * gain
            dn = r * (u - xh * jnp.mean(u * xh, axis=-1, keepdims=True))
        dx_ref[0, :, lanes] = dn.astype(dx_ref.dtype)
    if eps is not None:
        if seq % tile:
            # rows past the sequence's end hold whatever the fetch left
            row = pl.program_id(1) * tile + jax.lax.broadcasted_iota(
                jnp.int32, dgain.shape, 0)
            dgain = jnp.where(row < seq, dgain, 0.0)
        dg_ref[0] = jnp.sum(dgain, axis=0, keepdims=True)


def _specs(cfg, B, T, HD, itemsize):
    """Tile, grid and the ``BlockSpec``s of rows, kernel view, tables and
    gain of a call on local ``(B, T, heads * D)`` rows."""
    from jax.experimental.pallas import tpu as pltpu

    D = HD // cfg.heads
    tile = _tile(T, HD * itemsize)
    rows = pl.BlockSpec((1, tile, HD), lambda b, i: (b, i, 0))
    view = pl.BlockSpec((cfg.heads, tile, D), lambda b, i: (b, i, 0))
    table = pl.BlockSpec((1, tile, 2 * D),
                         lambda b, i: (b if cfg.each else 0, i, 0))
    gain = pl.BlockSpec((1, D), lambda b, i: (0, 0))
    # three blocks of rows at most (dy, x, dx), double-buffered, the
    # tables' tile, and float32 temporaries the size of a block of rows
    # four times over (the compiler keeps the heads' apart)
    need = 6 * tile * HD * itemsize + 4 * tile * D * 4 \
        + 4 * tile * HD * 4
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=need if need > pa.VMEM_DEFAULT_BYTES else None)
    return tile, (B, pl.cdiv(T, tile)), rows, view, table, gain, params


def _forward_local(cfg, x, *shared):
    """Rows ``(B, T, heads * D)`` -> kernel view ``(B * heads, T, D)``;
    ``shared``: the tables and, with a norm, the gain ``(1, D)``."""
    B, T, HD = x.shape
    tile, grid, rows, view, table, gain, params = _specs(
        cfg, B, T, HD, x.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=cfg.heads,
                          rotary_dim=cfg.rotary_dim, eps=cfg.eps),
        grid=grid, in_specs=[rows, table] + [gain] * (len(shared) - 1),
        out_specs=view,
        out_shape=jax.ShapeDtypeStruct(
            (B * cfg.heads, T, HD // cfg.heads), x.dtype),
        interpret=cfg.interpret, compiler_params=params,
        name="placed_fwd")(x, *shared),


def _backward_local(cfg, dy, *more):
    """The cotangent in kernel view -> rows' ``(dx,)``, or with a norm
    (``more``: x, tables, gain) ``(dx, one partial row of the gain's
    gradient a grid step)``."""
    norm = cfg.eps is not None
    D = dy.shape[-1]
    B, T, HD = dy.shape[0] // cfg.heads, dy.shape[1], cfg.heads * D
    tile, grid, rows, view, table, gain, params = _specs(
        cfg, B, T, HD, dy.dtype.itemsize)
    out_specs, out_shape = [rows], [jax.ShapeDtypeStruct((B, T, HD),
                                                          dy.dtype)]
    if norm:
        out_specs.append(pl.BlockSpec(
            (1, 1, D), lambda b, i: (b * grid[1] + i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B * grid[1], 1, D),
                                              jnp.float32))
    return tuple(pl.pallas_call(
        functools.partial(_bwd_kernel, heads=cfg.heads,
                          rotary_dim=cfg.rotary_dim, eps=cfg.eps, seq=T,
                          tile=tile),
        grid=grid,
        in_specs=[view] + ([rows, table, gain] if norm else [table]),
        out_specs=out_specs, out_shape=out_shape,
        interpret=cfg.interpret, compiler_params=params,
        name="placed_bwd")(dy, *more))


def _over_rows(local_fn, out_ndims, cfg, rows, tables, *gain):
    """``local_fn(cfg, *rows, tables, *gain)`` directly, or a device its
    own batch rows under the ``mesh_rows`` layout in force: tables of
    ``(B, T)`` positions ride with the rows, tables of ``(T,)`` positions
    and the gain go to every device whole."""
    batched = rows + ((tables,) if cfg.each else ())
    return pa._over_rows(
        local_fn, out_ndims, cfg, *batched,
        shared=(() if cfg.each else (tables,)) + gain)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _placed_core(cfg, x, tables, gain):
    return _placed_fwd(cfg, x, tables, gain)[0]


def _placed_fwd(cfg, x, tables, gain):
    norm = cfg.eps is not None
    out, = _over_rows(_forward_local, (3,), cfg, (x,), tables,
                      *((gain,) if norm else ()))
    return out, (x if norm else None, tables, gain)


def _placed_bwd(cfg, res, dy):
    x, tables, gain = res
    if cfg.eps is None:
        dx, = _over_rows(_backward_local, (3,), cfg, (dy,), tables)
        return dx, jnp.zeros_like(tables), None
    dx, dgain = _over_rows(_backward_local, (3, 3), cfg, (dy, x), tables,
                           gain)
    return dx, jnp.zeros_like(tables), dgain.sum(0)


_placed_core.defvjp(_placed_fwd, _placed_bwd)


def placed(x, tables, heads, rotary_dim=None, gain=None, eps=1e-6,
           interpret=None):
    """A projection's ``(B, T, heads * D)`` rows with their per-head
    RMSNorm (``gain`` ``(D,)`` and ``eps``; None: no norm) and the rotary
    positions of ``rotary_tables``' ``tables`` over the first
    ``rotary_dim`` of a head (default: all), in the flash kernels'
    head-major view ``(B * heads, T, 1, D)``; differentiable in ``x`` and
    ``gain``."""
    B, T, HD = x.shape
    interpret = pa._default_interpret() if interpret is None else interpret
    cfg = _Cfg(int(heads), int(rotary_dim or HD // heads),
               None if gain is None else float(eps),
               tables.shape[0] == B, bool(interpret))
    if gain is not None:
        # the gradient comes back as the float32 (1, D) the kernel sums
        # into; the cast's own transpose returns it at the gain's dtype
        gain = gain.astype(jnp.float32).reshape(1, -1)
    return _placed_core(cfg, x, tables, gain).reshape(
        B * heads, T, 1, HD // heads)


def placed_attention(q, k, v, positions, q_gain=None, k_gain=None, *,
                     num_heads, num_kv_heads, causal=False, mask=None,
                     eps=1e-6, **rotary):
    """``multi_head_attention(rotary(norm(q)), rotary(norm(k)), v)`` of a
    grouped-query layer on the kernels' path, on ``(B, T, heads * D)``
    rows: q and k go through ``placed`` straight into the flash kernels'
    view, so no XLA operation names a head between the projections and the
    kernels.  ``rotary``: keywords of ``rotary_embedding``; ``mask`` a
    static ``AttnMask`` or None."""
    B, T, HD = q.shape
    D = HD // num_heads
    tables, r = rotary_tables(positions, D, **rotary), rotary.get("rotary_dim")
    kind = pa.rule_kind(causal, mask)
    with jax.named_scope("mx.attn.%s" % kind) if kind \
            else contextlib.nullcontext():
        out = pa.flash_attention_placed(
            placed(q, tables, num_heads, r, q_gain, eps),
            placed(k, tables, num_kv_heads, r, k_gain, eps),
            v.reshape(B, T, num_kv_heads, D), causal, mask=mask)
    return out.reshape(B, T, HD)
