"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has NO long-context support (SURVEY §5.7) — its closest
artifacts are the fused attention GEMMs (src/operator/contrib/
transformer.cc:650-826) bounded by single-GPU memory.  Here sequences are
sharded over a mesh axis ('sp'):

- ``ring_attention``: each device holds a Q/K/V shard; K/V blocks rotate
  around the ICI ring via ``ppermute`` while each hop's partial attention
  is accumulated with a numerically-stable online softmax (flash-attention
  style).  Compute overlaps communication — the classic ring schedule.
- ``ulysses_attention``: all-to-all reshard (seq→heads) so each device runs
  full-sequence attention for a head subset — lower comm volume for
  head-rich models.

Both are pure jax functions usable inside shard_map/pjit; the single-device
block kernel can be swapped for the Pallas flash kernel
(mxnet_tpu.ops.pallas_attention).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["ring_attention", "ulysses_attention", "local_attention_block"]


def local_attention_block(q, k, v, bias=None, scale=None):
    """Single-shard attention block returning (out_unnorm, lse-style stats)
    for online-softmax accumulation.  q:(B,H,Tq,D) k,v:(B,H,Tk,D)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
    return o, m[..., 0], l[..., 0]


def _ring_attn_sharded(q, k, v, axis_name, causal, scale, impl="dense",
                       block=512):
    """Per-shard body (runs under shard_map).  q,k,v: local (B,H,T_loc,D).

    impl='dense' materializes each visiting (T_loc, T_loc) score block;
    impl='flash' runs the Pallas flash kernel per hop and merges the
    normalized partials via their logsumexp (exact: softmax is associative
    under lse reweighting) — O(T_loc·D) memory per hop, MXU matmuls
    throughout, the ring-of-flash-blocks design for long context.  The
    kernels take ``(B, T, H, D)``; this function's shards are head-major,
    so its transposes stand at ITS boundary, once a call and not once a
    hop: q, k and v go to rows before the scan, K/V travel the ring as
    rows (``ppermute`` does not care), the accumulator is kept as rows and
    the result goes back head-major after the last hop.  No benchmark
    cell runs the ring, so no cell pays those four copies; a caller that
    holds rows already should get a rows entry point before it gets a
    cell."""
    axis_size = lax.psum(1, axis_name)
    rank = lax.axis_index(axis_name)
    B, H, T, D = q.shape
    scale_ = scale if scale is not None else 1.0 / (D ** 0.5)

    def block_bias(kv_rank):
        if not causal:
            return None
        # global positions of this device's queries vs the visiting block's
        q_pos = rank * T + jnp.arange(T)
        k_pos = kv_rank * T + jnp.arange(T)
        mask = q_pos[:, None] >= k_pos[None, :]
        return jnp.where(mask, 0.0, -1e30)[None, None]

    if impl == "flash":
        from ..ops.pallas_attention import flash_attention_lse

        bq = min(block, T)
        q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))  # rows

        def flash_hop(k_cur, v_cur, kv_rank):
            def hop(causal_flag):
                o, l = flash_attention_lse(q, k_cur, v_cur, causal_flag,
                                           scale_, bq, bq)
                return o.astype(jnp.float32), l

            if not causal:
                return hop(False)

            def skip(_):
                return (jnp.zeros((B, T, H, D), jnp.float32),
                        jnp.full((B, H, T), -jnp.inf, jnp.float32))

            # diagonal hop: in-block causal; earlier ranks: fully visible;
            # later ranks: fully masked
            idx = jnp.where(kv_rank == rank, 0,
                            jnp.where(kv_rank < rank, 1, 2))
            return lax.switch(idx, [lambda _: hop(True),
                                    lambda _: hop(False), skip], None)

        def step_flash(carry, i):
            o_acc, lse_acc, k_cur, v_cur = carry
            kv_rank = (rank - i) % axis_size
            o_blk, lse_blk = flash_hop(k_cur, v_cur, kv_rank)
            lse_new = jnp.logaddexp(lse_acc, lse_blk)
            # lse is head-major (B, H, T), the partials rows (B, T, H, D)
            w_a, w_b = (jnp.exp(x - lse_new).transpose(0, 2, 1)[..., None]
                        for x in (lse_acc, lse_blk))
            o_acc = o_acc * w_a + o_blk * w_b
            perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
            k_nxt = lax.ppermute(k_cur, axis_name, perm)
            v_nxt = lax.ppermute(v_cur, axis_name, perm)
            return (o_acc, lse_new, k_nxt, v_nxt), None

        zero_q = (q * 0).astype(jnp.float32)
        o0 = zero_q
        lse0 = zero_q[..., 0].transpose(0, 2, 1) - jnp.inf
        (o, _lse, _, _), _ = lax.scan(step_flash, (o0, lse0, k, v),
                                      jnp.arange(axis_size))
        return o.astype(q.dtype).transpose(0, 2, 1, 3)

    def step(carry, i):
        o_acc, m_acc, l_acc, k_cur, v_cur = carry
        kv_rank = (rank - i) % axis_size
        bias = block_bias(kv_rank)
        o_blk, m_blk, l_blk = local_attention_block(q, k_cur, v_cur,
                                                    bias=bias, scale=scale_)
        # online softmax merge
        m_new = jnp.maximum(m_acc, m_blk)
        alpha = jnp.exp(m_acc - m_new)
        beta = jnp.exp(m_blk - m_new)
        o_acc = o_acc * alpha[..., None] + o_blk * beta[..., None]
        l_acc = l_acc * alpha + l_blk * beta
        # rotate K/V to the next device on the ICI ring (overlaps with the
        # next block's compute under XLA's async collectives)
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (o_acc, m_new, l_acc, k_nxt, v_nxt), None

    # derive carries from q so they inherit the device-varying type the
    # scan body produces (shard_map vma rules)
    zero_q = (q * 0).astype(jnp.float32)
    o0 = zero_q
    m0 = zero_q[..., 0] - jnp.inf
    l0 = zero_q[..., 0]
    (o, m, l, _, _), _ = lax.scan(step, (o0, m0, l0, k, v),
                                  jnp.arange(axis_size))
    out = o / jnp.maximum(l[..., None], 1e-37)
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh=None, axis_name="sp", causal=False,
                   scale=None, impl="dense", block=512):
    """Context-parallel attention.  q,k,v: (B, H, T, D) with T sharded over
    ``axis_name`` when called under pjit/shard_map; standalone call shards
    internally over ``mesh``.  impl='flash' runs the Pallas flash kernel
    per ring hop (see _ring_attn_sharded).

    NB impl='flash' inside a CALLER-managed shard_map: pallas_call outputs
    carry no varying-axes annotation, so the enclosing shard_map must be
    created with ``check_vma=False`` — the mesh= path below does this
    automatically."""
    body = functools.partial(_ring_attn_sharded, axis_name=axis_name,
                             causal=causal, scale=scale, impl=impl,
                             block=block)
    if impl not in ("dense", "flash"):
        raise ValueError("ring_attention impl must be 'dense' or 'flash', "
                         "got %r" % (impl,))
    if mesh is None:
        # assume we're already inside a shard_map context
        return body(q, k, v)
    spec = P(None, None, axis_name, None)
    if impl == "flash":
        # pallas_call's out_shape carries no vma annotation; use the
        # relaxed shard_map (_smap.py)
        from ._smap import shard_map_compat

        sm = shard_map_compat(body, mesh=mesh,
                              in_specs=(spec, spec, spec), out_specs=spec)
    else:
        sm = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
    return sm(q, k, v)


def _ulysses_sharded(q, k, v, axis_name, causal, scale):
    """all-to-all: (B,H,T_loc,D) seq-sharded -> head-sharded full-seq."""
    axis_size = lax.psum(1, axis_name)
    B, H, T, D = q.shape
    h_loc = H // axis_size

    def to_heads(x):
        # (B, H, T_loc, D) -> (B, H/A, T_loc*A, D): split the head axis
        # across devices, gather the sequence axis (one tiled all-to-all)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def to_seq(x):
        # inverse reshard: (B, H/A, T_glob, D) -> (B, H, T_loc, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    Tg = qh.shape[2]
    bias = None
    if causal:
        mask = jnp.tril(jnp.ones((Tg, Tg), bool))
        bias = jnp.where(mask, 0.0, -1e30)[None, None]
    o, m, l = local_attention_block(qh, kh, vh, bias=bias, scale=scale)
    o = (o / jnp.maximum(l[..., None], 1e-37)).astype(q.dtype)
    return to_seq(o)


def ulysses_attention(q, k, v, mesh=None, axis_name="sp", causal=False,
                      scale=None):
    """DeepSpeed-Ulysses-style sequence parallelism: one all-to-all turns a
    sequence shard into a head shard, full attention runs locally, a second
    all-to-all restores sequence sharding."""
    body = functools.partial(_ulysses_sharded, axis_name=axis_name,
                             causal=causal, scale=scale)
    if mesh is None:
        return body(q, k, v)
    spec = P(None, None, axis_name, None)
    return shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)
