"""Flash attention: Pallas TPU kernel + blockwise-JAX fallback.

Reference parity: the reference's fastest attention path is
``_contrib_interleaved_matmul_selfatt_qk/valatt`` (src/operator/contrib/
transformer.cc:650-826) — cuBLAS strided-batch GEMMs that still materialize
the (Tq, Tk) score matrix in HBM.  The TPU-native design never materializes
it: the Pallas kernel streams K/V blocks through VMEM with an online-softmax
running (m, l, acc) state, so memory is O(T·D) and the MXU sees back-to-back
(block_q × D) @ (D × block_k) matmuls.

Three tiers:
- ``flash_attention``     — Pallas kernels fwd AND bwd (compiled on
                            the TPU, interpreted on the CPU backend so
                            the same kernels are testable there): the
                            backward recomputes per-block probabilities
                            from the saved logsumexp in dedicated dq and
                            dk/dv kernels, with in-kernel probability
                            dropout.  Takes and returns (B, T, H, D): at
                            heads of 64 or 32 the kernels' blocks index
                            the projections' rows, ``heads_per_step``
                            heads a grid step.
- ``blockwise_attention`` — pure-JAX lax.scan online softmax;
                            differentiable end-to-end; the fallback path.
- dense                   — plain einsum chain (ops/nn.py), best for short T.
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from ..base import MXNetError

_NEG_INF = -1e30


class AttnMask(NamedTuple):
    """A STATIC structured attention mask: a rule on (query index, key
    index) that the kernels evaluate on the tile's indices (never a
    ``(Tq, Tk)`` array) and whose fully masked tiles they skip.

    ``kind="block_diffusion"`` (BD3-LMs, Arriola et al. arXiv:2503.09573;
    SDAR's training): the sequence is ``[xt ; x0]``, ``2 * seq`` positions,
    noisy copy first; position ``i`` has block ``(i mod seq) // block``.
    A noisy query sees the noisy keys of its own block and the clean keys
    of earlier blocks; a clean query sees the clean keys of its own and
    earlier blocks.  ``seq**2 + seq*block`` of the ``4 seq**2`` pairs.

    ``kind="window"`` (causal sliding window, as Mistral and the Laguna
    family's ``sliding_attention`` layers have it): self-attention in
    which query ``i`` sees key ``j`` iff ``0 <= i - j < block``; ``block``
    is the window, ``seq`` is not used (0).  ``block * T - block *
    (block - 1) / 2`` of the ``T**2`` pairs at ``T >= block``."""
    kind: str
    seq: int
    block: int


def block_diffusion_mask(seq, block):
    if seq % block:
        raise MXNetError("block_diffusion_mask: seq %d is not a multiple "
                         "of the block length %d" % (seq, block))
    return AttnMask("block_diffusion", int(seq), int(block))


def window_mask(window):
    if window < 1:
        raise MXNetError("window_mask: a window of %d keys" % window)
    return AttnMask("window", 0, int(window))


def rule_kind(causal, mask):
    """The name of a call's structured rule, as its scope (``mx.attn.<kind>``)
    and ``mx.attn.tiles`` carry it: an ``AttnMask``'s kind, ``causal`` for
    the built-in rule, None where the call has neither (no mask, or an
    array)."""
    if isinstance(mask, AttnMask):
        return mask.kind
    return "causal" if causal and mask is None else None


def mask_allowed(mask, q_idx, k_idx):
    """The rule as a boolean expression of index arrays (broadcast
    against each other): what the kernels evaluate on a cut tile, from a
    ``(block_q, 1)`` column and a ``(1, block_k)`` row of indices, and what
    the dense path and the tests materialise at small sizes.

    Divisions, selects and subtractions stay on the operands' own shapes;
    only two compares and their join are as large as the broadcast."""
    if mask.kind == "window":
        return (k_idx <= q_idx) & (k_idx > q_idx - mask.block)
    if mask.kind != "block_diffusion":
        raise MXNetError("unknown attention mask kind %r" % (mask.kind,))
    L, b = mask.seq, mask.block
    q_clean, k_clean = q_idx >= L, k_idx >= L
    q_blk = jax.lax.div(jnp.where(q_clean, q_idx - L, q_idx), b)
    k_blk = jax.lax.div(jnp.where(k_clean, k_idx - L, k_idx), b)
    # a query sees the clean keys of the blocks under `below` and the noisy
    # keys of block `own` (none for a clean query); a key is coded so that
    # the compare it does not belong to is false.  Selects between integers
    # only: Mosaic has no select between booleans
    below = jnp.where(q_clean, q_blk + 1, q_blk)
    own = jnp.where(q_clean, -1, q_blk)
    k_as_clean = jnp.where(k_clean, k_blk, 2 ** 30)
    k_as_noisy = jnp.where(k_clean, -2, k_blk)
    return (k_as_clean < below) | (k_as_noisy == own)


# Tile-index arithmetic that runs on a traced tile index (a kernel's
# ``program_id``) and on a Python int alike, so that ``tile_counts`` counts
# in plain Python from the very functions the kernels loop over.  (Counting
# with eager jnp operations instead would compile a score of tiny programs
# while the step is traced.)  Non-negative operands only: ``//`` is
# ``lax.div`` there.
def _py(*xs):
    return all(isinstance(x, (int, bool)) for x in xs)


def _div(a, b):
    return a // b if _py(a) else jax.lax.div(a, b)


def _cdiv(a, b):
    return _div(a + (b - 1), b)


def _min(a, b):
    return min(a, b) if _py(a, b) else jnp.minimum(a, b)


def _max(a, b):
    return max(a, b) if _py(a, b) else jnp.maximum(a, b)


def _where(cond, a, b):
    return (a if cond else b) if _py(cond) else jnp.where(cond, a, b)


def _split(lo, hi, whole_lo, whole_hi, head=True, tail=True):
    """Tiles ``[lo, hi)`` as ``(lo, hi, cut)`` ranges in ascending order:
    cut tiles, then the tiles ``[whole_lo, whole_hi)`` (clipped into the
    range) that the rule allows whole, then cut tiles again.  ``head`` /
    ``tail`` are False where the caller knows that part empty, which saves
    tracing the tile body for it."""
    a = _min(_max(whole_lo, lo), hi)
    b = _min(_max(whole_hi, a), hi)
    return ([(lo, a, True)] if head else []) + [(a, b, False)] \
        + ([(b, hi, True)] if tail else [])


def _window_has_whole(window, block_q, block_k):
    """Can a window of ``window`` keys allow any tile whole?  The keys that
    EVERY row of a query tile sees are ``window - block_q + 1``, and a whole
    tile needs ``block_k`` of them.  Static: where it is False the kernels
    trace the cut body only (a window of one tile: both visited tiles are
    cut, the diagonal's and the far edge's)."""
    return window + 1 >= block_q + block_k


def _k_tiles(qi, block_q, block_k, seq_q, seq_k, causal=False, mask=None):
    """The key tiles that query tile ``qi`` has to visit (the forward and
    dq kernels' loop), as ``(lo, hi, cut)`` ranges of tile indices in
    ascending order (an empty range has hi <= lo).  Every tile outside
    them is masked whole and skipped.  A tile of a ``cut=False`` range is
    allowed WHOLE: every pair of it is, and it holds no padded key, so the
    body traced for it builds no index and no mask.  A tile that is not
    classified cheaply (a query tile astride ``mask.seq``, a padded last
    tile) counts as cut, never as whole."""
    nk = -(-seq_k // block_k)
    last = seq_k // block_k             # a padded last tile is always cut
    if mask is None and not causal:
        return _split(0, nk, 0, last, head=False, tail=last < nk)
    q0 = qi * block_q
    if mask is None:
        # right of the diagonal nothing is visited; a tile is whole when
        # its last key is not past the tile's first row
        hi = _min(nk, _cdiv(q0 + block_q, block_k))
        return _split(0, hi, 0, _min(_div(q0 + 1, block_k), last),
                      head=False)
    if mask.kind == "window":
        # from the first row's oldest key to the diagonal; a tile is whole
        # when its last key is not past the first row and its first key is
        # inside the last row's window
        w = mask.block
        lo = _div(_max(q0 - (w - 1), 0), block_k)
        hi = _min(nk, _cdiv(q0 + block_q, block_k))
        if not _window_has_whole(w, block_q, block_k):
            return [(lo, hi, True)]
        return _split(lo, hi, _cdiv(_max(q0 + block_q - w, 0), block_k),
                      _min(_div(q0 + 1, block_k), last))
    # block diffusion: noisy keys of the rows' own blocks (all cut), then
    # clean keys up to the last row's block
    L, b = mask.seq, mask.block
    q1 = _min(q0 + block_q, seq_q)                   # exclusive
    n1 = _min(q1, L)                                 # end of the noisy rows
    has_noisy = q0 < L
    # noisy rows [q0, n1): noisy keys of blocks blk(q0) .. blk(n1 - 1)
    last_noisy_blk = _div(_max(n1 - 1, 0), b)
    lo1 = _where(has_noisy, _div(_div(q0, b) * b, block_k), 0)
    hi1 = _where(has_noisy, _cdiv((last_noisy_blk + 1) * b, block_k), 0)
    # clean keys: blocks < blk(n1 - 1) for the noisy rows, <= blk(q1 - 1 - L)
    # for the clean ones
    end_noisy = _where(has_noisy, last_noisy_blk * b, 0)
    end_clean = _where(q1 > L, (_div(_max(q1 - 1 - L, 0), b) + 1) * b, 0)
    end = _max(end_noisy, end_clean)
    lo2 = _max(L // block_k, hi1)
    hi2 = _where(end > 0, _cdiv(L + end, block_k), 0)
    # EVERY row sees the clean keys under its first row's limit: blocks
    # < blk(q0) if the tile is all noisy, <= blk(q0 - L) if all clean, none
    # if it lies astride L
    whole_end = _where(
        q1 <= L, _div(q0, b) * b,
        _where(has_noisy, 0, (_div(_max(q0 - L, 0), b) + 1) * b))
    return [(lo1, hi1, True)] + _split(
        lo2, hi2, -(-L // block_k), _div(L + whole_end, block_k),
        head=L % block_k != 0)


def _q_tiles(j, block_q, block_k, seq_q, seq_k, causal=False, mask=None):
    """The query tiles that key tile ``j`` has to visit (the dkv kernel's
    loop), classified as ``_k_tiles`` does.  Here a tile with padded QUERY
    rows is the one that is always cut; padded key rows only make rows of
    dK and dV that the caller cuts off."""
    nq = -(-seq_q // block_q)
    last = seq_q // block_q
    if mask is None and not causal:
        return _split(0, nq, 0, last, head=False, tail=last < nq)
    k0 = j * block_k
    if mask is None:
        # q tiles left of this key tile see only masked scores; a tile is
        # whole when its first row is not before the tile's last key
        return _split(_div(k0, block_q), nq,
                      _cdiv(k0 + block_k - 1, block_q), last,
                      tail=last < nq)
    if mask.kind == "window":
        # from the diagonal to the last row that sees the tile's last key;
        # a tile is whole when its first row is not before the last key and
        # its last row still sees the first key
        w = mask.block
        lo = _div(k0, block_q)
        hi = _min(nq, _cdiv(_min(k0 + block_k, seq_k) + (w - 1), block_q))
        if not _window_has_whole(w, block_q, block_k):
            return [(lo, hi, True)]
        return _split(lo, hi, _cdiv(k0 + block_k - 1, block_q),
                      _min(_div(k0 + w, block_q), last))
    # block diffusion: noisy queries (of the noisy keys' own blocks, and of
    # later blocks than the first clean key's), then clean queries from the
    # first clean key's block on
    L, b = mask.seq, mask.block
    k1 = _min(k0 + block_k, seq_k)
    has_noisy, has_clean = k0 < L, k1 > L
    n1 = _min(k1, L)
    # noisy keys [k0, n1): noisy queries of blocks blk(k0) .. blk(n1 - 1)
    a_lo = _div(_div(k0, b) * b, block_q)
    a_hi = _cdiv((_div(_max(n1 - 1, 0), b) + 1) * b, block_q)
    # clean keys from block kb0 on: noisy queries of blocks > kb0
    kb0 = _div(_max(k0 - L, 0), b)
    b_lo = _div((kb0 + 1) * b, block_q)
    b_hi = _where((kb0 + 1) * b < L, -(-L // block_q), 0)
    lo1 = _min(_where(has_noisy, a_lo, nq),
               _where(has_clean & (b_hi > 0), b_lo, nq))
    hi1 = _max(_where(has_noisy, a_hi, 0), _where(has_clean, b_hi, 0))
    # clean queries of blocks >= kb0
    lo2 = _max(_div(L + kb0 * b, block_q), hi1)
    hi2 = _where(has_clean, nq, 0)
    # a tile of clean keys only is seen WHOLE by the noisy query tiles past
    # its last key's block, and by the clean ones from that block on
    kb1 = _div(_max(k1 - 1 - L, 0), b)
    return _split(lo1, hi1,
                  _where(has_noisy, hi1, _cdiv((kb1 + 1) * b, block_q)),
                  L // block_q, tail=L % block_q != 0) \
        + _split(lo2, hi2,
                 _where(has_noisy, hi2, _cdiv(L + kb1 * b, block_q)),
                 last, tail=last < nq)


def _loop_tiles(ranges, body, init):
    """``body(tile, carry, cut)`` over the ranges in turn, traced once a
    range: the tile's class is static."""
    carry = init
    for lo, hi, cut in ranges:
        if isinstance(lo, int) and isinstance(hi, int) and hi <= lo:
            continue
        carry = jax.lax.fori_loop(lo, hi, functools.partial(body, cut=cut),
                                  carry)
    return carry


def _cut_valid(qi, j, block_q, block_k, causal, mask, seq_q=None,
               seq_k=None, transposed=False):
    """What a CUT tile ``(qi, j)`` masks: a boolean that broadcasts to the
    tile, from a column of query indices and a row of key indices (the
    other way round for ``transposed`` scores), or None where the call
    masks nothing.  ``seq_q`` / ``seq_k`` are given where the call is
    padded on that side and the kernel has to mask the padding."""
    k_shape, q_shape = ((block_k, 1), (1, block_q)) if transposed \
        else ((1, block_k), (block_q, 1))
    k_idx = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, k_shape, 0 if transposed else 1)
    q_idx = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, q_shape, 1 if transposed else 0)
    valid = None
    if causal:
        valid = k_idx <= q_idx
    elif mask is not None:
        valid = mask_allowed(mask, q_idx, k_idx)
    for idx, seq in ((k_idx, seq_k), (q_idx, seq_q)):
        if seq is not None:
            valid = idx < seq if valid is None else valid & (idx < seq)
    return valid


# The block sizes a call uses when it names none: one pair for every
# shape and mask of the flash kernels, one K block for the scan.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
DEFAULT_BLOCKWISE_K = 256


# ---------------------------------------------------------------------------
# blockwise (pure JAX) — the reference semantics + the backward path
# ---------------------------------------------------------------------------
def blockwise_attention(q, k, v, causal=False, sm_scale=None, block_k=None,
                        dropout_p=0.0, dropout_key=None):
    """Memory-efficient attention via lax.scan over K/V blocks.

    q, k, v: (B, H, T, D).  Differentiable; O(T·D + T·block_k) live memory.

    ``dropout_p`` drops attention PROBABILITIES (the BERT recipe) without
    ever materializing the (T, T) matrix: the softmax denominator
    accumulates the undropped mass while the numerator applies a
    per-block threefry mask — exactly dropout(softmax(s)) @ v, computed
    online.  Deterministic per ``dropout_key``, so the vjp recomputation
    sees the same mask.

    ``block_k=None`` (default) is ``DEFAULT_BLOCKWISE_K`` (256).  Under
    dropout the per-block mask is folded by k-block index, so another
    ``block_k`` draws another mask."""
    block_k = DEFAULT_BLOCKWISE_K if block_k is None else int(block_k)
    if dropout_p > 0.0 and dropout_key is None:
        raise ValueError(
            "blockwise_attention: dropout_p > 0 requires dropout_key "
            "(e.g. jax.random.PRNGKey / mxnet_tpu.random.take_key())")
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    scale = sm_scale if sm_scale is not None else 1.0 / (D ** 0.5)
    block_k = min(block_k, Tk)
    nk = -(-Tk // block_k)
    pad = nk * block_k - Tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(B, H, nk, block_k, D).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, H, nk, block_k, D).transpose(2, 0, 1, 3, 4)
    qs = q.astype(jnp.float32) * scale
    q_idx = jnp.arange(Tq)

    def body(carry, inp):
        m, l, acc = carry
        kblk, vblk, j = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", qs, kblk.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        k_idx = j * block_k + jnp.arange(block_k)
        valid = k_idx < Tk
        if causal:
            valid = valid[None, :] & (k_idx[None, :] <= q_idx[:, None])
            s = jnp.where(valid[None, None], s, _NEG_INF)
        else:
            s = jnp.where(valid[None, None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        if dropout_p > 0.0:
            keep = 1.0 - dropout_p
            mask_bits = jax.random.bernoulli(
                jax.random.fold_in(dropout_key, j), keep, p.shape)
            p_num = p * mask_bits.astype(p.dtype) / keep
        else:
            p_num = p
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p_num, vblk.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, H, Tq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tq), jnp.float32)
    a0 = jnp.zeros((B, H, Tq, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kb, vb, jnp.arange(nk)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernels (forward + flash backward; reference fwd-only equivalent:
# src/operator/contrib/transformer.cc:650-826)
# ---------------------------------------------------------------------------
def _tile_keep_mask(seed_bh, tile, shape, dropout_p, interpret,
                    transposed=False):
    """Deterministic per-tile keep mask of a ``shape`` = (block_q,
    block_k) tile; ``transposed`` hands the SAME mask out keys first, for
    the dkv kernel's transposed tile.

    ``seed_bh`` is this (batch, head)'s word of the seed array (see
    ``_bh_seeds``) and ``tile`` the flat (q-block, k-block) index, so the
    SAME mask is reproducible from the forward kernel, the dq kernel (fixed
    qi, looping j) and the dkv kernel (fixed j, looping qi) without storing
    any bits.  Two words are all the core PRNG takes (Mosaic: "Setting seed
    with more than 2 values is not supported").  On TPU hardware the bits
    come from the core PRNG (pltpu.prng_*); interpret mode has no lowering
    for those, so it derives a threefry mask instead — each backend is
    self-consistent across its fwd/bwd passes, which is the only
    requirement (masks need not match across backends)."""
    if interpret:
        key = jax.random.fold_in(jax.random.PRNGKey(seed_bh), tile)
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, shape)
        return keep.T if transposed else keep
    from jax.experimental.pallas import tpu as pltpu

    pltpu.prng_seed(seed_bh, tile)
    bits = pltpu.prng_random_bits(shape)      # int32, all 2^32 patterns
    if transposed:
        bits = bits.T
    # signed compare: P(bits < t) = (t + 2^31) / 2^32 = 1 - dropout_p
    thresh = int((1.0 - dropout_p) * 2.0 ** 32) - 2 ** 31
    return bits < jnp.int32(min(thresh, 2 ** 31 - 1))


# Every product takes q, k, v and dO at the CALLER's dtype and accumulates
# in float32 (``preferred_element_type``); p and ds are cast to that dtype
# for the second products, after the dropout factor.  Softmax statistics,
# lse, delta and the accumulators are float32 whatever comes in, and the
# softmax scale multiplies the float32 scores, never a bf16 q.  A float32
# caller so keeps float32 operands in every product, a bf16 caller feeds
# the MXU bf16: the input's dtype decides, no switch does.
_NT = (((1,), (1,)), ((), ()))          # A @ B.T
_NN = (((1,), (0,)), ((), ()))          # A @ B


def _scores(a, b, scale, valid):
    """The tile's scaled scores ``a @ b.T`` in float32, masked where
    ``valid`` (None: nothing to mask) is false."""
    s = jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
    if scale != 1.0:
        s = s * scale
    return s if valid is None else jnp.where(valid, s, _NEG_INF)


# Heads a grid step (``heads_per_step``).  A step that serves several heads
# holds them side by side in blocks ``heads * D`` = 128 lanes wide and takes
# them one after the other, every product at the block's full width: the
# LEFT operand of a product contracted over the lanes (q or dO in ``A @
# B.T``, k or v in the transposed tile) has the other heads' lanes zeroed,
# which costs the MXU the passes a product over D lanes costs (a pass
# contracts 128 either way), and a product that MAKES lanes (``p @ v``,
# ``ds @ k``, ...) is right in the head's own lanes, which are selected
# into the step's result.  Measured against static lane slices of the refs
# (``q_ref[0, :, 64:128]``: a lane rotate a block) at BERT's shape: 1.7%
# less time over the three kernels (PERF.md section 6, PR 33).
def _own_lanes(a, heads, width):
    """The lanes of head ``a`` in a block of ``heads`` heads, as a (1,
    width) mask; None where the step serves one head (nothing to mask:
    the bodies are then the single-head bodies, operation for
    operation)."""
    if heads == 1:
        return None
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    d = width // heads
    return (lane >= a * d) & (lane < (a + 1) * d)


def _own(x, own):
    """``x`` with the other heads' lanes zeroed."""
    return x if own is None else jnp.where(own, x, jnp.zeros_like(x))


def _into(own, mine, step):
    """The step's result with head ``own``'s lanes taken from ``mine``
    (``step`` None: the first head)."""
    if own is None:
        return mine
    return jnp.where(own, mine, 0.0 if step is None else step)


def _flash_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *, heads,
                  scale, causal, block_q, block_k, seq_k, dropout_p,
                  interpret, mask=None, seq_q=None):
    """Forward for one (step of ``heads`` heads, q-block): the heads one
    after the other, each on its own lanes of the blocks (``_own_lanes``)
    and with its own statistics and dropout seed."""
    n = pl.program_id(0)
    qi = pl.program_id(1)
    width = q_ref.shape[-1]
    nk_all = pl.cdiv(seq_k, block_k)
    pad_k = seq_k if seq_k % block_k else None

    out = None
    for a in range(heads):
        own = _own_lanes(a, heads, width)
        q = _own(q_ref[0], own)                       # (block_q, width)

        def body(j, carry, cut):
            m, l, acc = carry
            kblk = k_ref[0, pl.ds(j * block_k, block_k), :]
            vblk = v_ref[0, pl.ds(j * block_k, block_k), :]
            valid = _cut_valid(qi, j, block_q, block_k, causal, mask,
                               seq_k=pad_k) if cut else None
            s = _scores(q, kblk, scale, valid)        # (block_q, block_k)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[:, None])
            corr = jnp.exp(m - m_new)
            # denominator accumulates UNdropped mass (the BERT recipe:
            # dropout(softmax(s)) @ v — normalization sees the full softmax)
            l_new = l * corr + p.sum(-1)
            if dropout_p > 0.0:
                keep = _tile_keep_mask(seed_ref[n * heads + a],
                                       qi * nk_all + j, p.shape, dropout_p,
                                       interpret)
                p = p * keep.astype(p.dtype) / (1.0 - dropout_p)
            acc_new = acc * corr[:, None] + jax.lax.dot_general(
                p.astype(vblk.dtype), vblk, _NN,
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        m0 = jnp.full((block_q,), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((block_q,), jnp.float32)
        a0 = jnp.zeros((block_q, width), jnp.float32)
        m, l, acc = _loop_tiles(
            _k_tiles(qi, block_q, block_k, seq_q, seq_k, causal, mask), body,
            (m0, l0, a0))
        out = _into(own, acc / jnp.maximum(l, 1e-30)[:, None], out)
        # lse rides a (8, block_q) tile — Mosaic requires the last two
        # block dims be (8k, 128k)-aligned, so a flat (1, block_q) row is
        # illegal on real TPU; sublane-broadcast and let the caller slice
        # row 0
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        lse_ref[a, 0] = jax.lax.broadcast_in_dim(lse, (8, block_q), (1,))
    o_ref[0] = out.astype(o_ref.dtype)


def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, out_ref, lse_ref,
                   *rest, heads, scale, causal, block_q, block_k, seq_k,
                   dropout_p, interpret, mask=None, seq_q=None):
    """dq for one (step of heads, q-block): ds = p∘(msc∘(dO·Vᵀ) − Δ);
    dq = scale·ds·K.

    Δ = rowsum(dO ∘ O) is made HERE, from the two blocks as they lie, and
    written out in lse's (8, block_q) rows for the dkv kernel: a reduction
    over D of the ``(B, T, H * D)`` rows is no program XLA writes well (it
    copies the float32 product into another layout first).  ``rest`` is
    ``(dq_ref, delta_ref)``, after a ``dlse_ref`` where the caller has a
    cotangent for lse: that folds into Δ (the softmax backward is ds =
    p·(dp − Δ) and ∂lse/∂s = p, so ds = p·(dp − (Δ − dlse))), and neither
    kernel needs to know."""
    *dlse_ref, dq_ref, delta_ref = rest
    n = pl.program_id(0)
    qi = pl.program_id(1)
    width = q_ref.shape[-1]
    nk_all = pl.cdiv(seq_k, block_k)
    pad_k = seq_k if seq_k % block_k else None

    dq_step = None
    for a in range(heads):
        own = _own_lanes(a, heads, width)
        q = _own(q_ref[0], own)
        do = _own(do_ref[0], own)
        lse = lse_ref[a, 0, 0, :][:, None]  # row 0 of the (8, block_q) tile
        delta = jnp.sum(do.astype(jnp.float32)
                        * out_ref[0].astype(jnp.float32), axis=-1)
        if dlse_ref:
            delta = delta - dlse_ref[0][a, 0, 0, :]
        delta_ref[a, 0] = jax.lax.broadcast_in_dim(delta, (8, block_q), (1,))
        delta = delta[:, None]

        def body(j, dq, cut):
            kblk = k_ref[0, pl.ds(j * block_k, block_k), :]
            vblk = v_ref[0, pl.ds(j * block_k, block_k), :]
            valid = _cut_valid(qi, j, block_q, block_k, causal, mask,
                               seq_k=pad_k) if cut else None
            s = _scores(q, kblk, scale, valid)
            p = jnp.exp(s - lse)                       # rows sum to 1
            dp = jax.lax.dot_general(do, vblk, _NT,
                                     preferred_element_type=jnp.float32)
            if dropout_p > 0.0:
                keep = _tile_keep_mask(seed_ref[n * heads + a],
                                       qi * nk_all + j, p.shape, dropout_p,
                                       interpret)
                dp = dp * keep.astype(dp.dtype) / (1.0 - dropout_p)
            ds = p * (dp - delta)
            return dq + jax.lax.dot_general(
                ds.astype(kblk.dtype), kblk, _NN,
                preferred_element_type=jnp.float32)

        dq = _loop_tiles(
            _k_tiles(qi, block_q, block_k, seq_q, seq_k, causal, mask), body,
            jnp.zeros((block_q, width), jnp.float32))
        dq_step = _into(own, dq, dq_step)
    dq_ref[0] = (dq_step * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, *, heads, scale, causal,
                    block_q, block_k, seq_q, seq_k, dropout_p, interpret,
                    mask=None):
    """dk/dv for one (step of heads, k-block), looping q blocks.

    dv = (p∘msc)ᵀ·dO;  dk = scale·dsᵀ·Q  with the SAME per-tile dropout
    mask as the forward (regenerated, not stored).  The tile is formed
    TRANSPOSED (``K Qᵀ``, keys down the rows): pᵀ and dsᵀ then enter their
    products as plain left operands (Mosaic takes no bf16 product
    contracted over dimension 0 of both operands), and lse and Δ broadcast
    along the rows as they lie, without a relayout a tile."""
    n = pl.program_id(0)
    j = pl.program_id(1)
    width = k_ref.shape[-1]
    nk_all = pl.cdiv(seq_k, block_k)
    pad_q = seq_q if seq_q % block_q else None

    dk_step = dv_step = None
    for a in range(heads):
        own = _own_lanes(a, heads, width)
        kblk = _own(k_ref[0], own)                    # (block_k, width)
        vblk = _own(v_ref[0], own)

        def body(qi, carry, cut):
            dk, dv = carry
            q = q_ref[0, pl.ds(qi * block_q, block_q), :]
            do = do_ref[0, pl.ds(qi * block_q, block_q), :]
            lse = lse_ref[a, qi, 0, :][None, :]  # (nq, 8, block_q), row 0
            delta = delta_ref[a, qi, 0, :][None, :]
            # padded KEY rows need no mask here: they only make rows of dK
            # and dV that the caller cuts off
            valid = _cut_valid(qi, j, block_q, block_k, causal, mask,
                               seq_q=pad_q, transposed=True) if cut else None
            s = _scores(kblk, q, scale, valid)        # (block_k, block_q)
            p = jnp.exp(s - lse)
            if cut and pad_q:
                p = jnp.where(valid, p, 0.0)          # padded q rows -> 0
            if dropout_p > 0.0:
                keep = _tile_keep_mask(
                    seed_ref[n * heads + a], qi * nk_all + j,
                    (block_q, block_k), dropout_p, interpret,
                    transposed=True).astype(p.dtype) / (1.0 - dropout_p)
            else:
                keep = None
            pm = p * keep if keep is not None else p
            dv = dv + jax.lax.dot_general(
                pm.astype(do.dtype), do, _NN,
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(vblk, do, _NT,
                                     preferred_element_type=jnp.float32)
            if keep is not None:
                dp = dp * keep
            ds = p * (dp - delta)
            dk = dk + jax.lax.dot_general(
                ds.astype(q.dtype), q, _NN,
                preferred_element_type=jnp.float32)
            return dk, dv

        dk, dv = _loop_tiles(
            _q_tiles(j, block_q, block_k, seq_q, seq_k, causal, mask), body,
            (jnp.zeros((block_k, width), jnp.float32),
             jnp.zeros((block_k, width), jnp.float32)))
        dk_step, dv_step = _into(own, dk, dk_step), _into(own, dv, dv_step)
    dk_ref[0] = (dk_step * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_step.astype(dv_ref.dtype)


def _smem_spec():
    """BlockSpec for the per-(batch, head) dropout seeds (SMEM on TPU)."""
    from jax.experimental.pallas import tpu as pltpu

    return pl.BlockSpec(memory_space=pltpu.SMEM)


def heads_per_step(head_dim, heads, kv_heads):
    """How many heads ONE grid step of the kernels serves when they read
    ``(B, T, heads * head_dim)`` rows as the projections wrote them, or 0
    where the kernels take a head-major view instead.

    Rows pay where a head-major copy is a copy: heads NARROWER than the
    128 lanes (BERT's 64).  There XLA cannot write a projection's product
    head-major for nothing (a ``(T, 64)`` tile half-fills its registers),
    so every transpose around a kernel call was a ``copy`` of its own; a
    block's last dimension has to be a multiple of 128 lanes or the whole
    array's, so such heads go ``128 // head_dim`` a block, if the head
    count divides by that and K/V are not grouped (a block of K/V then
    holds the same heads).  A single head spans its array either way.

    Everything else gets 0, and ``flash_attention`` hands the kernels a
    head-major view in which every head is a batch row of ONE head: heads
    of 80 or 96, an odd head count of 64, 64 with grouped KV heads, AND
    heads of a multiple of 128 lanes (SDAR, Laguna).  For those the view
    is no copy: XLA gives the transpose to the projection's product as
    its layout (``(T, 128)`` tiles are whole registers) and QK-norm and
    rotary run on it, while rows would need every 4-D elementwise
    operation between projection and kernel to keep rows' tiles (16
    positions of one head's lanes), which XLA's layouts for ``(B, T, H,
    D)`` do not: ``laguna_xs2_t8k`` lost 3.7% that way (PERF.md section
    6, PR 33)."""
    if heads == kv_heads == 1:
        return 1
    g = 128 // head_dim if head_dim < 128 and 128 % head_dim == 0 else 0
    return g if g and heads == kv_heads and heads % g == 0 else 0


def _steps(q, k):
    """``(heads a grid step, head blocks, group)`` of a call on local
    ``(B, T, H, D)`` arrays: blocks of ``heads * D`` lanes along the rows
    (as many in k and v as in q: heads that share a step are not grouped,
    and a grouped call comes head-major, one head a row), and the query
    heads a KV head serves."""
    B, _, H, D = q.shape
    g = heads_per_step(D, H, k.shape[2])
    return g, H // g, (B * H) // (k.shape[0] * k.shape[2])


def _pad_pack(q, k, v, block_q, block_k):
    """``(B, T, H, D)`` arrays as the rows the kernels index, ``(B, T
    padded to the block, H * D)``: a free reshape, and a pad on axis 1
    where T is not a multiple of the block.  Also the rows q was padded
    by."""
    pad_q = -q.shape[1] % block_q
    # k/v must be padded to a block multiple: pl.ds clamps its start at the
    # array edge, which would misalign rows against the k_idx mask
    pad_k = -k.shape[1] % block_k
    return _rows(q, pad_q), _rows(k, pad_k), _rows(v, pad_k), pad_q


def _rows(x, pad):
    x = x.reshape(x.shape[0], x.shape[1], -1)
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x


def _head_block(n, blocks):
    """Grid point ``n`` = batch row * ``blocks`` + head block, taken
    apart."""
    if blocks == 1:
        return n, 0
    return jax.lax.div(n, blocks), jax.lax.rem(n, blocks)


def _q_index(blocks, whole=False):
    """Index map of a block of q, dO, out, dq for grid point ``(b * blocks
    + h, tile)``: rows ``tile`` (or all of them) of batch row ``b``, lanes
    of head block ``h``."""
    def at(n, i):
        b, h = _head_block(n, blocks)
        return (b, 0 if whole else i, h)
    return at


def _kv_index(group, blocks, whole=True):
    """Index map of a K/V block for grid point ``(n, tile)``, ``n`` the
    query head block counted over the batch: with grouped KV heads, query
    head ``n`` reads KV head ``n // group`` where it lies in the
    ``(B, Tk, Hkv * D)`` rows, lanes ``[h D, (h + 1) D)`` of batch row
    ``b`` for KV head ``b * Hkv + h``.  K and V are never repeated in
    memory, and the consecutive grid points of one group keep the same
    block index, so a whole-K/V block is fetched once a group."""
    def at(n, i):
        b, h = _head_block(n if group == 1 else jax.lax.div(n, group),
                           blocks)
        return (b, 0 if whole else i, h)
    return at


class _Cfg(NamedTuple):
    """Static (hashable) kernel configuration shared by fwd and bwd."""
    causal: bool
    scale: float
    block_q: int
    block_k: int
    interpret: bool
    dropout_p: float
    mask: AttnMask | None = None


_MESH_ROWS = threading.local()


@contextlib.contextmanager
def mesh_rows(mesh, batch_axes):
    """Declare that the step being traced splits the batch over
    ``batch_axes`` of ``mesh``.

    Mosaic kernels cannot be partitioned automatically, so an engine that
    jits one program over a mesh (``FusedTrainer(mesh=...)``,
    ``mx.step`` on a ``GlobalMesh``) enters this around the call that
    traces it: the kernels then run under a ``shard_map`` in which every
    device works on its own batch rows — H, T and D whole — instead of
    failing to lower or gathering the batch to every chip.  Axes that do
    not divide B are left out, which costs a gather but stays correct."""
    prev = getattr(_MESH_ROWS, "layout", None)
    _MESH_ROWS.layout = (mesh, tuple(batch_axes))
    try:
        yield
    finally:
        _MESH_ROWS.layout = prev


def _over_rows(local_fn, out_ndims, cfg, *arrays, shared=()):
    """``local_fn(cfg, *arrays, *shared)`` — Pallas calls gridded over the
    leading batch rows of every array in ``arrays`` — directly, or per
    device under the ``mesh_rows`` layout in force; ``shared`` arrays have
    no batch rows (tables, gains) and every device takes them whole."""
    layout = getattr(_MESH_ROWS, "layout", None)
    # inside a caller's shard_map (ring attention, a pipeline stage, an
    # MoE expert) the arrays are one device's already
    if layout is None or jax.sharding.get_abstract_mesh().manual_axes:
        return local_fn(cfg, *arrays, *shared)
    mesh, batch_axes = layout
    keep, size = [], 1
    for a in batch_axes:
        if all(x.shape[0] % (size * mesh.shape[a]) == 0 for x in arrays):
            keep.append(a)
            size *= mesh.shape[a]
    spec = lambda ndim: P(tuple(keep) or None,  # noqa: E731
                          *(None,) * (ndim - 1))
    # pallas_call outputs carry no varying-axes annotation
    return jax.shard_map(
        functools.partial(local_fn, cfg), mesh=mesh,
        in_specs=tuple(spec(a.ndim) for a in arrays) + (P(),) * len(shared),
        out_specs=tuple(spec(n) for n in out_ndims),
        check_vma=False)(*arrays, *shared)


def _forward_local(cfg, seeds, q, k, v):
    """Forward kernel over local arrays, q ``(B, Tq, H, D)`` and k, v
    ``(B, Tk, Hkv, D)`` -> (out like q, lse ``(B, H, Tq padded to the q
    block)``)."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    block_q, block_k = min(cfg.block_q, Tq), min(cfg.block_k, Tk)
    qf, kf, vf, _ = _pad_pack(q, k, v, block_q, block_k)
    nq, Tk_pad = qf.shape[1] // block_q, kf.shape[1]
    g, blocks, group = _steps(q, k)
    width = g * D
    q_tile = pl.BlockSpec((1, block_q, width), _q_index(blocks))
    kv_all = pl.BlockSpec((1, Tk_pad, width), _kv_index(group, blocks))

    kernel = functools.partial(
        _flash_kernel, heads=g, scale=cfg.scale, causal=cfg.causal,
        block_q=block_q, block_k=block_k, seq_k=Tk,
        dropout_p=cfg.dropout_p, interpret=cfg.interpret, mask=cfg.mask,
        seq_q=Tq)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H // g, nq),
        in_specs=[_smem_spec(), q_tile, kv_all, kv_all],
        out_specs=[
            q_tile,
            pl.BlockSpec((g, 1, 8, block_q), lambda n, i: (n, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qf.shape, q.dtype),
            jax.ShapeDtypeStruct((B * H, nq, 8, block_q), jnp.float32),
        ],
        interpret=cfg.interpret,
        compiler_params=_vmem_params(cfg, Tq, Tk, q),
        name="flash_fwd",
    )(seeds.reshape(B * H), qf, kf, vf)
    lse = lse[:, :, 0, :].reshape(B, H, nq * block_q)
    return out[:, :Tq].reshape(q.shape), lse


def _backward_local(cfg, seeds, q, k, v, out, do, lse, *dlse):
    """dq and dk/dv kernels over local arrays laid out as the forward's;
    ``lse`` (and ``dlse``, where the caller has a cotangent for it) are
    (B, H, Tq padded to the q block)."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    block_q, block_k = min(cfg.block_q, Tq), min(cfg.block_k, Tk)
    qf, kf, vf, pad_q = _pad_pack(q, k, v, block_q, block_k)
    Tq_pad, Tk_pad = qf.shape[1], kf.shape[1]
    nq, nk = Tq_pad // block_q, Tk_pad // block_k
    # padded rows contribute zeros: their dO is padding too
    dof, outf = _rows(do, pad_q), _rows(out, pad_q)

    # widen lse rows to the (nq, 8, block_q) tile layout the kernels read
    # (see _flash_kernel's lse note)
    def _widen(x):
        x = x.reshape(B * H, nq, 1, block_q)
        return jnp.broadcast_to(x, (B * H, nq, 8, block_q))

    lse4 = _widen(lse)
    seedf = seeds.reshape(B * H)
    g, blocks, group = _steps(q, k)
    width = g * D
    q_tile = pl.BlockSpec((1, block_q, width), _q_index(blocks))
    q_all = pl.BlockSpec((1, Tq_pad, width), _q_index(blocks, whole=True))
    kv_all = pl.BlockSpec((1, Tk_pad, width), _kv_index(group, blocks))
    kv_at = _kv_index(group, blocks, whole=False)
    kv_tile = pl.BlockSpec((1, block_k, width), kv_at)
    row_tile = pl.BlockSpec((g, 1, 8, block_q), lambda n, i: (n, i, 0, 0))
    row_all = pl.BlockSpec((g, nq, 8, block_q), lambda n, j: (n, 0, 0, 0))

    smem_spec = _smem_spec()
    params = _vmem_params(cfg, Tq, Tk, q)
    dq_kernel = functools.partial(
        _bwd_dq_kernel, heads=g, scale=cfg.scale, causal=cfg.causal,
        block_q=block_q, block_k=block_k, seq_k=Tk,
        dropout_p=cfg.dropout_p, interpret=cfg.interpret, mask=cfg.mask,
        seq_q=Tq)
    dq, delta4 = pl.pallas_call(
        dq_kernel,
        grid=(B * H // g, nq),
        in_specs=[smem_spec, q_tile, kv_all, kv_all, q_tile, q_tile,
                  row_tile] + [row_tile] * len(dlse),
        out_specs=[q_tile, row_tile],
        out_shape=[jax.ShapeDtypeStruct(qf.shape, q.dtype),
                   jax.ShapeDtypeStruct(lse4.shape, jnp.float32)],
        interpret=cfg.interpret,
        compiler_params=params,
        name="flash_bwd_dq",
    )(seedf, qf, kf, vf, dof, outf, lse4, *map(_widen, dlse))

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, heads=g, scale=cfg.scale, causal=cfg.causal,
        block_q=block_q, block_k=block_k, seq_q=Tq, seq_k=Tk,
        dropout_p=cfg.dropout_p, interpret=cfg.interpret, mask=cfg.mask)
    if group == 1:
        dkv_tile, dkv_shape = kv_tile, kf.shape
        dkv_dtypes = (k.dtype, v.dtype)
    else:
        # one (dk, dv) a QUERY head, in float32, a slab a member of the
        # group, summed below (a sum of slabs, no relayout): the kernel
        # keeps one head's Q and dO resident, not eight
        dkv_tile = pl.BlockSpec(
            (None, 1, block_k, width),
            lambda n, j: (jax.lax.rem(n, group),) + kv_at(n, j))
        dkv_shape = (group,) + kf.shape
        dkv_dtypes = (jnp.float32, jnp.float32)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(B * H // g, nk),
        in_specs=[smem_spec, q_all, kv_tile, kv_tile, q_all, row_all,
                  row_all],
        out_specs=[dkv_tile, dkv_tile],
        out_shape=[jax.ShapeDtypeStruct(dkv_shape, dkv_dtypes[0]),
                   jax.ShapeDtypeStruct(dkv_shape, dkv_dtypes[1])],
        interpret=cfg.interpret,
        compiler_params=params,
        name="flash_bwd_dkv",
    )(seedf, qf, kf, vf, dof, lse4, delta4)

    if group > 1:
        dk, dv = (a.sum(0).astype(like.dtype)
                  for a, like in ((dk, k), (dv, v)))
    return dq[:, :Tq].reshape(q.shape), dk[:, :Tk].reshape(k.shape), \
        dv[:, :Tk].reshape(v.shape)


def _forward_call(cfg, seeds, q, k, v):
    return _over_rows(_forward_local, (4, 3), cfg, seeds, q, k, v)


def _flash_backward(cfg, seeds, q, k, v, out, lse, do, dlse=None):
    more = ()
    if dlse is not None:        # lse's cotangent, padded like lse
        pad_q = lse.shape[2] - dlse.shape[2]
        more = (jnp.pad(dlse.astype(jnp.float32),
                        ((0, 0), (0, 0), (0, pad_q))),)
    return _over_rows(_backward_local, (4, 4, 4), cfg, seeds, q, k, v, out,
                      do, lse, *more)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_core(cfg, seeds, q, k, v):
    return _forward_call(cfg, seeds, q, k, v)[0]


def _flash_core_fwd(cfg, seeds, q, k, v):
    out, lse = _forward_call(cfg, seeds, q, k, v)
    return out, (seeds, q, k, v, out, lse)


def _flash_core_bwd(cfg, res, do):
    import numpy as _onp

    seeds, q, k, v, out, lse = res
    dq, dk, dv = _flash_backward(cfg, seeds, q, k, v, out, lse, do)
    # int seeds: zero cotangent
    return _onp.zeros(seeds.shape, jax.dtypes.float0), dq, dk, dv


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.lru_cache(maxsize=None)
def tile_counts(seq_q, seq_k, block_q, block_k, causal=False, mask=None):
    """``(visited, whole, cut)`` tiles a head in the forward (the backward
    kernels visit the same tiles): counted in Python from ``_k_tiles``,
    the ranges the kernels loop over, one query tile after the other."""
    block_q, block_k = min(block_q, seq_q), min(block_k, seq_k)
    n = {True: 0, False: 0}
    for qi in range(-(-seq_q // block_q)):
        for lo, hi, cut in _k_tiles(qi, block_q, block_k, seq_q, seq_k,
                                    causal, mask):
            n[cut] += max(hi - lo, 0)
    return n[True] + n[False], n[False], n[True]


_TILES_NOTED = set()


def _note_tiles(cfg, q, k):
    """One ``mx.attn.tiles`` instant a distinct call, written where the
    call is traced: what the kernels will visit, at which dtype their
    products run, and how they read the call's arrays (``layout`` ``rows``
    with ``heads_per_step`` heads a grid step, or ``heads`` where the
    shape fell back to a head-major copy) at which ``head_dim``."""
    heads = heads_per_step(q.shape[3], q.shape[2], k.shape[2])
    what = (q.shape[1], k.shape[1], cfg.block_q, cfg.block_k, cfg.causal,
            cfg.mask)
    how = (str(q.dtype), heads, q.shape[3])
    if what + how in _TILES_NOTED:
        return
    _TILES_NOTED.add(what + how)
    from .. import trace as _trace

    visited, whole, cut = tile_counts(*what)
    _trace.instant("mx.attn.tiles", args={
        "kind": rule_kind(cfg.causal, cfg.mask) or "none",
        "visited": visited, "whole": whole, "cut": cut,
        "operand_dtype": how[0], "heads_per_step": heads or 1,
        "layout": "rows" if heads else "heads", "head_dim": q.shape[3]})


def _cfg_for(q, k, causal, sm_scale, block_q, block_k, interpret,
             dropout_p=0.0, mask=None):
    """The static kernel configuration of one call on ``(B, T, H, D)``
    arrays: interpret-or-compile resolved from the backend, the default
    softmax scale, and the fast-memory check made before anything is
    traced."""
    interpret = _default_interpret() if interpret is None else interpret
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    _check_vmem(q, k.shape[1], block_q, block_k, interpret)
    if mask is not None:
        if causal:
            raise MXNetError("flash_attention: causal= and mask= together")
        want = q.shape[1] if mask.kind == "window" else 2 * mask.seq
        if q.shape[1] != want or k.shape[1] != want:
            raise MXNetError(
                "flash_attention: a %s mask needs %d query and key "
                "positions, got %d and %d"
                % (mask.kind, want, q.shape[1], k.shape[1]))
    if q.shape[2] % k.shape[2]:
        raise MXNetError("flash_attention: %d query heads over %d KV heads"
                         % (q.shape[2], k.shape[2]))
    return _Cfg(bool(causal), float(scale), int(block_q), int(block_k),
                bool(interpret), float(dropout_p), mask)


def _kernel_views(*arrays):
    """The call's ``(B, T, H, D)`` arrays as the kernels take them: as
    they are where ``heads_per_step`` says rows, else head-major with
    every head a batch row of one head, ``(B * H, T, 1, D)`` (the
    transposes are plain JAX: autodiff returns the gradients the same
    way)."""
    q, k = arrays[:2]
    if heads_per_step(q.shape[3], q.shape[2], k.shape[2]):
        return arrays
    return tuple(map(_head_major, arrays))


def _head_major(x):
    return x.transpose(0, 2, 1, 3).reshape(-1, x.shape[1], 1, x.shape[3])


def _caller_view(out, B):
    """A kernel view's result as the caller's ``(B, T, H, D)``."""
    if out.shape[0] == B:
        return out
    return out.reshape((B, -1) + out.shape[1:2] + out.shape[3:]).transpose(
        0, 2, 1, 3)


def _no_seeds(q):
    return jnp.zeros((q.shape[0], q.shape[2]), jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_lse_core(cfg, q, k, v):
    out, lse = _forward_call(cfg, _no_seeds(q), q, k, v)
    return out, lse[:, :, :q.shape[1]]


def _flash_lse_fwd(cfg, q, k, v):
    outs = _flash_lse_core.fun(cfg, q, k, v)
    return outs, (q, k, v) + outs


def _flash_lse_bwd(cfg, res, cts):
    q, k, v, out, lse = res
    do, dlse = cts
    Tq = q.shape[1]
    bq = min(cfg.block_q, Tq)
    pad_q = -(-Tq // bq) * bq - Tq
    if pad_q:
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q)))
    return _flash_backward(cfg, _no_seeds(q), q, k, v, out, lse, do,
                           dlse=dlse)


_flash_lse_core.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_lse(q, k, v, causal=False, sm_scale=None, block_q=512,
                        block_k=512, interpret=None):
    """Flash attention returning (out, logsumexp) — the building block for
    ring/context-parallel composition (parallel/ring.py): partial results
    from different K/V shards merge exactly via their lse.  The lse
    cotangent is honored (it folds into the backward's delta term).

    ``q`` is ``(B, Tq, H, D)`` and ``k``, ``v`` ``(B, Tk, Hkv, D)``, the
    layout ``flash_attention`` takes; ``out`` comes back like ``q`` and the
    logsumexp head-major, ``(B, H, Tq)``, as the kernels write it."""
    cfg = _cfg_for(q, k, causal, sm_scale, block_q, block_k, interpret)
    _note_tiles(cfg, q, k)
    B, Tq, H, _ = q.shape
    out, lse = _flash_lse_core(cfg, *_kernel_views(q, k, v))
    return _caller_view(out, B), lse.reshape(B, H, Tq)


def _bh_seeds(dropout_key, B, H):
    """(B, H) int32 seed words: the dropout key hashed with the GLOBAL
    (batch, head) index.  Built outside the kernels, so under a mesh
    every device is handed its own rows and the masks do not depend on
    how B·H is sharded."""
    # fold ALL key words: threefry key_data for PRNGKey(s), s < 2^32 is
    # [0, s] — taking only word 0 would give every such key the same mask
    kd = jax.random.key_data(dropout_key).reshape(-1).astype(jnp.uint32)
    x = jnp.bitwise_xor(kd[0] * jnp.uint32(2654435761), kd[-1]) \
        if kd.shape[0] > 1 else kd[0]
    bh = jax.lax.broadcasted_iota(jnp.uint32, (B, H), 0) * jnp.uint32(H) \
        + jax.lax.broadcasted_iota(jnp.uint32, (B, H), 1)
    x = x ^ (bh * jnp.uint32(0x9E3779B9))
    # murmur3 finalizer: neighbouring (b, h) must not get neighbouring seeds
    x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
    return jax.lax.bitcast_convert_type(x ^ (x >> 16), jnp.int32)


def flash_attention(q, k, v, causal=False, sm_scale=None, block_q=None,
                    block_k=None, interpret=None, dropout_p=0.0,
                    dropout_key=None, mask=None):
    """Flash attention on the projections' own layout: ``q`` is ``(B, Tq,
    H, D)``, ``k`` and ``v`` ``(B, Tk, Hkv, D)`` (each a free reshape of a
    projection's ``(B, T, heads * D)`` rows), and the result comes back
    like ``q``.  Where heads are narrower than the 128 lanes (``D`` = 64 or
    32) the kernels' blocks index those rows where they lie, ``128 // D``
    heads a grid step (``heads_per_step``), so no head-major copy is made
    on the way in or out, forward or backward.  Every other shape (heads
    of a multiple of 128, where XLA makes the transpose the layout of the
    projection's product; ``D`` = 80, an odd head count of 64, 64 with
    grouped KV heads, where it is a copy) is transposed head-major HERE
    and runs through the same kernels with every head a batch row of one
    head; ``mx.attn.tiles`` says which (``layout``, ``heads_per_step``).

    ``k``/``v`` may hold fewer heads than ``q`` (``H`` a multiple of their
    count): each KV head then serves a group of consecutive query heads,
    read in place.  ``mask`` is a static ``AttnMask`` (``causal`` is the
    built-in case): all three kernels skip the tiles it masks whole, run
    the tiles it allows whole without any index or mask arithmetic, and
    mask the tiles it cuts from a column and a row of indices computed in
    the kernel (``mx.attn.tiles`` counts the three classes).  Every
    product takes its operands at the dtype of ``q``/``k``/``v`` and
    accumulates in float32; the softmax state is float32 whatever comes in.

    ``block_q``/``block_k`` default to ``DEFAULT_BLOCK_Q`` /
    ``DEFAULT_BLOCK_K`` (512 / 512) for every shape and mask; an explicit
    value wins, each on its own.

    Forward AND backward run Pallas kernels (interpreted on the CPU
    backend): the backward recomputes per-block probabilities from the
    saved logsumexp — residual memory stays O(T·D), and dq/dk/dv are
    back-to-back MXU matmuls (the fused equivalent the reference lacks;
    its interleaved_matmul kernels are fwd-only, transformer.cc:650-826).
    Attention-probability dropout runs IN-kernel from the TPU PRNG: the
    per-tile mask is regenerated — never stored — in fwd, dq and dkv
    passes, seeded by (key ⊕ batch·head, q-block·k-block), a head's mask
    the same however many heads share its grid step.

    Traced inside a program jitted over a mesh, the kernels need the
    engine's ``mesh_rows`` declaration (Mosaic kernels cannot be
    partitioned automatically): every device then runs them on its own
    batch rows.  A shape whose per-head K/V (or Q/dO) does not fit the
    kernels' fast memory (``flash_vmem_bytes``) raises ``MXNetError``
    when compiled for the chip; ``multi_head_attention(impl="auto")``
    never picks such a shape."""
    block_q = DEFAULT_BLOCK_Q if block_q is None else int(block_q)
    block_k = DEFAULT_BLOCK_K if block_k is None else int(block_k)
    cfg = _cfg_for(q, k, causal, sm_scale, block_q, block_k, interpret,
                   dropout_p, mask)
    _note_tiles(cfg, q, k)
    B, H = q.shape[0], q.shape[2]
    if dropout_p > 0.0:
        if dropout_key is None:
            raise ValueError("flash_attention: dropout_p > 0 requires "
                             "dropout_key")
        seeds = _bh_seeds(dropout_key, B, H)
    else:
        seeds = jnp.zeros((B, H), jnp.int32)
    q, k, v = _kernel_views(q, k, v)
    out = _flash_core(cfg, seeds.reshape(q.shape[0], q.shape[2]), q, k, v)
    return _caller_view(out, B)


def flash_attention_placed(q, k, v, causal=False, sm_scale=None, mask=None):
    """``flash_attention`` for a caller whose q and k stand in the kernels'
    head-major view already, ``(B * H, Tq, 1, D)`` and ``(B * Hkv, Tk, 1,
    D)`` as ``pallas_rotary.placed`` writes them (every head a batch row of
    one head: what ``_kernel_views`` would make of them); ``v`` is ``(B,
    Tk, Hkv, D)`` and the result ``(B, Tq, H, D)`` as ``flash_attention``'s.
    The same kernels under the same specs; no dropout."""
    B, Tk, kv_heads, D = v.shape
    H = q.shape[0] // B
    like_q = jax.ShapeDtypeStruct((B, q.shape[1], H, D), q.dtype)
    like_k = jax.ShapeDtypeStruct((B, Tk, kv_heads, D), k.dtype)
    cfg = _cfg_for(like_q, like_k, causal, sm_scale, DEFAULT_BLOCK_Q,
                   DEFAULT_BLOCK_K, None, mask=mask)
    _note_tiles(cfg, like_q, like_k)
    out = _flash_core(cfg, _no_seeds(q), q, k, _head_major(v))
    return _caller_view(out, B)


def _default_interpret():
    """Interpret the kernels on the CPU backend (tests), compile them on
    the TPU; any other backend has no lowering and is an error, not a
    quiet interpreter."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise MXNetError(
            "flash_attention: Pallas TPU kernels lower for the 'tpu' "
            "backend and are interpreted on 'cpu'; the default backend is "
            "%r — pass impl='flash' (blockwise) or impl='dense'" % backend)
    return backend == "cpu"


# ---------------------------------------------------------------------------
# fast-memory envelope
# ---------------------------------------------------------------------------
# Mosaic gives a kernel 16 MiB of scoped VMEM unless it asks for more; a
# v5e core has 128 MiB, of which the kernels may ask for this much.
VMEM_DEFAULT_BYTES = 16 << 20
VMEM_BUDGET_BYTES = 96 << 20


def flash_vmem_bytes(seq_q, seq_k, head_dim, itemsize,
                     block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Scoped VMEM the hungriest of the three kernels needs, in bytes —
    an upper estimate, checked against the v5e compiler in
    tests/python/unittest/test_chip_compile.py.

    The forward and dq kernels keep a grid step's whole K and V resident
    (``(Tk, heads * D)`` blocks: one head, or the ``128 // D`` heads that
    share 128 lanes, ``heads_per_step``), the dkv kernel its whole Q and
    dO plus the lse/Δ rows of each of its heads in their 8-sublane layout;
    Pallas double-buffers every operand block, and a row of D < 128 fills
    a 128-lane tile whether one head lies in it (the head-major view) or
    several.  On top of the operands comes the working set of one
    (block_q, block_k) tile, one head's at a time: scores, probabilities,
    mask and their products in float32, and the copies of p and ds at the
    operands' dtype that enter the second products (the compiler lets them
    share room: the count of six float32 tiles still covers its need,
    which the chip-compile tests check with dropout on)."""
    lanes = -(-head_dim // 128) * 128
    heads = lanes // head_dim if lanes % head_dim == 0 else 1
    block_q, block_k = min(block_q, seq_q), min(block_k, seq_k)
    pad = lambda t, b: -(-t // b) * b  # noqa: E731
    row = lanes * itemsize
    resident_kv = 2 * 2 * pad(seq_k, block_k) * row
    resident_q = 2 * 2 * pad(seq_q, block_q) * (row + heads * 8 * 4)
    blocks = 2 * 4 * max(block_q, block_k) * row
    tile = 6 * block_q * block_k * 4 + 4 * (block_q + block_k) * lanes * 4
    return max(resident_kv, resident_q) + blocks + tile


def _vmem_params(cfg, seq_q, seq_k, q):
    """Ask the compiler for the scoped VMEM the shape needs where the
    default would not do (long T: a grid step's whole K/V is resident)."""
    from jax.experimental.pallas import tpu as pltpu

    need = flash_vmem_bytes(seq_q, seq_k, q.shape[-1], q.dtype.itemsize,
                            cfg.block_q, cfg.block_k)
    return pltpu.CompilerParams(
        vmem_limit_bytes=need if need > VMEM_DEFAULT_BYTES else None)


def _check_vmem(q, seq_k, block_q, block_k, interpret):
    """Raise the repo's own error before Mosaic's "scoped allocation ...
    exceeded scoped vmem limit" does.  Only a compile for the chip has a
    limit to exceed; the interpreter has no VMEM."""
    if interpret:
        return
    need = flash_vmem_bytes(q.shape[1], seq_k, q.shape[3],
                            q.dtype.itemsize, block_q, block_k)
    if need > VMEM_BUDGET_BYTES:
        raise MXNetError(
            "flash_attention: Tq=%d Tk=%d D=%d %s needs ~%d MiB of VMEM "
            "(the kernels keep a head's whole K/V resident), over the "
            "%d MiB the kernels may take; use impl='flash' (blockwise) or "
            "shorter sequences per call (ring_attention shards T)"
            % (q.shape[1], seq_k, q.shape[3], q.dtype, need >> 20,
               VMEM_BUDGET_BYTES >> 20))


def use_flash(seq_q, seq_k, head_dim, has_mask, itemsize=4):
    """Dispatch heuristic for impl='auto': flash pays off once the score
    matrix no longer fits the fusion footprint; dense einsum wins short-T.
    A shape outside the kernels' fast-memory envelope is never sent to
    them (``itemsize`` defaults to the float32 worst case).  Heads of up
    to 256 are admitted; on the chip the kernels have run, in a cell of
    the benchmark, at heads of 64 (BERT), 128 (SDAR, Laguna) and 256
    (GLM-4.7-Flash's latent attention: two 128-lane tiles a head, about
    30 MiB of scoped VMEM at T = 8,192)."""
    if has_mask:
        return False
    return seq_q * seq_k >= 256 * 256 and head_dim <= 256 and \
        flash_vmem_bytes(seq_q, seq_k, head_dim, itemsize) \
        <= VMEM_BUDGET_BYTES
