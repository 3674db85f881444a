"""The flash kernels under a static structured mask (block diffusion), with
grouped KV heads, against dense attention under the rule written as a
boolean expression; rotary positions and QK-norm against hand-written forms.
Kernels run in interpret mode on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import pallas_attention as pa


def _rule(seq, block):
    """The rule by hand, position by position."""
    t = 2 * seq
    out = np.zeros((t, t), bool)
    for i in range(t):
        for j in range(t):
            bi, bj = (i % seq) // block, (j % seq) // block
            if i < seq:
                out[i, j] = (j < seq and bi == bj) or (j >= seq and bj < bi)
            else:
                out[i, j] = j >= seq and bj <= bi
    return out


def _dense(q, k, v, allowed):
    """The oracle on the kernels' own layout, (B, T, H, D)."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(allowed, s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def _qkv(seq, heads, kv_heads, d=16, batch=2, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    t = 2 * seq
    return (jax.random.normal(k[0], (batch, t, heads, d)),
            jax.random.normal(k[1], (batch, t, kv_heads, d)),
            jax.random.normal(k[2], (batch, t, kv_heads, d)),
            jax.random.normal(k[3], (batch, t, heads, d)))


@pytest.mark.parametrize("seq,block", [(16, 4), (12, 2), (20, 4)])
def test_rule_as_index_expression_matches_the_rule_by_hand(seq, block):
    mask = pa.block_diffusion_mask(seq, block)
    i = jnp.arange(2 * seq, dtype=jnp.int32)
    got = np.asarray(pa.mask_allowed(mask, i[:, None], i[None, :]))
    assert (got == _rule(seq, block)).all()
    assert got.sum() == seq * seq + seq * block


def _tiles(ranges):
    """{tile: cut} of a range function's ``(lo, hi, cut)`` list, which has
    to be ascending and disjoint."""
    out, at = {}, 0
    for lo, hi, cut in ranges:
        lo, hi = int(lo), int(hi)
        if hi > lo:
            assert lo >= at, (ranges, at)
            at = hi
            out.update((t, bool(cut)) for t in range(lo, hi))
    return out


def _check_classes(allowed, bq, bk, k_tiles, q_tiles):
    """Against the materialised rule ``allowed`` (real rows and keys only):
    every tile with an allowed pair is visited, both loops stay inside the
    grid, and a tile called whole has no masked pair and no padding."""
    tq, tk = allowed.shape
    nq, nk = -(-tq // bq), -(-tk // bk)
    pad = np.zeros((nq * bq, nk * bk), bool)
    pad[:tq, :tk] = allowed
    tile = lambda i, j: pad[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]  # noqa
    seen = {}
    for i in range(nq):
        got = _tiles(k_tiles(jnp.int32(i)))
        assert got == _tiles(k_tiles(i))    # traced index and Python int
        assert set(got) <= set(range(nk)), (i, got)
        for j in range(nk):
            assert j in got or not tile(i, j).any(), (i, j)
        for j, cut in got.items():
            # the forward's rows beyond Tq are thrown away: real rows only
            rows = tile(i, j)[:max(0, min(bq, tq - i * bq))]
            assert cut or (rows.all() and (j + 1) * bk <= tk), (i, j)
        seen[i] = got
    for j in range(nk):
        got = _tiles(q_tiles(jnp.int32(j)))
        assert got == _tiles(q_tiles(j))
        assert set(got) <= set(range(nq)), (j, got)
        for i in range(nq):
            assert i in got or not tile(i, j).any(), (i, j)
        for i, cut in got.items():
            cols = tile(i, j)[:, :max(0, min(bk, tk - j * bk))]
            assert cut or (cols.all() and (i + 1) * bq <= tq), (i, j)
    return seen


@pytest.mark.parametrize("seq,block,bq,bk", [
    (16, 4, 8, 8), (32, 4, 8, 4), (40, 8, 16, 32),      # L a tile multiple
    (24, 4, 16, 8), (20, 4, 8, 16), (28, 4, 8, 8), (36, 4, 16, 16),
    (64, 4, 16, 16), (64, 16, 16, 16), (64, 32, 16, 8),  # b < = > the tile
    (44, 4, 16, 16), (44, 4, 32, 8),    # a tile astride L; 2L no multiple
    (18, 2, 8, 16), (50, 10, 16, 16), (12, 12, 8, 8)])
def test_tile_ranges_hold_every_tile_with_an_allowed_pair(seq, block, bq, bk):
    """The ranges of each loop cover every tile that holds an allowed pair,
    stay inside the grid, and (L a multiple of both tiles) hold nothing
    else: the other tiles are the ones skipped.  A tile they call whole is
    allowed whole; with blocks shorter than the tiles every tile that the
    rule cuts is called cut and every other visited one whole."""
    mask = pa.block_diffusion_mask(seq, block)
    t = 2 * seq
    allowed = _rule(seq, block)
    nq, nk = -(-t // bq), -(-t // bk)
    seen = _check_classes(
        allowed, bq, bk,
        lambda i: pa._k_tiles(i, bq, bk, t, t, mask=mask),
        lambda j: pa._q_tiles(j, bq, bk, t, t, mask=mask))
    if seq % bq == 0 and seq % bk == 0:
        for i in range(nq):
            for j in range(nk):
                part = allowed[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
                assert (j in seen[i]) == part.any(), (i, j)
                if j in seen[i] and block < min(bq, bk):
                    assert seen[i][j] == (not part.all()), (i, j)
        q_seen = {j: _tiles(pa._q_tiles(jnp.int32(j), bq, bk, t, t,
                                        mask=mask)) for j in range(nk)}
        assert {(i, j): c for i in seen for j, c in seen[i].items()} == \
            {(i, j): c for j in q_seen for i, c in q_seen[j].items()}
    if (seq, block, bq, bk) == (16, 4, 8, 8):
        assert sum(map(len, seen.values())) == 2 + 3 + 3    # of 16 tiles


def test_the_cells_call_visits_80_tiles_a_head_56_whole_and_24_cut(
        monkeypatch):
    """``mx.attn.tiles`` at sdar_30b_a3b_bd4k's shape, from a trace of the
    call alone (``eval_shape`` runs nothing)."""
    from mxnet_tpu import trace

    mask = pa.block_diffusion_mask(4096, 4)
    assert pa.tile_counts(8192, 8192, 512, 512, False, mask) == (80, 56, 24)
    assert pa.tile_counts(512, 512, 512, 512) == (1, 1, 0)      # BERT's
    monkeypatch.setattr(pa, "_TILES_NOTED", set())
    q = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 4, 128), jnp.bfloat16)

    def noted():
        return [e["args"] for e in trace.events()
                if e["name"] == "mx.attn.tiles"
                and e["args"]["visited"] == 80]

    before = len(noted())
    for _ in range(2):      # one instant a distinct call, not one a trace
        jax.eval_shape(lambda q, k, v: pa.flash_attention(q, k, v, mask=mask),
                       q, kv, kv)
    assert noted()[before:] == [{
        "kind": "block_diffusion", "visited": 80, "whole": 56, "cut": 24,
        "operand_dtype": "bfloat16", "heads_per_step": 1, "layout": "heads",
        "head_dim": 128}]


@pytest.mark.parametrize("seq,block,bq,bk,heads,kv_heads", [
    (32, 4, 16, 16, 4, 2),     # L a multiple of the tile, grouped KV
    (24, 4, 16, 16, 2, 2),     # L NOT a multiple of the tile
    (40, 4, 16, 32, 4, 1),     # uneven tiles, one KV head for all
])
def test_masked_kernels_match_dense_values_and_gradients(
        seq, block, bq, bk, heads, kv_heads):
    mask = pa.block_diffusion_mask(seq, block)
    q, k, v, w = _qkv(seq, heads, kv_heads)
    allowed = jnp.asarray(_rule(seq, block))

    def flash(q, k, v):
        return pa.flash_attention(q, k, v, mask=mask, block_q=bq, block_k=bk)

    np.testing.assert_allclose(flash(q, k, v), _dense(q, k, v, allowed),
                               atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_dense(*a, allowed) * w),
                    (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):     # dq (flash_bwd_dq), dk, dv (flash_bwd_dkv)
        np.testing.assert_allclose(g, r, atol=5e-6)


def test_grouped_kv_heads_equal_repeated_k_and_v():
    q, k, v, w = _qkv(32, 8, 2, seed=3)
    for kw in ({}, {"causal": True}):
        def grouped(q, k, v):
            return pa.flash_attention(q, k, v, block_q=16, block_k=16, **kw)

        def repeated(q, k, v):
            return pa.flash_attention(q, jnp.repeat(k, 4, 2),
                                      jnp.repeat(v, 4, 2), block_q=16,
                                      block_k=16, **kw)

        np.testing.assert_allclose(grouped(q, k, v), repeated(q, k, v),
                                   atol=1e-6)
        got = jax.grad(lambda *a: jnp.sum(grouped(*a) * w), (0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(repeated(*a) * w),
                        (0, 1, 2))(q, k, v)
        for g, r in zip(got, want):
            np.testing.assert_allclose(g, r, atol=5e-6)


def test_multi_head_attention_takes_the_rule_to_the_kernels_or_dense():
    seq, block = 128, 4          # 256 positions: the kernels' threshold
    mask = pa.block_diffusion_mask(seq, block)
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    q = nd.NDArray(jax.random.normal(k[0], (1, 256, 4 * 16)))
    kk = nd.NDArray(jax.random.normal(k[1], (1, 256, 2 * 16)))
    v = nd.NDArray(jax.random.normal(k[2], (1, 256, 2 * 16)))
    kw = dict(num_heads=4, num_kv_heads=2, mask=mask)
    dense = nd.multi_head_attention(q, kk, v, impl="dense", **kw).asnumpy()
    np.testing.assert_allclose(
        nd.multi_head_attention(q, kk, v, impl="pallas", **kw).asnumpy(),
        dense, atol=2e-6)
    # 'auto' lands on the kernels: no (T, T) scores in the program
    text = jax.jit(lambda a, b, c: nd.multi_head_attention(
        nd.NDArray(a), nd.NDArray(b), nd.NDArray(c), **kw)._data).lower(
            q._data, kk._data, v._data).as_text()
    assert "4x256x256" not in text and "dot_general" in text
    assert pa.use_flash(8192, 8192, 128, False, 2)
    with pytest.raises(MXNetError, match="arbitrary mask"):
        nd.multi_head_attention(q, kk, v, impl="pallas", num_heads=4,
                                num_kv_heads=2,
                                mask=nd.NDArray(jnp.ones((256, 256), bool)))
    with pytest.raises(MXNetError, match="positions"):
        pa.flash_attention(q._data.reshape(1, 256, 4, 16)[:, :128],
                           kk._data.reshape(1, 256, 2, 16),
                           v._data.reshape(1, 256, 2, 16), mask=mask)


def test_rotary_and_qk_norm_against_a_hand_written_form():
    mx.random.seed(4)
    units, heads, kv_heads, d, theta = 32, 4, 2, 8, 100.0
    attn = nn.GroupedQueryAttention(units, heads, kv_heads, d,
                                    rope_theta=theta, epsilon=1e-6)
    attn.initialize()
    rs = np.random.RandomState(0)
    attn.query_norm.gamma.set_data(nd.array(1 + 0.1 * rs.randn(d)))
    attn.key_norm.gamma.set_data(nd.array(1 + 0.1 * rs.randn(d)))
    x = rs.randn(2, 6, units).astype("float32")
    pos = np.array([0, 1, 2, 0, 1, 2])      # position i mod 3, as [xt ; x0]
    got = attn(nd.array(x), nd.array(pos, dtype="int32")).asnumpy()

    p = {n: v.data().asnumpy() for n, v in attn.collect_params().items()}

    def rope(h):            # rotate pairs (i, i + d/2) by pos * theta^(-2i/d)
        out = np.empty_like(h)
        for i in range(d // 2):
            ang = pos * theta ** (-2.0 * i / d)
            c, s = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
            a, b = h[..., i], h[..., i + d // 2]
            out[..., i], out[..., i + d // 2] = a * c - b * s, b * c + a * s
        return out

    def rows(name, n, norm):
        h = (x @ p[name + "_proj.weight"].T).reshape(2, 6, n, d)
        if not norm:
            return h
        h = h / np.sqrt((h ** 2).mean(-1, keepdims=True) + 1e-6) \
            * p[name + "_norm.gamma"]
        return rope(h)

    q, k, v = rows("query", heads, True), rows("key", kv_heads, True), \
        rows("value", kv_heads, False)
    k, v = np.repeat(k, 2, axis=2), np.repeat(v, 2, axis=2)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    w = np.exp(s - s.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    o = np.einsum("bhqk,bkhd->bqhd", w, v).reshape(2, 6, heads * d)
    np.testing.assert_allclose(got, o @ p["out_proj.weight"].T, atol=2e-5)


def test_rms_norm_keeps_the_dtype_of_its_input_under_a_float32_gain():
    x = nd.NDArray(jnp.ones((2, 8), jnp.bfloat16))
    out = nd.rms_norm(x, nd.NDArray(jnp.full((8,), 2.0, jnp.float32)))
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.asnumpy().astype("float32"), 2.0,
                               rtol=1e-2)
