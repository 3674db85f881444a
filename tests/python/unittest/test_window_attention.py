"""The flash kernels under the causal sliding-window rule (``AttnMask`` kind
``window``) and under the plain causal rule with grouped KV heads, against
dense attention under the rule written position by position.  Kernels run
in interpret mode on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import pallas_attention as pa

from test_block_diffusion_attention import _check_classes, _dense, _tiles


def _rule(t, window):
    """The rule by hand: query i sees key j iff 0 <= i - j < window."""
    out = np.zeros((t, t), bool)
    for i in range(t):
        for j in range(t):
            out[i, j] = 0 <= i - j < window
    return out


def _qkv(t, heads, kv_heads, d=16, batch=2, seed=0, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(key, (batch, t, n, d)).astype(dtype)
                 for key, n in zip(k, (heads, kv_heads, kv_heads, heads)))


@pytest.mark.parametrize("t,window", [(16, 4), (13, 5), (9, 1), (8, 12)])
def test_rule_as_index_expression_matches_the_rule_by_hand(t, window):
    mask = pa.window_mask(window)
    i = jnp.arange(t, dtype=jnp.int32)
    got = np.asarray(pa.mask_allowed(mask, i[:, None], i[None, :]))
    assert (got == _rule(t, window)).all()
    w = min(window, t)
    assert got.sum() == w * t - w * (w - 1) // 2
    with pytest.raises(MXNetError, match="window"):
        pa.window_mask(0)


@pytest.mark.parametrize("t,window,bq,bk", [
    (64, 16, 16, 16),           # the cell's geometry: window = one tile
    (64, 16, 8, 8), (64, 40, 8, 8), (96, 33, 16, 8),  # tiles allowed whole
    (64, 8, 16, 16), (64, 5, 16, 32),     # a window shorter than a tile
    (50, 16, 16, 16), (44, 12, 16, 8),    # T no multiple of the tile
    (60, 14, 8, 16), (45, 7, 16, 16),     # ... nor of the window
    (40, 64, 16, 16),                     # a window longer than T: causal
    (24, 1, 8, 8)])                       # every query sees itself alone
def test_tile_ranges_hold_every_tile_with_an_allowed_pair(t, window, bq, bk):
    """The ranges of each loop cover every tile that holds an allowed pair
    and stay inside the grid; a tile they call whole is allowed whole and
    holds no padding.  Where T is a multiple of both tiles they hold
    nothing else, and each visited tile's class is the true one."""
    mask = pa.window_mask(window)
    allowed = _rule(t, window)
    nq, nk = -(-t // bq), -(-t // bk)
    seen = _check_classes(
        allowed, bq, bk,
        lambda i: pa._k_tiles(i, bq, bk, t, t, mask=mask),
        lambda j: pa._q_tiles(j, bq, bk, t, t, mask=mask))
    if t % bq == 0 and t % bk == 0:
        for i in range(nq):
            for j in range(nk):
                part = allowed[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
                assert (j in seen[i]) == part.any(), (i, j)
                if j in seen[i]:
                    assert seen[i][j] == (not part.all()), (i, j)
        q_seen = {j: _tiles(pa._q_tiles(j, bq, bk, t, t, mask=mask))
                  for j in range(nk)}
        assert {(i, j): c for i in seen for j, c in seen[i].items()} == \
            {(i, j): c for j in q_seen for i, c in q_seen[j].items()}
    if (t, window, bq, bk) == (64, 16, 16, 16):
        # a window of one tile: the diagonal and the far edge, both cut
        assert [len(v) for v in seen.values()] == [1, 2, 2, 2]
        assert not any(c is False for v in seen.values() for c in v.values())


def test_a_window_of_one_tile_traces_the_cut_body_only():
    """No whole tile can exist, and the kernels know it statically: one
    range, so the body is traced once."""
    mask = pa.window_mask(512)
    assert len(pa._k_tiles(jnp.int32(3), 512, 512, 8192, 8192,
                           mask=mask)) == 1
    assert len(pa._q_tiles(jnp.int32(3), 512, 512, 8192, 8192,
                           mask=mask)) == 1
    assert len(pa._k_tiles(3, 128, 128, 8192, 8192, mask=mask)) == 3


def test_the_cells_calls_visit_31_and_136_tiles_a_head(monkeypatch):
    """``mx.attn.tiles`` at laguna_xs2_t8k's two shapes, from a trace of
    the calls alone (``eval_shape`` runs nothing)."""
    from mxnet_tpu import trace

    mask = pa.window_mask(512)
    assert pa.tile_counts(8192, 8192, 512, 512, False, mask) == (31, 0, 31)
    assert pa.tile_counts(8192, 8192, 512, 512, True, None) == (136, 120, 16)
    # allowed pairs a head against the pairs of the visited tiles
    assert 512 * 8192 - 512 * 511 // 2 == 4_063_488
    assert 31 * 512 * 512 == 8_126_464
    monkeypatch.setattr(pa, "_TILES_NOTED", set())
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16)

    def noted():
        return [e["args"] for e in trace.events()
                if e["name"] == "mx.attn.tiles"
                and e["args"]["visited"] in (31, 136)]

    before = len(noted())
    for heads, kw in ((64, {"mask": mask}), (48, {"causal": True})):
        q = jax.ShapeDtypeStruct((1, 8192, heads, 128), jnp.bfloat16)
        for _ in range(2):      # one instant a distinct call
            jax.eval_shape(lambda q, k, v: pa.flash_attention(q, k, v, **kw),
                           q, kv, kv)
    assert noted()[before:] == [
        {"kind": "window", "visited": 31, "whole": 0, "cut": 31,
         "operand_dtype": "bfloat16", "heads_per_step": 1, "layout": "heads",
         "head_dim": 128},
        {"kind": "causal", "visited": 136, "whole": 120, "cut": 16,
         "operand_dtype": "bfloat16", "heads_per_step": 1, "layout": "heads",
         "head_dim": 128}]


@pytest.mark.parametrize("t,window,bq,bk,heads,kv_heads", [
    (64, 16, 16, 16, 4, 2),     # the cell's geometry, grouped KV
    (64, 40, 8, 8, 2, 2),       # tiles allowed whole between cut ones
    (50, 12, 16, 16, 4, 1),     # T no multiple of tile or window
    (44, 7, 16, 8, 6, 2),       # uneven tiles, a window under a tile
    (40, 64, 16, 16, 2, 1),     # the window holds the whole sequence
])
def test_window_kernels_match_dense_values_and_gradients(
        t, window, bq, bk, heads, kv_heads):
    mask = pa.window_mask(window)
    q, k, v, w = _qkv(t, heads, kv_heads)
    allowed = jnp.asarray(_rule(t, window))

    def flash(q, k, v):
        return pa.flash_attention(q, k, v, mask=mask, block_q=bq, block_k=bk)

    np.testing.assert_allclose(flash(q, k, v), _dense(q, k, v, allowed),
                               atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_dense(*a, allowed) * w),
                    (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):     # dq (flash_bwd_dq), dk, dv (flash_bwd_dkv)
        np.testing.assert_allclose(g, r, atol=5e-6)


@pytest.mark.parametrize("t,bq,bk,heads,kv_heads", [
    (64, 16, 16, 6, 2),         # 48 over 8 in small: groups of 3... of 6/2
    (50, 16, 8, 4, 1)])
def test_causal_kernels_with_grouped_heads_match_dense(t, bq, bk, heads,
                                                       kv_heads):
    q, k, v, w = _qkv(t, heads, kv_heads, seed=2)
    allowed = jnp.asarray(_rule(t, t))          # a window of T is causal

    def flash(q, k, v):
        return pa.flash_attention(q, k, v, causal=True, block_q=bq,
                                  block_k=bk)

    np.testing.assert_allclose(flash(q, k, v), _dense(q, k, v, allowed),
                               atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_dense(*a, allowed) * w),
                    (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=5e-6)


@pytest.mark.parametrize("kw", [{"mask": pa.window_mask(16)},
                                {"causal": True}], ids=["window", "causal"])
def test_bf16_calls_stay_within_two_rounding_steps_of_float32(kw):
    """bf16 operands, float32 accumulation and softmax state: the result
    is the float32 result of the ROUNDED inputs to two steps of bf16
    (2 x 2**-8 of the largest value), forward and backward."""
    q, k, v, w = _qkv(64, 4, 2, seed=5, dtype=jnp.bfloat16)

    def flash(q, k, v):
        return pa.flash_attention(q, k, v, block_q=16, block_k=16, **kw)

    def loss(q, k, v):
        return jnp.sum(flash(q, k, v).astype(jnp.float32)
                       * w.astype(jnp.float32))

    wide = [a.astype(jnp.float32) for a in (q, k, v)]
    got, want = flash(q, k, v), flash(*wide)
    assert got.dtype == jnp.bfloat16
    step = 2.0 ** -8
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) \
        <= 2 * step * float(jnp.abs(want).max())
    for g, r in zip(jax.grad(loss, (0, 1, 2))(q, k, v),
                    jax.grad(loss, (0, 1, 2))(*wide)):
        assert g.dtype == jnp.bfloat16
        assert float(jnp.abs(g.astype(jnp.float32) - r).max()) \
            <= 2 * step * float(jnp.abs(r).max())


def test_multi_head_attention_takes_both_rules_to_the_kernels_or_dense():
    t, heads, kv_heads, d = 256, 4, 2, 16     # 256: the kernels' threshold
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    q = nd.NDArray(jax.random.normal(k[0], (1, t, heads * d)))
    kk = nd.NDArray(jax.random.normal(k[1], (1, t, kv_heads * d)))
    v = nd.NDArray(jax.random.normal(k[2], (1, t, kv_heads * d)))
    for kw, scope in (({"mask": pa.window_mask(48)}, "mx.attn.window"),
                      ({"causal": True}, "mx.attn.causal")):
        kw = dict(kw, num_heads=heads, num_kv_heads=kv_heads)
        dense = nd.multi_head_attention(q, kk, v, impl="dense",
                                        **kw).asnumpy()
        np.testing.assert_allclose(
            nd.multi_head_attention(q, kk, v, impl="pallas", **kw).asnumpy(),
            dense, atol=2e-6)
        # 'auto' lands on the kernels: no (T, T) scores in the program,
        # and the call carries its scope
        lowered = jax.jit(lambda a, b, c: nd.multi_head_attention(
            nd.NDArray(a), nd.NDArray(b), nd.NDArray(c), **kw)._data).lower(
                q._data, kk._data, v._data)
        assert "4x256x256" not in lowered.as_text()
        assert scope in lowered.as_text(debug_info=True)
    with pytest.raises(MXNetError, match="positions"):
        pa.flash_attention(q._data.reshape(1, t, heads, d)[:, :128],
                           kk._data.reshape(1, t, kv_heads, d),
                           v._data.reshape(1, t, kv_heads, d),
                           mask=pa.window_mask(48))
    with pytest.raises(MXNetError, match="together"):
        pa.flash_attention(q._data.reshape(1, t, heads, d),
                           kk._data.reshape(1, t, kv_heads, d),
                           v._data.reshape(1, t, kv_heads, d), causal=True,
                           mask=pa.window_mask(48))
