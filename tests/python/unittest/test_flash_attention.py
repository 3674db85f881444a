"""Pallas flash attention: forward + FLASH BACKWARD kernels (VERDICT r3
item 7) against the dense softmax oracle, incl. in-kernel dropout.

Runs in interpret mode on CPU — the same kernel code lowers to Mosaic on
TPU hardware (tests/python/unittest/test_chip_compile.py compiles it).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import pallas_attention as pa

B, H, D = 2, 2, 32


def _dense(q, k, v, causal, scale=None):
    """The oracle on the kernels' own layout, (B, T, H, D)."""
    T, Tk = q.shape[1], k.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        m = jnp.tril(jnp.ones((T, Tk), bool))
        s = jnp.where(m, s, -1e30)
    w = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32)).astype(
        q.dtype)


def _rand(T, seed=0):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(B, T, H, D).astype(np.float32))  # noqa
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("T", [128, 192])  # 192: exercises padding
def test_flash_backward_matches_dense(causal, T):
    q, k, v = _rand(T)
    g = jnp.asarray(np.random.RandomState(1)
                    .randn(B, T, H, D).astype(np.float32))

    def loss_flash(q, k, v):
        return (pa.flash_attention(q, k, v, causal=causal, block_q=64,
                                   block_k=64) * g).sum()

    def loss_dense(q, k, v):
        return (_dense(q, k, v, causal) * g).sum()

    out = pa.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    np.testing.assert_allclose(out, _dense(q, k, v, causal), rtol=2e-5,
                               atol=2e-5)
    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("q k v".split(), got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5,
                                   err_msg="d" + name)


def test_flash_backward_bf16_runs():
    q, k, v = _rand(128)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    dq = jax.grad(lambda q_: pa.flash_attention(
        q_, k, v, block_q=64, block_k=64).astype(jnp.float32).sum())(q)
    assert dq.dtype == jnp.bfloat16
    assert bool(jnp.isfinite(dq.astype(jnp.float32)).all())


def test_flash_dropout_deterministic_and_unbiased():
    q, k, v = _rand(128)
    key = jax.random.PRNGKey(3)
    f = lambda: pa.flash_attention(q, k, v, block_q=64, block_k=64,  # noqa
                                   dropout_p=0.3, dropout_key=key)
    a, b = f(), f()
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    base = pa.flash_attention(q, k, v, block_q=64, block_k=64)
    assert not np.allclose(np.asarray(a), np.asarray(base))
    # unbiasedness: averaging over keys approaches the no-dropout output
    acc = np.zeros_like(np.asarray(base))
    n = 24
    for i in range(n):
        acc += np.asarray(pa.flash_attention(
            q, k, v, block_q=64, block_k=64, dropout_p=0.3,
            dropout_key=jax.random.PRNGKey(100 + i)))
    resid = np.abs(acc / n - np.asarray(base)).mean()
    assert resid < 0.08, resid


def test_flash_dropout_gradient_finite_difference():
    q, k, v = _rand(96, seed=5)
    key = jax.random.PRNGKey(11)
    g = jnp.ones_like(q)

    def loss(q_, k_, v_):
        return (pa.flash_attention(q_, k_, v_, block_q=32, block_k=32,
                                   dropout_p=0.25, dropout_key=key)
                * g).sum()

    dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    rs = np.random.RandomState(2)
    d = jnp.asarray(rs.randn(*q.shape).astype(np.float32))
    # float32 sums of 12,288 outputs: at 1e-3 the difference quotient's own
    # round-off is 1-2% of dq (it moves with where the kernels round)
    eps = 1e-2
    for name, darg, idx in (("dq", dq, 0), ("dk", dk, 1), ("dv", dv, 2)):
        args = [q, k, v]
        ap = list(args)
        am = list(args)
        ap[idx] = args[idx] + eps * d
        am[idx] = args[idx] - eps * d
        num = (float(loss(*ap)) - float(loss(*am))) / (2 * eps)
        ana = float((darg * d).sum())
        assert abs(num - ana) < 2e-2 * max(1.0, abs(num)), \
            (name, num, ana)


def test_flash_vs_blockwise_same_math_no_dropout():
    q, k, v = _rand(160, seed=7)
    a = pa.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    b = pa.blockwise_attention(          # the scan runs head-major
        *(x.transpose(0, 2, 1, 3) for x in (q, k, v)), causal=True,
        block_k=64).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


def test_mha_op_routes_dropout_through_pallas():
    from mxnet_tpu import autograd, nd

    rs = np.random.RandomState(0)
    T, HD, heads = 256, 64, 2
    x = nd.array(rs.randn(2, T, HD).astype(np.float32))
    x.attach_grad()
    import mxnet_tpu as mx

    mx.random.seed(0)
    with autograd.record(train_mode=True):
        out = nd.multi_head_attention(
            x, x, x, num_heads=heads, attn_dropout=0.1,
            dropout_key=jax.random.PRNGKey(0), impl="pallas")
        L = out.sum()
    L.backward()
    assert x.grad is not None
    assert bool(jnp.isfinite(x.grad._data).all())


def test_flash_dropout_distinct_masks_for_small_seeds():
    # threefry key_data(PRNGKey(s)) = [0, s] for s < 2^32; the seed fold
    # must use BOTH words or every small seed shares one mask
    q, k, v = _rand(128)
    a = pa.flash_attention(q, k, v, block_q=64, block_k=64, dropout_p=0.3,
                           dropout_key=jax.random.PRNGKey(1))
    b = pa.flash_attention(q, k, v, block_q=64, block_k=64, dropout_p=0.3,
                           dropout_key=jax.random.PRNGKey(2))
    assert not np.allclose(np.asarray(a), np.asarray(b))


def test_flash_attention_lse_matches_dense_oracle():
    """(out, lse) API: lse equals logsumexp of the score rows, the lse
    cotangent folds into the backward correctly, and split-KV partials
    merge exactly (the ring-of-flash-blocks invariant)."""
    q, k, v = _rand(96, seed=9)
    out, lse = pa.flash_attention_lse(q, k, v, block_q=32, block_k=32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(jax.scipy.special.logsumexp(s, -1)),
        rtol=1e-5, atol=1e-6)
    # split-KV merge identity
    o1, l1 = pa.flash_attention_lse(q, k[:, :48], v[:, :48],
                                    block_q=32, block_k=16)
    o2, l2 = pa.flash_attention_lse(q, k[:, 48:], v[:, 48:],
                                    block_q=32, block_k=16)
    lm = jnp.logaddexp(l1, l2)
    # out is (B, T, H, D), lse head-major (B, H, T)
    w1, w2 = (jnp.exp(l - lm).transpose(0, 2, 1)[..., None]
              for l in (l1, l2))
    om = o1 * w1 + o2 * w2
    np.testing.assert_allclose(np.asarray(om), np.asarray(out),
                               rtol=1e-5, atol=1e-6)
    # full grads incl. the lse cotangent, vs a dense oracle
    g = jnp.asarray(np.random.RandomState(1)
                    .randn(*q.shape).astype(np.float32))
    h = jnp.asarray(np.random.RandomState(2)
                    .randn(B, H, q.shape[1]).astype(np.float32))

    def loss(q_, k_, v_):
        o, l = pa.flash_attention_lse(q_, k_, v_, block_q=32, block_k=32)
        return (o * g).sum() + (l * h).sum()

    def loss_ref(q_, k_, v_):
        s_ = jnp.einsum("bqhd,bkhd->bhqk", q_, k_) / np.sqrt(D)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s_, -1), v_)
        return (o * g).sum() + (jax.scipy.special.logsumexp(s_, -1)
                                * h).sum()

    got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5,
                                   err_msg="d" + name)


@pytest.mark.parametrize("dropout_p", [0.0, 0.2])
def test_flash_under_mesh_rows_matches_unsharded(dropout_p):
    """The ``mesh_rows`` shard_map (what FusedTrainer / mx.step declare on
    a mesh) gives the unsharded kernels' output and gradients — dropout
    included: the seeds are hashed from the GLOBAL (batch, head) index, so
    the masks do not depend on how B is laid over devices."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    rs = np.random.RandomState(3)
    q, k, v, g = (jnp.asarray(rs.randn(4, 128, 2, D).astype(np.float32))
                  for _ in range(4))
    key = jax.random.PRNGKey(5)

    def fwd_bwd(q, k, v):
        out, vjp = jax.vjp(lambda q, k, v: pa.flash_attention(
            q, k, v, block_q=64, block_k=64, dropout_p=dropout_p,
            dropout_key=key if dropout_p else None), q, k, v)
        return (out,) + vjp(g)

    want = jax.jit(fwd_bwd)(q, k, v)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mdl"))
    rows = NamedSharding(mesh, P("dp"))
    with pa.mesh_rows(mesh, ("dp",)):
        # another function object: jit must trace again, not reuse `want`'s
        sharded = jax.jit(lambda *qkv: fwd_bwd(*qkv))
        args = [jax.device_put(x, rows) for x in (q, k, v)]
        assert "shard_map" in str(jax.make_jaxpr(sharded)(*args))
        got = sharded(*args)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    # each device worked on its own B/2 rows
    assert got[0].sharding.is_equivalent_to(rows, 4)


def test_default_interpret_rejects_other_backends(monkeypatch):
    """Interpreted on cpu, compiled on tpu; anything else is an error,
    not a quiet interpreter."""
    from mxnet_tpu.base import MXNetError

    assert pa._default_interpret() is True          # the CPU suite
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pa._default_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(MXNetError, match="'gpu'"):
        pa._default_interpret()


# ---------------------------------------------------------------------------
# the tile body: classes of tiles, and the dtype of the products' operands
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("tq,tk,bq,bk", [
    (64, 64, 16, 16), (64, 64, 16, 32), (64, 64, 32, 8),
    (72, 72, 16, 16), (40, 100, 16, 32), (100, 40, 32, 16), (50, 50, 64, 64)])
def test_causal_tile_classes_against_the_materialised_triangle(tq, tk, bq,
                                                               bk):
    from test_block_diffusion_attention import _check_classes

    allowed = np.tril(np.ones((tq, tk), bool))
    _check_classes(
        allowed, min(bq, tq), min(bk, tk),
        lambda i: pa._k_tiles(i, min(bq, tq), min(bk, tk), tq, tk,
                              causal=True),
        lambda j: pa._q_tiles(j, min(bq, tq), min(bk, tk), tq, tk,
                              causal=True))
    if (tq, tk, bq, bk) == (64, 64, 16, 16):    # 4 cut on the diagonal
        assert pa.tile_counts(tq, tk, bq, bk, True) == (10, 6, 4)


@pytest.mark.parametrize("tq,tk,bq,bk,want", [
    (64, 64, 16, 16, (16, 16, 0)),      # nothing to mask: no tile is cut
    (72, 88, 32, 32, (9, 6, 3)),        # the padded last key tile a row
    (512, 512, 512, 512, (1, 1, 0)),    # BERT's call
])
def test_unmasked_calls_cut_only_the_padded_last_tile(tq, tk, bq, bk, want):
    assert pa.tile_counts(tq, tk, bq, bk) == want


_CALLS = {      # (Tq, Tk, query heads, KV heads, keywords)
    "plain": (64, 64, 2, 2, {}),
    "padded": (72, 88, 2, 2, {}),
    "causal": (96, 96, 2, 2, {"causal": True}),
    "causal_padded_grouped": (80, 80, 4, 2, {"causal": True}),
    "causal_uneven_tiles": (96, 96, 2, 2, {"causal": True, "block_q": 16}),
    "dropout": (64, 64, 2, 2, {"dropout_p": 0.2}),
    "dropout_causal_padded": (72, 72, 4, 2, {"causal": True,
                                             "dropout_p": 0.2}),
    "masked_grouped": (128, 128, 4, 2,
                       {"mask": pa.block_diffusion_mask(64, 4)}),
    "masked_astride": (88, 88, 2, 1,       # tiles astride L, a padded end
                       {"mask": pa.block_diffusion_mask(44, 4),
                        "block_q": 16, "block_k": 16}),
    "masked_dropout": (64, 64, 2, 2,
                       {"mask": pa.block_diffusion_mask(32, 4),
                        "block_q": 16, "block_k": 16, "dropout_p": 0.1}),
}


def _all_four(tq, tk, heads, kv_heads, kw, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (2, tq, heads, 16)).astype(dtype)
    k = jax.random.normal(ks[1], (2, tk, kv_heads, 16)).astype(dtype)
    v = jax.random.normal(ks[2], (2, tk, kv_heads, 16)).astype(dtype)
    w = jax.random.normal(ks[3], (2, tq, heads, 16))
    kw = dict({"block_q": 32, "block_k": 32}, **kw)
    if kw.get("dropout_p"):
        kw["dropout_key"] = jax.random.PRNGKey(5)

    def loss(q, k, v):
        return (pa.flash_attention(q, k, v, **kw).astype(jnp.float32)
                * w).sum()

    return (pa.flash_attention(q, k, v, **kw),) + jax.grad(
        loss, (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("call", sorted(_CALLS))
def test_whole_cut_split_is_bitwise_neutral(call, dtype, monkeypatch):
    """Skipping the mask arithmetic on a tile that is allowed whole changes
    no bit: the same call with every visited tile classified as cut."""
    tq, tk, heads, kv_heads, kw = _CALLS[call]
    _, whole, _ = pa.tile_counts(tq, tk, kw.get("block_q", 32),
                                 kw.get("block_k", 32),
                                 kw.get("causal", False), kw.get("mask"))
    assert whole > 0                    # or the case shows nothing
    got = _all_four(tq, tk, heads, kv_heads, kw, dtype)
    for name in ("_k_tiles", "_q_tiles"):
        monkeypatch.setattr(
            pa, name, lambda *a, _real=getattr(pa, name), **k: [
                (lo, hi, True) for lo, hi, _ in _real(*a, **k)])
    want = _all_four(tq, tk, heads, kv_heads, kw, dtype)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == dtype
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)),
                                      err_msg=name)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it (the kernels'
    bodies, their loops, the custom_vjp's branches)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("kw", [{}, {"causal": True}, {"dropout_p": 0.1},
                                {"mask": pa.block_diffusion_mask(64, 4)}],
                         ids=["plain", "causal", "dropout", "masked"])
def test_a_float32_call_keeps_float32_operands_in_every_product(kw):
    """The operands' dtype follows the caller's: float32 in, float32
    products, and no cast of p or ds (no float is converted at all)."""
    kw = dict(kw, **({"dropout_key": jax.random.PRNGKey(0)}
                     if "dropout_p" in kw else {}))
    x = jnp.ones((1, 128, 2, 16), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: pa.flash_attention(q, k, v, block_q=32, block_k=32,
                                           **kw).sum(), (0, 1, 2)))(x, x, x)
    eqns = list(_eqns(jaxpr.jaxpr))
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) >= 2 + 3 + 4
    for e in dots:
        assert [v.aval.dtype for v in e.invars] == [jnp.float32] * 2
        assert e.params["preferred_element_type"] == jnp.float32
    casts = [e for e in eqns if e.primitive.name == "convert_element_type"
             and jnp.issubdtype(e.invars[0].aval.dtype, jnp.floating)
             and e.invars[0].aval.shape]    # not a weakly typed constant
    assert not casts, casts


def test_a_bf16_call_feeds_the_products_bf16_and_accumulates_in_float32():
    x = jnp.ones((1, 128, 2, 16), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: pa.flash_attention(
            q, k, v, block_q=32, block_k=32).astype(jnp.float32).sum(),
        (0, 1, 2)))(x, x, x)
    dots = [e for e in _eqns(jaxpr.jaxpr)
            if e.primitive.name == "dot_general"]
    assert len(dots) == 2 + 3 + 4
    for e in dots:
        assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16] * 2
        assert e.outvars[0].aval.dtype == jnp.float32


@pytest.mark.parametrize("kw,kv_heads", [
    ({}, 4), ({"causal": True}, 4), ({"causal": True}, 2),
    ({"mask": pa.block_diffusion_mask(128, 4)}, 2)],
    ids=["plain", "causal", "causal_grouped", "masked_grouped"])
def test_bf16_kernels_agree_with_the_dense_bf16_path(kw, kv_heads):
    """Forward and the three gradients against ``ops/nn.py``'s dense path
    on the same bf16 arrays (bf16 products accumulated in float32, the
    probabilities cast to bf16 for the second product): within two
    rounding steps of bf16 at the tensor's scale."""
    from mxnet_tpu import nd

    heads, d, t = 4, 32, 256
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (2, t, heads * d)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, t, kv_heads * d)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, t, kv_heads * d)).astype(jnp.bfloat16)
    w = jax.random.normal(ks[3], (2, t, heads * d))

    def run(impl):
        def f(q, k, v):
            return nd.multi_head_attention(
                nd.NDArray(q), nd.NDArray(k), nd.NDArray(v),
                num_heads=heads, num_kv_heads=kv_heads, impl=impl,
                **kw)._data

        def loss(q, k, v):
            return (f(q, k, v).astype(jnp.float32) * w).sum()

        return (f(q, k, v),) + jax.grad(loss, (0, 1, 2))(q, k, v)

    for name, a, b in zip(("out", "dq", "dk", "dv"), run("pallas"),
                          run("dense")):
        assert a.dtype == jnp.bfloat16
        a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
        step = 2.0 ** -8 * np.abs(b).max()
        assert np.abs(a - b).max() <= 2 * step, (name, np.abs(a - b).max(),
                                                 step)


def _blocks_seen(monkeypatch):
    """Record the (block_q, block_k) each flash call resolves to."""
    seen = []
    real = pa._cfg_for

    def spy(q, k, causal, sm_scale, block_q, block_k, *a, **kw):
        seen.append((block_q, block_k))
        return real(q, k, causal, sm_scale, block_q, block_k, *a, **kw)

    monkeypatch.setattr(pa, "_cfg_for", spy)
    return seen


_DEFAULT_BLOCK_CALLS = {    # (call, T, keywords)
    "flash": (pa.flash_attention, 1024, {}),
    "flash_causal": (pa.flash_attention, 1024, {"causal": True}),
    "flash_block_diffusion": (pa.flash_attention, 1024,
                              {"mask": pa.block_diffusion_mask(512, 4)}),
    "lse": (pa.flash_attention_lse, 1024, {}),
    "lse_causal": (pa.flash_attention_lse, 1024, {"causal": True}),
    "blockwise": (pa.blockwise_attention, 512, {}),
    "blockwise_causal": (pa.blockwise_attention, 512, {"causal": True}),
}


@pytest.mark.parametrize("case", sorted(_DEFAULT_BLOCK_CALLS))
def test_blocks_left_out_are_the_module_constants(case):
    """A call that names no block runs the call that names 512 / 512 (the
    flash kernels) or 256 (the scan), bit for bit, at a length that holds
    more than one such block."""
    call, T, kw = _DEFAULT_BLOCK_CALLS[case]
    assert (pa.DEFAULT_BLOCK_Q, pa.DEFAULT_BLOCK_K,
            pa.DEFAULT_BLOCKWISE_K) == (512, 512, 256)
    blocks = {"block_k": 256} if call is pa.blockwise_attention else \
        {"block_q": 512, "block_k": 512}
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    shape = (1, 1, T, 16) if call is pa.blockwise_attention \
        else (1, T, 1, 16)          # the scan runs head-major
    q, k, v = (jax.random.normal(key, shape) for key in ks)
    got = jax.tree_util.tree_leaves(call(q, k, v, **kw))
    want = jax.tree_util.tree_leaves(call(q, k, v, **kw, **blocks))
    other = jax.tree_util.tree_leaves(call(
        q, k, v, **kw, **{name: 128 for name in blocks}))
    for a, b, c in zip(got, want, other):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        # another block sums in another order: the comparison can fail
        assert np.asarray(a).tobytes() != np.asarray(c).tobytes()


@pytest.mark.parametrize("kw,want", [
    ({}, (512, 512)),
    ({"block_q": 64}, (64, 512)),
    ({"block_k": 32}, (512, 32)),
    ({"block_q": 16, "block_k": 32}, (16, 32)),
    ({"block_q": 64, "dropout_p": 0.1}, (64, 512)),
])
def test_an_explicit_block_wins_each_on_its_own(kw, want, monkeypatch):
    seen = _blocks_seen(monkeypatch)
    q, k, v = _rand(64)
    if kw.get("dropout_p"):
        kw = dict(kw, dropout_key=jax.random.PRNGKey(1))
    pa.flash_attention(q, k, v, **kw)
    assert seen == [want]


# ---------------------------------------------------------------------------
# the rows layout: the kernels index the projections' (B, T, H * D) rows
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("d,heads,kv_heads,want", [
    (64, 12, 12, 2), (32, 8, 8, 4), (64, 2, 2, 2),      # heads share lanes
    (64, 1, 1, 1), (80, 1, 1, 1), (128, 1, 1, 1),   # one head IS its array
    (128, 32, 4, 0), (128, 64, 8, 0), (256, 2, 2, 0),   # head-major is free
    (80, 4, 4, 0), (96, 2, 1, 0),               # no legal block of rows
    (64, 3, 3, 0), (32, 6, 6, 0),               # an odd count under pairing
    (64, 4, 2, 0),                              # 64 with grouped KV heads
])
def test_heads_per_step_is_a_rule_on_the_shape(d, heads, kv_heads, want):
    assert pa.heads_per_step(d, heads, kv_heads) == want


def _mha(q, k, v, **kw):
    from mxnet_tpu.ops.nn import multi_head_attention

    return multi_head_attention.fn(q, k, v, **kw)


def _outer_eqns(jaxpr):
    """Every equation AROUND the kernels: the jaxprs inside the custom_vjp
    and its branches, not the kernels' own bodies."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _outer_eqns(sub)


_CELL_CALLS = {     # (B, T, query heads, KV heads, D, the call's rule)
    "bert_base_t512": (64, 512, 12, 12, 64, {}),
    "sdar_30b_a3b_bd4k": (2, 8192, 32, 4, 128,
                          {"mask": pa.block_diffusion_mask(4096, 4)}),
    "laguna_xs2_t8k_causal": (1, 8192, 48, 8, 128, {"causal": True}),
    "laguna_xs2_t8k_window": (1, 8192, 64, 8, 128,
                              {"mask": pa.window_mask(512)}),
}


@pytest.mark.parametrize("cell", sorted(_CELL_CALLS))
def test_no_copy_stands_between_the_projections_and_the_kernels(cell):
    """``multi_head_attention`` forward and gradient at the cells' attention
    shapes (traced, nothing runs): three kernel calls.  Heads of 64 (BERT):
    every call on the ``(B, T, H * D)`` rows as the projections wrote them,
    and no ``transpose`` of anything as large as K around them.  Heads of
    128 (SDAR, Laguna): every call on the head-major view, one head a batch
    row, whose transposes XLA gives to the projections' products as their
    layout (``test_chip_compile.py`` compiles that)."""
    B, T, H, Hkv, D, kw = _CELL_CALLS[cell]
    q = jax.ShapeDtypeStruct((B, T, H * D), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, T, Hkv * D), jnp.bfloat16)

    def loss(q, k, v):
        return _mha(q, k, v, num_heads=H, num_kv_heads=Hkv, impl="pallas",
                    **kw).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, (0, 1, 2)))(q, kv, kv)
    eqns = list(_outer_eqns(jaxpr.jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert sorted(e.params["name"] for e in calls) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    rows = pa.heads_per_step(D, H, Hkv) > 0
    assert rows == (D == 64)
    want = {(B, T, H * D), (B, T, Hkv * D)} if rows \
        else {(B * H, T, D), (B * Hkv, T, D)}
    for e in calls:
        big = {v.aval.shape for v in e.invars
               if v.aval.ndim == 3 and v.aval.dtype == jnp.bfloat16}
        assert big == want, big
    moved = [e.invars[0].aval.shape for e in eqns
             if e.primitive.name == "transpose"
             and e.invars[0].aval.size >= B * T * Hkv * D]
    assert bool(moved) != rows, moved


def test_a_shape_the_rule_does_not_serve_is_transposed_in_flash_attention():
    """D = 80: the same three calls, on a head-major view in which every
    head is a batch row of one head."""
    B, T, H, D = 2, 256, 4, 80
    x = jax.ShapeDtypeStruct((B, T, H * D), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: _mha(
        q, k, v, num_heads=H, impl="pallas").sum(), (0, 1, 2)))(x, x, x)
    eqns = list(_outer_eqns(jaxpr.jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 3
    for e in calls:
        assert (B * H, T, D) in [v.aval.shape for v in e.invars]
    assert any(e.primitive.name == "transpose" for e in eqns)


@pytest.mark.parametrize("name,t,heads,kv_heads,d,kw", [
    # one head a batch row of the head-major view, grouped KV heads,
    # under each rule
    ("d128_grouped_plain", 64, 4, 2, 128, {}),
    ("d128_grouped_causal", 64, 4, 2, 128, {"causal": True}),
    ("d128_grouped_window", 64, 4, 2, 128, {"mask": pa.window_mask(24)}),
    ("d128_grouped_block_diffusion", 64, 4, 2, 128,
     {"mask": pa.block_diffusion_mask(32, 4)}),
    # heads that share the 128 lanes of a block
    ("d64_pairs", 64, 4, 4, 64, {}),
    ("d64_pairs_causal", 64, 4, 4, 64, {"causal": True}),
    ("d32_fours", 64, 8, 8, 32, {}),
    ("d32_fours_causal", 64, 8, 8, 32, {"causal": True}),
    # T no multiple of the blocks: padded on axis 1 of the rows
    ("d64_pairs_padded", 50, 4, 4, 64, {"causal": True}),
    ("d128_grouped_padded", 50, 4, 2, 128, {"causal": True}),
    # shapes that fall back to the head-major view
    ("d80", 40, 2, 2, 80, {}),
    ("d80_grouped_causal", 40, 4, 2, 80, {"causal": True}),
    ("d64_odd_heads", 40, 3, 3, 64, {}),
    ("d64_grouped", 40, 4, 2, 64, {"causal": True}),
])
def test_every_layout_rule_matches_the_dense_path(name, t, heads, kv_heads,
                                                  d, kw, monkeypatch):
    """Output and the three gradients through ``multi_head_attention``,
    kernels against dense, from ``(B, T, H * D)`` rows; tiles of 16 so that
    every call has several, whole and cut."""
    monkeypatch.setattr(pa, "DEFAULT_BLOCK_Q", 16)
    monkeypatch.setattr(pa, "DEFAULT_BLOCK_K", 16)
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    q, k, v, w = (jax.random.normal(key, (2, t, n * d))
                  for key, n in zip(ks, (heads, kv_heads, kv_heads, heads)))

    def run(impl):
        def loss(q, k, v):
            out = _mha(q, k, v, num_heads=heads, num_kv_heads=kv_heads,
                       impl=impl, **kw)
            return (out * w).sum(), out

        (_, out), grads = jax.value_and_grad(loss, (0, 1, 2),
                                             has_aux=True)(q, k, v)
        return (out,) + grads

    for what, a, b in zip(("out", "dq", "dk", "dv"), run("pallas"),
                          run("dense")):
        np.testing.assert_allclose(a, b, atol=1e-5, err_msg=what)


@pytest.mark.parametrize("d,heads", [(64, 2), (32, 4)])
def test_a_shared_grid_step_draws_each_heads_own_dropout_mask(d, heads):
    """The heads of one grid step against single-head calls handed the
    same ``seeds[b, h]``: outputs and gradients equal to the order of the
    products' sums (another mask would be an error of the values' own
    size), so no head's mask depends on how many heads share its step."""
    B, T = 2, 64
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    q, k, v, do = (jax.random.normal(key, (B, T, heads, d)) for key in ks)
    cfg = pa._cfg_for(q, k, False, None, 32, 32, None, dropout_p=0.3)
    seeds = pa._bh_seeds(jax.random.PRNGKey(9), B, heads)
    assert pa.heads_per_step(d, heads, heads) == heads
    assert pa.heads_per_step(d, 1, 1) == 1

    def call(seeds, q, k, v, do):
        out, vjp = jax.vjp(lambda q, k, v: pa._flash_core(cfg, seeds, q, k,
                                                          v), q, k, v)
        return (out,) + vjp(do)

    together = call(seeds, q, k, v, do)
    assert not np.allclose(together[0], pa.flash_attention(
        q, k, v, block_q=32, block_k=32))         # the masks are at work
    for h in range(heads):
        alone = call(seeds[:, h:h + 1],
                     *(x[:, :, h:h + 1] for x in (q, k, v, do)))
        for what, a, b in zip(("out", "dq", "dk", "dv"), together, alone):
            np.testing.assert_allclose(
                np.asarray(a[:, :, h:h + 1]), np.asarray(b), atol=2e-6,
                err_msg="%s of head %d" % (what, h))


@pytest.mark.parametrize("layout,heads,kv_heads,d", [
    ("pairs", 2, 2, 64), ("fours", 4, 4, 32),
    ("head_major_view_grouped", 4, 2, 128), ("head_major_view", 3, 3, 64)])
def test_every_layout_under_mesh_rows_matches_one_device(layout, heads,
                                                         kv_heads, d):
    """``dp`` = 2 under ``mesh_rows`` on the virtual devices against one
    device, bit for bit, dropout on: each rule of ``heads_per_step``, and
    the head-major view (whose batch rows are B * H)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    B, T = 4, 64
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    q, k, v, g = (jax.random.normal(key, (B, T, n, d)) for key, n in
                  zip(ks, (heads, kv_heads, kv_heads, heads)))
    key = jax.random.PRNGKey(5)

    def fwd_bwd(q, k, v):
        out, vjp = jax.vjp(lambda q, k, v: pa.flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32, dropout_p=0.2,
            dropout_key=key), q, k, v)
        return (out,) + vjp(g)

    want = jax.jit(fwd_bwd)(q, k, v)
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    rows = NamedSharding(mesh, P("dp"))
    with pa.mesh_rows(mesh, ("dp",)):
        sharded = jax.jit(lambda *qkv: fwd_bwd(*qkv))
        args = [jax.device_put(x, rows) for x in (q, k, v)]
        assert "shard_map" in str(jax.make_jaxpr(sharded)(*args))
        got = sharded(*args)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_the_tiles_instant_says_how_the_kernels_read_the_call(monkeypatch):
    """``mx.attn.tiles`` at BERT's shape (two heads a grid step, rows) and
    at a shape the rule does not serve (D = 80: head-major, one head);
    SDAR's and Laguna's calls are pinned beside their tile counts."""
    from mxnet_tpu import trace

    monkeypatch.setattr(pa, "_TILES_NOTED", set())

    def noted():
        return [e["args"] for e in trace.events()
                if e["name"] == "mx.attn.tiles"
                and e["args"]["operand_dtype"] == "bfloat16"
                and e["args"]["kind"] == "none"]

    before = len(noted())
    for shape in ((64, 512, 12, 64), (2, 512, 4, 80), (64, 512, 12, 64)):
        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        jax.eval_shape(pa.flash_attention, x, x, x)
    tiles = {"kind": "none", "visited": 1, "whole": 1, "cut": 0,
             "operand_dtype": "bfloat16"}
    assert noted()[before:] == [
        dict(tiles, heads_per_step=2, layout="rows", head_dim=64),
        dict(tiles, heads_per_step=1, layout="heads", head_dim=80)]
