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
    T, Tk = q.shape[2], k.shape[2]
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        m = jnp.tril(jnp.ones((T, Tk), bool))
        s = jnp.where(m, s, -1e30)
    w = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v.astype(jnp.float32)).astype(
        q.dtype)


def _rand(T, seed=0):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(B, H, T, D).astype(np.float32))  # noqa
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("T", [128, 192])  # 192: exercises padding
def test_flash_backward_matches_dense(causal, T):
    q, k, v = _rand(T)
    g = jnp.asarray(np.random.RandomState(1)
                    .randn(B, H, T, D).astype(np.float32))

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
    eps = 1e-3
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
    b = pa.blockwise_attention(q, k, v, causal=True, block_k=64)
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
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(jax.scipy.special.logsumexp(s, -1)),
        rtol=1e-5, atol=1e-6)
    # split-KV merge identity
    o1, l1 = pa.flash_attention_lse(q, k[:, :, :48], v[:, :, :48],
                                    block_q=32, block_k=16)
    o2, l2 = pa.flash_attention_lse(q, k[:, :, 48:], v[:, :, 48:],
                                    block_q=32, block_k=16)
    lm = jnp.logaddexp(l1, l2)
    om = o1 * jnp.exp(l1 - lm)[..., None] + o2 * jnp.exp(l2 - lm)[..., None]
    np.testing.assert_allclose(np.asarray(om), np.asarray(out),
                               rtol=1e-5, atol=1e-6)
    # full grads incl. the lse cotangent, vs a dense oracle
    g = jnp.asarray(np.random.RandomState(1)
                    .randn(*q.shape).astype(np.float32))
    h = jnp.asarray(np.random.RandomState(2)
                    .randn(*q.shape[:3]).astype(np.float32))

    def loss(q_, k_, v_):
        o, l = pa.flash_attention_lse(q_, k_, v_, block_q=32, block_k=32)
        return (o * g).sum() + (l * h).sum()

    def loss_ref(q_, k_, v_):
        s_ = jnp.einsum("bhqd,bhkd->bhqk", q_, k_) / np.sqrt(D)
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s_, -1), v_)
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
    q, k, v, g = (jnp.asarray(rs.randn(4, 2, 128, D).astype(np.float32))
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
