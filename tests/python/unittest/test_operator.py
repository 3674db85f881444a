"""Operator tests (reference tests/python/unittest/test_operator.py —
numpy-parity forward + numeric gradient checks)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.test_utils import assert_almost_equal, check_numeric_gradient

UNARY_CASES = [
    ("relu", lambda x: np.maximum(x, 0), (-2, 2)),
    ("sigmoid", lambda x: 1 / (1 + np.exp(-x)), (-3, 3)),
    ("tanh", np.tanh, (-2, 2)),
    ("exp", np.exp, (-1, 1)),
    ("log", np.log, (0.1, 3)),
    ("sqrt", np.sqrt, (0.1, 4)),
    ("square", np.square, (-2, 2)),
    ("abs", np.abs, (-2, 2)),
    ("floor", np.floor, (-2, 2)),
    ("ceil", np.ceil, (-2, 2)),
    ("sin", np.sin, (-3, 3)),
    ("cos", np.cos, (-3, 3)),
    ("arctan", np.arctan, (-2, 2)),
    ("log1p", np.log1p, (-0.5, 2)),
    ("expm1", np.expm1, (-1, 1)),
    ("rsqrt", lambda x: 1 / np.sqrt(x), (0.5, 3)),
]


@pytest.mark.parametrize("name,ref,rng", UNARY_CASES)
def test_unary_forward(name, ref, rng):
    x = np.random.uniform(rng[0], rng[1], (3, 4)).astype(np.float32)
    out = getattr(nd, name)(nd.array(x))
    assert_almost_equal(out.asnumpy(), ref(x), rtol=1e-4, atol=1e-5)


def test_softmax_ops():
    x = np.random.rand(2, 5).astype(np.float32)
    sm = nd.softmax(nd.array(x)).asnumpy()
    ref = np.exp(x) / np.exp(x).sum(-1, keepdims=True)
    assert_almost_equal(sm, ref, rtol=1e-4)
    lsm = nd.log_softmax(nd.array(x)).asnumpy()
    assert_almost_equal(lsm, np.log(ref), rtol=1e-4)
    # masked softmax via length
    ln = nd.array([2, 5], dtype="int32")
    sm2 = nd.softmax(nd.array(x), axis=-1, length=ln).asnumpy()
    assert abs(sm2[0, 2:].sum()) < 1e-6


def test_fully_connected():
    x = np.random.rand(4, 7).astype(np.float32)
    w = np.random.rand(3, 7).astype(np.float32)
    b = np.random.rand(3).astype(np.float32)
    out = nd.fully_connected(nd.array(x), nd.array(w), nd.array(b),
                             num_hidden=3)
    assert_almost_equal(out.asnumpy(), x @ w.T + b, rtol=1e-4, atol=1e-4)
    check_numeric_gradient(
        lambda a, ww: nd.fully_connected(a, ww, None, num_hidden=3,
                                         no_bias=True),
        [np.random.rand(2, 5), np.random.rand(3, 5)])


def test_convolution_forward():
    import torch
    import torch.nn.functional as F

    x = np.random.rand(2, 3, 8, 8).astype(np.float32)
    w = np.random.rand(5, 3, 3, 3).astype(np.float32)
    b = np.random.rand(5).astype(np.float32)
    out = nd.convolution(nd.array(x), nd.array(w), nd.array(b),
                         kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                         num_filter=5)
    ref = F.conv2d(torch.tensor(x), torch.tensor(w), torch.tensor(b),
                   stride=2, padding=1).numpy()
    assert_almost_equal(out.asnumpy(), ref, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("layout,spatial", [("NCW", (8,)), ("NCHW", (8, 8)),
                                            ("NCDHW", (4, 4, 4))])
def test_convolution_default_layout_is_the_named_one(layout, spatial):
    """``convolution(x, w)`` is, bit for bit, the call that names the
    channel-first layout of its rank: one path, no other behind it."""
    rs = np.random.RandomState(1)
    x = nd.array(rs.rand(2, 3, *spatial).astype(np.float32))
    w = nd.array(rs.rand(4, 3, *(3,) * len(spatial)).astype(np.float32))
    kw = dict(kernel=(3,) * len(spatial), num_filter=4, no_bias=True)
    got = nd.convolution(x, w, **kw).asnumpy()
    want = nd.convolution(x, w, layout=layout, **kw).asnumpy()
    assert got.shape[:2] == (2, 4)
    assert got.tobytes() == want.tobytes()


def test_pooling():
    import torch
    import torch.nn.functional as F

    x = np.random.rand(2, 3, 8, 8).astype(np.float32)
    out = nd.pooling(nd.array(x), kernel=(2, 2), stride=(2, 2),
                     pool_type="max")
    ref = F.max_pool2d(torch.tensor(x), 2).numpy()
    assert_almost_equal(out.asnumpy(), ref)
    out = nd.pooling(nd.array(x), kernel=(2, 2), stride=(2, 2),
                     pool_type="avg")
    ref = F.avg_pool2d(torch.tensor(x), 2).numpy()
    assert_almost_equal(out.asnumpy(), ref, rtol=1e-5)
    gp = nd.pooling(nd.array(x), global_pool=True, pool_type="avg")
    assert_almost_equal(gp.asnumpy()[..., 0, 0], x.mean(axis=(2, 3)),
                        rtol=1e-5)


def test_batch_norm():
    x = np.random.rand(4, 3, 5, 5).astype(np.float32)
    gamma = np.random.rand(3).astype(np.float32)
    beta = np.random.rand(3).astype(np.float32)
    mean = np.zeros(3, np.float32)
    var = np.ones(3, np.float32)
    out, nm, nv = nd.batch_norm(nd.array(x), nd.array(gamma),
                                nd.array(beta), nd.array(mean),
                                nd.array(var), training=True, momentum=0.9)
    bm = x.mean(axis=(0, 2, 3))
    bv = x.var(axis=(0, 2, 3))
    ref = (x - bm[None, :, None, None]) / np.sqrt(
        bv[None, :, None, None] + 1e-5) * gamma[None, :, None, None] + \
        beta[None, :, None, None]
    assert_almost_equal(out.asnumpy(), ref, rtol=1e-3, atol=1e-4)
    assert_almost_equal(nm.asnumpy(), 0.9 * mean + 0.1 * bm, rtol=1e-4)


@pytest.mark.parametrize("stat_dtype,same", [("float32", True),
                                             ("bfloat16", False)])
def test_batch_norm_stat_dtype_default_is_float32(stat_dtype, same):
    """``stat_dtype=None`` reduces the batch statistics in float32, bit
    for bit; "bfloat16" is the other value and moves them."""
    rs = np.random.RandomState(2)
    x = nd.array(rs.rand(4, 3, 5, 5).astype(np.float32))
    args = [nd.array(rs.rand(3).astype(np.float32)) for _ in range(2)] + \
        [nd.array(np.zeros(3, np.float32)), nd.array(np.ones(3, np.float32))]
    got = nd.batch_norm(x, *args, training=True)
    want = nd.batch_norm(x, *args, training=True, stat_dtype=stat_dtype)
    equal = [a.asnumpy().tobytes() == b.asnumpy().tobytes()
             for a, b in zip(got, want)]
    assert equal == [same] * 3
    assert all(np.isfinite(b.asnumpy()).all() for b in want)


def test_layer_norm():
    x = np.random.rand(4, 6).astype(np.float32)
    g = np.ones(6, np.float32)
    b = np.zeros(6, np.float32)
    out = nd.layer_norm(nd.array(x), nd.array(g), nd.array(b)).asnumpy()
    ref = (x - x.mean(-1, keepdims=True)) / np.sqrt(
        x.var(-1, keepdims=True) + 1e-5)
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-5)


def test_embedding_and_grad():
    w = np.random.rand(10, 4).astype(np.float32)
    idx = np.array([1, 3, 1], np.int32)
    out = nd.embedding(nd.array(idx, dtype="int32"), nd.array(w))
    assert_almost_equal(out.asnumpy(), w[idx])
    check_numeric_gradient(
        lambda ww: nd.embedding(nd.array(idx, dtype="int32"), ww),
        [w])


def test_sequence_ops():
    x = np.random.rand(4, 2, 3).astype(np.float32)  # (T, B, C)
    ln = nd.array([2, 4], dtype="int32")
    masked = nd.sequence_mask(nd.array(x), ln, use_sequence_length=True,
                              value=0.0).asnumpy()
    assert (masked[2:, 0] == 0).all()
    assert_almost_equal(masked[:, 1], x[:, 1])
    last = nd.sequence_last(nd.array(x), ln, use_sequence_length=True)
    assert_almost_equal(last.asnumpy()[0], x[1, 0])
    assert_almost_equal(last.asnumpy()[1], x[3, 1])


def test_ctc_loss():
    T, B, V = 10, 2, 5
    logits = np.random.rand(T, B, V).astype(np.float32)
    labels = np.array([[1, 2, 0, 0], [2, 3, 4, 0]], np.float32)
    lens = np.array([2, 3], np.int32)
    loss = nd.ctc_loss(nd.array(logits), nd.array(labels),
                       label_lengths=nd.array(lens, dtype="int32"))
    assert loss.shape == (B,)
    assert (loss.asnumpy() > 0).all()


def test_attention_matches_naive():
    B, T, H, D = 2, 6, 2, 4
    q = np.random.rand(B, T, H * D).astype(np.float32)
    k = np.random.rand(B, T, H * D).astype(np.float32)
    v = np.random.rand(B, T, H * D).astype(np.float32)
    out = nd.multi_head_attention(nd.array(q), nd.array(k), nd.array(v),
                                  num_heads=H).asnumpy()
    qh = q.reshape(B, T, H, D).transpose(0, 2, 1, 3)
    kh = k.reshape(B, T, H, D).transpose(0, 2, 1, 3)
    vh = v.reshape(B, T, H, D).transpose(0, 2, 1, 3)
    s = (qh @ kh.transpose(0, 1, 3, 2)) / np.sqrt(D)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    ref = (p @ vh).transpose(0, 2, 1, 3).reshape(B, T, H * D)
    assert_almost_equal(out, ref, rtol=1e-3, atol=1e-4)


def test_where_clip_gather():
    x = np.random.rand(3, 3).astype(np.float32) - 0.5
    out = nd.where(nd.array(x) > 0, nd.array(x), nd.zeros((3, 3)))
    assert_almost_equal(out.asnumpy(), np.where(x > 0, x, 0))
    assert_almost_equal(nd.clip(nd.array(x), -0.2, 0.2).asnumpy(),
                        np.clip(x, -0.2, 0.2))
    data = nd.array(np.arange(9).reshape(3, 3).astype(np.float32))
    indices = nd.array([[0, 2], [1, 1]], dtype="int32")
    out = nd.gather_nd(data, indices)
    assert out.asnumpy().tolist() == [1.0, 7.0]


def test_activation_dispatch():
    x = nd.array([-1.0, 0.5])
    for act in ("relu", "sigmoid", "tanh", "softrelu", "softsign", "gelu",
                "silu", "mish"):
        y = nd.Activation(x, act_type=act)
        assert y.shape == x.shape
    for act in ("leaky", "elu", "selu", "gelu"):
        y = nd.LeakyReLU(x, act_type=act)
        assert y.shape == x.shape


def test_optimize_for_rejects_unknown_backend():
    import pytest

    import mxnet_tpu as mx

    sym_x = mx.sym.Variable("x")
    sym_y = sym_x + 1
    sym_y.optimize_for("XLA")  # known: no-op
    with pytest.raises(mx.MXNetError, match="unknown partitioning"):
        sym_y.optimize_for("MKLDNN")


def test_config_env_registry(monkeypatch):
    import mxnet_tpu as mx

    table = mx.config.describe()
    assert "MXNET_KVSTORE_BUCKET_BYTES" in table
    monkeypatch.delenv("MXNET_KVSTORE_BUCKET_BYTES", raising=False)
    assert mx.config.current()["MXNET_KVSTORE_BUCKET_BYTES"] == 4 << 20
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_BYTES", "8388608")
    assert mx.config.current()["MXNET_KVSTORE_BUCKET_BYTES"] == 8388608
    monkeypatch.setenv("MXNET_TYPO_VAR", "1")
    unknown = mx.config.check_unknown(warn=False)
    assert "MXNET_TYPO_VAR" in unknown
