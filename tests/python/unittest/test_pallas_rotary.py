"""The Pallas pass between a decoder layer's q / k projections and the flash
kernels (``ops/pallas_rotary.py``, interpreted here): against
``rotary_embedding(rms_norm(x))`` in the kernels' view, forward, dx and the
gain's gradient; ``GroupedQueryAttention`` on both forms; the rule that
chooses the form, and the programs of the shapes the rule leaves alone."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd, trace
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.nn import transformer
from mxnet_tpu.ops import nn as ops_nn, pallas_attention as pa, \
    pallas_rotary as pr

YARN = dict(rotary_dim=64, factor=1.3,
            inv_freq=tuple(1e4 ** (-i / 32.0) / (1 + i % 3)
                           for i in range(32)))
ROTARY = {"whole": dict(theta=1e6), "partial": dict(theta=1e4, rotary_dim=64),
          "partial_yarn": YARN}


def _xla_form(x, positions, heads, gain, **rotary):
    """What ``transformer._placed`` computes, in the kernels' view."""
    B, T, _ = x.shape
    h = x.reshape(B, T, heads, -1)
    if gain is not None:
        h = ops_nn.rms_norm.fn(h, gain, eps=1e-6)
    h = ops_nn.rotary_embedding.fn(h, positions, **rotary)
    return h.transpose(0, 2, 1, 3).reshape(B * heads, T, 1, -1)


def _kernel_form(x, positions, heads, gain, **rotary):
    tables = pr.rotary_tables(positions, x.shape[-1] // heads, **rotary)
    return pr.placed(x, tables, heads, rotary.get("rotary_dim"), gain, 1e-6)


def _ulps(got, want, x):
    """The largest difference in units of the last place of ``want``'s
    dtype: at each element's own magnitude where the result was rounded to
    bfloat16 (down to a hundredth of the largest input, under which the
    float32 terms' own rounding shows), at the magnitude of the terms that
    were added where it is float32."""
    eps = float(jnp.finfo(want.dtype).eps)
    floor = float(jnp.abs(x.astype(jnp.float32)).max()) \
        / (1 if want.dtype == jnp.float32 else 100)
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    return float((np.abs(got - want)
                  / (eps * np.maximum(np.abs(want), floor))).max())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("batched_positions", [False, True],
                         ids=["T", "BT"])
@pytest.mark.parametrize("norm", [False, True], ids=["plain", "norm"])
@pytest.mark.parametrize("rotary", sorted(ROTARY))
def test_one_pass_is_the_xla_form_forward_and_backward(
        monkeypatch, rotary, norm, batched_positions, dtype):
    """8 heads (a KV group of 8) in tiles of 16 positions of which the last
    is cut (T = 40), against ``rotary_embedding(rms_norm(x))``: the
    rounding points are the same, so the two agree to one unit of the last
    place (the interpreter and XLA contract multiply-adds differently, and
    the norm's sum runs in another order), the gain's gradient, a sum over
    all rows, to its float32 rounding."""
    monkeypatch.setattr(pr, "MAX_TILE", 16)
    B, T, heads, D = 2, 40, 8, 128
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(B, T, heads * D), dtype)
    gain = jnp.asarray(1 + 0.1 * rs.randn(D), dtype) if norm else None
    positions = jnp.asarray(rs.randint(0, 5000, (B, T)) if batched_positions
                            else np.arange(T) * 7, jnp.int32)
    w = jnp.asarray(rs.randn(B * heads, T, 1, D), jnp.float32)
    kw = ROTARY[rotary]
    got = _kernel_form(x, positions, heads, gain, **kw)
    want = _xla_form(x, positions, heads, gain, **kw)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert _ulps(got, want, x) <= 1.0

    def loss(form):
        return lambda x, gain: (form(x, positions, heads, gain, **kw)
                                .astype(jnp.float32) * w).sum()

    wrt = (0, 1) if norm else (0,)
    got = jax.grad(loss(_kernel_form), wrt)(x, gain)
    want = jax.grad(loss(_xla_form), wrt)(x, gain)
    scale = float(jnp.abs(want[0].astype(jnp.float32)).max())
    np.testing.assert_allclose(
        np.asarray(got[0], np.float32), np.asarray(want[0], np.float32),
        rtol=0, atol=2 * float(jnp.finfo(dtype).eps) * scale)
    if norm:
        assert got[1].dtype == want[1].dtype and got[1].shape == (D,)
        np.testing.assert_allclose(
            np.asarray(got[1], np.float32), np.asarray(want[1], np.float32),
            rtol=2 * float(jnp.finfo(dtype).eps), atol=1e-4)


@pytest.mark.parametrize("heads,T", [(1, 24), (8, 512), (2, 1024)])
def test_one_head_and_whole_tiles(heads, T):
    """A single head (a KV group of 1) on a sequence shorter than a tile,
    and sequences of whole tiles of the size the rule of bytes gives."""
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(1, T, heads * 128), jnp.bfloat16)
    gain = jnp.asarray(1 + 0.1 * rs.randn(128), jnp.bfloat16)
    positions = jnp.arange(T, dtype=jnp.int32)
    assert pr._tile(T, x.shape[2] * 2) == min(T, 512)
    got = _kernel_form(x, positions, heads, gain, **YARN)
    assert _ulps(got, _xla_form(x, positions, heads, gain, **YARN), x) <= 1.0


def test_the_tile_follows_the_rows_bytes():
    """128 positions at 64 heads of 128 in bf16, 512 at 8 and fewer."""
    assert pr._tile(8192, 64 * 128 * 2) == 128
    assert pr._tile(8192, 48 * 128 * 2) == 128
    assert pr._tile(8192, 32 * 128 * 2) == 256
    assert pr._tile(8192, 8 * 128 * 2) == 512
    assert pr._tile(8192, 4 * 128 * 2) == 512
    assert pr._tile(300, 64 * 128 * 4) == 64 and pr._tile(40, 1024) == 40


def _layer_and_grads(attn, x, pos, w):
    for p in attn.collect_params().values():
        p.zero_grad()
    with autograd.record():
        loss = (attn(x, pos) * w).sum()
    loss.backward()
    return loss.asnumpy(), {n: p.grad().asnumpy()
                            for n, p in attn.collect_params().items()}


@pytest.mark.parametrize("kw,heads,kv_heads", [
    (dict(qk_norm=True), 8, 1),                     # SDAR's layer, a group of 8
    (dict(qk_norm=False, gate=True, window=64,
          rotary=dict(YARN)), 4, 2),                # Laguna's, partial YaRN
], ids=["qk_norm_causal", "gate_window_yarn"])
def test_attention_at_heads_of_128_agrees_with_itself_on_the_xla_form(
        monkeypatch, kw, heads, kv_heads):
    """T = 256 is the shortest call the flash kernels take: the layer runs
    the Pallas pass; with the rule turned off it runs ``_placed``.  Output
    and every parameter's gradient agree to float32 rounding."""
    mx.random.seed(3)
    attn = nn.GroupedQueryAttention(64, heads, kv_heads, 128,
                                    rope_theta=1e4, causal=True, **kw)
    attn.initialize()
    rs = np.random.RandomState(0)
    if attn.query_norm is not None:
        attn.query_norm.gamma.set_data(nd.array(1 + 0.1 * rs.randn(128)))
        attn.key_norm.gamma.set_data(nd.array(1 + 0.1 * rs.randn(128)))
    x = nd.array(rs.randn(2, 256, 64).astype("float32"))
    pos = nd.array(np.arange(256), dtype="int32")
    w = nd.array(rs.randn(2, 256, 64).astype("float32"))
    seen = []
    monkeypatch.setattr(trace, "instant",
                        lambda name, args=None: seen.append((name, args)))
    monkeypatch.setattr(transformer, "_PLACED_NOTED", set())
    got, got_grads = _layer_and_grads(attn, x, pos, w)
    monkeypatch.setattr(pr, "serves", lambda *a: False)
    want, want_grads = _layer_and_grads(attn, x, pos, w)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert set(got_grads) == set(want_grads) and len(got_grads) >= 5
    for name, g in want_grads.items():
        np.testing.assert_allclose(got_grads[name], g, rtol=0,
                                   atol=2e-5 * np.abs(g).max(), err_msg=name)
    # one instant a projection and form, the kernel first
    placed = [a for n, a in seen if n == "mx.attn.placed"]
    rotary_dim = 64 if "rotary" in kw else 128
    assert placed == [
        {"heads": h, "head_dim": 128, "rotary_dim": rotary_dim,
         "norm": bool(kw["qk_norm"]), "form": form}
        for form in ("kernel", "xla") for h in (heads, kv_heads)]


def _parents_forward(attn, x, positions, mask=None):
    """``GroupedQueryAttention.forward`` as the parent commit wrote it."""
    causal = attn._causal and mask is None
    q, k, v = attn.query_proj(x), attn.key_proj(x), attn.value_proj(x)
    b, t = x.shape[0], x.shape[1]

    def placed(h, heads, norm):
        h = h.reshape((b, t, heads, -1))
        if norm is not None:
            h = norm(h)
        return nd.rotary_embedding(h, positions, **attn._rotary)

    with transformer._attn_scope(pa.rule_kind(causal, mask)):
        out = nd.multi_head_attention(
            placed(q, attn._heads, attn.query_norm).reshape(q.shape),
            placed(k, attn._kv_heads, attn.key_norm).reshape(k.shape),
            v, num_heads=attn._heads, num_kv_heads=attn._kv_heads,
            mask=mask, causal=causal)
    return attn.out_proj(out)


@pytest.mark.parametrize("head_dim,T", [(64, 256), (128, 128)])
def test_heads_of_64_and_the_dense_path_trace_the_parents_program(
        head_dim, T, monkeypatch):
    """The rule from the shapes alone: heads narrower than the lanes, or a
    call the flash kernels do not take, run ``rotary_embedding`` as it was,
    operation for operation."""
    assert not pr.serves(T, head_dim, False, 4)
    assert pr.serves(256, 128, False, 2) and pr.serves(8192, 128, False, 2)
    assert not pr.serves(256, 128, True, 2)     # an array mask: dense
    attn = nn.GroupedQueryAttention(64, 4, 2, head_dim, causal=True)
    attn.initialize()
    x = jnp.zeros((1, T, 64), jnp.float32)
    pos = nd.array(np.arange(T), dtype="int32")
    seen = []
    monkeypatch.setattr(trace, "instant",
                        lambda name, args=None: seen.append((name, args)))
    monkeypatch.setattr(transformer, "_PLACED_NOTED", set())
    now = jax.make_jaxpr(lambda a: attn(nd.NDArray(a), pos)._data)(x)
    parent = jax.make_jaxpr(
        lambda a: _parents_forward(attn, nd.NDArray(a), pos)._data)(x)
    assert str(now) == str(parent)
    assert "placed_fwd" not in str(now)
    assert [a["form"] for n, a in seen if n == "mx.attn.placed"] \
        == ["xla", "xla"]


def test_latent_attention_traces_the_parents_program(monkeypatch):
    """GLM's rotary runs on 64-lane slices of a head of 256 and on ONE
    rotary key: ``_placed`` in the XLA form, ``mx.attn.placed`` says so, and
    no Pallas pass stands in front of the flash kernels."""
    attn = nn.LatentAttention(64, 2, 24, 16, 192, 64, 256, rope_theta=1e4)
    attn.initialize()
    seen = []
    monkeypatch.setattr(trace, "instant",
                        lambda name, args=None: seen.append((name, args)))
    monkeypatch.setattr(transformer, "_PLACED_NOTED", set())
    pos = nd.array(np.arange(256), dtype="int32")
    text = str(jax.make_jaxpr(lambda a: attn(nd.NDArray(a), pos)._data)(
        jnp.zeros((1, 256, 64), jnp.float32)))
    assert "flash_fwd" in text and "placed_fwd" not in text
    assert [a for n, a in seen if n == "mx.attn.placed"] == [
        {"heads": 1, "head_dim": 64, "rotary_dim": 64, "norm": False,
         "form": "xla"},
        {"heads": 2, "head_dim": 64, "rotary_dim": 64, "norm": False,
         "form": "xla"}]


def test_the_kernel_form_names_its_kernels_under_the_layers_scope():
    attn = nn.GroupedQueryAttention(64, 4, 2, 128, window=64)
    attn.initialize()
    pos = nd.array(np.arange(256), dtype="int32")

    def loss(a):
        return attn(nd.NDArray(a), pos)._data.sum()

    text = jax.jit(jax.grad(loss)).lower(
        jnp.zeros((1, 256, 64), jnp.float32)).as_text(debug_info=True)
    for name in ("placed_fwd", "placed_bwd", "flash_fwd", "flash_bwd_dq"):
        assert re.search(r'mx\.attn\.window[^"]*/%s' % name, text), name
    # no kernel of this module carries a flash kernel's name: the readers
    # of the benchmark find the flash kernels by theirs
    assert not any(k in n for k in ("flash_fwd", "flash_bwd")
                   for n in ("placed_fwd", "placed_bwd"))


@pytest.mark.parametrize("batched_positions", [False, True],
                         ids=["T", "BT"])
def test_under_a_mesh_every_device_takes_its_own_batch_rows(
        batched_positions):
    """Traced under ``mesh_rows`` the two calls run in a ``shard_map`` over
    the batch: rows, kernel view and the gain's partial sums split on
    their leading axis, tables of ``(T,)`` positions and the gain go to
    every device whole, tables of ``(B, T)`` positions with the rows."""
    from mxnet_tpu import parallel

    mesh = parallel.make_mesh({"dp": 4})
    B, T, heads = 4, 24, 2
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(B, T, heads * 128), jnp.float32)
    gain = jnp.asarray(1 + 0.1 * rs.randn(128), jnp.float32)
    positions = jnp.asarray(rs.randint(0, 99, (B, T)) if batched_positions
                            else np.arange(T), jnp.int32)

    def loss(x, gain):
        return (_kernel_form(x, positions, heads, gain, **YARN) ** 2).sum()

    want = jax.value_and_grad(loss, (0, 1))(x, gain)
    with pa.mesh_rows(mesh, ("dp",)):
        lowered = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(x, gain)
    assert lowered.as_text().count("sdy.manual_computation") == 2 \
        or lowered.as_text().count("shard_map") >= 2
    got = lowered.compile()(x, gain)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-6,
                                   atol=1e-5)
