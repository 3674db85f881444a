"""Transformer layers, flash attention, BERT, and LM tests (CPU mesh)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.gluon import model_zoo, nn
from mxnet_tpu.test_utils import assert_almost_equal

import jax
import jax.numpy as jnp


def _rand(*shape):
    return np.random.RandomState(hash(shape) % (2**31)).rand(*shape) \
        .astype(np.float32)


# ---- attention impl consistency -------------------------------------------
@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_dense(causal):
    from mxnet_tpu.ops import pallas_attention as pa

    B, H, T, D = 2, 3, 64, 16
    q, k, v = (jnp.asarray(_rand(B, H, T, D)) for _ in range(3))
    out = pa.blockwise_attention(q, k, v, causal=causal, block_k=16)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    assert_almost_equal(np.asarray(out), np.asarray(ref), rtol=1e-4,
                        atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_flash_matches_dense(causal):
    """interpret=True runs the identical kernel logic on CPU."""
    from mxnet_tpu.ops import pallas_attention as pa

    B, H, T, D = 1, 2, 128, 8
    q, k, v = (jnp.asarray(_rand(B, T, H, D)) for _ in range(3))
    out = pa.flash_attention(q, k, v, causal, None, 32, 32, True)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    assert_almost_equal(np.asarray(out), np.asarray(ref), rtol=1e-4,
                        atol=1e-5)


def test_flash_attention_grad():
    from mxnet_tpu.ops import pallas_attention as pa

    B, H, T, D = 1, 1, 32, 8
    q, k, v = (jnp.asarray(_rand(B, T, H, D)) for _ in range(3))

    def loss_flash(q_, k_, v_):
        return pa.flash_attention(q_, k_, v_, True, None, 16, 16,
                                  True).sum()

    def loss_dense(q_, k_, v_):
        s = jnp.einsum("bqhd,bkhd->bhqk", q_, k_) / np.sqrt(D)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(s, -1), v_).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        assert_almost_equal(np.asarray(a), np.asarray(b), rtol=1e-3,
                            atol=1e-4)


def test_mha_op_impl_dispatch():
    B, T, H, D = 2, 32, 4, 8
    q = nd.array(_rand(B, T, H * D))
    dense = nd.multi_head_attention(q, q, q, num_heads=H, impl="dense")
    flash = nd.multi_head_attention(q, q, q, num_heads=H, impl="flash")
    assert_almost_equal(dense.asnumpy(), flash.asnumpy(), rtol=1e-4,
                        atol=1e-5)


# ---- layers ----------------------------------------------------------------
def test_multi_head_attention_layer():
    layer = nn.MultiHeadAttention(32, 4)
    layer.initialize()
    x = nd.array(_rand(2, 10, 32))
    out = layer(x)
    assert out.shape == (2, 10, 32)
    # cross attention
    mem = nd.array(_rand(2, 7, 32))
    out = layer(x, mem, mem)
    assert out.shape == (2, 10, 32)
    # TP hints: out_proj row-parallel
    assert layer.out_proj.weight.sharding == (None, "tp")
    assert layer.query_proj.weight.sharding == ("tp", None)


def test_transformer_encoder_shapes_and_grad():
    enc = nn.TransformerEncoder(2, 16, 64, 4, dropout=0.1)
    enc.initialize()
    x = nd.array(_rand(2, 12, 16))
    x.attach_grad()
    with autograd.record():
        out = enc(x)
        loss = (out * out).sum()
    loss.backward()
    assert out.shape == (2, 12, 16)
    assert np.isfinite(x.grad.asnumpy()).all()
    assert np.abs(x.grad.asnumpy()).sum() > 0


def test_transformer_hybridize_consistent():
    enc = nn.TransformerEncoder(1, 8, 32, 2, dropout=0.0)
    enc.initialize()
    x = nd.array(_rand(2, 6, 8))
    eager = enc(x).asnumpy()
    enc.hybridize()
    hybrid = enc(x).asnumpy()
    assert_almost_equal(eager, hybrid, rtol=1e-5, atol=1e-6)


def test_sinusoidal_positional_embedding():
    pe = nn.SinusoidalPositionalEmbedding(16)
    x = nd.zeros((1, 5, 16))
    out = pe(x).asnumpy()
    assert_almost_equal(out[0, 0, 0::2], np.sin(np.zeros(8)), atol=1e-6)
    assert np.abs(out[0, 1:]).max() > 0


# ---- BERT ------------------------------------------------------------------
def test_bert_model_forward():
    net = model_zoo.BERTModel(vocab_size=100, units=32, hidden_size=64,
                              num_layers=2, num_heads=4, max_length=16)
    net.initialize()
    B, T = 2, 12
    ids = nd.array(np.random.RandomState(0).randint(0, 100, (B, T)))
    tt = nd.zeros((B, T))
    vlen = nd.array(np.array([12, 7], np.float32))
    seq, pooled = net(ids, tt, vlen)
    assert seq.shape == (B, T, 32)
    assert pooled.shape == (B, 32)


def test_bert_pretraining_step_decreases_loss():
    from mxnet_tpu.gluon.model_zoo.bert import pretraining_loss

    rs = np.random.RandomState(1)
    net = model_zoo.BERTForPretraining(
        vocab_size=50, units=16, hidden_size=32, num_layers=1, num_heads=2,
        max_length=16, dropout=0.0)
    net.initialize()
    trainer = mx.gluon.Trainer(net.collect_params(), "adam",
                               {"learning_rate": 1e-2})
    B, T, M = 4, 8, 2
    ids = nd.array(rs.randint(0, 50, (B, T)))
    pos = nd.array(np.tile(np.array([1, 3]), (B, 1)).astype(np.int32))
    labels = nd.array(rs.randint(0, 50, (B, M)))
    weights = nd.ones((B, M))
    nsp = nd.array(rs.randint(0, 2, (B,)))

    losses = []
    for _ in range(8):
        with autograd.record():
            mlm, nsp_s = net(ids, None, None, pos)
            L = pretraining_loss(mlm, nsp_s, labels, weights, nsp)
        L.backward()
        trainer.step(1)
        losses.append(float(L.asscalar()))
    assert losses[-1] < losses[0]


# The masked-slot selection as the zoo wrote it before it selected rows: the
# index broadcast to (B, M, C), so every element of every slot is a slice of
# its own (gather slice_sizes=(1, 1, 1), a scatter-add of single elements
# backward).  Kept here as the form the zoo's selection is held against.
def _element_indexed_scores(net, ids, types, positions):
    seq, _ = net.bert(ids, types, None)
    h = nd.take_along_axis(
        seq, positions.astype("int32").expand_dims(-1)
        .broadcast_to(positions.shape + (seq.shape[-1],)), axis=1)
    h = net.mlm_ln(net.mlm_transform(h))
    emb = net.bert.word_embed.weight.data()
    return nd.dot(h.reshape((-1, h.shape[-1])), emb.T) \
        .reshape(h.shape[:-1] + (emb.shape[0],))


_SLOT_B, _SLOT_T, _SLOT_C, _SLOT_V = 3, 10, 24, 50
_SLOT_POSITIONS = {
    "distinct": [[1, 4, 7], [0, 2, 9], [3, 5, 6]],
    "repeated": [[2, 2, 5], [7, 7, 7], [1, 8, 1]],
    "edges": [[0, 9, 4], [9, 0, 0], [0, 9, 9]],
}


def _slot_net(dtype):
    mx.random.seed(7)
    net = model_zoo.BERTForPretraining(
        vocab_size=_SLOT_V, units=_SLOT_C, hidden_size=48, num_layers=1,
        num_heads=2, max_length=_SLOT_T, dropout=0.0)
    net.initialize()
    if dtype != "float32":
        net.cast(dtype)
    rs = np.random.RandomState(3)
    ids = nd.array(rs.randint(0, _SLOT_V, (_SLOT_B, _SLOT_T)))
    types = nd.array(rs.randint(0, 2, (_SLOT_B, _SLOT_T)))
    return net, ids, types


@pytest.mark.parametrize("case", sorted(_SLOT_POSITIONS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bert_masked_slots_equal_the_element_indexed_form(dtype, case):
    net, ids, types = _slot_net(dtype)
    positions = nd.array(np.array(_SLOT_POSITIONS[case], np.int32))
    M = positions.shape[1]
    # a scalar that weighs every score differently, so a slot that came
    # from the wrong row or a cotangent that went to one shows
    weigh = nd.array(np.random.RandomState(5).randn(_SLOT_B, M, _SLOT_V)
                     .astype(np.float32))

    def scores_and_grads(scores_of):
        with autograd.record():
            scores = scores_of()
            L = nd.sum(scores.astype("float32") * weigh)
        L.backward()
        return scores.asnumpy(), {
            n: p.grad().asnumpy().astype(np.float32)
            for n, p in net.collect_params().items() if p.grad_req != "null"}

    want, want_g = scores_and_grads(
        lambda: _element_indexed_scores(net, ids, types, positions))
    got, got_g = scores_and_grads(lambda: net(ids, types, None, positions)[0])
    assert got.shape == (_SLOT_B, M, _SLOT_V) and got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)  # a copy of rows: no rounding
    assert set(got_g) == set(want_g) and len(got_g) > 10
    # the same cotangents, added in another order where a position repeats:
    # float32 differs by its own round-off, bfloat16 by a rounding step or
    # two (2**-8 each) of the sums that repeated rows make.  A leaf whose
    # gradient is round-off alone (the key bias: softmax does not see it) is
    # held to a thousandth of the largest leaf's scale.
    rtol = 1e-5 if dtype == "float32" else 2.0 ** -6
    largest = max(np.abs(g).max() for g in want_g.values())
    assert largest > 1.0
    for name, g in want_g.items():
        np.testing.assert_allclose(
            got_g[name], g, rtol=0, err_msg=name,
            atol=rtol * max(np.abs(g).max(), 1e-3 * largest))


def _single_element_moves(jaxpr, width):
    """The gathers and scatter-adds of ``jaxpr`` (and of every jaxpr inside
    it) that move single elements of an operand whose last dimension is
    ``width``: the slice, or the update window, all ones."""
    found = []
    for eqn in jaxpr.eqns:
        operand = eqn.invars[0].aval if eqn.invars else None
        wide = getattr(operand, "shape", ())[-1:] == (width,)
        if eqn.primitive.name == "gather" and wide and \
                all(s == 1 for s in eqn.params["slice_sizes"]):
            found.append(("gather", operand.shape))
        if eqn.primitive.name == "scatter-add" and wide and \
                not eqn.params["dimension_numbers"].update_window_dims:
            found.append(("scatter-add", operand.shape))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _single_element_moves(sub, width)
    return found


def test_bert_masked_slots_move_rows_not_elements():
    from mxnet_tpu.gluon import HybridBlock

    net, ids, types = _slot_net("float32")
    positions = nd.array(np.array(_SLOT_POSITIONS["distinct"], np.int32))

    class Step(HybridBlock):
        def __init__(self, scores_of):
            super().__init__()
            self.net, self._scores_of = net, scores_of

        def forward(self, ids, types, positions):
            return self._scores_of(ids, types, positions)

    def moves(scores_of):
        apply_fn, params = Step(scores_of).export_pure(training=True)

        def scalar(params):
            (scores,), _ = apply_fn(params, jax.random.PRNGKey(0), ids._data,
                                    types._data, positions._data)
            return jnp.sum(scores.astype(jnp.float32) ** 2)

        closed = jax.make_jaxpr(jax.value_and_grad(scalar))(params)
        return _single_element_moves(closed.jaxpr, _SLOT_C)

    # the check sees the old form: one gather and one scatter-add of single
    # elements of the (B, T, C) hidden states
    old = moves(lambda i, t, p: _element_indexed_scores(net, i, t, p))
    shape = (_SLOT_B, _SLOT_T, _SLOT_C)
    assert ("gather", shape) in old and ("scatter-add", shape) in old
    # and none in the zoo's; the embedding lookups (rows of C) pass
    assert moves(lambda i, t, p: net(i, t, None, p)[0]) == []


# ---- language models -------------------------------------------------------
def test_lstm_lm_forward_and_state():
    net = model_zoo.StandardRNNLM(vocab_size=40, embed_size=16,
                                  hidden_size=16, num_layers=2, dropout=0.0)
    net.initialize()
    ids = nd.array(np.random.RandomState(2).randint(0, 40, (3, 7)))
    logits = net(ids)
    assert logits.shape == (3, 7, 40)
    states = net.begin_state(3)
    logits, new_states = net(ids, states)
    assert logits.shape == (3, 7, 40)
    assert new_states[0].shape == states[0].shape


def test_lstm_lm_trains():
    rs = np.random.RandomState(3)
    net = model_zoo.standard_lstm_lm_200(vocab_size=30)
    net.initialize()
    trainer = mx.gluon.Trainer(net.collect_params(), "adam",
                               {"learning_rate": 1e-2})
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    x = nd.array(rs.randint(0, 30, (4, 6)))
    y = nd.array(rs.randint(0, 30, (4, 6)))
    losses = []
    for _ in range(5):
        with autograd.record():
            logits = net(x)
            L = loss_fn(logits.reshape((-1, 30)),
                        y.reshape((-1,))).mean()
        L.backward()
        trainer.step(1)
        losses.append(float(L.asscalar()))
    assert losses[-1] < losses[0]


def test_gpt_lm_causal():
    """Future tokens must not affect past logits (causality check)."""
    net = model_zoo.TransformerLM(vocab_size=20, units=16, hidden_size=32,
                                  num_layers=1, num_heads=2, max_length=16,
                                  dropout=0.0)
    net.initialize()
    rs = np.random.RandomState(4)
    ids = rs.randint(0, 20, (1, 8))
    logits1 = net(nd.array(ids)).asnumpy()
    ids2 = ids.copy()
    ids2[0, -1] = (ids2[0, -1] + 1) % 20
    logits2 = net(nd.array(ids2)).asnumpy()
    assert_almost_equal(logits1[0, :-1], logits2[0, :-1], rtol=1e-4,
                        atol=1e-5)
    assert np.abs(logits1[0, -1] - logits2[0, -1]).max() > 1e-6


def test_blockwise_attention_dropout_semantics():
    """Blockwise probability dropout == dropout(softmax(s)) @ v computed
    online: mean over keys converges to the undropped output, the softmax
    denominator stays undropped, and grads flow."""
    from mxnet_tpu.ops import pallas_attention

    rs = np.random.RandomState(0)
    B, H, T, D = 1, 2, 64, 16
    q = jnp.asarray(rs.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rs.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rs.randn(B, H, T, D).astype(np.float32))

    ref = pallas_attention.blockwise_attention(q, k, v, block_k=16)
    import functools

    run = jax.jit(functools.partial(pallas_attention.blockwise_attention,
                                    block_k=16, dropout_p=0.3))
    outs = [run(q, k, v, dropout_key=jax.random.PRNGKey(seed))
            for seed in range(200)]
    mean = jnp.stack(outs).mean(0)
    err = float(jnp.abs(mean - ref).max() / (jnp.abs(ref).max() + 1e-6))
    assert err < 0.2, "dropout must be unbiased, rel err %.3f" % err
    # deterministic per key
    a = pallas_attention.blockwise_attention(
        q, k, v, block_k=16, dropout_p=0.3,
        dropout_key=jax.random.PRNGKey(7))
    b = pallas_attention.blockwise_attention(
        q, k, v, block_k=16, dropout_p=0.3,
        dropout_key=jax.random.PRNGKey(7))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    # differentiable
    g = jax.grad(lambda qq: pallas_attention.blockwise_attention(
        qq, k, v, block_k=16, dropout_p=0.3,
        dropout_key=jax.random.PRNGKey(1)).sum())(q)
    assert float(jnp.abs(g).sum()) > 0


def test_mha_auto_uses_flash_with_dropout_long_seq():
    """T=512 + attn dropout must route to the Pallas kernel (in-kernel
    per-tile dropout, r4), not dense (the BERT pretrain configuration)."""
    from mxnet_tpu import nd
    from mxnet_tpu import random as mxrandom

    rs = np.random.RandomState(1)
    B, T, H, D = 1, 512, 2, 32
    q = nd.array(rs.randn(B, T, H * D).astype(np.float32))
    key = mxrandom.take_key()
    out = nd.multi_head_attention(q, q, q, num_heads=H, attn_dropout=0.1,
                                  dropout_key=key)
    assert out.shape == (B, T, H * D)
    # pin the ROUTING: auto == explicit pallas bit-for-bit (same key and
    # per-tile masks); the dense path draws one full-matrix mask and
    # would differ
    out_flash = nd.multi_head_attention(q, q, q, num_heads=H,
                                        attn_dropout=0.1, dropout_key=key,
                                        impl="pallas")
    np.testing.assert_allclose(out.asnumpy(), out_flash.asnumpy())
    out_dense = nd.multi_head_attention(q, q, q, num_heads=H,
                                        attn_dropout=0.1, dropout_key=key,
                                        impl="dense")
    assert not np.allclose(out.asnumpy(), out_dense.asnumpy())
    # parity: dropout_p=0 flash vs dense on the same inputs
    o_flash = nd.multi_head_attention(q, q, q, num_heads=H, impl="flash")
    o_dense = nd.multi_head_attention(q, q, q, num_heads=H, impl="dense")
    np.testing.assert_allclose(o_flash.asnumpy(), o_dense.asnumpy(),
                               rtol=2e-3, atol=2e-4)
