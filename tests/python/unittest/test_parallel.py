"""Parallelism tests on the 8-device virtual CPU mesh (SURVEY §4
fake-backend strategy: multi-chip semantics validated without TPUs)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, parallel
from mxnet_tpu.gluon import nn
from mxnet_tpu.test_utils import assert_almost_equal

import jax
import jax.numpy as jnp


def _mesh_or_skip(axes):
    try:
        return parallel.make_mesh(axes)
    except Exception as exc:  # pragma: no cover
        pytest.skip(str(exc))


def test_make_mesh():
    assert len(jax.devices()) == 8, "conftest must force 8 cpu devices"
    mesh = parallel.make_mesh({"dp": 2, "tp": -1})
    assert mesh.shape["dp"] == 2
    assert mesh.shape["tp"] == 4


def test_fused_trainer_dp():
    mesh = _mesh_or_skip({"dp": 8})
    np.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(10))
    net.initialize()
    trainer = parallel.FusedTrainer(
        net, loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        mesh=mesh)
    X = np.random.rand(16, 8).astype(np.float32)
    Y = np.random.randint(0, 10, 16).astype(np.int32)
    losses = [float(trainer.step(X, Y).asscalar()) for _ in range(10)]
    assert losses[-1] < losses[0]
    trainer.sync_block()
    out = net(nd.array(X))
    assert out.shape == (16, 10)


def test_fused_trainer_lower_reads_without_stepping():
    """``_lower(x, y)`` hands ``chip_smoke.py`` the step program (text,
    compile()) and leaves the training state alone."""
    mesh = _mesh_or_skip({"dp": 4})
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu", in_units=8),
            nn.Dense(10, in_units=32))
    net.initialize()
    trainer = parallel.FusedTrainer(net, loss="softmax_ce", optimizer="sgd",
                                    mesh=mesh)
    X = np.random.RandomState(0).rand(16, 8).astype(np.float32)
    Y = np.random.RandomState(1).randint(0, 10, 16).astype(np.int32)
    lowered = trainer._lower(X, Y)                # builds the program too
    assert "stablehlo" in lowered.as_text()
    assert trainer._step_count == 0
    l0 = float(trainer.step(X, Y).asnumpy())
    compiled = trainer._lower(X, Y).compile()
    assert "all-reduce" in compiled.as_text()     # the dp grad reduction
    assert trainer._step_count == 1
    assert float(trainer.step(X, Y).asnumpy()) < l0


def test_fused_trainer_tp_sharding():
    mesh = _mesh_or_skip({"dp": 2, "tp": 4})
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(8))
    net.initialize()
    trainer = parallel.FusedTrainer(
        net, loss="softmax_ce", optimizer="adam",
        optimizer_params={"learning_rate": 0.01}, mesh=mesh)
    X = np.random.rand(8, 4).astype(np.float32)
    Y = np.random.randint(0, 8, 8).astype(np.int32)
    l0 = float(trainer.step(X, Y).asscalar())
    l1 = float(trainer.step(X, Y).asscalar())
    assert np.isfinite(l0) and np.isfinite(l1)
    # weight of first Dense should be sharded over tp on axis 0
    spec = trainer._param_specs
    dense0_w = [k for k in spec if k.endswith("weight")][0]
    assert spec[dense0_w][0] == "tp"


def test_fused_matches_eager_sgd():
    """Single-device fused step == imperative Trainer step."""
    np.random.seed(3)
    X = np.random.rand(8, 5).astype(np.float32)
    Y = np.random.randint(0, 4, 8).astype(np.float32)

    def build():
        mx.random.seed(11)
        net = nn.HybridSequential()
        net.add(nn.Dense(6, activation="tanh"), nn.Dense(4))
        net.initialize()
        net(nd.array(X))
        return net

    net_e = build()
    trainer = gluon.Trainer(net_e.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        L = loss_fn(net_e(nd.array(X)), nd.array(Y)).mean()
    L.backward()
    trainer.step(1)  # rescale 1 => plain mean loss grads

    net_f = build()
    fused = parallel.FusedTrainer(net_f, loss="softmax_ce", optimizer="sgd",
                                  optimizer_params={"learning_rate": 0.1,
                                                    "momentum": 0.0})
    fused.step(X, Y.astype(np.int32))
    fused.sync_block()
    for (k, pe), (_, pf) in zip(net_e.collect_params().items(),
                                net_f.collect_params().items()):
        assert_almost_equal(pe.data().asnumpy(), pf.data().asnumpy(),
                            rtol=1e-3, atol=1e-5, names=("eager", "fused"))


def test_ring_attention_matches_full():
    mesh = _mesh_or_skip({"sp": 8})
    B, H, T, D = 2, 4, 32, 8
    np.random.seed(0)
    q = jnp.asarray(np.random.rand(B, H, T, D).astype(np.float32))
    k = jnp.asarray(np.random.rand(B, H, T, D).astype(np.float32))
    v = jnp.asarray(np.random.rand(B, H, T, D).astype(np.float32))
    out = parallel.ring_attention(q, k, v, mesh=mesh, axis_name="sp")
    # dense reference
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    assert_almost_equal(np.asarray(out), np.asarray(ref), rtol=1e-3,
                        atol=1e-4)


def test_ring_attention_causal():
    mesh = _mesh_or_skip({"sp": 4})
    B, H, T, D = 1, 2, 16, 4
    np.random.seed(1)
    q = jnp.asarray(np.random.rand(B, H, T, D).astype(np.float32))
    k = jnp.asarray(np.random.rand(B, H, T, D).astype(np.float32))
    v = jnp.asarray(np.random.rand(B, H, T, D).astype(np.float32))
    out = parallel.ring_attention(q, k, v, mesh=mesh, axis_name="sp",
                                  causal=True)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask, s, -1e30)
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    assert_almost_equal(np.asarray(out), np.asarray(ref), rtol=1e-3,
                        atol=1e-4)


def test_ulysses_attention_matches_full():
    mesh = _mesh_or_skip({"sp": 4})
    B, H, T, D = 2, 8, 16, 4
    np.random.seed(2)
    q = jnp.asarray(np.random.rand(B, H, T, D).astype(np.float32))
    k = jnp.asarray(np.random.rand(B, H, T, D).astype(np.float32))
    v = jnp.asarray(np.random.rand(B, H, T, D).astype(np.float32))
    out = parallel.ulysses_attention(q, k, v, mesh=mesh, axis_name="sp")
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    assert_almost_equal(np.asarray(out), np.asarray(ref), rtol=1e-3,
                        atol=1e-4)


def test_kvstore_local_and_dist():
    from mxnet_tpu import kvstore

    kv = kvstore.create("local")
    kv.init("w", nd.ones((3,)))
    out = nd.zeros((3,))
    kv.push("w", [nd.ones((3,)), nd.ones((3,))])
    kv.pull("w", out)
    assert_almost_equal(out.asnumpy(), np.full(3, 2.0, np.float32))

    kvd = kvstore.create("dist_sync")
    assert kvd.num_workers == 1
    kvd.init("g", nd.ones((2,)))
    out2 = nd.zeros((2,))
    kvd.pushpull("g", nd.full((2,), 3.0), out=out2)
    assert_almost_equal(out2.asnumpy(), np.full(2, 3.0, np.float32))


def test_trainer_with_kvstore_multi_replica():
    """Two grad replicas summed through kvstore (multi-device data
    parallel semantics, reference trainer.py:385)."""
    from mxnet_tpu import kvstore

    kv = kvstore.create("device")
    g1, g2 = nd.ones((2,)), nd.full((2,), 2.0)
    kv.pushpull("k", [g1, g2], out=[g1, g2])
    assert_almost_equal(g1.asnumpy(), np.full(2, 3.0, np.float32))


def _clone_net(seed, units=(32, 10), in_units=8):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(units[0], activation="relu"), nn.Dense(units[1]))
    net.initialize()
    # resolve deferred shapes
    net(nd.array(np.zeros((2, in_units), np.float32)))
    return net


def test_grad_accum_parity():
    """FusedTrainer(grad_accum=k) on one batch of size k*b must match
    grad_accum=1 on the same batch (mean-of-means == overall mean)."""
    X = np.random.RandomState(0).rand(16, 8).astype(np.float32)
    Y = np.random.RandomState(1).randint(0, 10, 16).astype(np.int32)

    def run(accum, steps=3):
        net = _clone_net(7)
        tr = parallel.FusedTrainer(
            net, loss="softmax_ce", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            grad_accum=accum)
        losses = [float(tr.step(X, Y).asscalar()) for _ in range(steps)]
        tr.sync_block()
        return losses, net(nd.array(X)).asnumpy()

    l1, out1 = run(1)
    l4, out4 = run(4)
    np.testing.assert_allclose(l1, l4, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out1, out4, rtol=1e-4, atol=1e-5)


def test_grad_accum_rejects_indivisible_batch():
    net = _clone_net(3)
    tr = parallel.FusedTrainer(net, loss="softmax_ce", grad_accum=3)
    X = np.zeros((8, 8), np.float32)
    Y = np.zeros((8,), np.int32)
    with pytest.raises(mx.base.MXNetError):
        tr.step(X, Y)


def test_zero1_state_sharded_and_parity():
    """zero=True shards optimizer state over dp (ZeRO-1): per-device state
    shards shrink ~dp×, training matches the replicated-state result."""
    X = np.random.RandomState(2).rand(16, 8).astype(np.float32)
    Y = np.random.RandomState(3).randint(0, 10, 16).astype(np.int32)

    def run(zero):
        mesh = _mesh_or_skip({"dp": 8})
        net = _clone_net(11)
        tr = parallel.FusedTrainer(
            net, loss="softmax_ce", optimizer="adam",
            optimizer_params={"learning_rate": 1e-2}, mesh=mesh, zero=zero)
        losses = [float(tr.step(X, Y).asscalar()) for _ in range(5)]
        tr.sync_block()
        return tr, losses, net(nd.array(X)).asnumpy()

    tr_z, loss_z, out_z = run(True)
    tr_r, loss_r, out_r = run(False)
    np.testing.assert_allclose(loss_z, loss_r, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(out_z, out_r, rtol=1e-3, atol=1e-4)
    # the dense-layer moment buffers must actually be sharded over dp
    sharded = 0
    for leaf in jax.tree_util.tree_leaves(tr_z._opt_state):
        shard = leaf.addressable_shards[0].data
        if shard.size < leaf.size:
            assert shard.size * 8 == leaf.size  # split 8-way
            sharded += 1
    assert sharded >= 2, "no optimizer-state leaf was dp-sharded"


def test_zero_requires_mesh():
    net = _clone_net(5)
    with pytest.raises(mx.base.MXNetError):
        parallel.FusedTrainer(net, loss="softmax_ce", zero=True)


# ---- pipeline parallelism (GPipe over pp axis) ----------------------------

def _mlp_for_pipeline(seed):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(16, activation="relu"),
            nn.Dense(16, activation="relu"), nn.Dense(8))
    net.initialize()
    return net


def test_pipeline_trainer_loss_parity():
    """PipelineTrainer (pp=2, M=4 microbatches) must track single-device
    full-batch training step for step: same loss trajectory."""
    mesh = _mesh_or_skip({"pp": 2})
    np.random.seed(1)
    X = np.random.rand(16, 12).astype(np.float32)
    Y = np.random.randint(0, 8, 16).astype(np.int32)

    net_p = _mlp_for_pipeline(7)
    net_s = _mlp_for_pipeline(7)  # identical init
    pipe = parallel.PipelineTrainer(
        net_p, loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        mesh=mesh, num_microbatches=4)
    ref = parallel.FusedTrainer(
        net_s, loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9})

    losses_p, losses_r = [], []
    for _ in range(5):
        losses_p.append(float(pipe.step(X, Y).asscalar()))
        losses_r.append(float(ref.step(X, Y).asscalar()))
    assert_almost_equal(np.array(losses_p), np.array(losses_r),
                        rtol=1e-3, atol=1e-4)
    assert losses_p[-1] < losses_p[0], "pipeline training must reduce loss"


def test_pipeline_trainer_dp_pp():
    """dp x pp mesh: batch sharded over dp inside each microbatch."""
    mesh = _mesh_or_skip({"dp": 2, "pp": 2})
    np.random.seed(2)
    X = np.random.rand(16, 12).astype(np.float32)
    Y = np.random.randint(0, 8, 16).astype(np.int32)
    net_p = _mlp_for_pipeline(9)
    net_s = _mlp_for_pipeline(9)
    pipe = parallel.PipelineTrainer(
        net_p, loss="softmax_ce", optimizer="adam",
        optimizer_params={"learning_rate": 1e-2},
        mesh=mesh, num_microbatches=4)
    ref = parallel.FusedTrainer(
        net_s, loss="softmax_ce", optimizer="adam",
        optimizer_params={"learning_rate": 1e-2})
    for _ in range(3):
        lp = float(pipe.step(X, Y).asscalar())
        lr_ = float(ref.step(X, Y).asscalar())
        assert abs(lp - lr_) < 1e-3 * max(1.0, abs(lr_))


def test_pipeline_sync_block_roundtrip():
    """sync_block writes trained stage weights back into the Gluon block;
    eager forward then matches the pipeline's learned params."""
    mesh = _mesh_or_skip({"pp": 2})
    np.random.seed(3)
    X = np.random.rand(8, 12).astype(np.float32)
    Y = np.random.randint(0, 8, 8).astype(np.int32)
    net = _mlp_for_pipeline(11)
    net(nd.array(X))  # resolve deferred shapes before snapshotting
    before = {n: p.data().asnumpy().copy()
              for n, p in net.collect_params().items()}
    pipe = parallel.PipelineTrainer(
        net, loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.5},
        mesh=mesh, num_microbatches=2)
    for _ in range(3):
        pipe.step(X, Y)
    pipe.sync_block()
    changed = any(
        not np.allclose(before[n], p.data().asnumpy())
        for n, p in net.collect_params().items())
    assert changed, "sync_block must write back updated weights"


def test_pipeline_rejects_batchnorm():
    mesh = _mesh_or_skip({"pp": 2})
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.BatchNorm(), nn.Dense(8))
    net.initialize()
    pipe = parallel.PipelineTrainer(net, loss="softmax_ce", mesh=mesh,
                                    num_microbatches=2)
    X = np.random.rand(8, 12).astype(np.float32)
    Y = np.random.randint(0, 8, 8).astype(np.int32)
    with pytest.raises(mx.MXNetError):
        pipe.step(X, Y)


def test_pipeline_partition_skewed_sizes():
    """Back-/front-heavy layer weights must still split into S non-empty,
    max-weight-minimizing contiguous stages (regression: quantile sweep
    produced empty stages)."""
    from mxnet_tpu.parallel.pipeline import _partition_stages

    class FakeChild:
        def __init__(self, n):
            self._n = n

        def collect_params(self):
            class FakeParam:
                def __init__(self, n):
                    self.shape = (n,)
            return {"w": FakeParam(self._n)}

    back_heavy = [FakeChild(4), FakeChild(4), FakeChild(4), FakeChild(512)]
    stages = _partition_stages(back_heavy, 2)
    assert [len(s) for s in stages] == [3, 1]
    front_heavy = [FakeChild(100), FakeChild(1), FakeChild(1)]
    stages = _partition_stages(front_heavy, 3)
    assert [len(s) for s in stages] == [1, 1, 1]


# ---- expert parallelism (MoE over ep axis) --------------------------------

def test_moe_dense_forward_shapes_and_routing():
    mx.random.seed(5)
    moe = nn.MoE(num_experts=4, hidden_size=16, units=8, top_k=2)
    moe.initialize()
    x = nd.array(np.random.RandomState(0).rand(10, 8).astype(np.float32))
    y = moe(x)
    assert y.shape == (10, 8)
    assert np.all(np.isfinite(y.asnumpy()))
    # top_k=E means full soft mixture: output must differ from top_k=1
    mx.random.seed(5)
    moe1 = nn.MoE(num_experts=4, hidden_size=16, units=8, top_k=1)
    moe1.initialize()
    y1 = moe1(x)
    assert not np.allclose(y.asnumpy(), y1.asnumpy())


def test_moe_apply_matches_dense_gather():
    """Expert-parallel all_to_all dispatch == single-device dense-gather
    reference when capacity is ample (no token drops)."""
    mesh = _mesh_or_skip({"ep": 4})
    mx.random.seed(6)
    moe = nn.MoE(num_experts=8, hidden_size=16, units=8, top_k=2)
    moe.initialize()
    x = np.random.RandomState(1).rand(16, 8).astype(np.float32)
    ref = moe(nd.array(x)).asnumpy()
    out = parallel.moe_apply(moe, nd.array(x), mesh=mesh, axis_name="ep",
                             capacity_factor=float(8))  # capacity >= T_loc
    assert_almost_equal(out.asnumpy(), ref, rtol=1e-4, atol=1e-5)


def test_moe_apply_aux_loss_and_capacity_drop():
    mesh = _mesh_or_skip({"ep": 2})
    mx.random.seed(7)
    moe = nn.MoE(num_experts=4, hidden_size=8, units=4, top_k=1)
    moe.initialize()
    x = np.random.RandomState(2).rand(8, 4).astype(np.float32)
    out, aux = parallel.moe_apply(moe, nd.array(x), mesh=mesh,
                                  axis_name="ep", capacity_factor=4.0,
                                  return_aux=True)
    a = float(aux.asscalar())
    # balanced routing gives aux ~= 1; any routing is >= 1 - slack
    assert np.isfinite(a) and a > 0.5, a
    # tiny capacity drops tokens -> output rows can be zero but finite
    out2 = parallel.moe_apply(moe, nd.array(x), mesh=mesh, axis_name="ep",
                              capacity_factor=0.25)
    assert np.all(np.isfinite(out2.asnumpy()))


def test_zero_warns_when_nothing_shards():
    import warnings

    mesh = _mesh_or_skip({"dp": 8})
    mx.random.seed(13)
    net = nn.HybridSequential()
    net.add(nn.Dense(3, in_units=5))  # no dim divisible by 8
    net.initialize()
    tr = parallel.FusedTrainer(net, loss="softmax_ce", optimizer="adam",
                               mesh=mesh, zero=True)
    X = np.random.rand(8, 5).astype(np.float32)
    Y = np.random.randint(0, 3, 8).astype(np.int32)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        tr.step(X, Y)
    assert any("zero=True had no effect" in str(x.message) for x in w)


def test_pipeline_transformer_stack():
    """GPipe over transformer encoder cells: rank-3 (B,T,C) activations
    flow through the padded boundary buffers; loss decreases."""
    mesh = _mesh_or_skip({"pp": 2})
    mx.random.seed(0)
    net = nn.HybridSequential()
    for _ in range(4):
        net.add(nn.TransformerEncoderCell(16, 32, 4, dropout=0.0))
    net.add(nn.Dense(8, flatten=False, in_units=16))
    net.initialize()
    tr = parallel.PipelineTrainer(
        net, loss_fn=lambda outs, y: ((outs[0] - y) ** 2).mean(),
        optimizer="adam", optimizer_params={"learning_rate": 1e-3},
        mesh=mesh, num_microbatches=2)
    rs = np.random.RandomState(0)
    X = rs.rand(4, 6, 16).astype(np.float32)
    Y = rs.rand(4, 6, 8).astype(np.float32)
    losses = [float(tr.step(X, Y).asscalar()) for _ in range(6)]
    assert losses[-1] < losses[0], losses


def test_make_hybrid_mesh_dcn_ici():
    """Multi-slice mesh helper: outer DCN axes x inner ICI axes, and a
    two-tier psum (ICI reduce inside, one DCN hop outside) matches a flat
    global sum."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = parallel.make_hybrid_mesh({"dp_dcn": 2}, {"dp": 4})
    assert mesh.axis_names == ("dp_dcn", "dp")
    assert mesh.shape == {"dp_dcn": 2, "dp": 4}

    x = jnp.arange(16.0).reshape(8, 2)
    xs = jax.device_put(x, NamedSharding(mesh, P(("dp_dcn", "dp"))))

    def tier_sum(v):
        inner = jax.lax.psum(v, "dp")     # ICI tier
        return jax.lax.psum(inner, "dp_dcn")  # single DCN hop

    from jax.experimental.shard_map import shard_map

    got = shard_map(tier_sum, mesh=mesh,
                    in_specs=P(("dp_dcn", "dp")),
                    out_specs=P())(xs)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(x).reshape(8, 1, 2).sum(0),
                               rtol=1e-6)


def test_make_hybrid_mesh_too_many_devices():
    with pytest.raises(Exception):
        parallel.make_hybrid_mesh({"a": 4}, {"b": 4})


def test_fused_trainer_on_hybrid_mesh():
    """Two-tier data parallelism: batch sharded over (dp_dcn, dp) — grads
    reduce inside each ICI slice then once over DCN; loss matches the flat
    dp=8 mesh run exactly."""
    import numpy as np

    from mxnet_tpu import nd
    from mxnet_tpu.gluon import nn

    def build():
        import mxnet_tpu as mx

        mx.random.seed(11)
        net = nn.Dense(4, in_units=6)
        net.initialize()
        return net

    rs = np.random.RandomState(0)
    x = rs.randn(16, 6).astype(np.float32)
    y = rs.randint(0, 4, 16).astype(np.int32)

    def run(mesh, batch_axes):
        net = build()
        tr = parallel.FusedTrainer(
            net, loss="softmax_ce", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1}, mesh=mesh,
            batch_axes=batch_axes)
        losses = [float(tr.step(x, y).asnumpy()) for _ in range(3)]
        tr.sync_block()
        return losses, net.weight.data().asnumpy()

    flat_losses, flat_w = run(parallel.make_mesh({"dp": 8}), ("dp",))
    hy_losses, hy_w = run(
        parallel.make_hybrid_mesh({"dp_dcn": 2}, {"dp": 4}),
        ("dp_dcn", "dp"))
    np.testing.assert_allclose(hy_losses, flat_losses, rtol=1e-5)
    np.testing.assert_allclose(hy_w, flat_w, rtol=1e-5)


def test_grad_accum_with_zero_and_tp():
    """grad_accum composes with ZeRO-1 state sharding AND a dp x tp mesh:
    parity vs the accum=1 replicated run (round-2 verdict called this
    combination untested)."""
    import numpy as np

    from mxnet_tpu.gluon import nn

    def build():
        import mxnet_tpu as mx

        mx.random.seed(13)
        net = nn.Dense(8, in_units=8)
        net.initialize()
        return net

    rs = np.random.RandomState(0)
    x = rs.randn(16, 8).astype(np.float32)
    y = rs.randint(0, 8, 16).astype(np.int32)

    def run(accum, zero):
        mesh = parallel.make_mesh({"dp": 4, "tp": 2})
        tr = parallel.FusedTrainer(
            net := build(), loss="softmax_ce", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1}, mesh=mesh,
            grad_accum=accum, zero=zero)
        losses = [float(tr.step(x, y).asnumpy()) for _ in range(3)]
        tr.sync_block()
        return losses, net.weight.data().asnumpy()

    base_losses, base_w = run(accum=1, zero=False)
    acc_losses, acc_w = run(accum=4, zero=True)
    np.testing.assert_allclose(acc_losses, base_losses, rtol=1e-4)
    np.testing.assert_allclose(acc_w, base_w, rtol=1e-4, atol=1e-5)


def test_fused_trainer_lr_scheduler():
    """optimizer_params['lr_scheduler'] drives the compiled step without
    recompiles (reference Trainer contract): a zero-LR schedule freezes
    the weights, a two-phase FactorScheduler matches two fixed-LR runs."""
    from mxnet_tpu.gluon import nn

    def build():
        import mxnet_tpu as mx

        mx.random.seed(17)
        net = nn.Dense(4, in_units=4)
        net.initialize()
        return net

    rs = np.random.RandomState(0)
    x = rs.randn(8, 4).astype(np.float32)
    y = rs.randint(0, 4, 8).astype(np.int32)

    class ZeroLR:
        def __call__(self, num_update):
            return 0.0

    net = build()
    w0 = net.weight.data().asnumpy().copy()
    tr = parallel.FusedTrainer(
        net, loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.5, "momentum": 0.0,
                          "lr_scheduler": ZeroLR()})
    for _ in range(2):
        tr.step(x, y)
    tr.sync_block()
    np.testing.assert_allclose(net.weight.data().asnumpy(), w0, rtol=1e-6)

    # two-phase schedule: 2 steps at 0.2, 2 at 0.1 — must match two
    # fixed-LR trainers run back to back on the same weights
    class TwoPhase:
        def __call__(self, num_update):
            # num_update starts at 1 (reference phase)
            return 0.2 if num_update <= 2 else 0.1

    net_s = build()
    tr_s = parallel.FusedTrainer(
        net_s, loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.2, "momentum": 0.0,
                          "lr_scheduler": TwoPhase()})
    for _ in range(4):
        tr_s.step(x, y)
    tr_s.sync_block()

    net_m = build()
    tr_m1 = parallel.FusedTrainer(
        net_m, loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.2, "momentum": 0.0})
    tr_m1.step(x, y); tr_m1.step(x, y)
    tr_m1.sync_block()
    tr_m2 = parallel.FusedTrainer(
        net_m, loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.0})
    tr_m2.step(x, y); tr_m2.step(x, y)
    tr_m2.sync_block()
    np.testing.assert_allclose(net_s.weight.data().asnumpy(),
                               net_m.weight.data().asnumpy(), rtol=1e-5)


def test_pipeline_trainer_lr_scheduler():
    """PipelineTrainer honors lr_scheduler like FusedTrainer: zero LR
    freezes the stage weights."""
    from mxnet_tpu.gluon import nn

    mesh = _mesh_or_skip({"pp": 2, "dp": 4})
    mx.random.seed(19)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=8, activation="relu"),
            nn.Dense(4, in_units=8))
    net.initialize()

    class ZeroLR:
        def __call__(self, num_update):
            return 0.0

    tr = parallel.PipelineTrainer(
        net, mesh=mesh, num_microbatches=4, loss="softmax_ce",
        optimizer="sgd",
        optimizer_params={"learning_rate": 0.5, "momentum": 0.0,
                          "lr_scheduler": ZeroLR()})
    rs = np.random.RandomState(0)
    x = rs.randn(16, 8).astype(np.float32)
    y = rs.randint(0, 4, 16).astype(np.int32)
    tr.step(x, y)
    tr.step(x, y)
    tr.sync_block()
    w0 = net[0].weight.data().asnumpy()
    mx.random.seed(19)
    net2 = nn.HybridSequential()
    net2.add(nn.Dense(8, in_units=8, activation="relu"),
             nn.Dense(4, in_units=8))
    net2.initialize()
    np.testing.assert_allclose(w0, net2[0].weight.data().asnumpy(),
                               rtol=1e-6)


def test_ring_attention_flash_impl_matches_dense():
    """impl='flash' (Pallas kernel per ring hop, lse-merged partials) must
    match impl='dense' and full attention, causal and not, incl. grads."""
    mesh = _mesh_or_skip({"sp": 8})
    import jax
    import jax.numpy as jnp

    rs = np.random.RandomState(0)
    B, H, T, D = 1, 2, 64, 16
    q = jnp.asarray(rs.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rs.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rs.randn(B, H, T, D).astype(np.float32))
    for causal in (False, True):
        dense = parallel.ring_attention(q, k, v, mesh=mesh, causal=causal)
        flash = parallel.ring_attention(q, k, v, mesh=mesh, causal=causal,
                                        impl="flash", block=8)
        np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                                   rtol=2e-4, atol=2e-5)
        # full-sequence oracle
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        if causal:
            m = jnp.tril(jnp.ones((T, T), bool))
            s = jnp.where(m, s, -1e30)
        want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        np.testing.assert_allclose(np.asarray(flash), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

        g = jnp.asarray(rs.randn(B, H, T, D).astype(np.float32))
        # ALL THREE grads: dk/dv exercise the dlse-folded backward and
        # the cotangent routing through the reversed ppermute ring
        gf = jax.grad(lambda q_, k_, v_: (parallel.ring_attention(
            q_, k_, v_, mesh=mesh, causal=causal, impl="flash", block=8)
            * g).sum(), argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(lambda q_, k_, v_: (parallel.ring_attention(
            q_, k_, v_, mesh=mesh, causal=causal) * g).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4,
                                       err_msg="d" + name)


def test_ring_attention_flash_under_mesh_rows():
    """An engine's ``mesh_rows`` declaration leaves alone a kernel that
    already runs inside a shard_map (the ring's): the arrays there are one
    device's, and a second shard_map over the same mesh cannot nest."""
    from mxnet_tpu.ops import pallas_attention as pa

    mesh = _mesh_or_skip({"dp": 2, "sp": 4})
    rs = np.random.RandomState(1)
    q, k, v, g = (jnp.asarray(rs.randn(2, 2, 64, 16).astype(np.float32))
                  for _ in range(4))

    def fwd_bwd(q, k, v):
        out, vjp = jax.vjp(lambda q, k, v: parallel.ring_attention(
            q, k, v, mesh=mesh, causal=True, impl="flash", block=8),
            q, k, v)
        return (out,) + vjp(g)

    want = jax.jit(fwd_bwd)(q, k, v)
    with pa.mesh_rows(mesh, ("dp",)):
        # another function object: jit must trace again, not reuse `want`'s
        got = jax.jit(lambda *qkv: fwd_bwd(*qkv))(q, k, v)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


class _RingNet(gluon.HybridBlock):
    """Sequence-parallel attention called "directly inside a pjit'd step"
    (gluon/nn/transformer.py): 2 heads x 16 over the ``sp`` ring."""

    def __init__(self, mesh, impl):
        super().__init__()
        self._mesh, self._impl = mesh, impl
        self.qkv = nn.Dense(96, in_units=16, flatten=False)
        self.head = nn.Dense(4, in_units=32)

    def forward(self, x):
        B, T, _ = x.shape
        q, k, v = self.qkv(x)._data.reshape(B, T, 3, 2, 16).transpose(
            2, 0, 3, 1, 4)
        out = parallel.ring_attention(q, k, v, mesh=self._mesh,
                                      impl=self._impl, block=8)
        return self.head(nd.NDArray(
            out.transpose(0, 2, 1, 3).reshape(B, T, 32).mean(1)))


def test_fused_trainer_ring_flash_on_dp_sp_mesh():
    """FusedTrainer on a {dp, sp} mesh declares ``mesh_rows`` around its
    trace; a block whose attention is the ring of flash kernels still
    lowers there and trains like the ring of dense blocks."""
    mesh = _mesh_or_skip({"dp": 2, "sp": 4})
    rs = np.random.RandomState(2)
    X = rs.randn(4, 64, 16).astype(np.float32)
    Y = rs.randint(0, 4, 4).astype(np.int32)
    losses = {}
    for impl in ("dense", "flash"):
        mx.random.seed(7)
        net = _RingNet(mesh, impl)
        net.initialize()
        trainer = parallel.FusedTrainer(
            net, loss="softmax_ce", optimizer="sgd",
            optimizer_params={"learning_rate": 0.5}, mesh=mesh)
        losses[impl] = [float(trainer.step(X, Y).asnumpy())
                        for _ in range(3)]
    assert losses["flash"][-1] < losses["flash"][0]
    np.testing.assert_allclose(losses["flash"], losses["dense"], rtol=1e-4)


# ---------------------------------------------------------------------------
# 1F1B pipeline (VERDICT r4 item 5): per-stage programs, no lax.switch
# ---------------------------------------------------------------------------

def test_1f1b_schedule_validity_and_memory_bound():
    """The built schedule respects data deps; peak in-flight activations
    per stage are bounded by min(M, S-s) (1F1B) vs M (GPipe)."""
    from mxnet_tpu.parallel.pipeline_1f1b import (
        build_1f1b_schedule, schedule_stats)

    S, M = 4, 16     # M = 4*S, the VERDICT config
    order = build_1f1b_schedule(S, M)
    assert len(order) == 2 * S * M
    seen = set()
    for s, kind, m in order:
        if kind == "F":
            assert s == 0 or ("F", s - 1, m) in seen
        else:
            assert ("F", s, m) in seen
            assert s == S - 1 or ("B", s + 1, m) in seen
        seen.add((kind, s, m))

    st_1f1b = schedule_stats(S, M, "1f1b")
    st_gpipe = schedule_stats(S, M, "gpipe")
    for s in range(S):
        assert st_1f1b["peak_inflight"][s] <= min(M, S - s), \
            st_1f1b["peak_inflight"]
        assert st_gpipe["peak_inflight"][s] == M
    # bubble: both schedules idle (S-1) fill + (S-1) drain slots; at
    # M=4S the fraction stays below the analytic (S-1)/(M+S-1) with
    # F=1,B=2 tick costs
    assert st_1f1b["bubble_fraction"] <= st_gpipe["bubble_fraction"] + 1e-9
    assert st_1f1b["bubble_fraction"] < (S - 1) / (M + S - 1) + 1e-9, \
        st_1f1b["bubble_fraction"]


def test_1f1b_trainer_matches_fused_s4():
    """S=4 1F1B run matches FusedTrainer loss trajectory (the VERDICT
    done-bar) — per-stage programs, natural shapes, remat backward."""
    mesh = _mesh_or_skip({"pp": 4})
    np.random.seed(4)
    X = np.random.rand(16, 12).astype(np.float32)
    Y = np.random.randint(0, 8, 16).astype(np.int32)
    net_p = _mlp_for_pipeline(21)
    net_s = _mlp_for_pipeline(21)
    pipe = parallel.PipelineTrainer(
        net_p, loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        mesh=mesh, num_microbatches=8, schedule="1f1b")
    ref = parallel.FusedTrainer(
        net_s, loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    losses_p, losses_r = [], []
    for _ in range(5):
        losses_p.append(float(pipe.step(X, Y).asscalar()))
        losses_r.append(float(ref.step(X, Y).asscalar()))
    assert_almost_equal(np.array(losses_p), np.array(losses_r),
                        rtol=1e-3, atol=1e-4)
    assert losses_p[-1] < losses_p[0]
    # runtime memory bound observed, not just scheduled
    S, M = 4, 8
    for s, peak in enumerate(pipe.last_peak_inflight):
        assert peak <= min(M, S - s), pipe.last_peak_inflight


def test_1f1b_dp_pp_and_sync_block():
    """pp x dp 1F1B: batch sharded over dp; sync_block writes stage
    params back."""
    mesh = _mesh_or_skip({"pp": 2, "dp": 2})
    np.random.seed(5)
    X = np.random.rand(16, 12).astype(np.float32)
    Y = np.random.randint(0, 8, 16).astype(np.int32)
    net_p = _mlp_for_pipeline(23)
    net_s = _mlp_for_pipeline(23)
    pipe = parallel.PipelineTrainer(
        net_p, loss="softmax_ce", optimizer="adam",
        optimizer_params={"learning_rate": 1e-2},
        mesh=mesh, num_microbatches=4, schedule="1f1b")
    ref = parallel.FusedTrainer(
        net_s, loss="softmax_ce", optimizer="adam",
        optimizer_params={"learning_rate": 1e-2})
    for _ in range(3):
        lp = float(pipe.step(X, Y).asscalar())
        lr_ = float(ref.step(X, Y).asscalar())
        assert abs(lp - lr_) < 1e-3 * max(1.0, abs(lr_))
    pipe.sync_block()
    ref.sync_block()
    # logits drift apart at fp-accumulation level after 3 adam steps;
    # the LOSS the two models achieve must agree
    def eager_loss(net):
        out = net(nd.array(X)).asnumpy()
        logp = out - np.log(np.exp(out - out.max(1, keepdims=True))
                            .sum(1, keepdims=True)) - out.max(
                                1, keepdims=True)
        return -logp[np.arange(len(Y)), Y].mean()

    assert abs(eager_loss(net_p) - eager_loss(net_s)) < 5e-3


def test_1f1b_state_dict_roundtrip():
    mesh = _mesh_or_skip({"pp": 2})
    np.random.seed(6)
    X = np.random.rand(8, 12).astype(np.float32)
    Y = np.random.randint(0, 8, 8).astype(np.int32)
    net_a = _mlp_for_pipeline(31)
    net_b = _mlp_for_pipeline(31)
    a = parallel.PipelineTrainer(
        net_a, loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.1},
        mesh=mesh, num_microbatches=2, schedule="1f1b")
    b = parallel.PipelineTrainer(
        net_b, loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.1},
        mesh=mesh, num_microbatches=2, schedule="1f1b")
    for _ in range(2):
        a.step(X, Y)
    state = a.state_dict()
    b.load_state_dict(state)   # parked (pre-setup), applied at first step
    la = float(a.step(X, Y).asscalar())
    lb = float(b.step(X, Y).asscalar())
    assert abs(la - lb) < 1e-5 * max(1.0, abs(la))


def test_interleaved_schedule_cuts_bubble():
    """Megatron-style interleaved 1F1B: bubble shrinks ~1/V vs plain
    1F1B at the same microbatch count."""
    from mxnet_tpu.parallel.pipeline_1f1b import (
        build_interleaved_schedule, interleaved_stats, schedule_stats)

    S, M = 4, 16
    base = schedule_stats(S, M, "1f1b")["bubble_fraction"]
    for V in (2, 4):
        order = build_interleaved_schedule(S, V, M)
        assert len(order) == 2 * S * V * M
        seen = set()
        C = S * V
        for c, kind, m in order:
            if kind == "F":
                assert c == 0 or ("F", c - 1, m) in seen
            else:
                assert ("F", c, m) in seen
                assert c == C - 1 or ("B", c + 1, m) in seen
            seen.add((kind, c, m))
        bub = interleaved_stats(S, V, M)["bubble_fraction"]
        assert bub < base / V * 1.3, (V, bub, base)
    with pytest.raises(mx.MXNetError):
        build_interleaved_schedule(4, 2, 6)   # M % S != 0


def test_interleaved_trainer_matches_fused():
    """pp=2, V=2 (4 chunks over 4 layers): loss parity with
    FusedTrainer."""
    mesh = _mesh_or_skip({"pp": 2})
    np.random.seed(8)
    X = np.random.rand(16, 12).astype(np.float32)
    Y = np.random.randint(0, 8, 16).astype(np.int32)
    net_p = _mlp_for_pipeline(41)
    net_s = _mlp_for_pipeline(41)
    pipe = parallel.PipelineTrainer(
        net_p, loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        mesh=mesh, num_microbatches=4, schedule="1f1b",
        num_virtual_stages=2)
    ref = parallel.FusedTrainer(
        net_s, loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    losses_p, losses_r = [], []
    for _ in range(4):
        losses_p.append(float(pipe.step(X, Y).asscalar()))
        losses_r.append(float(ref.step(X, Y).asscalar()))
    assert_almost_equal(np.array(losses_p), np.array(losses_r),
                        rtol=1e-3, atol=1e-4)
    assert losses_p[-1] < losses_p[0]
    # 4 chunks ran (peak tracked per chunk)
    assert len(pipe.last_peak_inflight) == 4


def test_1f1b_bf16_mixed_precision():
    """dtype='bfloat16' on the 1F1B engine: f32 master params, bf16
    stage compute; boundary activations/cotangents ride bf16; loss
    tracks the f32 run loosely and training still converges."""
    mesh = _mesh_or_skip({"pp": 2})
    np.random.seed(9)
    X = np.random.rand(16, 12).astype(np.float32)
    Y = np.random.randint(0, 8, 16).astype(np.int32)
    pipe16 = parallel.PipelineTrainer(
        _mlp_for_pipeline(51), loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        mesh=mesh, num_microbatches=4, schedule="1f1b",
        dtype="bfloat16")
    pipe32 = parallel.PipelineTrainer(
        _mlp_for_pipeline(51), loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        mesh=mesh, num_microbatches=4, schedule="1f1b")
    l16, l32 = [], []
    for _ in range(5):
        l16.append(float(pipe16.step(X, Y).asscalar()))
        l32.append(float(pipe32.step(X, Y).asscalar()))
    assert l16[-1] < l16[0], l16
    # loose cross-precision gate: bf16 rounding compounds through
    # momentum steps and is backend-dependent (deflake precedent a92c1c8)
    assert abs(l16[-1] - l32[-1]) < 0.1 * max(1.0, abs(l32[-1])), \
        (l16, l32)
    # master params stay f32
    for p in pipe16.params:
        for v in p.values():
            assert str(v.dtype) == "float32"
    # gpipe still rejects bf16 (SPMD engine is f32-only by design)
    with pytest.raises(mx.MXNetError):
        parallel.PipelineTrainer(
            _mlp_for_pipeline(52), loss="softmax_ce", mesh=mesh,
            num_microbatches=4, dtype="bfloat16")
