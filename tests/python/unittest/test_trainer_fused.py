"""Multi-tensor fused optimizer apply + bucketed pushpull (ISSUE 5).

Covers: fused-vs-eager numerical parity per optimizer, group
partitioning (dtype / lr_mult / stype splits), pushpull_all bucket
ordering + determinism + count bound, ZeRO fused parity, fallback
triggers (row_sparse, kill switch, non-fusable optimizers), buffer
donation (no stale-weight aliasing), and the O(groups)-programs-per-
step acceptance criterion via telemetry counters.
"""
import math
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.kvstore import collective
from mxnet_tpu.kvstore.base import KVStoreBase
from mxnet_tpu.optimizer import multi_tensor

# the fused program replays the SAME jnp ops as the eager path with
# bit-identical hyperparameter scalars; the only permitted divergence
# is XLA contracting mul+add chains into FMAs inside the one fused
# program (excess precision), worth a few ulps
RTOL, ATOL = 1e-5, 1e-7


@pytest.fixture(autouse=True)
def _telemetry_on():
    was = telemetry.ENABLED
    telemetry.enable()
    yield
    if not was:
        telemetry.disable()


def _params(spec, grad_seed=3):
    """Build bare initialized Parameters from [(shape, dtype, kwargs)]
    with deterministic synthetic gradients already attached."""
    rs = np.random.RandomState(grad_seed)
    params = {}
    for k, (shape, dtype, kw) in enumerate(spec):
        p = gluon.Parameter(name="p%d" % k, shape=shape, dtype=dtype, **kw)
        p.initialize(init="xavier" if len(shape) > 1 else "zeros")
        g = rs.randn(*shape).astype("float32")
        p.grad()._data = nd.array(g).astype(dtype)._data
        params["p%d" % k] = p
    return params


def _weights(params):
    return {k: p.data().asnumpy().copy() for k, p in params.items()}


def _run(optname, opt_params, spec, steps=3, fused=True, seed=0,
         trainer_kwargs=None, lr_hook=None):
    mx.random.seed(seed)
    params = _params(spec)
    trainer = gluon.Trainer(params, optname, dict(opt_params),
                            **(trainer_kwargs or {}))
    env_before = os.environ.pop("MXNET_MULTI_TENSOR", None)
    if not fused:
        os.environ["MXNET_MULTI_TENSOR"] = "0"
    try:
        for s in range(steps):
            if lr_hook is not None:
                lr_hook(trainer, s)
            trainer.update(2)
    finally:
        os.environ.pop("MXNET_MULTI_TENSOR", None)
        if env_before is not None:
            os.environ["MXNET_MULTI_TENSOR"] = env_before
    return trainer, _weights(params)


_DENSE_SPEC = [((8, 4), "float32", {}), ((8,), "float32", {}),
               ((4, 8), "float32", {}), ((3, 3, 2), "float32", {})]


@pytest.mark.parametrize("optname,opt_params", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("sgd", {"learning_rate": 0.1}),
    ("nag", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 0.01, "wd": 1e-4}),
    ("adamw", {"learning_rate": 0.01, "wd": 0.01}),
    ("lamb", {"learning_rate": 0.01}),
    ("rmsprop", {"learning_rate": 0.01, "centered": True}),
    ("adagrad", {"learning_rate": 0.05}),
    ("ftrl", {"learning_rate": 0.1}),
    ("signum", {"learning_rate": 0.01}),
])
def test_fused_eager_parity(optname, opt_params):
    t_f, w_fused = _run(optname, opt_params, _DENSE_SPEC, fused=True)
    t_e, w_eager = _run(optname, opt_params, _DENSE_SPEC, fused=False)
    assert len(t_f._mt_groups) == 1
    assert len(t_e._mt_groups) == 0
    for k in w_fused:
        np.testing.assert_allclose(w_fused[k], w_eager[k],
                                   rtol=RTOL, atol=ATOL, err_msg=k)


def test_fused_parity_with_lr_scheduler_no_retrace():
    """Per-step scheduler lr flows through host-scalar slots: values
    match eager and the group compiles exactly once."""
    from mxnet_tpu.optimizer import lr_scheduler

    sched = {"learning_rate": 0.1,
             "lr_scheduler": lr_scheduler.FactorScheduler(step=1,
                                                          factor=0.7)}
    before = telemetry.value("trainer_fused_builds_total",
                             {"optimizer": "SGD"})
    t_f, w_fused = _run("sgd", dict(sched, momentum=0.9), _DENSE_SPEC,
                        steps=4, fused=True)
    builds = telemetry.value("trainer_fused_builds_total",
                             {"optimizer": "SGD"}) - before
    assert builds == 1, "scheduler lr caused per-step retraces"
    sched2 = {"learning_rate": 0.1,
              "lr_scheduler": lr_scheduler.FactorScheduler(step=1,
                                                           factor=0.7)}
    _, w_eager = _run("sgd", dict(sched2, momentum=0.9), _DENSE_SPEC,
                      steps=4, fused=False)
    for k in w_fused:
        np.testing.assert_allclose(w_fused[k], w_eager[k],
                                   rtol=RTOL, atol=ATOL)


def test_set_learning_rate_rebuilds_and_stays_correct():
    def hook(trainer, s):
        if s == 2:
            trainer.set_learning_rate(0.02)

    t_f, w_fused = _run("sgd", {"learning_rate": 0.1, "momentum": 0.9},
                        _DENSE_SPEC, steps=4, fused=True, lr_hook=hook)
    _, w_eager = _run("sgd", {"learning_rate": 0.1, "momentum": 0.9},
                      _DENSE_SPEC, steps=4, fused=False, lr_hook=hook)
    for k in w_fused:
        np.testing.assert_allclose(w_fused[k], w_eager[k],
                                   rtol=RTOL, atol=ATOL)


def test_multi_precision_fused_parity():
    spec = [((8, 4), "float16", {}), ((4,), "float16", {})]
    mp = {"learning_rate": 0.1, "momentum": 0.9, "multi_precision": True}
    t_f, w_fused = _run("sgd", mp, spec, fused=True)
    _, w_eager = _run("sgd", mp, spec, fused=False)
    assert len(t_f._mt_groups) == 1
    for k in w_fused:
        np.testing.assert_allclose(
            w_fused[k].astype("float32"), w_eager[k].astype("float32"),
            rtol=1e-2, atol=1e-3, err_msg=k)
    # the f32 master (state[0]) carries the real parity contract
    masters = [s[0].asnumpy() for s in t_f._states.values()]
    assert all(m.dtype == np.float32 for m in masters)


# ---------------------------------------------------------------------------
# group partitioning
# ---------------------------------------------------------------------------

def test_partition_splits_on_dtype_lr_and_stype():
    spec = [((4, 4), "float32", {}),
            ((4, 4), "float32", {}),
            ((4, 4), "float16", {}),                  # dtype split
            ((4, 4), "float32", {"lr_mult": 0.5}),    # lr split
            ((6, 4), "float32",                       # row_sparse: eager
             {"grad_stype": "row_sparse"})]
    mx.random.seed(0)
    params = _params(spec)
    trainer = gluon.Trainer(params, "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    trainer.update(2)
    table = multi_tensor.group_table(trainer)
    assert len(table) == 3, table
    assert sorted(r["params"] for r in table) == [1, 1, 2]
    # the row_sparse param took the eager path (its group never formed)
    assert sum(r["params"] for r in table) == 4


def test_partition_reasons():
    mx.random.seed(0)
    params = _params([((4, 4), "float32", {}),
                      ((6, 4), "float32", {"grad_stype": "row_sparse"})])
    trainer = gluon.Trainer(params, "sgd", {"learning_rate": 0.1})
    trainer._init_kvstore()
    for i, p in enumerate(trainer._params):
        trainer._maybe_init_states(i, p)
    items = [(i, p, p.grad()) for i, p in enumerate(trainer._params)]
    groups, eager = multi_tensor.partition(trainer, items)
    assert len(groups) == 1
    assert [(i, reason) for i, _, _, reason in eager] == []
    # convert grad 1 to an actual RowSparseNDArray like _update does
    from mxnet_tpu.ndarray.sparse import row_sparse_from_dense

    items[1] = (1, trainer._params[1],
                row_sparse_from_dense(trainer._params[1].grad()))
    groups, eager = multi_tensor.partition(trainer, items)
    assert len(groups) == 1 and len(eager) == 1
    assert eager[0][3] == "row_sparse"


def test_fallback_kill_switch_and_nonfusable():
    before = telemetry.value("trainer_eager_updates_total",
                             {"reason": "disabled"})
    _run("sgd", {"learning_rate": 0.1}, _DENSE_SPEC, steps=1,
         fused=False)
    assert telemetry.value("trainer_eager_updates_total",
                           {"reason": "disabled"}) - before == \
        len(_DENSE_SPEC)
    # nadam mutates python state per step; sgld draws RNG at trace time
    for optname in ("nadam", "sgld"):
        before = telemetry.value("trainer_eager_updates_total",
                                 {"reason": "optimizer"})
        t, _ = _run(optname, {"learning_rate": 0.01}, _DENSE_SPEC,
                    steps=1, fused=True)
        assert len(t._mt_groups) == 0
        assert telemetry.value("trainer_eager_updates_total",
                               {"reason": "optimizer"}) - before == \
            len(_DENSE_SPEC)


def test_custom_subclass_not_fused_unless_registered():
    from mxnet_tpu.optimizer import SGD

    class MySGD(SGD):
        def update(self, index, weight, grad, state):
            super().update(index, weight, grad, state)

    mx.random.seed(0)
    params = _params(_DENSE_SPEC)
    trainer = gluon.Trainer(params, MySGD(learning_rate=0.1))
    trainer.update(2)
    assert len(trainer._mt_groups) == 0
    assert not multi_tensor.is_fusable(trainer._optimizer)


# ---------------------------------------------------------------------------
# donation / aliasing
# ---------------------------------------------------------------------------

def test_donation_no_stale_weight_aliasing():
    mx.random.seed(0)
    params = _params(_DENSE_SPEC)
    trainer = gluon.Trainer(params, "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    handles = {k: p.data() for k, p in params.items()}
    before = _weights(params)
    grads = {k: p.grad().asnumpy().copy() for k, p in params.items()}
    trainer.update(2)
    for k, p in params.items():
        # the SAME handle object observes the new value (in-place update
        # contract), and the value actually moved
        assert handles[k] is p.data()
        now = p.data().asnumpy()
        assert not np.array_equal(now, before[k]), k
        np.testing.assert_array_equal(handles[k].asnumpy(), now)
        # grads are NOT donated: still readable and unchanged
        np.testing.assert_array_equal(p.grad().asnumpy(), grads[k])
    trainer.update(2)  # a second step over donated buffers still works
    state = trainer._states[0]
    mom = state.asnumpy() if not isinstance(state, tuple) else None
    if mom is not None:
        assert np.abs(mom).max() > 0


# ---------------------------------------------------------------------------
# pushpull_all + bucket planning
# ---------------------------------------------------------------------------

def test_plan_buckets_ordering_and_bound():
    kib = 1024
    sizes = [(300 * kib, "float32")] * 10
    plan = collective.plan_buckets(sizes, bucket_bytes=1024 * kib)
    # order-preserving: flattened plan is exactly 0..9
    assert [i for b in plan for i in b] == list(range(10))
    total = sum(s for s, _ in sizes)
    assert len(plan) <= math.ceil(total / (1024.0 * kib))
    # deterministic
    assert plan == collective.plan_buckets(sizes,
                                           bucket_bytes=1024 * kib)
    # per-bucket fill reaches the bound except possibly the tail
    for b in plan[:-1]:
        assert sum(sizes[i][0] for i in b) >= 1024 * kib


def test_plan_buckets_dtype_splits_and_oversize():
    kib = 1024
    sizes = [(10 * kib, "float32"), (10 * kib, "bfloat16"),
             (5000 * kib, "float32"), (10 * kib, "float32")]
    plan = collective.plan_buckets(sizes, bucket_bytes=1024 * kib)
    # dtype switch forces a flush; the oversize array closes its own
    # bucket immediately
    assert plan == [[0], [1], [2], [3]]
    one = collective.plan_buckets([(10, "float32")] * 3,
                                  bucket_bytes=1 << 20)
    assert one == [[0, 1, 2]]


def test_plan_buckets_reads_the_environment_at_each_call(monkeypatch):
    """``MXNET_KVSTORE_BUCKET_BYTES`` set after import is the size the
    next plan closes its buckets at: nothing caches the default."""
    sizes = [(1 << 20, "float32")] * 8
    monkeypatch.delenv("MXNET_KVSTORE_BUCKET_BYTES", raising=False)
    assert collective.default_bucket_bytes() == 4 << 20
    assert len(collective.plan_buckets(sizes)) == 2
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_BYTES", str(2 << 20))
    assert collective.default_bucket_bytes() == 2 << 20
    assert len(collective.plan_buckets(sizes)) == 4
    # an explicit size wins over the environment
    assert len(collective.plan_buckets(sizes, bucket_bytes=8 << 20)) == 1


def test_observe_bucket_fill_uses_plan_bucket_bytes():
    """The fill histogram must normalize against the plan's ACTUAL
    bucket size, not the env default."""
    telemetry.reset()
    # one 1 MiB bucket against a 1 MiB plan = fill 1.0 (not the 0.25
    # that normalizing against the 4 MiB env default would report)
    collective.observe_bucket_fill([1 << 20], bucket_bytes=1 << 20)
    tot = telemetry.totals()
    assert tot["allreduce_bucket_fill_count"] == 1
    assert abs(tot["allreduce_bucket_fill_sum"] - 1.0) < 1e-9


def test_observe_bucket_fill_env_not_cached(monkeypatch):
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_BYTES", str(1 << 20))
    assert collective.default_bucket_bytes() == 1 << 20
    telemetry.reset()
    collective.observe_bucket_fill([1 << 20])  # denom from env NOW
    tot = telemetry.totals()
    assert abs(tot["allreduce_bucket_fill_sum"] - 1.0) < 1e-9
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_BYTES", str(4 << 20))
    assert collective.default_bucket_bytes() == 4 << 20


def test_pushpull_all_local_store_and_trainer_wiring():
    mx.random.seed(0)
    params = _params(_DENSE_SPEC)
    trainer = gluon.Trainer(params, "sgd", {"learning_rate": 0.1},
                            kvstore="device")
    g0 = {k: p.grad().asnumpy().copy() for k, p in params.items()}
    trainer.step(2)   # _allreduce_grads -> pushpull_all -> update
    for k, p in params.items():
        # single worker: the all-reduced grad is the grad itself
        np.testing.assert_allclose(p.grad().asnumpy(), g0[k], rtol=1e-6)


def test_pushpull_all_base_default_loops_per_key():
    calls = []

    class ToyStore(KVStoreBase):
        def pushpull(self, key, value, out=None, priority=0):
            calls.append(key)

    ToyStore().pushpull_all([3, 1, 2], ["a", "b", "c"])
    assert calls == [3, 1, 2]


def test_collective_pushpull_all_single_process():
    kv = collective.CollectiveKVStore()
    vals = [nd.array(np.full((4,), float(i + 1), np.float32))
            for i in range(3)]
    outs = [nd.zeros((4,)) for _ in range(3)]
    kv.pushpull_all(list(range(3)), vals, out=outs)
    for i, o in enumerate(outs):
        np.testing.assert_allclose(o.asnumpy(), np.full((4,), i + 1.0))


# ---------------------------------------------------------------------------
# ZeRO-1 fused path
# ---------------------------------------------------------------------------

def test_zero_fused_parity_and_single_program():
    from mxnet_tpu import parallel

    mesh = parallel.make_mesh({"dp": 2})
    spec = [((8, 4), "float32", {}), ((4, 8), "float32", {}),
            ((8,), "float32", {})]

    before = telemetry.value("trainer_fused_apply_total",
                             {"optimizer": "Adam"})
    t_z, w_zero = _run("adam", {"learning_rate": 0.05}, spec, steps=3,
                       fused=True,
                       trainer_kwargs={"zero": True, "mesh": mesh})
    applies = telemetry.value("trainer_fused_apply_total",
                              {"optimizer": "Adam"}) - before
    assert len(t_z._mt_groups) == 1
    assert applies == 3, "expected ONE fused zero program per step"
    _, w_eager = _run("adam", {"learning_rate": 0.05}, spec, steps=3,
                      fused=False)
    for k in w_zero:
        np.testing.assert_allclose(w_zero[k], w_eager[k],
                                   rtol=RTOL, atol=ATOL, err_msg=k)
    # the ZeRO memory contract survives the fused path: at least one
    # state leaf stays dp-sharded
    import jax

    found = False
    for state in t_z._states.values():
        for leaf in jax.tree_util.tree_leaves(state):
            n_shards = len({s.device for s in
                            leaf._data.addressable_shards})
            if leaf._data.size >= 2 and n_shards > 1:
                found = True
    assert found, "no optimizer state leaf sharded over dp"


# ---------------------------------------------------------------------------
# acceptance: O(groups) programs per step on a >=50-param model
# ---------------------------------------------------------------------------

def test_acceptance_50_param_model_program_counts():
    mx.random.seed(0)
    net = nn.HybridSequential()
    for _ in range(25):
        net.add(nn.Dense(8, in_units=8))
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.01})
    assert len(trainer._params) >= 50
    x = nd.array(np.random.RandomState(0).rand(4, 8).astype(np.float32))

    def step():
        with autograd.record():
            loss = (net(x) ** 2).mean()
        loss.backward()
        trainer.step(4)

    step()  # builds
    n_groups = len(trainer._mt_groups)
    assert n_groups == 1
    apply_b = telemetry.value("trainer_fused_apply_total",
                              {"optimizer": "Adam"})
    build_b = telemetry.value("trainer_fused_builds_total",
                              {"optimizer": "Adam"})
    eager_b = telemetry.value("trainer_eager_updates_total")
    for _ in range(3):
        step()
    # O(groups) compiled update programs per step, zero retraces, zero
    # eager fallbacks
    assert telemetry.value("trainer_fused_apply_total",
                           {"optimizer": "Adam"}) - apply_b == \
        3 * n_groups
    assert telemetry.value("trainer_fused_builds_total",
                           {"optimizer": "Adam"}) - build_b == 0
    assert telemetry.value("trainer_eager_updates_total") - eager_b == 0
    assert telemetry.value("trainer_fused_groups") == n_groups
    # collective side: the bucket plan for ALL grads obeys the
    # ceil(total_bytes / bucket) bound
    grads = [(p.grad().size * p.grad().dtype.itemsize,
              str(p.grad().dtype)) for p in trainer._params]
    total = sum(n for n, _ in grads)
    plan = collective.plan_buckets(grads)
    assert len(plan) <= max(1, math.ceil(
        total / float(collective.default_bucket_bytes())))
    # fused-vs-eager parity on the same 50-param model
    w_fused = {k: p.data().asnumpy() for k, p in
               net.collect_params().items()}
    mx.random.seed(0)
    net2 = nn.HybridSequential()
    for _ in range(25):
        net2.add(nn.Dense(8, in_units=8))
    net2.initialize()
    trainer2 = gluon.Trainer(net2.collect_params(), "adam",
                             {"learning_rate": 0.01})
    os.environ["MXNET_MULTI_TENSOR"] = "0"
    try:
        for _ in range(4):
            with autograd.record():
                loss = (net2(x) ** 2).mean()
            loss.backward()
            trainer2.step(4)
    finally:
        del os.environ["MXNET_MULTI_TENSOR"]
    for k, p in net2.collect_params().items():
        np.testing.assert_allclose(w_fused[k], p.data().asnumpy(),
                                   rtol=RTOL, atol=ATOL, err_msg=k)


def test_group_table_shape():
    t, _ = _run("adam", {"learning_rate": 0.01}, _DENSE_SPEC, steps=1)
    rows = multi_tensor.group_table(t)
    assert len(rows) == 1
    r = rows[0]
    assert r["optimizer"] == "Adam" and r["params"] == 4
    assert r["programs_per_step"] == 1 and r["bytes"] > 0
    assert r["host_scalar_slots"] > 0


def test_load_checkpoint_resumed_counts_stay_live(tmp_path):
    """``load_checkpoint`` rebinds ``_index_update_count`` to a fresh
    dict; resumed fused Adam steps must read the RESTORED counts (bias
    correction t keeps advancing), not a dict captured at trace time —
    and the resumed trajectory must match an uninterrupted eager run."""
    mx.random.seed(0)
    params = _params([((6, 4), "float32", {}), ((6,), "float32", {})])
    trainer = gluon.Trainer(params, "adam", {"learning_rate": 0.01})
    for _ in range(3):
        trainer.update(2)
    trainer.save_checkpoint(str(tmp_path))
    for _ in range(2):  # diverge past the checkpoint, then rewind
        trainer.update(2)
    trainer.load_checkpoint(str(tmp_path))
    assert trainer._mt_groups == {}  # cached programs dropped on load
    for _ in range(2):
        trainer.update(2)
    counts = trainer._optimizer._index_update_count
    assert sorted(counts.values()) == [5, 5]
    resumed = _weights(params)
    _, straight = _run("adam", {"learning_rate": 0.01},
                       [((6, 4), "float32", {}), ((6,), "float32", {})],
                       steps=5, fused=False)
    for k in resumed:
        np.testing.assert_allclose(resumed[k], straight[k],
                                   rtol=RTOL, atol=ATOL, err_msg=k)


def test_failed_group_falls_back_without_double_count():
    """A group whose program fails at launch degrades to eager updates
    WITHOUT double-bumping the update counts (the snapshot/rewind in
    _apply_group), so the degraded step's bias correction matches a
    pure eager run bit-for-bit."""
    spec = [((4, 4), "float32", {})]
    mx.random.seed(0)
    params = _params(spec)
    trainer = gluon.Trainer(params, "adam", {"learning_rate": 0.01})
    trainer.update(2)
    (key, group), = trainer._mt_groups.items()

    def boom(*a, **k):
        raise RuntimeError("synthetic launch failure")

    group.jfn = boom
    group.cfn = None
    trainer.update(2)  # degrades to eager, counts bumped exactly once
    assert key not in trainer._mt_groups
    counts = trainer._optimizer._index_update_count
    assert sorted(counts.values()) == [2]
    degraded = _weights(params)
    _, eager = _run("adam", {"learning_rate": 0.01}, spec, steps=2,
                    fused=False)
    for k in degraded:
        np.testing.assert_allclose(degraded[k], eager[k],
                                   rtol=RTOL, atol=ATOL, err_msg=k)
