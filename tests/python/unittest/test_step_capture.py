"""mx.step whole-program training-step capture (ISSUE 11).

Covers: captured-vs-stitched bit parity (params + optimizer state,
SGD and Adam, >= 10 steps, scheduler lr change with zero retrace),
the ONE-executable telemetry proof (no separate cachedop / fused-group
/ monitor-stat builds during captured steps), fused health numerics
matching the PR 7 per-group values, in-program skip_step mutating
nothing, the MXNET_STEP_CAPTURE kill switch and every fallback path
(poisoned capture, non-fusable optimizer, dispatch failure) still
applying the step, bucket-fill telemetry from the captured plan, the
bucket-ordered psum segment under shard_map, remat policies, the
resilience.Supervisor and mx.dist deadline seams, checkpoint-restore
invalidation, and compile-cache warm start of a StepProgram.
"""
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, monitor, nd, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.resilience import inject
from mxnet_tpu.step import StepProgram, capture

BATCH, DIN, DOUT = 8, 12, 4


@pytest.fixture(autouse=True)
def _clean():
    telemetry.enable()
    inject.clear()
    monitor.core.reset()
    yield
    inject.clear()
    monitor.disable()
    monitor.core.reset()
    for var in ("MXNET_MONITOR_SENTINEL", "MXNET_STEP_CAPTURE",
                "MXNET_STEP_REMAT", "MXNET_DIST_COLLECTIVE_TIMEOUT"):
        os.environ.pop(var, None)


def _data(seed=0, nan_at=None):
    rs = np.random.RandomState(seed)
    x = rs.randn(BATCH, DIN).astype(np.float32)
    if nan_at is not None:
        x[nan_at] = np.nan
    y = rs.randn(BATCH, DOUT).astype(np.float32)
    return nd.array(x), nd.array(y)


def _make(optname="sgd", opt_params=None, seed=0, bn=False,
          hybridize=True):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    if bn:
        net.add(nn.Dense(16, in_units=DIN), nn.BatchNorm(),
                nn.Dense(DOUT, in_units=16))
    else:
        net.add(nn.Dense(16, activation="relu", in_units=DIN),
                nn.Dense(DOUT, in_units=16))
    net.initialize()
    if hybridize:
        net.hybridize()
    trainer = gluon.Trainer(
        net.collect_params(), optname,
        dict(opt_params or {"learning_rate": 0.1, "momentum": 0.9}))
    return net, trainer


def _run_stitched(net, trainer, steps, loss_fn=None, lr_hook=None):
    loss_fn = loss_fn or gluon.loss.L2Loss()
    x, y = _data()
    for s in range(steps):
        if lr_hook is not None:
            lr_hook(trainer, s)
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(BATCH)
    return loss


def _run_captured(net, trainer, steps, loss_fn=None, lr_hook=None):
    prog = trainer.capture(net, loss_fn or gluon.loss.L2Loss())
    x, y = _data()
    for s in range(steps):
        if lr_hook is not None:
            lr_hook(trainer, s)
        loss = prog(x, y)
    return prog, loss


def _assert_same_params(net_a, net_b):
    pa, pb = net_a.collect_params(), net_b.collect_params()
    for k in pa:
        np.testing.assert_array_equal(pa[k].data().asnumpy(),
                                      pb[k].data().asnumpy(), err_msg=k)


def _assert_same_states(tr_a, tr_b):
    import jax

    assert set(tr_a._states) == set(tr_b._states)
    for i in tr_a._states:
        la = jax.tree_util.tree_leaves(tr_a._states[i])
        lb = jax.tree_util.tree_leaves(tr_b._states[i])
        assert len(la) == len(lb)
        for a, b in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(a._data),
                                          np.asarray(b._data),
                                          err_msg="state %d" % i)


# ---------------------------------------------------------------------------
# bit parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("optname,opt_params", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01, "wd": 1e-4}),
])
def test_captured_bit_parity(optname, opt_params):
    """>= 10 captured steps produce BIT-identical params, optimizer
    state, update counts and loss vs the stitched Trainer.step path."""
    net_s, tr_s = _make(optname, opt_params)
    loss_s = _run_stitched(net_s, tr_s, 10)
    net_c, tr_c = _make(optname, opt_params)
    prog, loss_c = _run_captured(net_c, tr_c, 10)
    assert prog.report()["paths"] == {"captured": 10, "stitched": 0}
    np.testing.assert_array_equal(loss_s.asnumpy(), loss_c.asnumpy())
    _assert_same_params(net_s, net_c)
    _assert_same_states(tr_s, tr_c)
    assert tr_s._step_count == tr_c._step_count == 10
    assert tr_s._optimizer.num_update == tr_c._optimizer.num_update
    assert dict(tr_s._optimizer._index_update_count) == \
        dict(tr_c._optimizer._index_update_count)


def test_scheduler_lr_change_zero_retrace():
    """A per-step scheduler lr flows through the host-scalar slots:
    bit parity with the stitched scheduler run and EXACTLY one captured
    program build (zero per-step retraces), Adam included (per-param
    bias-correction t rides the same slots)."""
    from mxnet_tpu.optimizer import lr_scheduler

    def sched():
        return {"learning_rate": 0.05,
                "lr_scheduler": lr_scheduler.FactorScheduler(step=2,
                                                             factor=0.5)}

    net_s, tr_s = _make("adam", sched())
    _run_stitched(net_s, tr_s, 8)
    net_c, tr_c = _make("adam", sched())
    before = telemetry.value("step_capture_builds_total")
    prog, _ = _run_captured(net_c, tr_c, 8)
    assert telemetry.value("step_capture_builds_total") - before == 1, \
        "scheduler lr caused captured-program retraces"
    _assert_same_params(net_s, net_c)
    _assert_same_states(tr_s, tr_c)


def test_bn_forward_state_parity():
    """Functionalized forward state (BatchNorm running stats) written
    back from the captured program matches the stitched path exactly;
    trained weights match to FMA tolerance (the whole-program XLA
    fusion may contract mul+add chains the stitched op sequence keeps
    separate)."""
    net_s, tr_s = _make(bn=True)
    _run_stitched(net_s, tr_s, 5)
    net_c, tr_c = _make(bn=True)
    prog, _ = _run_captured(net_c, tr_c, 5)
    assert prog.report()["paths"]["captured"] == 5
    pa, pb = net_s.collect_params(), net_c.collect_params()
    for k in pa:
        a, b = pa[k].data().asnumpy(), pb[k].data().asnumpy()
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7,
                                   err_msg=k)


# ---------------------------------------------------------------------------
# the ONE-executable proof + fused health numerics
# ---------------------------------------------------------------------------

def test_one_executable_telemetry():
    """A captured step is ONE program: after the single capture build,
    further steps add zero cachedop builds, zero fused-group builds,
    zero monitor stat-program builds — with monitoring ON."""
    monitor.enable()
    net, trainer = _make()
    prog = trainer.capture(net, gluon.loss.L2Loss())
    x, y = _data()
    prog(x, y)  # capture + first dispatch
    deltas = {}
    names = ("step_capture_builds_total", "cachedop_build_total",
             "trainer_fused_builds_total", "monitor_stat_builds_total",
             "trainer_fused_apply_total")
    before = {n: telemetry.value(n) for n in names}
    for _ in range(4):
        prog(x, y)
    for n in names:
        deltas[n] = telemetry.value(n) - before[n]
    assert deltas == {n: 0.0 for n in names}, deltas
    assert prog.report()["paths"]["captured"] == 5


def test_fused_stats_match_stitched_monitor():
    """The stat vectors computed INSIDE the captured program equal the
    PR 7 per-group values the stitched observe_update hook publishes
    (same labels, same numbers)."""
    monitor.enable()
    net_s, tr_s = _make()
    _run_stitched(net_s, tr_s, 3)
    assert monitor.core.flush(5)
    stitched_vals = monitor.core.group_values()
    monitor.core.reset()
    net_c, tr_c = _make()
    _run_captured(net_c, tr_c, 3)
    assert monitor.core.flush(5)
    captured_vals = monitor.core.group_values()
    assert set(captured_vals) == set(stitched_vals) != set()
    for label in stitched_vals:
        for field, want in stitched_vals[label].items():
            np.testing.assert_allclose(
                captured_vals[label][field], want, rtol=1e-6, atol=1e-9,
                err_msg="%s.%s" % (label, field))


def test_skip_step_inside_program_mutates_nothing():
    """An injected NaN gradient under policy=skip_step where-selects
    no-op updates ON DEVICE: params, optimizer state, update counts,
    num_update and step_count are all untouched, and the next clean
    step applies normally."""
    os.environ["MXNET_MONITOR_SENTINEL"] = "skip_step"
    monitor.enable()
    net, trainer = _make("adam", {"learning_rate": 0.01})
    prog = trainer.capture(net, gluon.loss.L2Loss())
    x, y = _data()
    prog(x, y)
    params0 = {k: p.data().asnumpy().copy()
               for k, p in net.collect_params().items()}
    import jax

    states0 = {i: [np.asarray(leaf._data).copy() for leaf in
                   jax.tree_util.tree_leaves(trainer._states[i])]
               for i in trainer._states}
    counts0 = dict(trainer._optimizer._index_update_count)
    nu0, sc0 = trainer._optimizer.num_update, trainer._step_count
    xbad, _ = _data(nan_at=3)
    loss = prog(xbad, y)
    assert np.isnan(loss.asnumpy()).any()
    for k, p in net.collect_params().items():
        np.testing.assert_array_equal(params0[k], p.data().asnumpy(),
                                      err_msg=k)
    for i in states0:
        now = [np.asarray(leaf._data) for leaf in
               jax.tree_util.tree_leaves(trainer._states[i])]
        for a, b in zip(states0[i], now):
            np.testing.assert_array_equal(a, b, err_msg="state %d" % i)
    assert dict(trainer._optimizer._index_update_count) == counts0
    assert trainer._optimizer.num_update == nu0
    assert trainer._step_count == sc0
    assert monitor.core.flush(5)
    assert monitor.summary()["skipped_steps"] == 1
    prog(x, y)
    assert trainer._step_count == sc0 + 1


def test_policy_raise_names_group_and_mutates_nothing():
    os.environ["MXNET_MONITOR_SENTINEL"] = "raise"
    monitor.enable()
    net, trainer = _make()
    prog = trainer.capture(net, gluon.loss.L2Loss())
    x, y = _data()
    prog(x, y)
    params0 = {k: p.data().asnumpy().copy()
               for k, p in net.collect_params().items()}
    nu0 = trainer._optimizer.num_update
    xbad, _ = _data(nan_at=0)
    with pytest.raises(MXNetError, match="nonfinite"):
        prog(xbad, y)
    for k, p in net.collect_params().items():
        np.testing.assert_array_equal(params0[k], p.data().asnumpy())
    assert trainer._optimizer.num_update == nu0
    # the raise is a verdict, not a capture failure: no stitched
    # replay ran (that would double-apply), the program stays live,
    # and the next clean step is captured and applied
    sc = trainer._step_count
    prog(x, y)
    rep = prog.report()
    assert rep["paths"]["stitched"] == 0
    assert trainer._step_count == sc + 1
    assert rep["programs"], "sentinel raise killed the captured program"


# ---------------------------------------------------------------------------
# kill switch + fallbacks: never a lost step
# ---------------------------------------------------------------------------

def test_kill_switch_runs_stitched():
    os.environ["MXNET_STEP_CAPTURE"] = "0"
    net_s, tr_s = _make()
    _run_stitched(net_s, tr_s, 3)
    net_c, tr_c = _make()
    prog, _ = _run_captured(net_c, tr_c, 3)
    rep = prog.report()
    assert rep["paths"] == {"captured": 0, "stitched": 3}
    assert [f["reason"] for f in rep["fallbacks"]] == ["disabled"]
    assert tr_c._step_count == 3
    _assert_same_params(net_s, net_c)
    _assert_same_states(tr_s, tr_c)


def test_poisoned_capture_falls_back_step_applied():
    """MXNET_FAULTS site step_capture at capture time: the capture is
    poisoned, the step runs stitched, and NOTHING is lost."""
    inject.plan("step_capture@0")
    net, trainer = _make()
    before = telemetry.value("step_capture_fallback_total")
    prog, _ = _run_captured(net, trainer, 2)
    rep = prog.report()
    assert rep["paths"]["stitched"] == 2 and rep["paths"]["captured"] == 0
    assert rep["fallbacks"][0]["reason"] == "injected_fault"
    assert trainer._step_count == 2
    assert telemetry.value("step_capture_fallback_total") - before == 1


def test_non_fusable_optimizer_falls_back():
    class MySGD(mx.optimizer.SGD):
        pass

    mx.random.seed(0)
    net = nn.Dense(DOUT, in_units=DIN)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(),
                            MySGD(learning_rate=0.1))
    prog = trainer.capture(net, gluon.loss.L2Loss())
    x, y = _data()
    prog(x, y)
    rep = prog.report()
    assert rep["paths"]["stitched"] == 1
    assert rep["fallbacks"][0]["reason"] == "eager_members"
    assert trainer._step_count == 1


def test_dispatch_failure_falls_back_and_rewinds_once():
    """A broken program at dispatch degrades to stitched with the step
    still applied and the count bump rewound exactly once — final
    state is bit-identical to a pure stitched run (Adam would expose
    any double-bumped bias-correction t)."""
    net_s, tr_s = _make("adam", {"learning_rate": 0.01})
    _run_stitched(net_s, tr_s, 4)

    net_c, tr_c = _make("adam", {"learning_rate": 0.01})
    prog = tr_c.capture(net_c, gluon.loss.L2Loss())
    x, y = _data()
    prog(x, y)  # step 1 captured
    cap = next(iter(prog._programs.values()))

    def boom(*a, **k):
        raise RuntimeError("poisoned executable")

    cap.cfn = None
    cap.jfn = boom
    prog(x, y)  # step 2: dispatch fails -> stitched
    rep = prog.report()
    assert rep["fallbacks"][0]["reason"] == "dispatch_error"
    assert tr_c._step_count == 2
    for _ in range(2):  # steps 3-4: the poisoned signature stays
        prog(x, y)      # stitched for good (no rebuild loops)
    assert prog.report()["paths"] == {"captured": 1, "stitched": 3}
    _assert_same_params(net_s, net_c)
    _assert_same_states(tr_s, tr_c)
    assert tr_s._optimizer.num_update == tr_c._optimizer.num_update


# ---------------------------------------------------------------------------
# collective segment: bucket plan telemetry + psum structure
# ---------------------------------------------------------------------------

def test_bucket_fill_fed_from_captured_plan():
    """Satellite: allreduce_bucket_fill observes the captured program's
    bucket plan each dispatch — but only when collectives actually run
    (world > 1), mirroring the per-call path (which reduces nothing in
    a world of one), so the two paths stay comparable in telemetry."""
    net, trainer = _make()
    prog = trainer.capture(net, gluon.loss.L2Loss())
    x, y = _data()
    prog(x, y)
    n_buckets = len(prog.report()["programs"][0]["bucket_plan"])
    assert n_buckets >= 1
    # world of one: no collective ran, no phantom fill samples
    before = telemetry.value("allreduce_bucket_fill")
    prog(x, y)
    assert telemetry.value("allreduce_bucket_fill") == before
    # multi-process world: one observation per bucket per dispatch
    prog._world = 2
    before = telemetry.value("allreduce_bucket_fill")
    for _ in range(3):
        prog(x, y)
    assert telemetry.value("allreduce_bucket_fill") - before == \
        3 * n_buckets


def test_bucket_allreduce_psums_per_bucket():
    """Under an SPMD axis each bucket is ONE psum over only its member
    grads (bucket-ordered dependency structure — early buckets carry
    no dependency on later ones)."""
    import jax
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from mxnet_tpu.step.capture import _bucket_allreduce

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 (virtual) devices")
    mesh = Mesh(np.array(devs[:2]), ("dp",))
    g1 = np.arange(6, dtype=np.float32).reshape(2, 3)
    g2 = np.ones((2, 2), np.float32)
    g3 = np.full((2, 1), 2.0, np.float32)

    def f(a, b, c):
        return tuple(_bucket_allreduce([a, b, c], [[0, 1], [2]], "dp"))

    fm = shard_map(f, mesh=mesh, in_specs=(P("dp"),) * 3,
                   out_specs=(P(None),) * 3)
    o1, o2, o3 = fm(g1, g2, g3)
    np.testing.assert_array_equal(np.asarray(o1), (g1[0] + g1[1])[None])
    np.testing.assert_array_equal(np.asarray(o2), (g2[0] + g2[1])[None])
    np.testing.assert_array_equal(np.asarray(o3), (g3[0] + g3[1])[None])
    # identity in a world of one: summing a single replica's gradient
    out = _bucket_allreduce([g1, g2], [[0, 1]], None)
    assert out[0] is g1 and out[1] is g2


# ---------------------------------------------------------------------------
# rematerialization policy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["all", "blocks"])
def test_remat_bit_parity(mode):
    os.environ["MXNET_STEP_REMAT"] = mode
    net_c, tr_c = _make()
    prog, _ = _run_captured(net_c, tr_c, 5)
    assert prog.report()["paths"]["captured"] == 5
    assert prog.report()["programs"][0]["remat"] == mode
    os.environ.pop("MXNET_STEP_REMAT")
    net_s, tr_s = _make()
    _run_stitched(net_s, tr_s, 5)
    _assert_same_params(net_s, net_c)


def test_remat_blocks_degrades_on_stateful_forward():
    """BatchNorm mutates traced forward state, which cannot cross a
    per-block jax.checkpoint — the POLICY degrades to remat=all (one
    stitched step, then captured again), never a lost step."""
    os.environ["MXNET_STEP_REMAT"] = "blocks"
    net, trainer = _make(bn=True)
    prog = trainer.capture(net, gluon.loss.L2Loss())
    x, y = _data()
    for _ in range(4):
        prog(x, y)
    rep = prog.report()
    assert trainer._step_count == 4
    assert "remat_blocks_degraded" in [f["reason"]
                                       for f in rep["fallbacks"]]
    assert rep["paths"]["captured"] >= 2
    assert all(p["remat"] == "all" for p in rep["programs"])


# ---------------------------------------------------------------------------
# interaction seams: supervisor / dist deadline / checkpoint restore
# ---------------------------------------------------------------------------

def test_supervisor_transient_at_captured_program(tmp_path):
    """A transient fault at the captured-program dispatch under the
    resilience.Supervisor rewinds the count bump once, restores, and
    resumes to a bit-identical end state vs an unfaulted run."""
    from mxnet_tpu.resilience.supervisor import (Backoff, GluonStepLoop,
                                                 Supervisor)

    def batches(step):
        rs = np.random.RandomState(step % 5)
        return (rs.rand(BATCH, DIN).astype(np.float32),
                rs.rand(BATCH, DOUT).astype(np.float32))

    def build(with_capture):
        net, trainer = _make("adam", {"learning_rate": 0.01}, seed=3)
        prog = trainer.capture(net, gluon.loss.L2Loss()) \
            if with_capture else None
        return GluonStepLoop(net, trainer, gluon.loss.L2Loss(),
                             step_program=prog)

    n = 6
    ref = build(False)
    for s in range(n):
        ref.step(*batches(s))

    loop = build(True)
    inject.plan("step_capture@3:transient")
    sup = Supervisor(loop, mx.checkpoint.CheckpointManager(
        str(tmp_path)), checkpoint_every=2,
        backoff=Backoff(base=0.0, jitter=0.0), max_restarts=2)
    losses = sup.run(batches, n)
    assert sup.restarts == 1 and len(losses) == n
    _assert_same_params(ref.block, loop.block)
    assert ref.trainer._optimizer.num_update == \
        loop.trainer._optimizer.num_update


def test_collective_deadline_wraps_captured_dispatch():
    """MXNET_DIST_COLLECTIVE_TIMEOUT bounds the WHOLE captured dispatch
    in a multi-process world; a miss raises the transient-classified
    DistTimeout with the count bump rewound — and, unlike the stitched
    allreduce, marks the state suspect (donated buffers may have been
    consumed mid-program)."""
    from mxnet_tpu.dist.timeouts import DistTimeout

    net, trainer = _make()
    prog = trainer.capture(net, gluon.loss.L2Loss())
    x, y = _data()
    prog(x, y)
    cap = next(iter(prog._programs.values()))
    orig_cfn, orig_jfn = cap.cfn, cap.jfn

    def slow_call(*args):
        time.sleep(1.0)
        return (orig_cfn or orig_jfn)(*args)

    cap.cfn = None
    cap.jfn = slow_call
    prog._world = 2  # pretend a peer exists
    os.environ["MXNET_DIST_COLLECTIVE_TIMEOUT"] = "0.2"
    nu0 = trainer._optimizer.num_update
    counts0 = dict(trainer._optimizer._index_update_count)
    with pytest.raises(DistTimeout) as exc_info:
        prog(x, y)
    assert exc_info.value.mx_fault_kind == "transient"
    assert exc_info.value.mx_state_clean is False
    assert trainer._optimizer.num_update == nu0
    assert dict(trainer._optimizer._index_update_count) == counts0
    os.environ.pop("MXNET_DIST_COLLECTIVE_TIMEOUT")
    prog._world = 1
    cap.cfn, cap.jfn = orig_cfn, orig_jfn
    prog(x, y)  # the program is intact and serves again
    assert trainer._step_count == 2


def test_checkpoint_restore_invalidates_and_resumes_bit_identical(
        tmp_path):
    """load_checkpoint rebinds optimizer-state arrays: captured
    programs are invalidated, the next step re-captures, and the
    resumed run matches an uninterrupted one bit for bit (live
    _index_update_count reads included)."""
    net_s, tr_s = _make("adam", {"learning_rate": 0.01})
    _run_stitched(net_s, tr_s, 6)

    net_c, tr_c = _make("adam", {"learning_rate": 0.01})
    prog = tr_c.capture(net_c, gluon.loss.L2Loss())
    x, y = _data()
    for _ in range(3):
        prog(x, y)
    tr_c.save_checkpoint(str(tmp_path))
    tr_c.load_checkpoint(str(tmp_path))
    assert not prog._programs  # invalidated by the restore
    for _ in range(3):
        prog(x, y)
    assert prog.report()["paths"]["captured"] == 6
    _assert_same_params(net_s, net_c)
    _assert_same_states(tr_s, tr_c)


def test_compile_cache_serves_step_program(tmp_path):
    """The captured program fingerprints into the mx.compile persistent
    cache: a fresh capture (new trainer/program, same step) restores
    the executable with zero fresh XLA compiles and bit-identical
    results."""
    from mxnet_tpu import compile as mxcompile

    mxcompile.enable(dir=str(tmp_path))
    try:
        net1, tr1 = _make()
        prog1, _ = _run_captured(net1, tr1, 3)
        assert prog1.report()["programs"][0]["provenance"] == "fresh"
        assert prog1.report()["programs"][0]["fingerprint"]
        hits = telemetry.value("compile_cache_hit_total")
        net2, tr2 = _make()
        prog2, _ = _run_captured(net2, tr2, 3)
        assert prog2.report()["programs"][0]["provenance"] == "cache"
        assert telemetry.value("compile_cache_hit_total") - hits == 1
        _assert_same_params(net1, net2)
    finally:
        mxcompile.disable()


# ---------------------------------------------------------------------------
# surface
# ---------------------------------------------------------------------------

def test_capture_api_and_report():
    net, trainer = _make()
    with pytest.raises(MXNetError, match="Trainer"):
        capture(net, gluon.loss.L2Loss())
    other = nn.Dense(1, in_units=DIN)
    with pytest.raises(MXNetError, match="two different blocks"):
        capture(net, gluon.loss.L2Loss(), trainer=trainer, block=other)
    prog = capture(trainer, gluon.loss.L2Loss(), block=net)
    assert isinstance(prog, StepProgram)
    prog2 = capture(net, gluon.loss.L2Loss(), trainer=trainer)
    x, y = _data()
    prog2(x, y)
    rep = prog2.report()
    program = rep["programs"][0]
    segs = [s["segment"] for s in program["segments"]]
    assert segs[:4] == ["forward", "loss", "backward", "allreduce"]
    assert segs[-1] == "apply"
    assert program["donation"]["params"]["donated"] is True
    assert program["donation"]["optimizer_state"]["donated"] is True
    assert program["host_scalar_slots"] >= 1
    allreduce = program["segments"][3]
    assert allreduce["buckets"] == len(program["bucket_plan"])


def test_report_carries_the_bucket_size_the_plan_was_built_with(
        monkeypatch):
    """``bucket_bytes`` in the report is ``MXNET_KVSTORE_BUCKET_BYTES`` as
    it stood at capture (the default when unset) and shapes the plan; the
    report says nothing of where a size came from, since there is one
    place."""
    from mxnet_tpu.kvstore import collective

    x, y = _data()
    monkeypatch.delenv("MXNET_KVSTORE_BUCKET_BYTES", raising=False)
    net, trainer = _make()
    prog = trainer.capture(net, gluon.loss.L2Loss())
    prog(x, y)
    program = prog.report()["programs"][0]
    assert program["bucket_bytes"] == collective.default_bucket_bytes() \
        == 4 << 20
    assert len(program["bucket_plan"]) == 1
    assert "bucket_bytes_provenance" not in program
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_BYTES", "64")
    net, trainer = _make()
    prog = trainer.capture(net, gluon.loss.L2Loss())
    prog(x, y)
    program = prog.report()["programs"][0]
    assert program["bucket_bytes"] == 64
    assert len(program["bucket_plan"]) > 1


def test_non_hybrid_block_rejected():
    class Plain(gluon.Block):
        def forward(self, x):
            return x

    net, trainer = _make()
    with pytest.raises(MXNetError, match="HybridBlock"):
        capture(Plain(), gluon.loss.L2Loss(), trainer=trainer)
