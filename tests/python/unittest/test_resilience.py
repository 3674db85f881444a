"""mx.resilience tests: deterministic fault-plan replay, exception
classification routing, backoff/budget-window math, bounded health probes,
supervisor resume bit-parity vs an uninterrupted run, preemption
(in-process and a real SIGTERM subprocess drill), bisect isolation of
poisoned serve requests, and circuit-breaker open/half-open/close."""
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel, resilience, serve, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.resilience import inject, preempt
from mxnet_tpu.resilience.supervisor import (Backoff, GluonStepLoop,
                                             RestartBudget, Supervisor,
                                             classify, health_check)
from mxnet_tpu.serve.breaker import CircuitBreaker

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


@pytest.fixture(autouse=True)
def _clean():
    telemetry.enable()
    telemetry.reset()
    inject.clear()
    preempt.clear()
    yield
    inject.clear()
    preempt.clear()
    telemetry.enable()
    telemetry.reset()


def _trainer(seed):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu", in_units=8),
            nn.Dense(4, in_units=16))
    net.initialize()
    return parallel.FusedTrainer(
        net, loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9})


def _batches(step):
    rs = np.random.RandomState(step % 7)
    return (rs.rand(16, 8).astype(np.float32),
            rs.randint(0, 4, 16).astype(np.int32))


def _params_of(tr):
    return {k: np.asarray(v) for k, v in tr.params.items()}


def _supervisor(tr, mgr, **kw):
    kw.setdefault("backoff", Backoff(base=0.0, jitter=0.0))
    kw.setdefault("checkpoint_every", 2)
    return Supervisor(tr, mgr, **kw)


# ---------------------------------------------------------------------------
# fault plan
# ---------------------------------------------------------------------------

def test_fault_plan_parse():
    p = inject.FaultPlan.parse(
        "trainer_step@5, collective@*:io*2,serve_poison@req-9,"
        "checkpoint_marker@0:abort")
    got = [(e.site, e.key, e.kind, e.count) for e in p.entries]
    # serve_poison defaults to UNLIMITED (count None): the poison must
    # survive bisect retries and later dispatches of the same drill
    assert got == [("trainer_step", "5", "transient", 1),
                   ("collective", "*", "io", 2),
                   ("serve_poison", "req-9", "transient", None),
                   ("checkpoint_marker", "0", "abort", 1)]


def test_fault_plan_rejects_garbage():
    with pytest.raises(mx.MXNetError, match="MXNET_FAULTS"):
        inject.FaultPlan.parse("no-at-sign")
    with pytest.raises(mx.MXNetError, match="kind"):
        inject.FaultPlan.parse("a@0:bogus")


def test_fault_plan_env_refresh(monkeypatch):
    monkeypatch.setenv("MXNET_FAULTS", "collective@1:io")
    inject.refresh_env()
    assert inject.active()
    with pytest.raises(OSError):
        inject.fire("collective", seq=1)
    assert not inject.poisoned("anything")


def test_fire_deterministic_replay():
    """The same plan fires at the same internal sequence positions,
    run after run — the property every drill rests on."""

    def firing_pattern():
        inject.plan("collective@2,collective@4")
        fired = []
        for i in range(6):
            try:
                inject.fire("collective")   # internal per-site counter
                fired.append(False)
            except inject.InjectedFault:
                fired.append(True)
        return fired

    first = firing_pattern()
    assert first == [False, False, True, False, True, False]
    assert firing_pattern() == first


def test_fire_kinds_and_counter():
    inject.plan("checkpoint_commit@0:io,trainer_step@0:fatal,"
                "collective@0")
    with pytest.raises(OSError):
        inject.fire("checkpoint_commit", seq=0)
    with pytest.raises(inject.InjectedFault) as fatal:
        inject.fire("trainer_step", seq=0)
    assert fatal.value.kind == "fatal"
    with pytest.raises(inject.InjectedFault) as trans:
        inject.fire("collective", seq=0)
    assert trans.value.kind == "transient"
    # one-shot entries are spent
    inject.fire("collective", seq=0)
    assert telemetry.value("resilience_faults_injected_total",
                           {"site": "collective"}) == 1
    assert telemetry.value("resilience_faults_injected_total",
                           {"site": "checkpoint_commit"}) == 1


def test_poisoned_is_non_consuming():
    inject.plan("serve_poison@req-7")
    assert inject.poisoned("req-7")
    assert inject.poisoned("req-7")     # bisect retries re-check
    assert not inject.poisoned("req-8")
    assert not inject.poisoned(None)


# ---------------------------------------------------------------------------
# classification / backoff / budget / health
# ---------------------------------------------------------------------------

def test_classify_classes():
    assert classify(OSError("disk")) == "transient"
    assert classify(TimeoutError()) == "transient"
    assert classify(ConnectionError()) == "transient"
    assert classify(RuntimeError("XLA device lost")) == "transient"
    assert classify(Exception("unknown")) == "transient"
    assert classify(ValueError("bad shape")) == "fatal"
    assert classify(TypeError()) == "fatal"
    assert classify(KeyError("p0")) == "fatal"
    assert classify(mx.MXNetError("contract")) == "fatal"
    assert classify(inject.InjectedFault("x", kind="transient")) == \
        "transient"
    assert classify(inject.InjectedFault("x", kind="fatal")) == "fatal"
    assert classify(inject.InjectedIOError("x")) == "transient"

    class VendorRPCError(Exception):
        pass

    resilience.register_transient(VendorRPCError)
    try:
        assert classify(VendorRPCError()) == "transient"
    finally:
        from mxnet_tpu.resilience.supervisor import _TRANSIENT_EXTRA

        _TRANSIENT_EXTRA.remove(VendorRPCError)
    marked = ValueError("but retryable")
    marked.mx_fault_kind = "transient"
    assert classify(marked) == "transient"


def test_backoff_math():
    b = Backoff(base=0.5, factor=2.0, max_delay=4.0, jitter=0.0)
    assert [b.delay(i) for i in range(5)] == [0.5, 1.0, 2.0, 4.0, 4.0]
    j = Backoff(base=1.0, factor=2.0, max_delay=60.0, jitter=0.25,
                seed=7)
    for i in range(4):
        d = j.delay(i)
        assert 2.0 ** i <= d <= 2.0 ** i * 1.25


def test_restart_budget_sliding_window():
    budget = RestartBudget(2, window_steps=100)
    assert budget.record(10) == 1 and not budget.exceeded(10)
    assert budget.record(50) == 2 and not budget.exceeded(50)
    assert budget.record(60) == 3 and budget.exceeded(60)
    # 150: the restarts at 10 and 50 aged out of the window
    assert budget.count(150) == 1 and not budget.exceeded(150)
    lifetime = RestartBudget(2, window_steps=None)
    for s in (10, 5000):
        lifetime.record(s)
    assert lifetime.record(90000) == 3 and lifetime.exceeded(90000)


def test_health_check_timeout_and_ok():
    report = health_check(timeout=30.0)
    assert report and all(v == "ok" for v in report.values()), report

    def hung_probe(device):
        time.sleep(30)

    t0 = time.perf_counter()
    report = health_check(timeout=0.2, devices=["dev0", "dev1"],
                          probe=hung_probe)
    assert time.perf_counter() - t0 < 5.0
    assert report["dev0"].startswith("error: timeout")
    assert report["dev1"].startswith("error: timeout")
    # compat surface: elastic.device_health_check grew the same bound
    report = mx.elastic.device_health_check(timeout=30.0)
    assert all(v == "ok" for v in report.values())


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------

def test_supervisor_resume_bit_identical(tmp_path):
    """An injected transient fault mid-run must restore + replay to
    BIT-IDENTICAL final parameters vs an uninterrupted run."""
    n = 8
    ref = _trainer(7)
    for s in range(n):
        ref.step(*_batches(s))

    tr = _trainer(7)
    mgr = mx.checkpoint.CheckpointManager(str(tmp_path))
    inject.plan("trainer_step@5")
    sup = _supervisor(tr, mgr, max_restarts=2)
    losses = sup.run(_batches, n)
    assert sup.restarts == 1
    assert len(losses) == n
    for k, v in _params_of(ref).items():
        np.testing.assert_array_equal(v, _params_of(tr)[k],
                                      err_msg=k)
    assert telemetry.value("resilience_restarts_total",
                           {"kind": "transient"}) == 1


def test_supervisor_gluon_loop_collective_fault(tmp_path):
    """The imperative path: a fault at the collective pushpull_all site
    under a GluonStepLoop-driven supervisor restores and resumes."""

    def build(seed):
        mx.random.seed(seed)
        net = nn.Dense(4, in_units=8)
        net.initialize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1, "momentum": 0.9})
        loss = gluon.loss.SoftmaxCrossEntropyLoss()
        return GluonStepLoop(net, trainer, loss)

    n = 6
    ref = build(3)
    for s in range(n):
        ref.step(*_batches(s))

    loop = build(3)
    inject.plan("collective@3")
    sup = _supervisor(loop, mx.checkpoint.CheckpointManager(
        str(tmp_path)), max_restarts=2)
    losses = sup.run(_batches, n)
    assert sup.restarts == 1 and len(losses) == n
    for k, p in ref.block.collect_params().items():
        np.testing.assert_array_equal(
            p.data().asnumpy(),
            loop.block.collect_params()[k].data().asnumpy(), err_msg=k)


def test_supervisor_fatal_raises_immediately(tmp_path):
    tr = _trainer(9)
    real = tr.step

    def bad_step(x, y):
        if tr._step_count == 2:
            raise ValueError("shape bug")
        return real(x, y)

    tr.step = bad_step
    sup = _supervisor(tr, mx.checkpoint.CheckpointManager(
        str(tmp_path)), max_restarts=3)
    with pytest.raises(mx.MXNetError, match="fatal training error"):
        sup.run(_batches, 6)
    assert sup.restarts == 0


def test_supervisor_budget_gives_up(tmp_path):
    tr = _trainer(9)
    tr.step = lambda x, y: (_ for _ in ()).throw(
        RuntimeError("permanently broken"))
    sup = _supervisor(tr, mx.checkpoint.CheckpointManager(
        str(tmp_path)), max_restarts=2)
    with pytest.raises(mx.MXNetError, match="after 2 restarts"):
        sup.run(_batches, 5)


def test_on_failure_exception_does_not_mask_original(tmp_path):
    """Satellite: a raising on_failure callback must not replace the
    training error in the recovery path."""
    tr = _trainer(11)
    boom = {"armed": True}
    real = tr.step

    def flaky(x, y):
        if boom["armed"] and tr._step_count == 3:
            boom["armed"] = False
            raise RuntimeError("injected device failure")
        return real(x, y)

    tr.step = flaky
    seen = []

    def bad_callback(step, exc):
        seen.append((step, str(exc)))
        raise ValueError("buggy observer")

    sup = _supervisor(tr, mx.checkpoint.CheckpointManager(
        str(tmp_path)), max_restarts=2, on_failure=bad_callback)
    losses = sup.run(_batches, 6)
    assert len(losses) == 6
    assert sup.restarts == 1
    assert seen and "injected device failure" in seen[0][1]


def test_checkpoint_commit_io_fault_retried(tmp_path):
    mgr = mx.checkpoint.CheckpointManager(str(tmp_path),
                                          retry_backoff=0.01)
    inject.plan("checkpoint_commit@0:io")
    path = mgr.save(3, {"w": np.arange(4, dtype=np.float32)})
    assert os.path.isdir(path)
    assert mgr.latest_step() == 3
    assert telemetry.value("checkpoint_retries_total") >= 1


def test_divergence_restore(tmp_path):
    n = 8
    ref = _trainer(13)
    for s in range(n):
        ref.step(*_batches(s))

    tr = _trainer(13)
    fired = {"armed": True}

    def batches(step):
        if fired["armed"] and step == 5:
            fired["armed"] = False
            from mxnet_tpu.trace import anomaly

            anomaly.divergence({"kind": "grad_norm_spike", "step": step})
        return _batches(step)

    sup = _supervisor(tr, mx.checkpoint.CheckpointManager(
        str(tmp_path)), max_restarts=3, restore_on_divergence=True)
    losses = sup.run(batches, n)
    assert sup.divergence_restores == 1
    assert len(losses) == n
    for k, v in _params_of(ref).items():
        np.testing.assert_array_equal(v, _params_of(tr)[k], err_msg=k)


# ---------------------------------------------------------------------------
# preemption
# ---------------------------------------------------------------------------

def test_preempt_emergency_checkpoint_then_resume(tmp_path):
    n = 8
    ref = _trainer(17)
    for s in range(n):
        ref.step(*_batches(s))

    tr = _trainer(17)
    mgr = mx.checkpoint.CheckpointManager(str(tmp_path))

    def batches(step):
        if step == 4 and not preempt.requested():
            preempt.request(grace=30.0)   # "SIGTERM" mid-epoch
        return _batches(step)

    sup = _supervisor(tr, mgr, checkpoint_every=100)
    losses = sup.run(batches, n)
    assert sup.preempted
    # the request landed DURING step 4, so the loop stopped at the
    # NEXT boundary: steps 0-4 ran, the emergency tag is the last
    # completed step
    assert len(losses) == 5
    assert sup.emergency_checkpoint and \
        os.path.isdir(sup.emergency_checkpoint)
    assert mgr.latest_step() == 4
    assert telemetry.value("resilience_emergency_saves_total") == 1

    preempt.clear()
    sup2 = _supervisor(tr, mgr, checkpoint_every=100)
    losses2 = sup2.run(batches, n)        # resumes at step 5
    assert not sup2.preempted
    for k, v in _params_of(ref).items():
        np.testing.assert_array_equal(v, _params_of(tr)[k], err_msg=k)


def test_preempt_during_failure_recovery(tmp_path):
    """Preemption racing a transient failure: the supervisor must skip
    the long backoff, restore from the checkpoint (the failed step may
    have half-mutated memory), and only then emergency-save; with NO
    checkpoint the suspect state must not be persisted at all."""

    def run_one(root, every):
        mx.random.seed(19)
        tr = _trainer(19)
        mgr = mx.checkpoint.CheckpointManager(root)
        inject.plan("trainer_step@3")
        # preempt exactly when the injected failure fires (the
        # on_failure observer runs before the backoff sleep) — a
        # wall-clock Timer here raced the step loop and flaked
        sup = Supervisor(tr, mgr, checkpoint_every=every,
                         backoff=Backoff(base=30.0, jitter=0.0),
                         on_failure=lambda step, exc:
                         preempt.request(grace=30.0))
        t0 = time.perf_counter()
        sup.run(_batches, 10)
        assert time.perf_counter() - t0 < 15.0   # never slept 30s
        assert sup.preempted
        inject.clear()
        preempt.clear()
        return sup, mgr

    sup, mgr = run_one(str(tmp_path / "with-ckpt"), 2)
    assert not sup._state_suspect
    assert sup.emergency_checkpoint is not None
    assert mgr.latest_step() is not None

    sup, mgr = run_one(str(tmp_path / "no-ckpt"), 100)
    assert sup._state_suspect                    # failed mid-step,
    assert sup.emergency_checkpoint is None      # nothing durable ->
    assert mgr.latest_step() is None             # nothing saved


_SIGTERM_CHILD = r"""
import os, sys, time
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import parallel, resilience
from mxnet_tpu.gluon import nn

root, ready = sys.argv[1], sys.argv[2]
mx.random.seed(1)
net = nn.Dense(4, in_units=8)
net.initialize()
tr = parallel.FusedTrainer(net, loss="softmax_ce", optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})

def batches(step):
    rs = np.random.RandomState(step % 5)
    if step == 3:
        open(ready, "w").write(str(os.getpid()))
    time.sleep(0.05 if step >= 3 else 0.0)
    return (rs.rand(8, 8).astype(np.float32),
            rs.randint(0, 4, 8).astype(np.int32))

assert resilience.install()
mgr = mx.checkpoint.CheckpointManager(root)
sup = resilience.Supervisor(tr, mgr, checkpoint_every=1000,
                            exit_on_preempt=True)
sup.run(batches, 100000)
print("NOT PREEMPTED")
sys.exit(1)
"""


def test_sigterm_drill_subprocess(tmp_path):
    """Real SIGTERM: the child stops at the step boundary, flushes an
    emergency checkpoint, and exits with the preemption code."""
    root = str(tmp_path / "ckpt")
    ready = str(tmp_path / "ready")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_PREEMPT_GRACE_SECONDS="30")
    proc = subprocess.Popen(
        [sys.executable, "-c", _SIGTERM_CHILD, root, ready],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT)
    try:
        deadline = time.time() + 120
        while not os.path.exists(ready):
            assert proc.poll() is None, proc.stdout.read().decode()
            assert time.time() < deadline, "child never reached step 3"
            time.sleep(0.05)
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert rc == preempt.exit_code(), proc.stdout.read().decode()
    from mxnet_tpu.checkpoint import latest_step

    assert latest_step(root) is not None


_ABORT_CHILD = r"""
import sys
import numpy as np
import mxnet_tpu as mx

mgr = mx.checkpoint.CheckpointManager(sys.argv[1])
mgr.save(1, {"w": np.arange(8, dtype=np.float32)})
mx.resilience.plan("checkpoint_marker@0:abort")
mgr.save(2, {"w": np.arange(8, dtype=np.float32) * 2})
print("SURVIVED THE ABORT")
sys.exit(1)
"""


def test_writer_killed_mid_commit_recovers(tmp_path):
    """The torn-checkpoint drill: the writer dies (os._exit) after the
    shards/manifest land but before the COMMITTED marker; discovery
    must keep serving step 1 and a fresh save must succeed."""
    root = str(tmp_path / "ckpt")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _ABORT_CHILD, root], cwd=REPO, env=env,
        capture_output=True, timeout=300)
    assert proc.returncode == inject.ABORT_EXIT_CODE, \
        proc.stdout.decode() + proc.stderr.decode()
    mgr = mx.checkpoint.CheckpointManager(root)
    assert mgr.latest_step() == 1          # torn step 2 never listed
    _, tree = mgr.restore()
    np.testing.assert_array_equal(np.asarray(tree["w"]),
                                  np.arange(8, dtype=np.float32))
    mgr.save(2, {"w": np.arange(8, dtype=np.float32) * 2})
    assert mgr.latest_step() == 2


# ---------------------------------------------------------------------------
# serve: poison isolation + circuit breaker
# ---------------------------------------------------------------------------

def _serve_fixture(tmp_path, **cfg_kwargs):
    def make():
        return nn.Dense(4, flatten=False, in_units=16)

    blk = make()
    blk.initialize()
    blk(mx.nd.zeros((1, 2, 16)))
    root = str(tmp_path / "sckpt")
    blk.save_checkpoint(root, step=1)
    cfg_kwargs.setdefault("max_batch_size", 4)
    cfg_kwargs.setdefault("batch_sizes", (4,))
    cfg_kwargs.setdefault("sample_shapes", [(8, 16)])
    cfg = serve.ServeConfig(**cfg_kwargs)
    return serve.Server(make, root=root, config=cfg)


def test_poison_request_fails_alone(tmp_path):
    srv = _serve_fixture(tmp_path, max_wait_us=200000)
    try:
        inject.plan("serve_poison@poison-1")
        x = np.ones((4, 16), dtype="float32")
        futs = [srv.submit_async(x, request_id="req-%d" % i)
                for i in range(2)]
        bad = srv.submit_async(x, request_id="poison-1")
        futs.append(srv.submit_async(x, request_id="req-3"))
        for f in futs:                     # batch-mates all succeed
            assert f.result(timeout=60).shape == (4, 4)
        with pytest.raises(inject.InjectedFault, match="poison"):
            bad.result(timeout=60)
        assert telemetry.value("serve_poison_requests_total") == 1
        assert telemetry.value("serve_bisect_splits_total") >= 1
        # one poisoned request in one dispatch is one strike — far from
        # the default threshold, so the breaker stays closed
        assert all(b["state"] == "closed"
                   for b in srv.breakers().values())
        # and the scheduler thread survived
        out = srv.submit(x, request_id="after")
        assert out.shape == (4, 4)
    finally:
        srv.shutdown()


def test_breaker_state_machine_unit():
    clock = {"t": 0.0}
    b = CircuitBreaker(threshold=2, cooldown=10.0,
                       clock=lambda: clock["t"])
    assert b.allow() and not b.blocked()
    assert not b.record_failure()
    assert b.record_failure()              # 2nd consecutive -> open
    assert b.state()["state"] == "open" and b.blocked()
    assert not b.allow()
    assert 0 < b.retry_after() <= 10.0
    clock["t"] += 10.0
    assert b.allow()                       # half-open trial admitted
    assert b.state()["state"] == "half-open"
    assert b.blocked()                     # trial in flight: submits
    assert not b.allow()                   # and dispatches fast-reject
    assert b.record_failure()              # trial failed -> re-open
    assert b.state()["state"] == "open"
    clock["t"] += 10.0
    assert b.allow()
    b.record_success()                     # trial passed -> closed
    assert b.state()["state"] == "closed" and b.trips == 2
    assert not b.blocked()
    b.record_failure()
    b.record_success()                     # success resets the count
    assert not b.record_failure()


def test_breaker_half_open_admits_exactly_one_trial():
    """Concurrent dispatches racing the half-open transition: exactly
    ONE wins the trial; the rest reject until the trial resolves.  A
    trial whose outcome never lands self-heals after one cooldown."""
    import threading

    clock = {"t": 0.0}
    b = CircuitBreaker(threshold=1, cooldown=5.0,
                       clock=lambda: clock["t"])
    assert b.record_failure()              # open
    clock["t"] += 5.0                      # cooldown elapsed
    results = []
    lock = threading.Lock()
    barrier = threading.Barrier(8)

    def worker():
        barrier.wait()
        ok = b.allow()
        with lock:
            results.append(ok)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sum(results) == 1, results      # exactly one trial
    assert b.state()["trial_inflight"]
    assert b.blocked()                     # submit-side fast-reject too
    b.record_success()                     # trial resolves -> closed
    assert b.state()["state"] == "closed" and not b.blocked()
    # stuck trial self-heals: admitted but never resolved, a fresh
    # trial is allowed one cooldown later
    assert b.record_failure()              # re-open
    clock["t"] += 5.0
    assert b.allow() and not b.allow()     # trial admitted, in flight
    clock["t"] += 5.0                      # outcome never landed
    assert b.allow()                       # replacement trial admitted


def test_breaker_half_open_trial_under_concurrent_dispatch(tmp_path):
    """Serve-level satellite contract: with the bucket half-open and a
    burst of concurrent requests, exactly one trial request reaches
    the model (and fails, re-opening the breaker) while every other
    request fast-rejects with BucketQuarantined."""
    import concurrent.futures as cf

    srv = _serve_fixture(tmp_path, breaker_threshold=1,
                         breaker_cooldown_s=0.3, max_batch_size=1,
                         max_wait_us=1000)
    try:
        inject.plan("serve_poison@*")      # every dispatch fails
        x = np.ones((4, 16), dtype="float32")
        with pytest.raises(inject.InjectedFault):
            srv.submit(x, request_id="open-it")   # 1 strike -> open
        assert any(b["state"] == "open"
                   for b in srv.breakers().values())
        time.sleep(0.35)                   # cooldown -> half-open
        futs = [srv.submit_async(x, request_id="burst-%d" % i)
                for i in range(6)]
        outcomes = {"poison": 0, "quarantined": 0}
        for f in futs:
            try:
                f.result(timeout=60)
                raise AssertionError("a burst request was served")
            except inject.InjectedFault:
                outcomes["poison"] += 1
            except serve.BucketQuarantined:
                outcomes["quarantined"] += 1
        assert outcomes == {"poison": 1, "quarantined": 5}, outcomes
        assert any(b["state"] == "open"
                   for b in srv.breakers().values())
        # trial succeeds once the poison clears: bucket recovers
        inject.clear()
        time.sleep(0.35)
        assert srv.submit(x, request_id="recover").shape == (4, 4)
        assert all(b["state"] == "closed"
                   for b in srv.breakers().values())
    finally:
        srv.shutdown()


def test_breaker_opens_visible_in_healthz_and_recovers(tmp_path):
    import json
    import urllib.request

    srv = _serve_fixture(tmp_path, breaker_threshold=2,
                         breaker_cooldown_s=0.3, max_wait_us=1000)
    host, port = srv.start_http()
    base = "http://%s:%d" % (host, port)
    try:
        inject.plan("serve_poison@*")      # every request poisons
        x = np.ones((4, 16), dtype="float32")
        for _ in range(2):                 # 2 failed dispatches -> open
            with pytest.raises(inject.InjectedFault):
                srv.submit(x, request_id="any")
        # open breaker: fast-reject at submit, visible in /healthz,
        # scheduler thread alive
        with pytest.raises(serve.BucketQuarantined):
            srv.submit(x, request_id="more")
        assert srv.healthy()
        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            body = json.loads(r.read())
        assert body["status"] == "degraded"
        assert any(b["state"] == "open"
                   for b in body["breakers"].values()), body
        # HTTP /predict against the quarantined bucket: 503 + Retry-After
        req = urllib.request.Request(
            base + "/predict",
            data=json.dumps({"inputs": x.tolist()}).encode(),
            headers={"X-Request-Id": "q-1"})
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=30)
        assert err.value.code == 503
        assert err.value.headers.get("Retry-After")
        assert err.value.headers.get("X-Request-Id") == "q-1"
        # cooldown passes, faults cleared: the half-open trial succeeds
        # and the breaker closes
        inject.clear()
        time.sleep(0.35)
        out = srv.submit(x, request_id="recovered")
        assert out.shape == (4, 4)
        assert all(b["state"] == "closed"
                   for b in srv.breakers().values())
        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            assert json.loads(r.read())["status"] == "ok"
    finally:
        srv.shutdown()


def test_overload_maps_to_503_with_retry_after(tmp_path):
    import json
    import threading
    import urllib.request

    srv = _serve_fixture(tmp_path, queue_depth=1, max_wait_us=1000)
    host, port = srv.start_http()
    base = "http://%s:%d" % (host, port)
    gate = threading.Event()
    real = srv.runner.run_batch

    def gated(requests):
        gate.wait()
        return real(requests)

    srv.runner.run_batch = gated
    try:
        x = np.ones((4, 16), dtype="float32")
        blocker = srv.submit_async(x)      # stalls in run_batch
        for _ in range(500):
            if srv.queue_depth() == 0:
                break
            time.sleep(0.01)
        filler = srv.submit_async(x)       # fills the depth-1 queue
        req = urllib.request.Request(
            base + "/predict",
            data=json.dumps({"inputs": x.tolist()}).encode(),
            headers={"X-Request-Id": "ovl-1"})
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=30)
        assert err.value.code == 503       # was 429; satellite contract
        assert err.value.headers.get("Retry-After") == "1"
        assert err.value.headers.get("X-Request-Id") == "ovl-1"
        gate.set()
        blocker.result(timeout=60)
        filler.result(timeout=60)
    finally:
        gate.set()
        srv.shutdown()
