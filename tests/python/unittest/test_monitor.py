"""mx.monitor tests (ISSUE 8): fused stat programs (correctness, one
build per group, zero per-step retraces), nonfinite sentinel policies
(skip_step bit-parity with never stepping — fused AND eager paths,
raise, warn), divergence dumps naming the offending group, the JSONL
health stream, the serve-side output guard, and the estimator
TrainingHealthHandler."""
import json
import math
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, monitor, nd, telemetry, trace
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.monitor import divergence, sentinel, stats


@pytest.fixture(autouse=True)
def _monitor_on(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TRACE_DUMP_MIN_SECONDS", "0")
    monkeypatch.setenv("MXNET_TRACE_DUMP_DIR", str(tmp_path / "dumps"))
    monkeypatch.delenv("MXNET_MONITOR_SENTINEL", raising=False)
    monkeypatch.delenv("MXNET_MONITOR_STREAM", raising=False)
    tel_was = telemetry.ENABLED
    telemetry.enable()
    telemetry.reset()
    trace.enable()  # divergence dumps are the flight recorder's
    monitor.reset()
    monitor.enable()
    yield
    monitor.flush(timeout=10.0)
    monitor.disable()
    monitor.reset()
    telemetry.reset()
    if not tel_was:
        telemetry.disable()


def _params(spec, grad_seed=3):
    """Bare initialized Parameters with deterministic synthetic grads
    (the test_trainer_fused recipe)."""
    rs = np.random.RandomState(grad_seed)
    params = {}
    for k, (shape, kw) in enumerate(spec):
        p = gluon.Parameter(name="p%d" % k, shape=shape,
                            dtype="float32", **kw)
        p.initialize(init="xavier" if len(shape) > 1 else "zeros")
        g = rs.randn(*shape).astype("float32")
        p.grad()._data = nd.array(g)._data
        params["p%d" % k] = p
    return params


_SPEC = [((8, 4), {}), ((8,), {}), ((4, 8), {"lr_mult": 0.5})]


def _trainer(optname="adam", opt_params=None, seed=0):
    mx.random.seed(seed)
    params = _params(_SPEC)
    return params, gluon.Trainer(params, optname,
                                 dict(opt_params
                                      or {"learning_rate": 0.01}))


def _poison(params, value=np.inf):
    p = list(params.values())[0]
    p.grad()._data = nd.array(
        np.full(p.shape, value, np.float32))._data


def _state_of(trainer):
    """Bitwise-comparable snapshot of everything the skip contract
    protects: params, optimizer state leaves, update counts."""
    import jax

    leaves = {}
    for i, st in trainer._states.items():
        leaves[i] = [np.asarray(x._data) for x in
                     jax.tree_util.tree_leaves(st)
                     if hasattr(x, "_data")]
    return ({k: p.data().asnumpy().copy()
             for k, p in zip(trainer._param_names, trainer._params)},
            leaves,
            dict(trainer._optimizer._index_update_count),
            trainer._optimizer.num_update,
            trainer._step_count)


def _assert_state_equal(a, b):
    wa, sa, ca, na, ka = a
    wb, sb, cb, nb, kb = b
    assert wa.keys() == wb.keys()
    for k in wa:
        np.testing.assert_array_equal(wa[k], wb[k])
    assert sa.keys() == sb.keys()
    for i in sa:
        assert len(sa[i]) == len(sb[i])
        for x, y in zip(sa[i], sb[i]):
            np.testing.assert_array_equal(x, y)
    assert ca == cb
    assert na == nb
    assert ka == kb


# ---------------------------------------------------------------------------
# feature flag + stat program correctness
# ---------------------------------------------------------------------------

def test_monitor_feature_flag():
    from mxnet_tpu import runtime

    assert runtime.features.is_enabled("MONITOR")
    assert mx.monitor is monitor
    monitor.disable()
    assert not runtime.features.is_enabled("MONITOR")
    monitor.enable()


def test_sentinel_policy_validation(monkeypatch):
    monkeypatch.setenv("MXNET_MONITOR_SENTINEL", "skip")  # typo
    with pytest.raises(MXNetError, match="skip_step"):
        sentinel.policy()


def test_stat_program_matches_numpy():
    import jax.numpy as jnp

    w = [jnp.asarray(np.array([[1.0, -2.0], [3.0, 4.0]], np.float32)),
         jnp.asarray(np.array([0.5, -0.5], np.float32))]
    g = [jnp.asarray(np.array([[np.inf, 1.0], [np.nan, -3.0]],
                              np.float32)),
         jnp.asarray(np.array([2.0, 0.0], np.float32))]
    st = stats.unpack(np.asarray(stats.group_stats(w, g)))
    assert st["w_nonfinite"] == 0
    assert st["g_nonfinite"] == 2
    np.testing.assert_allclose(
        st["w_norm"], math.sqrt(1 + 4 + 9 + 16 + 0.25 + 0.25),
        rtol=1e-6)
    # nonfinite elements are zeroed before the norm/max reductions
    np.testing.assert_allclose(st["g_norm"],
                               math.sqrt(1 + 9 + 4), rtol=1e-6)
    assert st["w_max_abs"] == 4.0
    assert st["g_max_abs"] == 3.0


def test_one_program_per_group_zero_retraces():
    params, trainer = _trainer()
    for _ in range(4):
        trainer.update(2)
    assert monitor.flush(timeout=10.0)
    groups = len(trainer._mt_groups)
    assert groups == 2  # lr_mult split
    assert telemetry.value("monitor_stat_builds_total") == groups
    assert telemetry.value("monitor_stat_programs_total") == groups * 4
    # the fused update engine is untouched by monitoring: still one
    # build per group, one program per group per step
    assert telemetry.value("trainer_fused_builds_total") == groups
    assert telemetry.value("trainer_fused_apply_total") == groups * 4
    s = monitor.summary()
    assert s["steps"] == 4
    assert s["grad_global_norm_last"] > 0
    assert s["grad_global_norm_max"] >= s["grad_global_norm_last"]
    assert s["nonfinite_steps"] == 0


def test_monitor_off_costs_nothing():
    monitor.disable()
    params, trainer = _trainer()
    for _ in range(2):
        trainer.update(2)
    assert telemetry.value("monitor_stat_builds_total") == 0
    assert telemetry.value("monitor_stat_programs_total") == 0
    assert monitor.summary()["steps"] == 0
    assert trainer._step_count == 2  # updates applied normally


def test_gauges_and_group_values():
    params, trainer = _trainer()
    trainer.update(2)
    assert monitor.flush(timeout=10.0)
    values = monitor.group_values()
    assert len(values) == 2
    for label, st in values.items():
        assert label.startswith("Adam:")
        assert st["g_norm"] > 0
        assert st["w_norm"] > 0
        assert telemetry.value("monitor_grad_norm",
                               {"group": label}) == \
            pytest.approx(st["g_norm"])
    assert telemetry.value("monitor_grad_global_norm") == \
        pytest.approx(math.sqrt(sum(st["g_norm"] ** 2
                                    for st in values.values())),
                      rel=1e-5)


# ---------------------------------------------------------------------------
# sentinel: skip_step bit-parity (the satellite acceptance test)
# ---------------------------------------------------------------------------

def _skip_parity(monkeypatch, eager):
    if eager:
        monkeypatch.setenv("MXNET_MULTI_TENSOR", "0")
    monkeypatch.setenv("MXNET_MONITOR_SENTINEL", "skip_step")
    # A steps twice cleanly, then gets poisoned grads; B steps twice
    # cleanly and never sees the third step.  After the skipped step A
    # must be BIT-IDENTICAL to B — params, every optimizer-state leaf,
    # _index_update_count, num_update, and the trainer step counter.
    params_a, ta = _trainer()
    params_b, tb = _trainer()
    for _ in range(2):
        ta.update(2)
        tb.update(2)
    _poison(params_a, np.inf)
    ta.update(2)
    _assert_state_equal(_state_of(ta), _state_of(tb))
    assert ta._step_count == 2
    assert telemetry.value("monitor_skipped_steps_total") == 1
    assert telemetry.value("monitor_sentinel_trips_total",
                           {"policy": "skip_step"}) == 1
    # the run recovers: a later healthy step applies normally
    rs = np.random.RandomState(9)
    for (pa, pb) in zip(params_a.values(), params_b.values()):
        g = rs.randn(*pa.shape).astype(np.float32)
        pa.grad()._data = nd.array(g)._data
        pb.grad()._data = nd.array(g)._data
    ta.update(2)
    tb.update(2)
    _assert_state_equal(_state_of(ta), _state_of(tb))
    assert ta._step_count == 3


def test_skip_step_bit_parity_fused(monkeypatch):
    _skip_parity(monkeypatch, eager=False)


def test_skip_step_bit_parity_eager(monkeypatch):
    _skip_parity(monkeypatch, eager=True)


def test_skip_step_nan_first_step(monkeypatch):
    # grads nonfinite on the VERY FIRST step: freshly-created (all
    # zero) state slots stay zero and counts stay empty — identical to
    # a trainer that initialized states but never stepped
    monkeypatch.setenv("MXNET_MONITOR_SENTINEL", "skip_step")
    params_a, ta = _trainer()
    params_b, tb = _trainer()
    _poison(params_a, np.nan)
    ta.update(2)
    for i, param in enumerate(tb._params):
        tb._maybe_init_states(i, param)
    _assert_state_equal(_state_of(ta), _state_of(tb))
    assert ta._optimizer._index_update_count == {}


def test_raise_policy(monkeypatch):
    monkeypatch.setenv("MXNET_MONITOR_SENTINEL", "raise")
    params, trainer = _trainer()
    before = {k: p.data().asnumpy().copy() for k, p in params.items()}
    _poison(params)
    with pytest.raises(MXNetError, match="nonfinite gradients"):
        trainer.update(2)
    for k, p in params.items():
        np.testing.assert_array_equal(p.data().asnumpy(), before[k])
    assert trainer._step_count == 0


def test_warn_policy_applies_update(monkeypatch, caplog):
    import logging

    monkeypatch.setenv("MXNET_MONITOR_SENTINEL", "warn")
    params, trainer = _trainer()
    _poison(params)
    with caplog.at_level(logging.WARNING, "mxnet_tpu.monitor"):
        trainer.update(2)
        assert monitor.flush(timeout=10.0)
    # warn does NOT veto: the step applied (and poisoned the params —
    # exactly why skip_step exists)
    assert trainer._step_count == 1
    assert not np.isfinite(
        list(params.values())[0].data().asnumpy()).all()
    assert telemetry.value("monitor_sentinel_trips_total",
                           {"policy": "warn"}) == 1
    assert telemetry.value("monitor_nonfinite_steps_total") == 1
    assert any("nonfinite gradients" in r.message for r in caplog.records)
    assert monitor.summary()["skipped_steps"] == 0


# ---------------------------------------------------------------------------
# divergence dumps
# ---------------------------------------------------------------------------

def _wait_for_dump(dump_dir, reason="divergence", timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if os.path.isdir(dump_dir):
            found = [f for f in os.listdir(dump_dir)
                     if reason in f and f.endswith(".json")]
            if found:
                return sorted(found)
        time.sleep(0.05)
    return []


def test_skip_step_divergence_dump_names_group(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_MONITOR_SENTINEL", "skip_step")
    params, trainer = _trainer()
    trainer.update(2)  # a healthy step seeds the flight ring
    _poison(params)
    trainer.update(2)
    dumps = _wait_for_dump(str(tmp_path / "dumps"))
    assert len(dumps) == 1, dumps
    with open(str(tmp_path / "dumps" / dumps[0])) as f:
        doc = json.load(f)
    meta = doc["traceEvents"][0]
    assert meta["args"]["reason"] == "divergence"
    assert meta["args"]["kind"] == "nonfinite_grads"
    assert meta["args"]["group"].startswith("Adam:p0")
    assert meta["args"]["policy"] == "skip_step"
    assert meta["args"]["grad_nonfinite"] == 32  # the (8,4) param
    assert telemetry.value("trace_dumps_total",
                           {"reason": "divergence"}) == 1


def test_grad_spike_detector():
    det = divergence.DivergenceDetector(spike_factor=5.0, window=16,
                                        min_samples=4)
    with trace.span("seed_ring"):  # dump needs a non-empty ring
        pass
    for _ in range(6):
        assert det.observe_grad_norm(1.0) is None
    path = det.observe_grad_norm(50.0)
    assert path is not None and "divergence" in path
    assert det.state()["spikes"] == 1
    # the spike joins the window: an equal follow-up is not a new spike
    assert det.observe_grad_norm(50.0) is None


def test_spike_detector_window_below_min_samples():
    # a window shorter than min_samples (default 8) must still warm up
    # and fire — it used to be silently dead for window 2..7
    det = divergence.DivergenceDetector(spike_factor=5.0, window=4)
    with trace.span("seed_ring"):
        pass
    for _ in range(6):
        assert det.observe_grad_norm(1.0) is None
    assert det.observe_grad_norm(1000.0) is not None
    assert det.state()["spikes"] == 1
    assert det.state()["window"] == 4  # configured, not fill


def test_ring_overflow_keeps_step_accounting(monkeypatch):
    monkeypatch.setenv("MXNET_MONITOR_RING", "1")
    monkeypatch.setenv("MXNET_MONITOR_SENTINEL", "skip_step")
    import mxnet_tpu.monitor.core as core

    # stall the publisher by monkeypatching _publish to block until
    # released, then overflow the 1-slot ring with a skipped entry
    import threading

    gate = threading.Event()
    orig = core._publish

    def slow_publish(entry):
        gate.wait(10.0)
        orig(entry)

    monkeypatch.setattr(core, "_publish", slow_publish)
    params, trainer = _trainer()
    trainer.update(2)       # entry 1: picked up by the publisher
    trainer.update(2)       # entry 2: sits in the 1-slot ring
    _poison(params)
    trainer.update(2)       # skipped entry displaces entry 2
    gate.set()
    assert monitor.flush(timeout=10.0)
    s = monitor.summary()
    # the displaced healthy step still counts as observed, and the
    # skipped/nonfinite accounting survives whichever entry dropped
    assert s["steps"] == 3, s
    assert s["dropped"] == 1, s
    assert s["skipped_steps"] == 1, s
    assert s["nonfinite_steps"] == 1, s


def test_spike_factor_zero_disables():
    det = divergence.DivergenceDetector(spike_factor=0.0, window=8,
                                        min_samples=2)
    for v in (1.0, 1.0, 1.0, 1e9):
        assert det.observe_grad_norm(v) is None
    assert det.state()["spikes"] == 0


def test_loss_nan_and_plateau():
    det = divergence.DivergenceDetector(plateau_window=3)
    with trace.span("seed_ring"):
        pass
    assert det.observe_loss(float("nan")) is not None
    assert det.state()["loss_nonfinite"] == 1
    # decreasing loss: no plateau
    for v in (5.0, 4.0, 3.0):
        assert det.observe_loss(v) is None
    # 3 observations without a new best -> one plateau episode
    assert det.observe_loss(3.5) is None
    assert det.observe_loss(3.5) is None
    path = det.observe_loss(3.4)
    assert path is not None
    assert det.state()["plateaus"] == 1
    assert det.observe_loss(3.4) is None  # still the same episode
    assert det.observe_loss(1.0) is None  # improvement ends the episode


# ---------------------------------------------------------------------------
# JSONL stream
# ---------------------------------------------------------------------------

def test_jsonl_stream(tmp_path, monkeypatch):
    path = str(tmp_path / "health.jsonl")
    monkeypatch.setenv("MXNET_MONITOR_STREAM", path)
    monkeypatch.setenv("MXNET_MONITOR_SENTINEL", "skip_step")
    params, trainer = _trainer()
    trainer.update(2)
    _poison(params)
    trainer.update(2)
    assert monitor.flush(timeout=10.0)
    with open(path) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    assert len(lines) == 2
    # seq disambiguates where step can't: a skipped step and its retry
    # share a trainer step id, but every line gets a fresh seq
    assert [ln["seq"] for ln in lines] == [1, 2]
    assert [ln["step"] for ln in lines] == [0, 1]
    assert not lines[0]["skipped"]
    assert lines[0]["grad_global_norm"] > 0
    assert lines[1]["skipped"]
    assert sum(g["nonfinite_grad"]
               for g in lines[1]["groups"].values()) == 32
    assert set(lines[0]["groups"]) == set(monitor.group_values())


def test_monitor_interval(monkeypatch):
    monkeypatch.setenv("MXNET_MONITOR_INTERVAL", "2")
    params, trainer = _trainer()
    for _ in range(4):
        trainer.update(2)
    assert monitor.flush(timeout=10.0)
    # steps 0 and 2 observed; 1 and 3 skipped by the sampling interval
    assert monitor.summary()["steps"] == 2


# ---------------------------------------------------------------------------
# serve output guard
# ---------------------------------------------------------------------------

class _NaNNet(gluon.HybridBlock):
    def __init__(self, poison=True):
        super().__init__()
        self._poison = poison

    def forward(self, x):
        return x * float("nan") if self._poison else x * 2.0


def test_serve_output_guard():
    from mxnet_tpu import serve

    runner = serve.ModelRunner(_NaNNet(), batch_sizes=(2,),
                               sample_shapes=[(4,)])
    srv = serve.Server(runner=runner)
    try:
        out = srv.submit(np.ones(4, np.float32))
        assert not np.isfinite(out).all()
        assert telemetry.value("serve_nonfinite_outputs_total") > 0
        assert telemetry.value("serve_nonfinite_batches_total") == 1
        health = srv.stats()["health"]
        assert health["monitor"] is True
        assert health["nonfinite_output_elems"] > 0
        assert health["nonfinite_batches"] == 1
    finally:
        srv.shutdown()


class _PadPoisonNet(gluon.HybridBlock):
    """Finite on real inputs, Inf exactly on zero-filled padding rows
    (1/x) — the false-positive shape the guard must NOT count."""

    def forward(self, x):
        return 1.0 / x


def test_serve_output_guard_ignores_padding_rows():
    from mxnet_tpu import serve

    # batch bucket 4 with a single request: 3 padding rows go Inf, the
    # served row stays finite — zero health events
    runner = serve.ModelRunner(_PadPoisonNet(), batch_sizes=(4,),
                               sample_shapes=[(4,)])
    srv = serve.Server(runner=runner)
    try:
        out = srv.submit(np.ones(4, np.float32))
        assert np.isfinite(out).all()
        assert telemetry.value("serve_nonfinite_outputs_total") == 0
        assert telemetry.value("serve_nonfinite_batches_total") == 0
    finally:
        srv.shutdown()


def test_serve_output_guard_clean_and_disabled():
    from mxnet_tpu import serve

    runner = serve.ModelRunner(_NaNNet(poison=False), batch_sizes=(2,),
                               sample_shapes=[(4,)])
    srv = serve.Server(runner=runner)
    try:
        srv.submit(np.ones(4, np.float32))
        assert telemetry.value("serve_nonfinite_batches_total") == 0
        monitor.disable()
        srv.submit(np.ones(4, np.float32))
        assert telemetry.value("serve_nonfinite_batches_total") == 0
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# estimator integration
# ---------------------------------------------------------------------------

def _loader(n=16):
    rs = np.random.RandomState(0)
    x = rs.randn(n, 4).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.float32)
    ds = gluon.data.ArrayDataset(x, y)
    return gluon.data.DataLoader(ds, batch_size=4)


def test_training_health_handler_stops_on_nan():
    from mxnet_tpu.gluon.contrib import estimator as est

    net = nn.Dense(2, in_units=4)
    net.initialize()

    calls = []

    def nan_loss(pred, label):
        calls.append(1)
        return (pred * float("nan")).mean()

    e = est.Estimator(net, nan_loss,
                      trainer=gluon.Trainer(net.collect_params(),
                                            "sgd",
                                            {"learning_rate": 0.1}))
    handler = est.TrainingHealthHandler()
    e.fit(_loader(), epochs=3, event_handlers=[handler])
    # first NaN batch stops the run: one batch, not 3 epochs x 4
    assert len(calls) == 1
    assert handler.nonfinite_batches == 1
    assert handler.stop_training
    assert divergence.DETECTOR.state()["loss_nonfinite"] >= 1


def test_training_health_handler_healthy_run():
    from mxnet_tpu.gluon.contrib import estimator as est

    net = nn.Dense(2, in_units=4)
    net.initialize()
    e = est.Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                      trainer=gluon.Trainer(net.collect_params(),
                                            "adam",
                                            {"learning_rate": 0.01}))
    handler = est.TrainingHealthHandler()
    e.fit(_loader(), epochs=2, event_handlers=[handler])
    assert handler.nonfinite_batches == 0
    assert not handler.stop_training
    assert monitor.flush(timeout=10.0)
    assert monitor.summary()["steps"] == 8  # 2 epochs x 4 batches
