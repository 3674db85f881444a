#!/usr/bin/env python
"""mx.monitor smoke (make monitor-smoke, CPU).

5-step imperative training with an Inf gradient INJECTED before step 3,
under ``MXNET_MONITOR=1 MXNET_MONITOR_SENTINEL=skip_step`` — the exact
configuration a guarded training run arms — asserting the
acceptance contracts end to end:

1. the poisoned step is SKIPPED whole: params/optimizer state/update
   counts bit-identical to before the step, trainer step_count frozen;
2. exactly ONE divergence flight-record dump is written, naming the
   offending parameter group;
3. the MXNET_MONITOR_STREAM JSONL parses: 5 lines, the injected step
   flagged ``skipped`` with the nonfinite count in its group row;
4. one stat program build per parameter group and ZERO per-step
   retraces (monitor_stat_builds_total == groups across all 5 steps),
   with the fused update engine untouched (trainer_fused_builds_total
   == groups).
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["MXNET_MONITOR"] = "1"
os.environ["MXNET_MONITOR_SENTINEL"] = "skip_step"
os.environ["MXNET_TRACE_DUMP_MIN_SECONDS"] = "0"

_TMP = tempfile.mkdtemp(prefix="mxnet_monitor_smoke_")
os.environ["MXNET_MONITOR_STREAM"] = os.path.join(_TMP, "health.jsonl")
os.environ["MXNET_TRACE_DUMP_DIR"] = _TMP

STEPS = 5
POISON_STEP = 2  # 0-based: "step 3"


def main():
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, monitor, nd, telemetry
    from mxnet_tpu.gluon import nn

    telemetry.enable()
    mx.random.seed(7)
    net = nn.HybridSequential()
    for _ in range(6):
        net.add(nn.Dense(16, in_units=16))
    net.initialize()
    params = net.collect_params()
    list(params.values())[-2].lr_mult = 0.5  # split a second group
    trainer = gluon.Trainer(params, "adam", {"learning_rate": 0.01})
    x = nd.array(np.random.RandomState(0).rand(4, 16).astype(np.float32))

    poisoned = list(params.values())[0]
    snap = {}
    for s in range(STEPS):
        with autograd.record():
            loss = (net(x) ** 2).mean()
        loss.backward()
        if s == POISON_STEP:
            snap["w"] = {k: p.data().asnumpy().copy()
                         for k, p in params.items()}
            snap["counts"] = dict(trainer._optimizer._index_update_count)
            snap["num_update"] = trainer._optimizer.num_update
            snap["step_count"] = trainer._step_count
            poisoned.grad()._data = nd.array(np.full(
                poisoned.grad().shape, np.inf, np.float32))._data
        trainer.step(4)
        if s == POISON_STEP:
            for k, p in params.items():
                np.testing.assert_array_equal(
                    p.data().asnumpy(), snap["w"][k],
                    err_msg="skip_step mutated parameter %s" % k)
            assert dict(trainer._optimizer._index_update_count) == \
                snap["counts"], "skip_step bumped _index_update_count"
            assert trainer._optimizer.num_update == snap["num_update"]
            assert trainer._step_count == snap["step_count"], \
                "skip_step advanced the trainer step counter"
    assert trainer._step_count == STEPS - 1
    assert monitor.flush(timeout=30.0), "publisher did not drain"

    s = monitor.summary()
    assert s["steps"] == STEPS, s
    assert s["nonfinite_steps"] == 1, s
    assert s["skipped_steps"] == 1, s
    print("[monitor-smoke] %d steps observed, 1 skipped (group table: "
          "%d groups)" % (s["steps"], len(monitor.group_values())))

    groups = len(trainer._mt_groups)
    assert groups == 2, "expected 2 update groups, got %d" % groups
    builds = telemetry.value("monitor_stat_builds_total")
    assert builds == groups, \
        "expected %d stat builds (1/group), saw %g — per-step retrace!" \
        % (groups, builds)
    fused_builds = telemetry.value("trainer_fused_builds_total")
    assert fused_builds == groups, \
        "monitor changed the fused update engine's builds (%g)" \
        % fused_builds
    assert telemetry.value("monitor_skipped_steps_total") == 1
    assert telemetry.value("monitor_sentinel_trips_total",
                           {"policy": "skip_step"}) == 1
    print("[monitor-smoke] %g stat builds for %d groups, fused engine "
          "untouched (%g builds)" % (builds, groups, fused_builds))

    # exactly one divergence dump, naming the offending group
    deadline = time.time() + 30.0
    dumps = []
    while time.time() < deadline:
        dumps = [f for f in os.listdir(_TMP) if "divergence" in f
                 and f.endswith(".json")]
        if dumps:
            break
        time.sleep(0.1)
    assert len(dumps) == 1, "expected exactly 1 divergence dump, " \
        "found %s" % dumps
    with open(os.path.join(_TMP, dumps[0])) as f:
        doc = json.load(f)
    meta = doc["traceEvents"][0]
    assert meta["name"] == "mx.trace.dump"
    assert meta["args"]["reason"] == "divergence", meta
    group = meta["args"].get("group", "")
    assert group.startswith("Adam:"), \
        "dump does not name the offending group: %r" % meta["args"]
    assert meta["args"]["kind"] == "nonfinite_grads"
    print("[monitor-smoke] divergence dump OK: %s (group %s)"
          % (dumps[0], group))

    # JSONL stream parses: STEPS lines, the poisoned one flagged
    with open(os.environ["MXNET_MONITOR_STREAM"]) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    assert len(lines) == STEPS, "stream has %d lines" % len(lines)
    flagged = [ln for ln in lines if ln["skipped"]]
    assert len(flagged) == 1, flagged
    bad = flagged[0]
    assert any(g["nonfinite_grad"] > 0 for g in bad["groups"].values())
    healthy = [ln for ln in lines if not ln["skipped"]]
    assert all(g["nonfinite_grad"] == 0
               for ln in healthy for g in ln["groups"].values())
    assert all(ln["grad_global_norm"] > 0 for ln in healthy)
    print("[monitor-smoke] JSONL stream OK: %d lines, step %d skipped"
          % (len(lines), bad["step"]))
    print("[monitor-smoke] OK")


if __name__ == "__main__":
    main()
