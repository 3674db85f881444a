"""What ``parallel.FusedTrainer.step`` leaves for a tracer to read: host
spans (``mx.step`` and its children, through ``mx.trace.span``) in the
flight ring and in the profiler's own trace, ``jax.named_scope``s for
forward, backward and optimizer in the step program, an ``mx.wait`` span at
the framework's sync point — and nothing else: the same numbers with
tracing on and off."""
import jax
import numpy as np
import pytest
from common import xplane_find, xplane_host_lines

import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, trace
from mxnet_tpu.gluon import nn

CHILDREN = ["mx.step.stage", "mx.step.rng", "mx.step.scalars",
            "mx.step.dispatch"]
SCOPES = ["jvp(mx.step.forward)", "transpose(jvp(mx.step.forward))",
          "mx.step.optimizer"]


@pytest.fixture(autouse=True)
def _ring():
    trace.enable()
    trace.clear()
    yield
    trace.enable()
    trace.clear()


def _trainer(mesh=None, **kw):
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu", in_units=8),
            nn.Dense(10, in_units=32))
    net.initialize()
    return parallel.FusedTrainer(
        net, loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        mesh=mesh, **kw)


def _batch(n=16, seed=0):
    rs = np.random.RandomState(seed)
    return (rs.rand(n, 8).astype(np.float32),
            rs.randint(0, 10, n).astype(np.int32))


def _steps_in_ring():
    """[(root event, [its children, in the order they ran])]."""
    evs = trace.events()
    roots = sorted((e for e in evs if e["name"] == "mx.step"),
                   key=lambda e: e["ts"])
    return [(r, sorted((e for e in evs if e["parent"] == r["span"]),
                       key=lambda e: e["ts"])) for r in roots]


def test_step_leaves_root_and_children_in_the_ring():
    trainer = _trainer()
    x, y = _batch()
    trainer.step(x, y)
    trainer.step(x, y)
    steps = _steps_in_ring()
    assert [r["args"]["step_num"] for r, _ in steps] == [0, 1]
    for root, kids in steps:
        assert [k["name"] for k in kids] == CHILDREN
        assert all(k["trace"] == root["trace"] for k in kids)
        assert all(root["ts"] <= k["ts"] and
                   k["ts"] + k["dur"] <= root["ts"] + root["dur"] + 1e-6
                   for k in kids)
        stage, _rng, _scalars, dispatch = kids
        assert stage["args"] == {"arrays": 2, "put_bytes": 0}
        # params + momentum + step, lr, key + x, y
        assert dispatch["args"]["leaves"] == 4 + 4 + 3 + 2
    # dotted names are no Prometheus names: no histogram was made
    assert mx.telemetry.get_metric("mx.step_seconds") is None


def test_stage_counts_the_bytes_put_on_a_mesh():
    trainer = _trainer(mesh=parallel.make_mesh({"dp": 4}))
    x, y = _batch()
    trainer.step(x, y)
    (_root, kids), = _steps_in_ring()
    assert kids[0]["args"] == {"arrays": 2,
                               "put_bytes": x.nbytes + y.nbytes}


def test_recompile_instant_only_when_the_shapes_change():
    trainer = _trainer()
    trainer.step(*_batch(16))
    trainer.step(*_batch(16, seed=1))

    def recompiles():
        return [e for e in trace.events() if e["name"] == "mx.step.recompile"]

    assert recompiles() == []          # the first program is no recompile
    trainer.step(*_batch(8))
    (ev,) = recompiles()
    assert ev["ph"] == "i"
    assert ev["args"] == {"step_num": 2, "shapes": "8x8;8"}
    assert ev["parent"] == _steps_in_ring()[2][0]["span"]
    trainer.step(*_batch(8, seed=1))
    trainer.step(*_batch(16))          # seen before: still in the cache
    assert len(recompiles()) == 1


@pytest.mark.parametrize("how", ["plain", "grad_accum", "dp_mesh", "zero2"])
def test_step_program_names_forward_backward_optimizer(how):
    kw = {"plain": {}, "grad_accum": {"grad_accum": 2},
          "dp_mesh": {"mesh": parallel.make_mesh({"dp": 4})},
          "zero2": {"mesh": parallel.make_mesh({"dp": 4}), "zero": 2}}[how]
    trainer = _trainer(**kw)
    text = trainer._lower(*_batch()).as_text(debug_info=True)
    for scope in SCOPES:
        assert scope in text, scope
    # the optimizer's arithmetic, and nothing of the model, is under its
    # scope; the model's products are under the forward's or the backward's
    dots = [ln for ln in text.splitlines() if "dot_general" in ln
            and "loc(" in ln and "mx.step" in ln]
    assert dots and not any("mx.step.optimizer" in ln for ln in dots)


def _run(n_steps, enabled):
    (trace.enable if enabled else trace.disable)()
    try:
        trainer = _trainer(dtype="bfloat16")
        losses = [trainer.step(*_batch(seed=i)).asnumpy()
                  for i in range(n_steps)]
        params = {n: np.asarray(v) for n, v in trainer.params.items()}
    finally:
        trace.enable()
    return losses, params


def test_tracing_changes_no_number():
    """Scopes and spans are metadata: losses and parameters after three
    steps are bitwise the same with mx.trace on and off."""
    on, off = _run(3, True), _run(3, False)
    for a, b in zip(on[0], off[0]):
        assert a.tobytes() == b.tobytes()
    assert on[1].keys() == off[1].keys()
    for n in on[1]:
        assert on[1][n].tobytes() == off[1][n].tobytes(), n


def test_step_spans_in_the_profilers_own_trace(tmp_path):
    """A real jax.profiler session: mx.step (a step annotation, step_num on
    the event) and its four children on the calling thread's line, nested;
    the loss fetch as mx.wait after it — also with the ring disabled."""
    trainer = _trainer()
    x, y = _batch()
    trainer.step(x, y)                       # compile outside the session
    trace.disable()
    trace.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            with jax.profiler.TraceAnnotation("test.loop"):
                loss = trainer.step(x, y)
            loss.asnumpy()
    finally:
        jax.profiler.stop_trace()
        trace.enable()
    lines = xplane_host_lines(str(tmp_path))
    steps = xplane_find(lines, "mx.step")
    assert [s[3]["step_num"] for s in steps] == [1, 2]
    assert all(s[3]["_r"] == 1 for s in steps)       # a step annotation
    loops = xplane_find(lines, "test.loop")
    for (line, lo, hi, _), loop in zip(steps, loops):
        assert loop[0] == line and loop[1] <= lo and hi <= loop[2]
        kids = [xplane_find(lines, n) for n in CHILDREN]
        inside = [[k for k in found if k[0] == line and lo <= k[1]
                   and k[2] <= hi] for found in kids]
        assert [len(k) for k in inside] == [1, 1, 1, 1]
        starts = [k[0][1] for k in inside]
        assert starts == sorted(starts)
    assert inside[3][0][3]["leaves"] == 13
    waits = xplane_find(lines, "mx.wait")
    assert len(waits) == 2 and waits[0][3]["nbytes"] == 4
    assert waits[0][1] >= steps[0][2]
    assert trace.events() == []                      # the ring was off


def test_wait_span_at_the_sync_points():
    a = nd.array(np.ones((2, 3), np.float32))
    trace.clear()
    a.asnumpy()
    a.wait_to_read()
    waits = [e for e in trace.events() if e["name"] == "mx.wait"]
    assert [w["args"] for w in waits] == [{"nbytes": 24}, {"nbytes": 24}]
