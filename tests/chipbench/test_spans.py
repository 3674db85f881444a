"""Tests of chipbench/spans.py, on the CPU: the arithmetic on a made-up
trace, the five readers silent (None, never 0) where a trace holds no ``mx.*``
span or scope, the protobuf reader against a file this JAX wrote, and each
fixture recorded on the chip repeating its numbers exactly.  No speed is read
here."""
import gzip
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "chipbench")


def _load(name, kind=""):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# the readers say `import spans`: run.py's directory is on sys.path when it
# runs as a script.  Here the module is put where that import finds it, and
# chipbench/ (with its trace.py) stays off the path of every other test
spans = sys.modules["spans"] = _load("spans")

MS = 1_000_000
FWD = "jit(step)/jvp(mx.step.forward)/dot_general"
BWD = "jit(step)/transpose(jvp(mx.step.forward))/dot_general"
OPT = "jit(step)/mx.step.optimizer/sub"


def _made_up():
    """Two steps.  Host (line 1): chipbench.step > mx.step > stage, rng,
    scalars, dispatch; then chipbench.fetch > mx.wait.  Device: a small
    program the rng span launches, 0.5 ms BEFORE that span by the clocks,
    then the step program with a forward, a backward under which a nested
    operation runs, an optimizer operation and one without a name."""
    host, modules, ops = [], [], []
    for k, t in enumerate((0, 20 * MS)):
        host += [
            [t, t + 5 * MS, "chipbench.step", 1, {}],
            [t + 1 * MS, t + 5 * MS, "mx.step", 1, {"step_num": k}],
            [t + 1 * MS, t + 2 * MS, "mx.step.stage", 1, {}],
            [t + 2 * MS, t + 2 * MS + MS // 2, "mx.step.rng", 1, {}],
            [t + 3 * MS, t + 5 * MS - MS // 2, "mx.step.dispatch", 1, {}],
            [t + 5 * MS, t + 18 * MS, "chipbench.fetch", 1, {}],
            [t + 6 * MS, t + 18 * MS, "mx.wait", 1, {}],
            [t + 7 * MS, t + 8 * MS, "mx.other_thread", 2, {}],
        ]
        modules += [[t + 3 * MS // 2, t + 3 * MS // 2 + 1000,
                     "jit__threefry_split(7)"],
                    [t + 6 * MS, t + 16 * MS, "jit_step(9)"]]
        ops += [[t + 3 * MS // 2, t + 3 * MS // 2 + 1000, "fusion.1", "", ""],
                [t + 6 * MS, t + 9 * MS, "fusion.2", FWD, "forward"],
                [t + 9 * MS, t + 13 * MS, "while.1", BWD,
                 "backward+optimizer"],
                [t + 10 * MS, t + 11 * MS, "fusion.3", FWD, ""],   # nested
                [t + 13 * MS, t + 15 * MS, "fusion.4", OPT, "optimizer"],
                [t + 15 * MS, t + 16 * MS, "copy.5", "", ""]]
    return {"host": sorted(host, key=lambda h: (h[0], -h[1])),
            "devices": {0: {"ops": sorted(ops), "modules": sorted(modules)}}}


def test_span_table_self_time_and_parents():
    s = spans.summary(_made_up())
    rows = s["spans"]
    assert rows["mx.step"] == {"count": 2, "median_ms": 4.0,
                               "self_median_ms": 1.0,  # 4 - 1 - 0.5 - 1.5
                               "parent": "chipbench.step"}
    assert rows["mx.step.dispatch"]["parent"] == "mx.step"
    assert rows["mx.step.dispatch"]["self_median_ms"] == 1.5
    assert rows["chipbench.step"]["self_median_ms"] == 1.0
    assert rows["chipbench.fetch"]["self_median_ms"] == 1.0
    assert rows["mx.wait"]["parent"] == "chipbench.fetch"
    # a span of another thread at the same time is nobody's child
    assert rows["mx.other_thread"]["parent"] is None
    assert rows["mx.wait"]["self_median_ms"] == 12.0
    assert s["metrics"]["step_pre_dispatch_ms"] == 2.0
    assert s["metrics"]["step_dispatch_ms"] == 1.5


def test_scopes_share_no_time_and_backward_is_not_forward():
    s = spans.summary(_made_up())
    sc = s["scopes"]
    assert sc["steps"] == 2 and sc["step_module_s"] == 0.020
    # the nested forward-named operation takes its 1 ms out of the while
    assert (sc["forward"], sc["backward"], sc["optimizer"],
            sc["unscoped"]) == (0.008, 0.006, 0.004, 0.002)
    assert sc["busy_s"] == 0.020
    # the while counts as backward, and holds optimizer instructions
    assert sc["holds_optimizer_s"] == 0.006
    m = s["metrics"]
    assert (m["step_forward_ms"], m["step_backward_ms"],
            m["step_optimizer_ms"]) == (4.0, 3.0, 2.0)
    assert spans.scope_of(BWD) == "backward"
    assert spans.scope_of("jit(step)/jvp(mx.step.forward)/transpose") == \
        "forward"                      # a transpose OPERATION of the forward
    assert spans.scope_of("jit(step)/jvp()/mul") == "unscoped"


def test_exclusive_on_overlapping_events():
    # a fusion 1..3, an asynchronous collective 2..4 over it, a gap, 6..9
    assert spans.exclusive([[1, 3], [2, 4], [6, 9]]) == [1, 2, 3]
    # nested twice, and an event that ends with its parent
    assert spans.exclusive([[0, 10], [2, 8], [3, 4], [8, 10]]) == [2, 5, 1, 2]
    assert spans.exclusive([]) == []


def test_clock_check_and_gap_names_after_the_shift():
    loaded = _made_up()
    s = spans.summary(loaded)
    # the rng span starts at 2.0 ms, its program shows at 1.5 ms
    assert s["skew_floor_ms"] == 0.5
    # the longest gap starts where the step program ends (16 ms): shifted by
    # 0.5 ms it lies in mx.wait; the next (1.5 ms .. 6 ms on the device)
    # starts in mx.step.rng once shifted, not in mx.step.stage
    assert s["idle_gaps_ms"][0] == ["mx.wait", 5.5]
    assert ["mx.step.rng", 4.499] in s["idle_gaps_ms"]
    # a program that never precedes its span: no skew can be shown
    for m in loaded["devices"][0]["modules"]:
        if m[2].startswith("jit__threefry"):
            m[0] += MS
            m[1] += MS
    assert spans.summary(loaded)["skew_floor_ms"] == 0.0


def test_readers_are_silent_without_spans_or_scopes():
    loaded = _made_up()
    loaded["host"] = [h for h in loaded["host"]
                      if h[2].startswith("chipbench.")]
    for op in loaded["devices"][0]["ops"]:
        op[3] = "jit(step)/jvp()/mul" if op[3] else ""
    s = spans.summary(loaded)
    assert s["metrics"] == dict.fromkeys(spans.METRICS)   # None, never 0
    assert s["skew_floor_ms"] is None
    assert s["scopes"]["unscoped"] == 0.020
    # no device plane at all (a CPU trace), no host span at all
    s = spans.summary({"host": [], "devices": {}})
    assert s["metrics"] == dict.fromkeys(spans.METRICS)
    assert s["scopes"] is None and s["idle_gaps_ms"] == []


def test_every_new_metric_has_a_reader_that_finds_no_trace(tmp_path,
                                                             monkeypatch):
    """Outside a traced run there is no .chipbench_trace/: each reader
    returns None and does not raise (the parent's side of a traced run
    differs only in that a trace is found and holds no mx.* span)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = [m for m in manifest["per_layer"] if m["name"] in spans.METRICS]
    assert [m["name"] for m in mine] == list(spans.METRICS)
    monkeypatch.setattr(spans, "ROOT", str(tmp_path))
    for m in mine:
        assert m["moves"] == "step_ms_p95" and m["unit"] == "ms"
        assert _load(m["name"], "metrics").read({}) is None


def test_op_names_from_a_file_this_jax_wrote(tmp_path):
    """The wire-format reader against the real thing: a jitted function with
    a scope, traced on the CPU backend, gives its instructions' op_names
    back through the Hlo Proto in the file."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def scoped(x):
        with jax.named_scope("mx.step.forward"):
            return jnp.sin(x) @ x

    x = jnp.ones((64, 64))
    jax.profiler.start_trace(str(tmp_path))   # the CPU backend keeps the
    #                                           HLO of what compiles in it
    try:
        scoped(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = spans._trace().find(str(tmp_path))
    programs = spans.hlo_op_names(path)
    mine = [names for program, names in programs.items()
            if program.startswith("jit_scoped")]
    assert mine, sorted(programs)
    named = [v for v, _fused in mine[0].values() if "mx.step.forward" in v]
    assert named and all(v.startswith("jit(scoped)/") for v in named)
    # a fusion tells what it holds (none is "backward" or "optimizer" here)
    assert {f for _v, f in mine[0].values()} <= {"", "forward"}
    assert spans.load(path)["devices"] == {}        # no TPU plane here


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(os.path.join(BENCH, "fixtures"))
    if f.endswith(".spans.json.gz")))
def test_summary_repeats_on_a_recorded_trace(name):
    """Spans and scopes recorded on the chip from PR 24's tree, trimmed to
    a few steps: the summary gives the recorded numbers exactly."""
    with gzip.open(os.path.join(BENCH, "fixtures", name), "rt") as f:
        fixture = json.load(f)
    loaded = fixture["loaded"]
    loaded["devices"] = {int(n): d for n, d in loaded["devices"].items()}
    got = json.loads(json.dumps(spans.summary(loaded)))
    assert got == fixture["summary"]
    m, sc = got["metrics"], got["scopes"]
    assert all(m[k] is not None and m[k] > 0 for k in spans.METRICS)
    assert sc["steps"] == fixture["steps"]
    # the four scopes are the step program's busy time, and little of it
    # is without a scope
    parts = sum(sc[k] for k in spans.SCOPES)
    assert abs(parts - sc["busy_s"]) < 1e-9 * sc["busy_s"]
    assert sc["busy_s"] <= sc["step_module_s"]
    assert sc["unscoped"] < 0.05 * sc["busy_s"]
    rows = got["spans"]
    assert rows["mx.step"]["parent"] == "chipbench.step"
    for child in ("mx.step.stage", "mx.step.rng", "mx.step.scalars",
                  "mx.step.dispatch"):
        assert rows[child]["parent"] == "mx.step"
    # step by step the part before the dispatch and the dispatch fit in
    # mx.step (their medians need not add up to its median)
    host = loaded["host"]
    for step in (h for h in host if h[2] == "mx.step"):
        inside = [h for h in host if h[2] == "mx.step.dispatch"
                  and step[0] <= h[0] and h[1] <= step[1]]
        assert len(inside) == 1
    assert got["skew_floor_ms"] is not None
