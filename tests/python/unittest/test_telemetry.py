"""mx.telemetry tests: registry semantics (labels, histogram buckets,
reset), Prometheus text-format validity, cross-stack instrumentation
(hybridize cache, engine pushes, transfer bytes, dataloader waits), the
profiler bridge, and the disabled fast path."""
import json
import re

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, telemetry


@pytest.fixture(autouse=True)
def _clean_registry():
    telemetry.enable()
    telemetry.reset()
    yield
    telemetry.enable()
    telemetry.reset()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_basics_and_labels():
    c = telemetry.counter("t_requests_total", "test counter", ("route",))
    c.labels(route="a").inc()
    c.labels(route="a").inc(2.5)
    c.labels("b").inc()
    assert c.labels(route="a").value == 3.5
    assert c.labels(route="b").value == 1.0
    assert telemetry.value("t_requests_total") == 4.5
    assert telemetry.value("t_requests_total", {"route": "a"}) == 3.5
    with pytest.raises(ValueError):
        c.labels(route="a").inc(-1)       # counters are monotonic
    with pytest.raises(ValueError):
        c.inc()                           # labelled metric needs .labels()
    with pytest.raises(ValueError):
        c.labels(route="a", rouet="b")    # typo'd label must not be dropped
    with pytest.raises(ValueError):
        c.labels()                        # missing label


def test_counter_registration_idempotent_and_typed():
    a = telemetry.counter("t_same_total", "x")
    b = telemetry.counter("t_same_total", "x")
    assert a is b
    with pytest.raises(ValueError):
        telemetry.gauge("t_same_total")   # kind mismatch
    with pytest.raises(ValueError):
        telemetry.counter("t_same_total", labelnames=("k",))


def test_gauge_set_inc_dec():
    g = telemetry.gauge("t_level")
    g.set(10)
    g.inc(5)
    g.dec(2)
    assert g.value == 13.0


def test_histogram_buckets_sum_count():
    h = telemetry.histogram("t_lat_seconds", "lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    child = h._delegate()
    assert child.count == 5
    assert child.sum == pytest.approx(56.05)
    cum = dict((telemetry._fmt_le(ub), c) for ub, c in child.cumulative())
    assert cum["0.1"] == 1
    assert cum["1.0"] == 3
    assert cum["10.0"] == 4
    assert cum["+Inf"] == 5


def test_reset_zeroes_but_keeps_registration():
    c = telemetry.counter("t_reset_total", "x", ("k",))
    c.labels(k="v").inc(7)
    telemetry.reset()
    assert telemetry.value("t_reset_total") == 0.0
    assert telemetry.get_metric("t_reset_total") is c
    # canonical framework metrics survive reset too
    assert telemetry.get_metric("cachedop_build_total") is not None


def test_snapshot_and_dump(tmp_path):
    telemetry.counter("t_snap_total", "x").inc(3)
    snap = telemetry.snapshot()
    assert snap["t_snap_total"]["type"] == "counter"
    assert snap["t_snap_total"]["samples"][0]["value"] == 3.0
    path = telemetry.dump(str(tmp_path / "telemetry.json"))
    with open(path) as f:
        loaded = json.load(f)
    assert loaded["metrics"]["t_snap_total"]["samples"][0]["value"] == 3.0


# ---------------------------------------------------------------------------
# prometheus exposition format
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'                    # metric name
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'            # first label
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})?'       # more labels
    r' (NaN|[-+]?(inf|Inf|[0-9.eE+-]+))$')          # value


def test_prometheus_parses_line_by_line():
    telemetry.counter("t_prom_total", "help text", ("k",)).labels(
        k="v").inc()
    telemetry.histogram("t_promh_seconds", "h", buckets=(0.5,)).observe(0.1)
    text = telemetry.prometheus()
    typed = set()
    for line in text.strip().split("\n"):
        if line.startswith("# TYPE"):
            _, _, name, kind = line.split()
            assert kind in ("counter", "gauge", "histogram")
            typed.add(name)
        elif not line.startswith("#"):
            assert _SAMPLE_RE.match(line), "bad sample line: %r" % line
    # every registered metric has a # TYPE line
    for name in telemetry.snapshot():
        assert name in typed, "missing # TYPE for %s" % name
    assert 't_prom_total{k="v"} 1.0' in text
    assert 't_promh_seconds_bucket{le="+Inf"} 1' in text
    assert "t_promh_seconds_count 1" in text


def test_prometheus_hostile_labels_round_trip():
    """Label values containing the three characters the exposition
    format escapes (backslash, double-quote, newline) must survive an
    export -> parse round trip bit-identically."""
    hostile = 'a\\b"c\nd'
    telemetry.counter("t_evil_total", "h", ("k",)).labels(
        k=hostile).inc(5)
    text = telemetry.prometheus()
    line = next(l for l in text.splitlines()
                if l.startswith("t_evil_total{"))
    m = re.match(r't_evil_total\{k="((?:[^"\\]|\\.)*)"\} 5\.0$', line)
    assert m, line
    unescaped = m.group(1).replace("\\\\", "\0").replace(
        '\\"', '"').replace("\\n", "\n").replace("\0", "\\")
    assert unescaped == hostile
    # the raw control characters must NOT leak into the exposition
    assert "\n" not in line


def test_prometheus_help_and_type_every_family():
    """Every exported family carries BOTH a # HELP and a # TYPE line
    (unconditionally — even families registered with empty help), and
    HELP text escapes backslash/newline per the exposition spec."""
    telemetry.counter("t_nohelp_total", "").inc()
    telemetry.gauge("t_helped", "multi\nline \\ help").set(1)
    text = telemetry.prometheus()
    helped = set()
    typed = set()
    for line in text.splitlines():
        if line.startswith("# HELP "):
            helped.add(line.split()[2])
        elif line.startswith("# TYPE "):
            typed.add(line.split()[2])
    for name in telemetry.snapshot():
        assert name in helped, "missing # HELP for %s" % name
        assert name in typed, "missing # TYPE for %s" % name
    assert "# HELP t_helped multi\\nline \\\\ help" in text


# ---------------------------------------------------------------------------
# timers + profiler bridge
# ---------------------------------------------------------------------------

def test_span_and_timed_record_histograms():
    with telemetry.span("t_step"):
        pass
    assert telemetry.get_metric("t_step_seconds")._delegate().count == 1

    calls = []

    @telemetry.timed("t_fn")
    def fn(x):
        calls.append(x)
        return x + 1

    assert fn(1) == 2
    assert calls == [1]
    assert telemetry.get_metric("t_fn_seconds")._delegate().count == 1


def test_span_feeds_profiler_when_trace_live(tmp_path):
    """While mx.profiler runs (a real jax.profiler session), a telemetry
    span is an event of the xplane it writes, not of a side list."""
    from common import xplane_find, xplane_host_lines

    from mxnet_tpu import profiler

    n0 = len(profiler._state["events"])
    was = profiler._config["filename"]
    profiler.set_config(filename=str(tmp_path / "p.json"))
    profiler.set_state("run")
    try:
        with telemetry.span("t_traced"):
            pass
    finally:
        profiler.set_state("stop")
        profiler.set_config(filename=was)
    assert len(profiler._state["events"]) == n0
    lines = xplane_host_lines(profiler._state["trace_dir"])
    assert len(xplane_find(lines, "t_traced")) == 1
    assert telemetry.get_metric("t_traced_seconds")._delegate().count == 1


def test_log_line_compact():
    telemetry.counter("t_log_total", "x").inc(2)
    line = telemetry.log_line()
    assert line.startswith("telemetry ")
    assert "t_log_total=2" in line


# ---------------------------------------------------------------------------
# cross-stack instrumentation
# ---------------------------------------------------------------------------

def test_hybridized_block_counts_build_and_hit():
    from mxnet_tpu.gluon import nn

    net = nn.Dense(4, in_units=3)
    net.initialize()
    net.hybridize()
    x = nd.array(np.ones((2, 3), np.float32))
    net(x)
    net(x)
    snap = telemetry.snapshot()

    def total(name):
        return sum(s["value"] for s in snap[name]["samples"])

    assert total("cachedop_build_total") == 1
    assert total("cachedop_hit_total") >= 1
    assert total("cachedop_recompile_total") == 0
    assert telemetry.value("cachedop_build_total",
                           {"block": "Dense"}) == 1
    assert telemetry.get_metric(
        "cachedop_build_seconds")._delegate().count == 1
    # a new shape signature = recompile
    net(nd.array(np.ones((5, 3), np.float32)))
    assert telemetry.value("cachedop_recompile_total") == 1


def test_transfer_bytes_both_directions():
    x = nd.array(np.ones((4, 8), np.float32))   # h2d: 128 bytes
    assert telemetry.value("transfer_bytes_total",
                           {"direction": "h2d"}) >= 128
    x.asnumpy()                                 # d2h: 128 bytes
    assert telemetry.value("transfer_bytes_total",
                           {"direction": "d2h"}) >= 128


def test_engine_push_counted():
    from mxnet_tpu import engine

    before = telemetry.value("engine_push_total")
    engine.get().push(lambda: None)
    assert telemetry.value("engine_push_total") == before + 1


def test_dataloader_wait_observed():
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    ds = ArrayDataset(np.arange(32, dtype=np.float32).reshape(8, 4))
    loader = DataLoader(ds, batch_size=4)
    batches = list(loader)
    assert len(batches) == 2
    assert telemetry.get_metric(
        "dataloader_batch_wait_seconds")._delegate().count >= 2


def test_sample_device_memory_never_raises():
    report = telemetry.sample_device_memory()
    assert isinstance(report, dict)   # CPU backends may report no stats


# ---------------------------------------------------------------------------
# disabled fast path
# ---------------------------------------------------------------------------

def test_disable_stops_instrumentation():
    from mxnet_tpu.gluon import nn

    telemetry.disable()
    try:
        assert not telemetry.ENABLED
        net = nn.Dense(2, in_units=2)
        net.initialize()
        net.hybridize()
        x = nd.array(np.ones((1, 2), np.float32))
        net(x)
        net(x)
        x.asnumpy()
        assert telemetry.value("cachedop_build_total") == 0
        assert telemetry.value("cachedop_hit_total") == 0
        assert telemetry.value("transfer_bytes_total") == 0
        # spans observe nothing while disabled
        with telemetry.span("t_off"):
            pass
        m = telemetry.get_metric("t_off_seconds")
        assert m is None or m._delegate().count == 0
    finally:
        telemetry.enable()
