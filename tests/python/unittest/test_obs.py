"""mx.obs tests: fleet merge semantics, local-only degradation, the
leave-one-out straggler detector (once-per-episode firing), SLO
burn-rate state transitions with injected clocks, step-time
attribution records, the bench_gate regression math, dump-event
capping, the membership beat-listener hooks, diagnose golden output,
and the disabled fast paths."""
import json
import os
import sys

import pytest

from mxnet_tpu import telemetry
from mxnet_tpu import obs
from mxnet_tpu.obs import attribution, core, fleet, slo_engine

REPO = os.path.join(os.path.dirname(__file__), "..", "..", "..")
sys.path.insert(0, os.path.join(REPO, "tools"))


@pytest.fixture(autouse=True)
def _clean_obs():
    telemetry.enable()
    telemetry.reset()
    core.enable()
    core.reset_steps()
    core.detach()
    fleet._reset_flags()
    slo_engine.clear()
    attribution.reset()
    yield
    core.detach()
    core.enable()
    core.reset_steps()
    fleet._reset_flags()
    slo_engine.clear()
    attribution.reset()
    telemetry.enable()
    telemetry.reset()


def _payload(rank, p50=None, metrics=None, steps=0):
    return {"rank": rank, "pid": 1000 + rank, "wall": 0.0,
            "step": steps, "steps_observed": steps, "step_p50_s": p50,
            "step_last_s": p50, "collective_wait_p50_s": None,
            "monitor": None, "metrics": metrics or {}}


class _DictKV:
    """Minimal membership-KV lookalike: set/get/list over a dict."""

    def __init__(self):
        self.data = {}

    def set(self, key, value):
        self.data[key] = value

    def get(self, key):
        return self.data.get(key)

    def list(self, prefix):
        pre = prefix.rstrip("/") + "/"
        return sorted({k[len(pre):].split("/")[0]
                       for k in self.data if k.startswith(pre)})


class _DeadKV(_DictKV):
    def set(self, key, value):
        raise OSError("kv unreachable")

    def list(self, prefix):
        raise OSError("kv unreachable")


class _FakeMembership:
    def __init__(self, kv, generation=7, rank=0):
        self.kv = kv
        self.generation = generation
        self.rank = rank


# ---------------------------------------------------------------------------
# merge_metrics
# ---------------------------------------------------------------------------

def test_merge_metrics_sums_counters_per_labelset():
    a = {"x_total": {"type": "counter", "help": "x", "samples": [
        {"labels": {"k": "a"}, "value": 2.0},
        {"labels": {"k": "b"}, "value": 1.0}]}}
    b = {"x_total": {"type": "counter", "help": "x", "samples": [
        {"labels": {"k": "a"}, "value": 3.0}]},
         "y": {"type": "gauge", "help": "y", "samples": [
             {"labels": {}, "value": 5.0}]}}
    merged = fleet.merge_metrics([a, b])
    by_label = {tuple(sorted(s["labels"].items())): s["value"]
                for s in merged["x_total"]["samples"]}
    assert by_label[(("k", "a"),)] == 5.0
    assert by_label[(("k", "b"),)] == 1.0
    assert merged["y"]["samples"][0]["value"] == 5.0


def test_merge_metrics_merges_histogram_buckets():
    def fam(count, total, buckets):
        return {"h_seconds": {"type": "histogram", "help": "h",
                              "samples": [{"labels": {}, "count": count,
                                           "sum": total,
                                           "buckets": buckets}]}}
    merged = fleet.merge_metrics([
        fam(3, 0.3, {"0.1": 1, "1.0": 3, "+Inf": 3}),
        fam(2, 4.0, {"0.1": 0, "1.0": 0, "+Inf": 2})])
    s = merged["h_seconds"]["samples"][0]
    assert s["count"] == 5
    assert s["sum"] == pytest.approx(4.3)
    assert s["buckets"] == {"0.1": 1, "1.0": 3, "+Inf": 5}


def test_merge_metrics_ignores_none_snapshots():
    assert fleet.merge_metrics([None, {}]) == {}


# ---------------------------------------------------------------------------
# FleetView: collection + degradation
# ---------------------------------------------------------------------------

def test_fleet_view_merges_published_ranks():
    kv = _DictKV()
    kv.set(core.obs_key(7, 0), _payload(0, p50=0.01))
    kv.set(core.obs_key(7, 1), _payload(1, p50=0.02))
    view = fleet.FleetView(kv=kv, generation=7, rank=0)
    view.refresh()
    assert view.ranks == [0, 1]
    assert not view.local_only
    rows = view.table(now=10.0)
    assert [r["rank"] for r in rows] == [0, 1]
    assert rows[0]["age_s"] == 10.0
    assert rows[1]["step_p50_s"] == 0.02


def test_fleet_view_degrades_to_local_only():
    # no KV at all -> this process's own payload under its own rank
    view = fleet.FleetView(rank=3)
    view.refresh()
    assert view.local_only
    assert view.ranks == [3]
    # a KV that raises degrades the same way (and never raises out)
    view = fleet.FleetView(kv=_DeadKV(), generation=7, rank=1)
    view.refresh()
    assert view.local_only
    assert view.ranks == [1]
    assert telemetry.value("obs_fleet_ranks") == 1


def test_fleet_totals_fold_histograms():
    metrics = {"n_total": {"type": "counter", "help": "",
                           "samples": [{"labels": {}, "value": 2.0}]},
               "h_seconds": {"type": "histogram", "help": "",
                             "samples": [{"labels": {}, "count": 4,
                                          "sum": 0.5, "buckets": {}}]}}
    kv = _DictKV()
    kv.set(core.obs_key(7, 0), _payload(0, metrics=metrics))
    kv.set(core.obs_key(7, 1), _payload(1, metrics=metrics))
    view = fleet.FleetView(kv=kv, generation=7, rank=0)
    totals = view.totals()
    assert totals["n_total"] == 4.0
    assert totals["h_seconds_count"] == 8
    assert totals["h_seconds_sum"] == pytest.approx(1.0)


def test_fleet_prometheus_has_rank_label_and_headers():
    kv = _DictKV()
    metrics = {"n_total": {"type": "counter", "help": "n help",
                           "samples": [{"labels": {}, "value": 2.0}]}}
    kv.set(core.obs_key(7, 0), _payload(0, metrics=metrics))
    kv.set(core.obs_key(7, 1), _payload(1, metrics=metrics))
    view = fleet.FleetView(kv=kv, generation=7, rank=0)
    text = view.prometheus()
    assert text.count("# HELP n_total n help") == 1
    assert text.count("# TYPE n_total counter") == 1
    assert 'n_total{rank="0"} 2.0' in text
    assert 'n_total{rank="1"} 2.0' in text


# ---------------------------------------------------------------------------
# straggler detection
# ---------------------------------------------------------------------------

def _view_with_p50s(p50s):
    kv = _DictKV()
    for r, p in p50s.items():
        kv.set(core.obs_key(7, r), _payload(r, p50=p))
    return fleet.FleetView(kv=kv, generation=7, rank=0)


def test_straggler_uses_peer_median_leave_one_out():
    # 2-rank fleet: an all-rank median would average the slow rank in
    # (0.5/0.255 < 2) and NEVER flag; the peer median must flag it
    view = _view_with_p50s({0: 0.01, 1: 0.5})
    assert view.stragglers(factor=2.0) == [1]
    # healthy fleet: nobody flagged
    assert _view_with_p50s({0: 0.01, 1: 0.011,
                            2: 0.012}).stragglers(factor=2.0) == []
    # one slow among many: peers' median stays fast
    assert _view_with_p50s({0: 0.01, 1: 0.011, 2: 0.012,
                            3: 0.1}).stragglers(factor=2.0) == [3]


def test_straggler_needs_two_ranks_and_positive_factor():
    assert _view_with_p50s({0: 9.0}).stragglers(factor=2.0) == []
    assert _view_with_p50s({0: 0.01, 1: 0.5}).stragglers(factor=0) == []
    # ranks without cadence are excluded, not treated as zero
    view = _view_with_p50s({0: 0.01, 1: None})
    assert view.stragglers(factor=2.0) == []


def test_check_stragglers_fires_once_per_episode(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TRACE_DUMP_DIR", str(tmp_path))
    view = _view_with_p50s({0: 0.01, 1: 0.5})
    flagged = view.check_stragglers(factor=2.0)
    assert flagged == [1]
    assert telemetry.value("obs_stragglers_total",
                           {"rank": "1"}) == 1
    # same episode re-checked: no second count
    assert view.check_stragglers(factor=2.0) == [1]
    assert telemetry.value("obs_stragglers_total") == 1
    # recovery unflags ...
    fast = _view_with_p50s({0: 0.01, 1: 0.012})
    assert fast.check_stragglers(factor=2.0) == []
    # ... and a NEW episode fires again
    again = _view_with_p50s({0: 0.01, 1: 0.7})
    assert again.check_stragglers(factor=2.0) == [1]
    assert telemetry.value("obs_stragglers_total") == 2


def test_check_stragglers_never_raises():
    view = fleet.FleetView(kv=_DeadKV(), generation=7, rank=0)
    assert view.check_stragglers() == []


# ---------------------------------------------------------------------------
# publisher + beat listeners
# ---------------------------------------------------------------------------

def test_publisher_writes_payload_into_kv():
    kv = _DictKV()
    m = _FakeMembership(kv, generation=7, rank=2)
    pub = core.Publisher(m, interval=0.0)
    core.note_step(0.02)
    assert pub.publish()
    rec = kv.get(core.obs_key(7, 2))
    assert rec["rank"] == 2
    assert rec["steps_observed"] == 1
    assert "metrics" in rec and rec["pid"] == os.getpid()
    assert telemetry.value("obs_publish_total") == 1


def test_publisher_dead_kv_counts_failures_never_raises():
    pub = core.Publisher(_FakeMembership(_DeadKV()), interval=0.0)
    assert pub.publish() is False
    assert pub.failures == 1
    assert telemetry.value("obs_publish_failures_total") == 1
    # the fleet view over the same dead KV degrades to local-only
    view = fleet.FleetView(kv=_DeadKV(), generation=7, rank=0)
    view.refresh()
    assert view.local_only


def test_publisher_rate_limit_and_disabled():
    kv = _DictKV()
    pub = core.Publisher(_FakeMembership(kv), interval=3600.0)
    assert pub.maybe_publish()
    assert pub.maybe_publish() is False      # inside the interval
    assert pub.publishes == 1
    core.disable()
    assert pub.publish() is False            # flag gates everything
    assert pub.failures == 0


def test_attach_detach_wires_beat_listener():
    from mxnet_tpu.dist import membership as mm

    kv = _DictKV()
    m = _FakeMembership(kv)
    pub = obs.attach(m, interval=0.0)
    assert core.publisher() is pub
    assert kv.get(core.obs_key(7, 0)) is not None   # attach publishes
    n0 = pub.publishes
    for cb in list(mm._BEAT_LISTENERS):
        cb(m)                                       # simulate one beat
    assert pub.publishes == n0 + 1
    core.detach()
    assert core.publisher() is None
    assert core._BEAT_CB[0] is None


def test_on_beat_dedups_and_removes():
    from mxnet_tpu.dist import membership as mm

    calls = []

    def cb(m):
        calls.append(m)

    before = list(mm._BEAT_LISTENERS)
    try:
        mm.on_beat(cb)
        mm.on_beat(cb)                               # dedup
        assert mm._BEAT_LISTENERS.count(cb) == 1
        mm.remove_beat_listener(cb)
        assert cb not in mm._BEAT_LISTENERS
        mm.remove_beat_listener(cb)                  # idempotent
    finally:
        mm._BEAT_LISTENERS[:] = before


# ---------------------------------------------------------------------------
# SLO burn-rate engine
# ---------------------------------------------------------------------------

def test_slo_requires_exactly_one_source():
    with pytest.raises(ValueError):
        slo_engine.slo("both", histogram="h", counter="c")
    with pytest.raises(ValueError):
        slo_engine.slo("neither")
    with pytest.raises(ValueError):
        slo_engine.slo("no_target", histogram="h")   # latency needs target


def test_slo_latency_page_and_recover(monkeypatch):
    monkeypatch.setenv("MXNET_OBS_SLO_FAST_SECONDS", "300")
    monkeypatch.setenv("MXNET_OBS_SLO_SLOW_SECONDS", "3600")
    h = telemetry.histogram("t_slo_seconds", "lat",
                            buckets=(0.1, 1.0))
    obj = obs.slo("t_p99", histogram="t_slo_seconds", q=0.99,
                  target=0.1)
    for _ in range(10):
        h.observe(0.05)
    assert obj.evaluate(now=0.0)["state"] == "OK"    # clean baseline

    for _ in range(40):
        h.observe(0.5)                               # 5x over target
    res = obj.evaluate(now=10.0)
    assert res["state"] == "PAGE"
    assert res["burn_fast"] >= 14.4 and res["burn_slow"] >= 14.4
    # the per-objective evaluate does NOT touch gauges — only the
    # module-level evaluate() does
    assert telemetry.value("obs_slo_state", {"slo": "t_p99"}) == 0
    assert slo_engine.evaluate(now=10.0)["t_p99"]["state"] == "PAGE"
    assert telemetry.value("obs_slo_state", {"slo": "t_p99"}) == 2
    assert slo_engine.worst(now=10.0) == "PAGE"

    # both windows roll past the bad burst; good-only traffic since
    for _ in range(100):
        h.observe(0.01)
    res = obj.evaluate(now=10000.0)
    assert res["state"] == "OK"
    assert res["burn_fast"] == 0.0
    assert slo_engine.states(now=10000.0) == {"t_p99": "OK"}


def test_slo_counter_form_and_quiet_window():
    c = telemetry.counter("t_req_total", "req", ("result",))
    obj = obs.slo("t_errs", counter="t_req_total",
                  bad={"result": "error"}, objective=0.9)
    assert obj.evaluate(now=0.0)["state"] == "OK"    # quiet = OK
    c.labels(result="ok").inc(1)
    c.labels(result="error").inc(9)                  # 90% errors
    # burn = (9/10) / (1 - 0.9) = 9.0: past warn (6.0), short of
    # page (14.4) on both windows
    res = obj.evaluate(now=1.0)
    assert res["state"] == "WARN"
    assert res["burn_fast"] == pytest.approx(9.0, rel=1e-3)
    # a loose objective CANNOT page: burn is capped at 1/budget = 10
    # < 14.4 even at a 100% error rate.  A tight one pages instantly.
    tight = obs.slo("t_errs_tight", counter="t_req_total",
                    bad={"result": "error"}, objective=0.999)
    tight.evaluate(now=0.0)
    c.labels(result="error").inc(90)
    assert obj.evaluate(now=2.0)["state"] == "WARN"
    assert tight.evaluate(now=2.0)["state"] == "PAGE"


def test_slo_overflow_bucket_counts_as_bad():
    # observations landing in +Inf cannot be proven under ANY finite
    # target — they must burn budget
    cum = [(0.1, 5.0), (float("inf"), 8.0)]
    assert slo_engine._le_count(cum, 0.5) == 5.0
    assert slo_engine._le_count(cum, 0.05) == pytest.approx(2.5)


def test_slo_evaluate_is_fail_soft():
    obj = obs.slo("t_sick", histogram="t_absent_seconds", target=0.1)
    obj._read = lambda: (_ for _ in ()).throw(RuntimeError("boom"))
    out = slo_engine.evaluate(now=0.0)
    assert out["t_sick"]["state"] == "OK"
    assert "error" in out["t_sick"]


# ---------------------------------------------------------------------------
# step cadence + attribution
# ---------------------------------------------------------------------------

def test_note_step_feeds_window_and_histogram():
    for d in (0.1, 0.2, 0.3):
        core.note_step(d)
    st = core.step_stats()
    assert st["steps_observed"] == 3
    assert st["step_p50_s"] == 0.2
    assert st["step_last_s"] == 0.3
    assert telemetry.get_metric(
        "obs_step_seconds")._delegate().count == 3
    core.reset_steps()
    assert core.step_stats()["steps_observed"] == 0


def test_note_step_disabled_is_noop():
    core.disable()
    core.note_step(1.0)
    assert core.step_stats()["steps_observed"] == 0


def test_observe_step_schema_and_shares(tmp_path, monkeypatch):
    stream = str(tmp_path / "attr.jsonl")
    monkeypatch.setenv("MXNET_OBS_ATTRIBUTION", stream)
    monkeypatch.setenv("MXNET_OBS_PEAK_TFLOPS", "0.001")
    rec = attribution.observe_step(
        5, 0.1, parts={"dispatch": 0.06, "writeback": 0.02,
                       "negative": -1.0},     # clamped to 0
        flops=2.0e6, path="captured")
    assert set(attribution.SCHEMA_KEYS) <= set(rec)
    assert rec["shares"]["dispatch"] == pytest.approx(0.6)
    assert rec["shares"]["negative"] == 0.0
    assert rec["shares"]["other"] == pytest.approx(0.2)
    assert sum(rec["shares"].values()) == pytest.approx(1.0)
    # mfu = flops / total_s / (peak_tflops * 1e12)
    assert rec["mfu"] == pytest.approx(2.0e6 / 0.1 / 1.0e9)
    with open(stream) as f:
        assert json.loads(f.readline())["step"] == 5
    assert attribution.summary()["records"] == 1
    assert telemetry.value("obs_attribution_records_total") == 1


def test_observe_step_clamps_oversubscribed_parts():
    # parts exceeding the total must not push shares past 1
    rec = attribution.observe_step(1, 0.1, parts={"a": 0.3, "b": 0.2})
    assert rec["shares"]["a"] == 1.0
    assert rec["shares"]["other"] == 0.0


def test_observe_step_disabled_or_bad_total_returns_none():
    assert attribution.observe_step(1, 0.0) is None
    core.disable()
    assert attribution.observe_step(1, 0.1) is None
    assert attribution.summary()["records"] == 0


def test_peak_flops_env_override(monkeypatch):
    monkeypatch.setenv("MXNET_OBS_PEAK_TFLOPS", "2.5")
    assert attribution.peak_flops() == 2.5e12


# ---------------------------------------------------------------------------
# fleetz / fleet_summary / runtime flag
# ---------------------------------------------------------------------------

def test_fleetz_disabled_and_local_only():
    core.disable()
    assert fleet.fleetz() == {"enabled": False}
    assert fleet.fleet_summary() == {}
    core.enable()
    doc = fleet.fleetz()
    assert doc["enabled"] and doc["local_only"]
    assert [r["rank"] for r in doc["ranks"]] == [0]
    summary = fleet.fleet_summary()
    assert summary["ranks_seen"] == 1 and summary["local_only"]


def test_runtime_feature_reports_obs():
    from mxnet_tpu import runtime

    assert runtime.features["OBS"].enabled
    core.disable()
    assert not runtime.features["OBS"].enabled


# ---------------------------------------------------------------------------
# trace dump event cap (satellite)
# ---------------------------------------------------------------------------

def test_dump_cap_keeps_newest_and_records_truncation(monkeypatch):
    from mxnet_tpu.trace import export

    events = list(range(10))
    monkeypatch.setenv("MXNET_TRACE_DUMP_MAX_EVENTS", "0")
    capped, extra = export._cap_events(events, None)
    assert capped == events and extra is None        # 0 = unbounded
    monkeypatch.setenv("MXNET_TRACE_DUMP_MAX_EVENTS", "4")
    capped, extra = export._cap_events(events, {"reason": "x"})
    assert capped == [6, 7, 8, 9]                    # newest kept
    assert extra["truncated_events"] == 6
    assert extra["dump_max_events"] == 4
    assert extra["reason"] == "x"


def test_dump_cap_applies_end_to_end(tmp_path, monkeypatch):
    from mxnet_tpu import trace

    monkeypatch.setenv("MXNET_TRACE_DUMP_MAX_EVENTS", "3")
    monkeypatch.setenv("MXNET_TRACE_DUMP_MIN_SECONDS", "0")
    was_on = trace.is_enabled()
    trace.enable()
    try:
        for i in range(8):
            with trace.span("t_cap_%d" % i):
                pass
        path = trace.dump(path=str(tmp_path / "capped.json"),
                          reason="test_cap")
        with open(path) as f:
            doc = json.load(f)
        meta = doc["traceEvents"][0]
        assert meta["name"] == "mx.trace.dump"
        assert meta["args"]["dump_max_events"] == 3
        assert meta["args"]["truncated_events"] > 0
        assert len(doc["traceEvents"]) <= 1 + 2 * 3  # meta + B/E pairs
    finally:
        # as found: the files that run after this one on the same xdist
        # worker (test_monitor's dumps) need the recorder left on
        if not was_on:
            trace.disable()


# ---------------------------------------------------------------------------
# diagnose golden output (satellite)
# ---------------------------------------------------------------------------

def _synthetic_snapshot():
    return {
        "t_lat_seconds": {"type": "histogram", "help": "lat",
                          "samples": [
                              {"labels": {}, "count": 10, "sum": 1.0,
                               "buckets": {"0.1": 5, "1.0": 10,
                                           "+Inf": 10}}]},
        "t_n_total": {"type": "counter", "help": "n",
                      "samples": [{"labels": {}, "value": 3.0}]},
        "t_empty_seconds": {"type": "histogram", "help": "e",
                            "samples": []},
    }


def test_diagnose_quantile_lines_golden():
    import diagnose

    lines = diagnose._quantile_lines(_synthetic_snapshot())
    # counters and empty histograms skipped; quantiles interpolated
    # from the synthetic buckets (p50 = bucket midpoint 0.1)
    assert lines == [
        "  t_lat_seconds                          "
        "p50=0.1 p95=0.91 p99=0.982"]


def test_diagnose_fleet_lines_golden():
    import diagnose

    doc = {"enabled": True, "generation": 7, "rank": 0,
           "local_only": False,
           "ranks": [
               {"rank": 0, "pid": 100, "age_s": 0.5, "step": 12,
                "steps_observed": 24, "step_p50_s": 0.01,
                "monitor": True, "straggler": False},
               {"rank": 1, "pid": 101, "age_s": 0.6, "step": 12,
                "steps_observed": 24, "step_p50_s": 0.5,
                "monitor": None, "straggler": True}],
           "stragglers": [1],
           "slo": {"serve_p99_ms": "PAGE"},
           "totals": {"obs_publish_total": 4.0}}
    assert diagnose._fleet_lines(doc) == [
        "enabled      : True",
        "generation   : 7",
        "view rank    : 0",
        "rank  pid      age_s   step     steps_seen step_p50_s   "
        "monitor   straggler",
        "0     100      0.5     12       24         0.01         "
        "True      -",
        "1     101      0.6     12       24         0.5          "
        "None      YES",
        "stragglers   : 1",
        "slo          : serve_p99_ms             PAGE",
        "fleet totals (nonzero):",
        "  obs_publish_total                        4.0",
    ]


def test_diagnose_fleet_lines_disabled_and_local_only():
    import diagnose

    assert diagnose._fleet_lines({"enabled": False}) == [
        "enabled      : False",
        "(set MXNET_OBS=1 or mxnet_tpu.obs.enable())"]
    doc = {"enabled": True, "generation": None, "rank": 2,
           "local_only": True, "ranks": [], "stragglers": [],
           "totals": {}}
    lines = diagnose._fleet_lines(doc)
    assert lines[2] == ("view rank    : 2  (LOCAL-ONLY: KV "
                        "unreachable or nothing published)")
    assert "stragglers   : (none)" in lines


# ---------------------------------------------------------------------------
# bench_gate (satellite: perf-regression gate math)
# ---------------------------------------------------------------------------

def _gate_mod():
    import bench_gate

    return bench_gate


def test_bench_gate_parse_rows_formats():
    bg = _gate_mod()
    row = {"metric": "m", "value": 1.0, "unit": "img/s"}
    # committed BENCH wrapper: rows ride in the "tail" JSON lines
    wrapper = json.dumps({"n": 1, "cmd": "x", "rc": 0,
                          "tail": "noise\n" + json.dumps(row) + "\n",
                          "parsed": row})
    assert bg.parse_rows(wrapper) == [row]
    # bare forms: JSON list, single dict, JSONL
    assert bg.parse_rows(json.dumps([row, row])) == [row, row]
    assert bg.parse_rows(json.dumps(row)) == [row]
    assert bg.parse_rows(json.dumps(row) + "\n" + json.dumps(row)) \
        == [row, row]
    assert bg.parse_rows("not json at all") == []


def test_bench_gate_trimmed_mean_and_direction():
    bg = _gate_mod()
    assert bg.trimmed_mean([10.0]) == 10.0
    assert bg.trimmed_mean([10.0, 20.0]) == 15.0
    # >= 3 samples: single min and max dropped
    assert bg.trimmed_mean([1.0, 10.0, 11.0, 12.0, 100.0]) == 11.0
    assert bg.direction("img/s") == "higher"
    assert bg.direction("tok/s") == "higher"
    assert bg.direction("ms") == "lower"
    assert bg.direction("seconds") == "lower"
    assert bg.direction(None) == "higher"            # default


def test_bench_gate_regression_both_directions():
    bg = _gate_mod()
    pools = {"thru": {"values": [100.0, 102.0], "unit": "img/s",
                      "files": ["BENCH_r01.json"]},
             "lat": {"values": [10.0, 10.2], "unit": "ms",
                     "files": ["BENCH_r01.json"]}}
    # throughput drop and latency rise both regress
    fresh = [{"metric": "thru", "value": 70.0, "unit": "img/s"},
             {"metric": "lat", "value": 14.0, "unit": "ms"}]
    verdicts, regressed = bg.gate(fresh, pools, threshold_pct=10.0)
    assert regressed
    assert [v["status"] for v in verdicts] == ["regression"] * 2
    assert verdicts[0]["direction"] == "higher"
    assert verdicts[1]["direction"] == "lower"
    # within threshold: both pass (latency IMPROVEMENT is not a fail)
    fresh = [{"metric": "thru", "value": 99.0, "unit": "img/s"},
             {"metric": "lat", "value": 8.0, "unit": "ms"}]
    verdicts, regressed = bg.gate(fresh, pools, threshold_pct=10.0)
    assert not regressed
    assert [v["status"] for v in verdicts] == ["ok"] * 2


def test_bench_gate_main_exit_codes(tmp_path):
    bg = _gate_mod()
    row = {"metric": "m", "value": 100.0, "unit": "img/s"}
    base = tmp_path / "BENCH_r01.json"
    base.write_text(json.dumps(
        {"n": 1, "cmd": "x", "rc": 0,
         "tail": json.dumps(row) + "\n", "parsed": row}))
    fresh = tmp_path / "fresh.jsonl"
    fresh.write_text(json.dumps(dict(row, value=60.0)) + "\n")
    assert bg.main(["--fresh", str(fresh),
                    "--baseline-dir", str(tmp_path)]) == 1
    fresh.write_text(json.dumps(dict(row, value=99.0)) + "\n")
    assert bg.main(["--fresh", str(fresh),
                    "--baseline-dir", str(tmp_path)]) == 0
    # nothing comparable: warn, do not fail the build
    fresh.write_text(json.dumps(
        {"metric": "unknown", "value": 1.0, "unit": "img/s"}) + "\n")
    assert bg.main(["--fresh", str(fresh),
                    "--baseline-dir", str(tmp_path)]) == 0


# ---------------------------------------------------------------------------
# test_report --slowest (satellite)
# ---------------------------------------------------------------------------

def test_report_parse_durations():
    import test_report

    text = ("== slowest durations ==\n"
            "1.25s call     tests/a.py::test_x\n"
            "0.50s setup    tests/b.py::test_y\n"
            "garbage line\n"
            "0.01s teardown tests/c.py::test_z\n")
    rows = test_report.parse_durations(text)
    assert rows == [
        {"test": "tests/a.py::test_x", "phase": "call",
         "seconds": 1.25},
        {"test": "tests/b.py::test_y", "phase": "setup",
         "seconds": 0.5},
        {"test": "tests/c.py::test_z", "phase": "teardown",
         "seconds": 0.01}]
