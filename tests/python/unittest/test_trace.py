"""mx.trace tests: span nesting / context propagation, flight-recorder
ring bounds, chrome-trace round-trips (trace.dump AND profiler.dump with
real per-thread tids), bucket-estimated telemetry quantiles, anomaly
dump triggers (slow step, serve deadline burst), the hang watchdog
firing on a deliberately-stalled step, and the serve request lifecycle
(X-Request-Id accepted + echoed, >= 4 nested phase spans per request /
per trainer step sharing one trace id on distinct threads)."""
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest
from common import xplane_find, xplane_host_lines

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, profiler, telemetry, trace
from mxnet_tpu.gluon import nn
from mxnet_tpu.trace.anomaly import DeadlineMissMonitor, SlowStepDetector
from mxnet_tpu.trace.watchdog import Watchdog


@pytest.fixture(autouse=True)
def _clean(tmp_path, monkeypatch):
    """Isolated dump dir + fresh ring/telemetry per test; no process
    watchdog left behind."""
    monkeypatch.setenv("MXNET_TRACE_DUMP_DIR", str(tmp_path))
    trace.enable()
    trace.clear()
    trace.export._LAST_BY_REASON.clear()  # fresh rate-limit windows
    telemetry.enable()
    telemetry.reset()
    yield
    trace.watchdog.uninstall()
    trace.enable()
    trace.clear()
    telemetry.enable()
    telemetry.reset()


# ---------------------------------------------------------------------------
# core: spans, context, ring
# ---------------------------------------------------------------------------

def test_span_nesting_parent_child_one_trace():
    with trace.span("outer"):
        outer_ctx = trace.current()
        with trace.span("inner"):
            assert trace.current().trace_id == outer_ctx.trace_id
    evs = {e["name"]: e for e in trace.events()}
    assert evs["inner"]["trace"] == evs["outer"]["trace"]
    assert evs["inner"]["parent"] == evs["outer"]["span"]
    assert evs["outer"]["parent"] is None
    # inner exits first: ring holds [inner, outer]
    assert [e["name"] for e in trace.events()] == ["inner", "outer"]


def test_span_feeds_telemetry_histogram_like_telemetry_span():
    with trace.span("tr_hist_demo"):
        pass
    m = telemetry.get_metric("tr_hist_demo_seconds")
    assert m is not None and m.count == 1
    # hist=False skips the histogram but still records the event
    with trace.span("tr_nohist_demo", hist=False):
        pass
    assert telemetry.get_metric("tr_nohist_demo_seconds") is None
    assert any(e["name"] == "tr_nohist_demo" for e in trace.events())


def test_context_crosses_threads_via_use():
    got = {}

    def worker(ctx):
        with trace.use(ctx):
            with trace.span("worker_phase"):
                got["trace"] = trace.current().trace_id

    with trace.span("root"):
        ctx = trace.current()
        t = threading.Thread(target=worker, args=(ctx,))
        t.start()
        t.join()
    evs = {e["name"]: e for e in trace.events()}
    assert got["trace"] == evs["root"]["trace"]
    assert evs["worker_phase"]["trace"] == evs["root"]["trace"]
    assert evs["worker_phase"]["parent"] == evs["root"]["span"]
    assert evs["worker_phase"]["tid"] != evs["root"]["tid"]


def test_disabled_trace_records_nothing_but_keeps_histograms():
    trace.disable()
    try:
        with trace.span("tr_disabled_demo"):
            pass
        assert trace.events() == []
        # telemetry histogram still observed (metrics stay whole even
        # when the flight recorder is off)
        assert telemetry.get_metric("tr_disabled_demo_seconds").count == 1
    finally:
        trace.enable()


def test_ring_is_bounded_and_counts_displaced():
    ring = trace.FlightRecorder(capacity=32)
    for i in range(100):
        ring.append({"name": "e%d" % i, "ts": float(i), "dur": 0.0})
    assert len(ring) == 32
    assert ring.dropped == 68
    names = [e["name"] for e in ring.events()]
    assert names[0] == "e68" and names[-1] == "e99"  # newest tail kept


def test_record_span_root_vs_child():
    ctx = trace.new_context()
    trace.record_span("req_root", 1.0, 0.5, ctx=ctx, root=True)
    trace.record_span("req_child", 1.0, 0.2, ctx=ctx)
    evs = {e["name"]: e for e in trace.events()}
    assert evs["req_root"]["span"] == ctx.span_id
    assert evs["req_root"]["parent"] is None
    assert evs["req_child"]["parent"] == ctx.span_id
    assert evs["req_child"]["trace"] == ctx.trace_id


def test_new_request_uses_client_id_and_sanitizes():
    ctx = trace.new_request("abc-123")
    assert ctx.trace_id == "abc-123"
    ctx = trace.new_request("x" * 500 + "\x00\n")
    assert len(ctx.trace_id) <= 128 and "\x00" not in ctx.trace_id
    trace.disable()
    try:
        assert trace.new_request("abc") is None
    finally:
        trace.enable()


# ---------------------------------------------------------------------------
# chrome-trace round-trips
# ---------------------------------------------------------------------------

def test_trace_dump_chrome_round_trip(tmp_path):
    def worker():
        with trace.span("thread_phase"):
            time.sleep(0.01)

    with trace.span("main_phase"):
        t = threading.Thread(target=worker, name="tr-worker")
        t.start()
        t.join()
        time.sleep(0.002)
    path = trace.dump(str(tmp_path / "t.json"))
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    by_name = {e["name"]: e for e in evs}
    # microsecond units: the 10ms sleep must land in [5ms, 1s]
    assert 5e3 < by_name["thread_phase"]["dur"] < 1e6
    # real pid + distinct per-thread tids
    assert by_name["main_phase"]["pid"] == os.getpid()
    assert by_name["thread_phase"]["tid"] != by_name["main_phase"]["tid"]
    # ids ride in args for Perfetto filtering
    assert by_name["main_phase"]["args"]["trace"]
    # thread_name metadata rows name the tracks
    tnames = [e for e in evs if e["name"] == "thread_name"]
    assert any(e["args"]["name"] == "tr-worker" for e in tnames)


def test_profiler_dump_real_tids_and_nesting(tmp_path):
    """Spans land in the profiler's OWN trace: a real jax.profiler
    session on the CPU backend holds them by name in a host plane, each
    on the line of the thread that ran it, nested spans nested, args as
    stats.  (Was: the same asserted on profiler.dump()'s side list, which
    spans no longer feed.)"""
    import jax

    profiler._state["events"].clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        def worker():
            with trace.span("prof_worker", args={"k": 3, "site": "w"}):
                pass
            with telemetry.span("tel_worker"):
                pass

        with trace.span("prof_outer"):
            with trace.span("prof_inner") as inner:
                inner.note(late=5)
        t = threading.Thread(target=worker, name="prof-thread")
        t.start()
        t.join()
        # a live session is this sink's only switch
        trace.disable()
        telemetry.disable()
        with trace.span("prof_ring_off", hist=False):
            pass
        with telemetry.span("tel_off"):
            pass
        trace.instant("prof_instant", args={"why": "x"})
    finally:
        trace.enable()
        telemetry.enable()
        jax.profiler.stop_trace()
    lines = xplane_host_lines(str(tmp_path))
    (outer,), (inner,), (work,), (tel,) = (
        xplane_find(lines, n)
        for n in ("prof_outer", "prof_inner", "prof_worker", "tel_worker"))
    assert work[0] != outer[0]           # another thread, another line
    assert tel[0] == work[0]
    # nesting is by time on the thread's line
    assert inner[0] == outer[0]
    assert outer[1] <= inner[1] and inner[2] <= outer[2]
    assert work[3]["k"] == 3 and work[3]["site"] == "w"
    assert inner[3]["late"] == 5
    assert len(xplane_find(lines, "prof_ring_off")) == 1
    assert len(xplane_find(lines, "tel_off")) == 1
    (inst,) = xplane_find(lines, "prof_instant")
    assert inst[3]["why"] == "x"
    # the ring kept its own event, ids and all; the side list is gone
    ring = {e["name"]: e for e in trace.events()}
    assert ring["prof_inner"]["parent"] == ring["prof_outer"]["span"]
    assert ring["prof_inner"]["args"] == {"late": 5}
    assert "prof_ring_off" not in ring
    assert profiler._state["events"] == []
    assert "prof_outer" not in profiler.dumps()


def test_profiler_span_records_tid_at_stop():
    profiler._state["events"].clear()
    with profiler.Task(profiler.Domain("d"), "tid_probe"):
        pass
    ev = [e for e in profiler._state["events"]
          if e["name"] == "tid_probe"][0]
    assert ev["tid"] == threading.get_ident()
    profiler._state["events"].clear()


# ---------------------------------------------------------------------------
# telemetry satellites: quantiles + cheap disabled exit
# ---------------------------------------------------------------------------

def test_histogram_quantiles_bucket_estimate():
    h = telemetry.histogram("tq_demo_seconds", "x",
                            buckets=(0.1, 1.0, 10.0))
    for _ in range(90):
        h.observe(0.05)   # bucket <=0.1
    for _ in range(10):
        h.observe(5.0)    # bucket <=10
    qs = telemetry.histogram_quantiles("tq_demo_seconds")
    assert 0.0 < qs[0.5] <= 0.1
    assert 1.0 < qs[0.95] <= 10.0
    assert 1.0 < qs[0.99] <= 10.0
    # merged across label children
    hl = telemetry.histogram("tq_lab_seconds", "x", ("k",),
                             buckets=(0.1, 1.0))
    hl.labels(k="a").observe(0.05)
    hl.labels(k="b").observe(0.5)
    qs = telemetry.histogram_quantiles("tq_lab_seconds")
    assert 0.1 < qs[0.99] <= 1.0
    # unknown / non-histogram names are empty, not an error
    assert telemetry.histogram_quantiles("nope") == {}
    telemetry.counter("tq_counter_total", "x")
    assert telemetry.histogram_quantiles("tq_counter_total") == {}


def test_totals_and_log_line_carry_quantiles():
    h = telemetry.histogram("tq_tot_seconds", "x", buckets=(0.1, 1.0))
    h.observe(0.05)
    tot = telemetry.totals(quantiles=True)
    assert "tq_tot_seconds_p50" in tot
    assert "tq_tot_seconds_p99" in tot
    # default totals() keeps its stable key set (bench rows diff it)
    assert "tq_tot_seconds_p50" not in telemetry.totals()
    assert "tq_tot_seconds_p99" in dict(
        (kv.split("=")[0], kv) for kv in telemetry.log_line().split())


def test_overflow_bucket_clamps_to_last_finite_bound():
    h = telemetry.histogram("tq_inf_seconds", "x", buckets=(0.1, 1.0))
    for _ in range(10):
        h.observe(50.0)  # all in +Inf
    qs = telemetry.histogram_quantiles("tq_inf_seconds")
    assert qs[0.99] == 1.0  # never invents a value past the buckets


def test_telemetry_span_disabled_exit_is_noop():
    telemetry.disable()
    try:
        with telemetry.span("tel_dead_demo"):
            pass
        assert telemetry.get_metric("tel_dead_demo_seconds") is None
        # a span straddling enable() observes nothing (half a duration
        # would be a lie)
        s = telemetry.span("tel_straddle_demo")
        s.__enter__()
        telemetry.enable()
        s.__exit__(None, None, None)
        assert telemetry.get_metric("tel_straddle_demo_seconds") is None
    finally:
        telemetry.enable()


# ---------------------------------------------------------------------------
# anomaly dumps
# ---------------------------------------------------------------------------

def _wait_for_file(path, timeout=10.0):
    """Anomaly dumps write on a background thread (the trigger sites
    are hot paths); poll until the file lands."""
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if path is not None and os.path.exists(path):
            return True
        time.sleep(0.01)
    return False


def test_slow_step_detector_dumps_on_outlier():
    det = SlowStepDetector(factor=3.0, window=64, min_samples=8)
    trace.instant("warm")  # dump() skips an empty ring
    for _ in range(16):
        assert det.observe(0.010) is None
    path = det.observe(0.500)  # 50x the trailing p99
    assert _wait_for_file(path), "async slow-step dump never landed"
    with open(path) as f:
        doc = json.load(f)
    head = doc["traceEvents"][0]
    assert head["name"] == "mx.trace.dump"
    assert head["args"]["reason"] == "slow_step"
    assert head["args"]["step_seconds"] == pytest.approx(0.5)
    end = time.monotonic() + 5.0
    while time.monotonic() < end and not telemetry.value(
            "trace_dumps_total", {"reason": "slow_step"}):
        time.sleep(0.01)
    assert telemetry.value("trace_dumps_total",
                           {"reason": "slow_step"}) == 1


def test_slow_step_detector_quiet_before_min_samples():
    det = SlowStepDetector(factor=3.0, window=64, min_samples=32)
    trace.instant("warm")
    for _ in range(8):
        assert det.observe(0.01) is None
    assert det.observe(10.0) is None  # still warming up: no dump
    det0 = SlowStepDetector(factor=0.0)
    assert det0.observe(10.0) is None  # factor 0 disables


def test_deadline_burst_monitor_dumps_once_per_burst():
    mon = DeadlineMissMonitor(burst=5, window_seconds=10.0)
    trace.instant("warm")
    paths = [mon.miss() for _ in range(5)]
    assert _wait_for_file(paths[-1]), "async burst dump never landed"
    assert all(p is None for p in paths[:-1])
    with open(paths[-1]) as f:
        head = json.load(f)["traceEvents"][0]
    assert head["args"]["reason"] == "deadline_burst"
    assert head["args"]["misses"] == 5
    # window cleared: the next miss starts a new episode
    assert mon.miss() is None


def test_dump_rate_limit_per_reason(monkeypatch):
    monkeypatch.setenv("MXNET_TRACE_DUMP_MIN_SECONDS", "3600")
    trace.instant("warm")
    assert trace.dump(reason="slow_step") is not None
    assert trace.dump(reason="slow_step") is None   # limited
    assert trace.dump(reason="manual") is not None  # manual never is


def test_dump_skips_empty_ring(tmp_path):
    assert trace.dump(str(tmp_path / "never.json")) is None
    assert not os.path.exists(str(tmp_path / "never.json"))


# ---------------------------------------------------------------------------
# hang watchdog
# ---------------------------------------------------------------------------

def test_watchdog_fires_on_stalled_step_and_dumps():
    """Acceptance: a deliberately-stalled step makes the watchdog emit
    a flight-record dump + all-thread stacks."""
    fired = threading.Event()
    wd = Watchdog(timeout=0.2, poll=0.05,
                  on_fire=lambda name, age: fired.set())
    wd.start()
    try:
        stall = threading.Event()

        def stalled_step():
            with trace.span("fake_step", hist=False):
                with wd.watch("fake_step"):
                    stall.wait(5.0)  # the hang

        t = threading.Thread(target=stalled_step, name="stalled-trainer")
        t.start()
        assert fired.wait(3.0), "watchdog never fired"
        stall.set()
        t.join()
    finally:
        wd.stop()
    name, stacks_path, trace_path = wd.last_report
    assert name == "fake_step" and wd.fires >= 1
    # all-thread stacks: the stalled thread is visible BY NAME with its
    # hung frame
    with open(stacks_path) as f:
        stacks = f.read()
    assert "stalled-trainer" in stacks
    assert "stalled_step" in stacks
    assert "fake_step" in stacks  # the scope that tripped
    # the flight record is valid chrome-trace JSON flagged reason=hang
    with open(trace_path) as f:
        doc = json.load(f)
    assert doc["traceEvents"][0]["args"]["reason"] == "hang"
    assert telemetry.value("trace_watchdog_fires_total",
                           {"scope": "fake_step"}) >= 1


def test_watchdog_beat_defers_firing():
    wd = Watchdog(timeout=0.2, poll=10)  # poll never ticks: check() by hand
    with wd.watch("loop") as w:
        time.sleep(0.25)
        w.beat()
        assert wd.check() == []          # beat reset the clock
        time.sleep(0.25)
        assert [s.name for s in wd.check()] == ["loop"]
        assert wd.check() == []          # one report per hang episode


def test_watchdog_idle_and_fast_scopes_never_fire():
    wd = Watchdog(timeout=0.2, poll=10)
    for _ in range(5):
        with wd.watch("quick"):
            pass
    assert wd.check() == []      # nothing active
    assert wd.active() == []


def test_watchdog_dry_run_writes_both_artifacts():
    trace.instant("warm")
    wd = Watchdog(timeout=60, poll=10)
    stacks_path, trace_path = wd.dry_run()
    assert os.path.exists(stacks_path)
    assert trace_path is not None and os.path.exists(trace_path)
    assert "MainThread" in open(stacks_path).read()
    # a drill dumps under its own never-rate-limited reason: it must
    # not consume a REAL hang's dump budget
    with open(trace_path) as f:
        assert json.load(f)["traceEvents"][0]["args"]["reason"] \
            == "dry_run"
    _, hang_trace = wd._fire("really_hung", 1.0)
    assert hang_trace is not None
    with open(hang_trace) as f:
        assert json.load(f)["traceEvents"][0]["args"]["reason"] == "hang"


def test_module_watch_is_free_when_unarmed():
    assert trace.watchdog.get() is None
    with trace.watchdog.watch("anything"):
        pass  # null scope: no watchdog, no registration, no thread
    assert trace.watchdog.get() is None
    wd = trace.watchdog.install(timeout=60)
    try:
        assert trace.watchdog.get() is wd and wd.alive
        with trace.watchdog.watch("real"):
            assert wd.active() == ["real"]
    finally:
        trace.watchdog.uninstall()
    assert not wd.alive


# ---------------------------------------------------------------------------
# instrumented hot paths
# ---------------------------------------------------------------------------

def test_trainer_step_records_nested_phase_spans():
    """Acceptance: one trainer step shows >= 4 nested phase spans
    sharing a single trace_id."""
    net = nn.Dense(8, in_units=8)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = nd.array(np.ones((2, 8), np.float32))
    with autograd.record():
        loss = (net(x) ** 2).mean()
    loss.backward()
    trace.clear()
    trainer.step(2)
    evs = trace.events()
    root = [e for e in evs if e["name"] == "trainer_step"]
    assert len(root) == 1
    tid = root[0]["trace"]
    names = set(e["name"] for e in evs if e["trace"] == tid)
    assert {"trainer_step", "trainer_allreduce",
            "trainer_update"} <= names
    assert len(names) >= 4, names
    # children nest under the step root (directly or transitively)
    spans = {e["span"]: e for e in evs if e["trace"] == tid}
    for e in evs:
        if e["trace"] == tid and e["name"] != "trainer_step":
            p = e
            while p["parent"] is not None:
                p = spans[p["parent"]]
            assert p["name"] == "trainer_step"


def test_checkpoint_save_spans_share_steps_trace(tmp_path):
    from mxnet_tpu import checkpoint

    mgr = checkpoint.CheckpointManager(str(tmp_path / "ck"))
    with trace.span("train_step_ck", hist=False):
        fut = mgr.save_async(1, {"w": nd.array(np.ones((4,)))})
        step_trace = trace.current().trace_id
    fut.result()
    mgr.wait()
    evs = [e for e in trace.events() if e["trace"] == step_trace]
    names = set(e["name"] for e in evs)
    assert {"checkpoint_snapshot", "checkpoint_save",
            "checkpoint_serialize", "checkpoint_commit"} <= names
    # serialize/commit ran on the writer thread, snapshot on ours —
    # same trace, different tracks
    by = {e["name"]: e for e in evs}
    assert by["checkpoint_commit"]["tid"] != \
        by["checkpoint_snapshot"]["tid"]
    assert by["checkpoint_commit"]["tname"] == "mx-checkpoint-writer"


# ---------------------------------------------------------------------------
# serve lifecycle + X-Request-Id
# ---------------------------------------------------------------------------

def _serving(tmp_path):
    from mxnet_tpu import serve

    blk = nn.Dense(4, flatten=False, in_units=16)
    blk.initialize()
    blk(mx.nd.zeros((1, 2, 16)))
    root = str(tmp_path / "ckpt")
    blk.save_checkpoint(root, step=1)

    def make():
        return nn.Dense(4, flatten=False, in_units=16)

    cfg = serve.ServeConfig(max_batch_size=4, batch_sizes=(4,),
                            sample_shapes=[(8, 16)], max_wait_us=1000)
    return serve.Server(make, root=root, config=cfg)


def test_serve_request_lifecycle_spans_one_trace(tmp_path):
    """Acceptance: one serve request shows >= 4 nested phase spans
    sharing a single trace_id, on distinct thread tracks."""
    with _serving(tmp_path) as srv:
        trace.clear()
        out = srv.submit(np.ones((5, 16), dtype="float32"),
                         request_id="req-42")
        assert out.shape == (5, 4)
    evs = [e for e in trace.events() if e["trace"] == "req-42"]
    names = set(e["name"] for e in evs)
    assert {"serve_enqueue", "serve_queue_wait", "serve_dispatch",
            "serve_execute", "serve_request"} <= names
    assert len(names) >= 4
    # submitter thread and scheduler thread are distinct tracks
    assert len(set(e["tid"] for e in evs)) >= 2
    assert any(e["tname"] == "mx-serve-scheduler" for e in evs)
    # queue-wait and dispatch hang off the request's root span
    root = [e for e in evs if e["name"] == "serve_request"][0]
    assert root["parent"] is None
    qw = [e for e in evs if e["name"] == "serve_queue_wait"][0]
    assert qw["parent"] == root["span"]
    disp = [e for e in evs if e["name"] == "serve_dispatch"][0]
    assert disp["parent"] == root["span"]
    exe = [e for e in evs if e["name"] == "serve_execute"][0]
    assert exe["parent"] == disp["span"]


def test_http_predict_echoes_x_request_id(tmp_path):
    with _serving(tmp_path) as srv:
        host, port = srv.start_http()
        base = "http://%s:%d" % (host, port)
        body = json.dumps(
            {"inputs": np.ones((5, 16)).tolist()}).encode()
        req = urllib.request.Request(
            base + "/predict", data=body,
            headers={"X-Request-Id": "client-abc-7"})
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.headers.get("X-Request-Id") == "client-abc-7"
            out = json.load(r)
        assert np.asarray(out["outputs"]).shape == (5, 4)
        # the id became the trace id: the request is greppable in the
        # flight record by the client's own correlation id
        assert any(e.get("trace") == "client-abc-7"
                   for e in trace.events())
        # errors echo it too
        bad = urllib.request.Request(
            base + "/predict",
            data=json.dumps(
                {"inputs": np.ones((99, 16)).tolist()}).encode(),
            headers={"X-Request-Id": "client-err-1"})
        try:
            urllib.request.urlopen(bad, timeout=10)
            assert False, "expected HTTP 400"
        except urllib.error.HTTPError as err:
            assert err.code == 400
            assert err.headers.get("X-Request-Id") == "client-err-1"


def test_http_x_request_id_echo_is_sanitized(tmp_path):
    """An obs-folded X-Request-Id (embedded CRLF survives Python's
    header parser) must not be echoed verbatim — that would be an HTTP
    response-splitting vector."""
    import socket

    with _serving(tmp_path) as srv:
        host, port = srv.start_http()
        body = json.dumps({"inputs": np.ones((5, 16)).tolist()}).encode()
        raw = (b"POST /predict HTTP/1.1\r\n"
               b"Host: smoke\r\n"
               b"Content-Length: %d\r\n"
               b"X-Request-Id: abc\r\n evil: injected\r\n"  # obs-fold
               b"Connection: close\r\n\r\n" % len(body)) + body
        with socket.create_connection((host, port), timeout=30) as s:
            s.sendall(raw)
            resp = b""
            while True:
                chunk = s.recv(65536)
                if not chunk:
                    break
                resp += chunk
    head = resp.split(b"\r\n\r\n", 1)[0].decode("latin1")
    # no injected header line: the CR/LF was stripped, the echo is one
    # printable-only value
    for line in head.split("\r\n"):
        assert not line.lower().startswith("evil:")
        assert not line.startswith(" evil:")
    assert head.startswith("HTTP/1.1 200")


def test_serve_timeout_records_request_outcome(tmp_path):
    from mxnet_tpu.serve.batching import BatchQueue, Request, \
        RequestTimeout

    q = BatchQueue(depth=8)
    req = Request((np.zeros((2, 2)),), 0,
                  deadline=time.perf_counter() - 1.0,
                  request_id="late-1")
    q.put(req)
    q.close()
    assert q.collect(4, 0.001) is None  # expires the dead request
    with pytest.raises(RequestTimeout):
        req.future.result(timeout=5)
    evs = [e for e in trace.events() if e.get("trace") == "late-1"]
    outcome = [e for e in evs if e["name"] == "serve_request"]
    assert outcome and outcome[0]["args"]["result"] == "timeout"


def test_runtime_trace_feature_flag():
    from mxnet_tpu import runtime

    assert runtime.features.is_enabled("TRACE")
