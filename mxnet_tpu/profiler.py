"""Profiler (reference src/profiler/profiler.h + python/mxnet/profiler.py —
Chrome-tracing JSON dumps, ProfileDomain/Task/Frame/Event/Counter/Marker,
engine-hooked op profiling).

TPU-native: backed by the XLA/PJRT profiler (jax.profiler): traces capture
device kernels, HLO ops, and host activity into an xplane that exports to
TensorBoard and Perfetto/Chrome-trace — superseding the ring-buffer
ProfileStat machinery.  The mx.profiler python surface (set_config /
set_state / dump / Task / Frame / Marker...) is preserved.
"""
from __future__ import annotations

import json
import os
import threading
import time

from .base import MXNetError, get_env

__all__ = ["set_config", "set_state", "state", "dump", "dumps", "pause",
           "resume", "device_op_stats", "memory_info", "Domain", "Task",
           "Frame", "Event", "Counter", "Marker", "profiler_set_config",
           "profiler_set_state"]

_config = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_memory": True,
    "profile_api": True,
    "aggregate_stats": False,
}
_state = {"running": False, "trace_dir": None, "events": []}
# one lock for every _state["events"] append AND Counter value updates —
# spans/counters are hit from dataloader worker threads and the engine
# path, and a torn read-modify-write would lose counts
_events_lock = threading.Lock()


def set_config(**kwargs):
    """Reference profiler.py:34 set_config."""
    _config.update(kwargs)


profiler_set_config = set_config


def set_state(state_name="stop", profile_process="worker"):
    """Reference profiler.py:92 set_state ('run'/'stop')."""
    import jax

    if state_name == "run" and not _state["running"]:
        trace_dir = os.path.splitext(_config["filename"])[0] + "_xplane"
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir)
        _state["running"] = True
        _state["trace_dir"] = trace_dir
    elif state_name == "stop" and _state["running"]:
        jax.profiler.stop_trace()
        _state["running"] = False
    elif state_name not in ("run", "stop"):
        raise MXNetError("state must be 'run' or 'stop'")


profiler_set_state = set_state


def state():
    return "run" if _state["running"] else "stop"


def pause(profile_process="worker"):
    if _state["running"]:
        set_state("stop")


def resume(profile_process="worker"):
    set_state("run")


def dump(finished=True, profile_process="worker"):
    """Write the chrome-trace JSON (reference profiler.py:125) of this
    module's own objects: ``Task``/``Frame``/``Event``/``Counter``/
    ``Marker``.  Device activity, and every ``mx.trace`` /
    ``mx.telemetry`` span, lives in the xplane directory next to it (the
    profiler's own trace, TensorBoard-loadable): spans are
    ``jax.profiler.TraceAnnotation``s there and no longer copied here.

    Events carry the REAL pid and the thread id recorded when each
    event was appended (plus ``thread_name`` metadata), so events from
    different threads land on separate Perfetto tracks instead of one
    overlapping tid-0 row."""
    if _state["running"] and finished:
        set_state("stop")
    pid = os.getpid()
    with _events_lock:
        events = list(_state["events"])
    threads = {}
    for ev in events:
        if ev.get("tid") and ev.get("tname"):
            threads.setdefault(ev["tid"], ev["tname"])
    meta = [{"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
             "args": {"name": tname}}
            for tid, tname in sorted(threads.items())]
    trace = {"traceEvents": meta + [
        {"name": ev["name"], "cat": ev.get("cat", "user"),
         "ph": ev.get("ph", "X"), "ts": ev["ts"] * 1e6,
         "dur": ev.get("dur", 0) * 1e6, "pid": pid,
         "tid": ev.get("tid", 0), "args": ev.get("args", {})}
        for ev in events]}
    with open(_config["filename"], "w") as f:
        json.dump(trace, f)
    return _config["filename"]


def dumps(reset=False, format="table", sort_by="total", ascending=False):
    """Aggregate stats string (reference profiler.py:154 + aggregate_
    stats.cc): aggregates of ``Task``/``Frame``/``Event`` objects only
    (``mx.trace`` and ``mx.telemetry`` spans are in the xplane, not in
    this list), plus the device-op table when a trace was captured and
    aggregate_stats is enabled."""
    by_name = {}
    for ev in _state["events"]:
        agg = by_name.setdefault(ev["name"], [0, 0.0])
        agg[0] += 1
        agg[1] += ev.get("dur", 0)
    lines = ["%-40s %8s %12s" % ("Name", "Calls", "Total(ms)")]
    for name, (calls, total) in sorted(by_name.items(),
                                       key=lambda kv: -kv[1][1]):
        lines.append("%-40s %8d %12.3f" % (name, calls, total * 1e3))
    if _config.get("aggregate_stats") and _state.get("trace_dir"):
        dev = device_op_stats()
        if dev:
            lines.append("")
            lines.append("%-48s %8s %12s" % ("Device op category",
                                             "Count", "Time(ms)"))
            for row in dev:
                lines.append("%-48s %8d %12.3f" % (
                    row["name"][:48], row["occurrences"],
                    row["time_ms"]))
    if reset:
        _state["events"].clear()
    return "\n".join(lines)


def device_op_stats(trace_dir=None, top=25):
    """Aggregate device-op table from the captured xplane (reference
    aggregate_stats.cc tables, rebuilt from the XLA profiler's data).

    Returns [{name, occurrences, time_ms}, ...] sorted by time, or [] if
    no trace/parser is available (xprof/tensorboard-plugin-profile parses
    the xplane)."""
    import glob

    trace_dir = trace_dir or _state.get("trace_dir")
    if not trace_dir:
        return []
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return []
    try:
        from xprof.convert import raw_to_tool_data as _rtd

        out, _ = _rtd.xspace_to_tool_data(files[-1:], "op_profile", {})
        data = json.loads(out.decode() if isinstance(out, bytes) else out)
    except Exception:
        return []
    rows = []

    def walk(node, depth):
        m = node.get("metrics", {})
        if depth == 2 and m.get("rawTime"):
            rows.append({"name": node.get("name", "?"),
                         "occurrences": int(m.get("occurrences", 0)),
                         "time_ms": m["rawTime"] / 1e9})
        for c in node.get("children", []):
            walk(c, depth + 1)

    root = data.get("byCategory") or data.get("byProgram") or {}
    walk(root, 0)
    rows.sort(key=lambda r: -r["time_ms"])
    return rows[:top]


def memory_info(device=None):
    """Device memory profiler (reference storage_profiler.cc GPU memory
    stats): per-device bytes in use / peak / limit from PJRT.  Backends
    without memory_stats (CPU) report {}."""
    import jax

    devices = [device] if device is not None else jax.local_devices()
    report = {}
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        report[str(d)] = {
            k: stats[k] for k in (
                "bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                "largest_alloc_size", "num_allocs")
            if k in stats}
    return report


class Domain:
    """Reference profiler.py Domain."""

    def __init__(self, name):
        self.name = name

    def new_task(self, name):
        return Task(self, name)

    def new_frame(self, name):
        return Frame(self, name)

    def new_event(self, name):
        return Event(name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_marker(self, name):
        return Marker(self, name)


class _Span:
    _kind = "span"

    def __init__(self, domain, name):
        self.name = name if isinstance(domain, Domain) else domain
        self._domain = domain.name if isinstance(domain, Domain) else "user"
        self._start = None
        self._jax_ctx = None

    def start(self):
        import jax

        self._start = time.perf_counter()
        self._jax_ctx = jax.profiler.TraceAnnotation(self.name)
        self._jax_ctx.__enter__()
        return self

    def stop(self):
        if self._jax_ctx is not None:
            self._jax_ctx.__exit__(None, None, None)
            self._jax_ctx = None
        if self._start is not None:
            # tid is recorded at append time (not dump time): the span
            # may be stopped from any thread, and dump() runs on
            # whichever thread asks for the file
            t = threading.current_thread()
            with _events_lock:
                _state["events"].append({
                    "name": self.name, "cat": self._kind,
                    "ts": self._start,
                    "dur": time.perf_counter() - self._start,
                    "tid": t.ident, "tname": t.name})
            self._start = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *a):
        self.stop()


class Task(_Span):
    _kind = "task"


class Frame(_Span):
    _kind = "frame"


class Event(_Span):
    _kind = "event"

    def __init__(self, name):
        super().__init__("user", name)


class Counter:
    def __init__(self, domain, name, value=None):
        self.name = name
        # `value or 0` collapsed an explicit 0/0.0 into int 0 (losing the
        # float-ness of 0.0 and conflating "unset" with "set to zero");
        # only None means unset
        self.value = 0 if value is None else value

    def _record(self, value):
        t = threading.current_thread()
        with _events_lock:
            self.value = value
            _state["events"].append({"name": self.name, "cat": "counter",
                                     "ph": "C", "ts": time.perf_counter(),
                                     "tid": t.ident, "tname": t.name,
                                     "args": {"value": value}})

    def set_value(self, value):
        self._record(value)

    def increment(self, delta=1):
        t = threading.current_thread()
        with _events_lock:
            self.value += delta
            _state["events"].append({"name": self.name, "cat": "counter",
                                     "ph": "C", "ts": time.perf_counter(),
                                     "tid": t.ident, "tname": t.name,
                                     "args": {"value": self.value}})

    def decrement(self, delta=1):
        self.increment(-delta)

    def __iadd__(self, delta):
        self.increment(delta)
        return self

    def __isub__(self, delta):
        self.decrement(delta)
        return self


class Marker:
    def __init__(self, domain, name):
        self.name = name

    def mark(self, scope="process"):
        t = threading.current_thread()
        with _events_lock:
            _state["events"].append({"name": self.name, "cat": "marker",
                                     "ph": "i", "ts": time.perf_counter(),
                                     "tid": t.ident, "tname": t.name})
