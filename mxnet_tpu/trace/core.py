"""mx.trace core — structured spans, trace propagation, flight recorder.

The always-on tracing layer sitting between ``mx.telemetry`` (aggregate
metrics, no per-event detail) and ``mx.profiler`` (heavyweight xplane
capture): every instrumented phase records ONE bounded-ring event with a
``trace_id`` / ``span_id`` / ``parent`` triple, so "where did THIS step /
THIS request spend its time" is answerable after the fact — including
after a crash or hang, when the ring is dumped as a Perfetto/Chrome
trace (``trace/export.py``).

Design constraints (same discipline as telemetry):

- Disabled cost is one boolean check per hook (``trace.ENABLED``) and
  the profiler annotation's own enabled-check (about a microsecond a
  span in all, measured in PERF.md section 6, PR 24);
  ``MXNET_TRACE_DISABLE=1`` flips it at import, ``disable()`` at runtime.
- Context propagation uses ``contextvars`` — spans nest naturally per
  thread/async-task, and ``use(ctx)`` hands a context across threads
  (serve scheduler, checkpoint writer) explicitly.
- The flight recorder is a fixed-size ring (``MXNET_TRACE_RING_EVENTS``,
  default 8192): memory is bounded no matter how long the process runs,
  and the LAST N events are exactly what a post-mortem needs.
- ``span(...)`` additionally feeds the ``mx.telemetry`` histogram for
  its name (unless ``hist=False``) and opens a
  ``jax.profiler.TraceAnnotation``: one context manager, three sinks.
  The annotation lands in the profiler's OWN trace (the ``.xplane.pb``
  of ``jax.profiler.start_trace``, ``mx.profiler.set_state("run")`` or
  a capture from XProf), on the profiler's clock, on the thread that
  ran the span, with ``args`` as the event's stats.  A live profiler
  session is that sink's only switch: it does not look at ``ENABLED``
  or at telemetry, and with no session live it costs the annotation's
  own enabled-check.  (A string value is cut at its first comma there:
  the profiler's ``name#k=v,k=v#`` encoding.)
"""
from __future__ import annotations

import contextvars
import itertools
import random
import threading
import time
from collections import deque, namedtuple

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from .. import telemetry
from ..base import get_env

__all__ = [
    "ENABLED", "enable", "disable",
    "TraceContext", "current", "current_trace_id", "new_context",
    "new_request", "sanitize_request_id", "use", "span", "instant",
    "record_span",
    "RECORDER", "FlightRecorder", "events", "clear",
]

ENABLED = not get_env("MXNET_TRACE_DISABLE", bool, False)

DEFAULT_RING_EVENTS = 8192


def enable():
    """Turn trace recording on (module-wide)."""
    global ENABLED
    ENABLED = True


def disable():
    """Turn trace recording off; the ring keeps its current events."""
    global ENABLED
    ENABLED = False


# ---------------------------------------------------------------------------
# ids + context
# ---------------------------------------------------------------------------

# span/trace ids: process-random prefix + monotonic counter — unique,
# lock-free (itertools.count is atomic in CPython), and cheap enough
# for per-phase allocation on hot paths
_PREFIX = "%08x" % random.getrandbits(32)
_COUNT = itertools.count(1)


def _new_id():
    return "%s%08x" % (_PREFIX, next(_COUNT))


TraceContext = namedtuple("TraceContext", ("trace_id", "span_id"))

_CTX = contextvars.ContextVar("mxnet_tpu_trace", default=None)


def current():
    """The active TraceContext of this thread/task (None outside any
    span)."""
    return _CTX.get()


def current_trace_id():
    ctx = _CTX.get()
    return ctx.trace_id if ctx is not None else None


def new_context(trace_id=None):
    """A fresh TraceContext: ``trace_id`` if given, else the active
    trace's id, else a new one.  The span_id is always new — use this
    to mint a root identity for a unit of work (e.g. one serve
    request) whose child spans will run on other threads."""
    if trace_id is None:
        cur = _CTX.get()
        trace_id = cur.trace_id if cur is not None else _new_id()
    return TraceContext(str(trace_id), _new_id())


def sanitize_request_id(request_id):
    """Client correlation id -> safe internal form: printable chars
    only, <= 128 long, None when nothing survives.  The ONE rule both
    the trace id and the HTTP X-Request-Id echo apply — a raw client
    value is a header-injection vector and must never round-trip
    unfiltered."""
    if request_id is None:
        return None
    return "".join(c for c in str(request_id)[:128]
                   if c.isprintable()) or None


def new_request(request_id=None):
    """Trace identity for one serving request.  A client-supplied
    ``request_id`` (X-Request-Id) BECOMES the trace id (sanitized via
    ``sanitize_request_id``) so a request can be found in a
    flight-record dump by the id the client logged.  Returns None when
    tracing is disabled (requests carry no dead weight)."""
    if not ENABLED:
        return None
    if request_id is not None:
        return new_context(trace_id=sanitize_request_id(request_id))
    return new_context()


class use:
    """Adopt ``ctx`` (a TraceContext or None) as the active context —
    the explicit cross-thread handoff: capture ``current()`` where the
    work is submitted, ``use(ctx)`` where it executes."""

    __slots__ = ("_ctx", "_token")

    def __init__(self, ctx):
        self._ctx = ctx
        self._token = None

    def __enter__(self):
        if self._ctx is not None:
            self._token = _CTX.set(self._ctx)
        return self._ctx

    def __exit__(self, *exc):
        if self._token is not None:
            _CTX.reset(self._token)
            self._token = None
        return False


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class FlightRecorder:
    """Bounded in-memory ring of trace events (the post-mortem record).

    Appends are a deque.append under one lock; the ring discards the
    oldest event once ``capacity`` is reached, so a process that traces
    forever holds a constant-memory tail of recent activity."""

    def __init__(self, capacity=None):
        if capacity is None:
            capacity = get_env("MXNET_TRACE_RING_EVENTS", int,
                               DEFAULT_RING_EVENTS)
        self._lock = threading.Lock()
        self._ring = deque(maxlen=max(16, int(capacity)))
        self.dropped = 0  # events displaced by the ring bound

    @property
    def capacity(self):
        return self._ring.maxlen

    def __len__(self):
        return len(self._ring)

    def append(self, event):
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
            self._ring.append(event)

    def events(self):
        """Snapshot of the ring, oldest first."""
        with self._lock:
            return list(self._ring)

    def clear(self):
        with self._lock:
            self._ring.clear()
            self.dropped = 0

    def resize(self, capacity):
        """Re-bound the ring, keeping the newest events."""
        with self._lock:
            old = list(self._ring)
            self._ring = deque(old[-int(capacity):],
                               maxlen=max(16, int(capacity)))


RECORDER = FlightRecorder()


def events():
    """Snapshot of the flight-recorder ring (oldest first)."""
    return RECORDER.events()


def clear():
    """Drop every buffered event (tests / between bench rows)."""
    RECORDER.clear()


def _record(name, cat, start, dur, trace_id, span_id, parent, args=None,
            ph="X"):
    t = threading.current_thread()
    ev = {"name": name, "cat": cat, "ph": ph, "ts": start, "dur": dur,
          "trace": trace_id, "span": span_id, "parent": parent,
          "tid": t.ident, "tname": t.name}
    if args:
        ev["args"] = args
    RECORDER.append(ev)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class span:
    """Timing context recording into the flight ring (with trace/span/
    parent propagation), the telemetry histogram for its name, and the
    profiler's own trace (a ``jax.profiler.TraceAnnotation``, whenever a
    profiler session is live).

    Parameters
    ----------
    name : str — span (and default histogram ``<name>_seconds``) name.
    hist : None | False | Metric — telemetry histogram to observe on
        exit.  None (default) get-or-creates ``<name>_seconds`` exactly
        like ``telemetry.span``; False skips the histogram (for sites
        that already meter their latency).
    cat : str — event category (Perfetto track color grouping).
    args : dict — extra event args (kept small: the ring holds refs).
    anomaly : bool — feed this span's duration to the slow-step
        detector (``trace/anomaly.py``) on exit.
    step_num : int — this span is one training step: ``step_num`` joins
        ``args`` and the annotation is a ``StepTraceAnnotation``, by
        which XProf groups the device's work.
    """

    __slots__ = ("name", "cat", "args", "_hist", "_anomaly", "_start",
                 "_ctx", "_parent", "_token", "_step", "_ann")

    def __init__(self, name, hist=None, cat="trace", args=None,
                 anomaly=False, step_num=None):
        self.name = name
        self.cat = cat
        if step_num is not None:
            args = dict(args or (), step_num=step_num)
        self.args = args
        self._hist = hist
        self._anomaly = anomaly
        self._start = None
        self._ctx = None
        self._parent = None
        self._token = None
        self._step = step_num is not None
        self._ann = None

    def note(self, **args):
        """Add ``args`` learned inside the span (the ring reads them at
        exit, the profiler's event takes them now)."""
        self.args = dict(self.args or (), **args)
        self._ann.set_metadata(**args)

    def __enter__(self):
        # made here, not in __init__: the annotation looks for a live
        # session when it is made
        self._ann = (StepTraceAnnotation if self._step
                     else TraceAnnotation)(self.name, **(self.args or {}))
        self._ann.__enter__()
        tr_on = ENABLED
        if not tr_on and (not telemetry.ENABLED
                          or self._hist is False):
            # dead for this span's lifetime but for the annotation:
            # tracing off AND nothing for telemetry to observe
            # (hist=False hot-path spans must cost no clock read when
            # the ring is disabled)
            return self
        self._start = time.perf_counter()
        if tr_on:
            parent = _CTX.get()
            self._parent = parent
            self._ctx = TraceContext(
                parent.trace_id if parent is not None else _new_id(),
                _new_id())
            self._token = _CTX.set(self._ctx)
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        if self._token is not None:
            _CTX.reset(self._token)
            self._token = None
        if self._start is None:
            return False
        dur = time.perf_counter() - self._start
        if ENABLED and self._ctx is not None:
            _record(self.name, self.cat, self._start, dur,
                    self._ctx.trace_id, self._ctx.span_id,
                    self._parent.span_id if self._parent is not None
                    else None, self.args)
        if telemetry.ENABLED and self._hist is not False:
            hist = self._hist
            if hist is None:
                hist = telemetry.histogram(
                    self.name + "_seconds",
                    "duration of %s spans" % self.name)
            hist.observe(dur)
        if self._anomaly:
            from . import anomaly

            anomaly.observe_step(dur)
        self._start = None
        self._ctx = None
        return False


def instant(name, cat="trace", args=None, ctx=None):
    """Record one zero-duration marker event (ph 'i') under ``ctx`` (or
    the active context), and a zero-length annotation in the profiler's
    trace."""
    with TraceAnnotation(name, **(args or {})):
        pass
    if not ENABLED:
        return
    if ctx is None:
        ctx = _CTX.get()
    _record(name, cat, time.perf_counter(), 0.0,
            ctx.trace_id if ctx else _new_id(),
            _new_id(), ctx.span_id if ctx else None, args, ph="i")


def record_span(name, start, dur, ctx=None, root=False, cat="trace",
                args=None):
    """Record a span with EXPLICIT timing — for phases whose start was
    observed before their identity existed on this thread (e.g. a serve
    request's queue wait, reconstructed at dispatch from its enqueue
    timestamp).  Ring only: the profiler's trace takes no event after
    the fact.

    With ``ctx``: the event joins that trace; ``root=True`` makes the
    event BE the context's own span (ctx.span_id, no parent) — the
    request-level root — while the default records a fresh child span
    under it."""
    if not ENABLED:
        return
    if ctx is None:
        ctx = _CTX.get()
    if ctx is None:
        ctx = TraceContext(_new_id(), _new_id())
        root = True
    if root:
        _record(name, cat, start, dur, ctx.trace_id, ctx.span_id, None,
                args)
    else:
        _record(name, cat, start, dur, ctx.trace_id, _new_id(),
                ctx.span_id, args)
