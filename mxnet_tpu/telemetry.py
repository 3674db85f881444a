"""Runtime telemetry: process-wide metrics registry + exporters.

Always-on, low-overhead observability for the runtime — the layer a full
xplane trace (mx.profiler) is too heavy for.  Counters/Gauges/Histograms
with labels cover compile-cache behaviour (gluon/block.py), engine pushes
(engine.py), host<->device transfer volume (ndarray), collective traffic
(kvstore/collective.py), dataloader stalls (gluon/data/dataloader.py) and
device-memory watermarks (``sample_device_memory`` over
``profiler.memory_info``).

Design constraints:

- Disabled cost is ONE boolean check per instrumentation hook
  (``if telemetry.ENABLED:``) — no dict lookups, no label/string work.
  ``MXNET_TELEMETRY_DISABLE=1`` flips it at import; ``disable()`` /
  ``enable()`` flip it at runtime.
- All mutation goes through one module lock, so metrics are safe to
  update from dataloader worker threads and the engine path.
- Timers use the monotonic clock (``time.perf_counter``); ``span(...)``
  and ``@timed(...)`` additionally open a ``jax.profiler.TraceAnnotation``,
  so ad-hoc telemetry spans land in the profiler's own trace (the
  ``.xplane.pb``) whenever a profiler session is live.

Exporters: ``prometheus()`` (text exposition format), ``snapshot()`` /
``dump(path)`` (JSON), ``totals()`` (flat name->value convenience), and
an optional periodic log line driven by MXNET_TELEMETRY_LOG_INTERVAL.
"""
from __future__ import annotations

import json
import logging
import threading
import time

from jax.profiler import TraceAnnotation

from .base import get_env

__all__ = [
    "ENABLED", "enable", "disable",
    "counter", "gauge", "histogram", "get_metric",
    "span", "timed",
    "snapshot", "totals", "value", "dump", "prometheus", "reset",
    "histogram_quantiles",
    "sample_device_memory", "log_line", "start_logger",
    "DEFAULT_BUCKETS",
]

_LOGGER = logging.getLogger("mxnet_tpu.telemetry")

# single lock for all registry + sample mutation (cheap: held only for
# a float add / list append, never across user code)
_LOCK = threading.Lock()
_REGISTRY = {}  # name -> metric, insertion-ordered

ENABLED = not get_env("MXNET_TELEMETRY_DISABLE", bool, False)

DEFAULT_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                   0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


def enable():
    """Turn instrumentation hooks back on (module-wide)."""
    global ENABLED
    ENABLED = True


def disable():
    """Turn instrumentation hooks off; metrics keep their current values."""
    global ENABLED
    ENABLED = False


# ---------------------------------------------------------------------------
# metric kinds
# ---------------------------------------------------------------------------

class _CounterChild:
    __slots__ = ("_value",)

    def __init__(self):
        self._value = 0.0

    def inc(self, amount=1.0):
        if amount < 0:
            raise ValueError("counters can only increase (got %r)" % amount)
        with _LOCK:
            self._value += amount

    @property
    def value(self):
        return self._value


class _GaugeChild:
    __slots__ = ("_value",)

    def __init__(self):
        self._value = 0.0

    def set(self, v):
        with _LOCK:
            self._value = float(v)

    def inc(self, amount=1.0):
        with _LOCK:
            self._value += amount

    def dec(self, amount=1.0):
        with _LOCK:
            self._value -= amount

    @property
    def value(self):
        return self._value


class _HistogramChild:
    __slots__ = ("_buckets", "_counts", "_sum", "_count")

    def __init__(self, buckets):
        self._buckets = buckets
        self._counts = [0] * (len(buckets) + 1)  # last = +Inf overflow
        self._sum = 0.0
        self._count = 0

    def observe(self, v):
        v = float(v)
        i = 0
        for i, ub in enumerate(self._buckets):
            if v <= ub:
                break
        else:
            i = len(self._buckets)
        with _LOCK:
            self._counts[i] += 1
            self._sum += v
            self._count += 1

    @property
    def count(self):
        return self._count

    @property
    def sum(self):
        return self._sum

    def read(self):
        """Locked consistent view: (count, sum, cumulative buckets)."""
        with _LOCK:
            count, total = self._count, self._sum
            counts = list(self._counts)
        out, acc = [], 0
        for ub, c in zip(self._buckets, counts):
            acc += c
            out.append((ub, acc))
        out.append((float("inf"), acc + counts[-1]))
        return count, total, out

    def cumulative(self):
        """[(upper_bound, cumulative_count), ...] ending with +Inf."""
        return self.read()[2]


_CHILD_FACTORY = {
    "counter": lambda m: _CounterChild(),
    "gauge": lambda m: _GaugeChild(),
    "histogram": lambda m: _HistogramChild(m.buckets),
}


class Metric:
    """A named metric family; label children are created on demand."""

    def __init__(self, kind, name, help="", labelnames=(), buckets=None):
        self.kind = kind
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self.buckets = tuple(sorted(buckets or DEFAULT_BUCKETS)) \
            if kind == "histogram" else None
        self._children = {}  # labelvalues tuple -> child
        self._default = None if self.labelnames \
            else _CHILD_FACTORY[kind](self)

    def labels(self, *values, **kwargs):
        if not self.labelnames:
            # a shadow () child would duplicate the default sample's
            # (empty-label) series in the prometheus output
            raise ValueError("%s has no labels: use it directly"
                             % self.name)
        if kwargs:
            if values:
                raise ValueError("pass labels positionally or by name, "
                                 "not both")
            if set(kwargs) != set(self.labelnames):
                raise ValueError(
                    "%s takes labels %s, got %s"
                    % (self.name, self.labelnames, sorted(kwargs)))
            values = tuple(kwargs[k] for k in self.labelnames)
        values = tuple(str(v) for v in values)
        if len(values) != len(self.labelnames):
            raise ValueError("%s expects labels %s, got %r"
                             % (self.name, self.labelnames, values))
        child = self._children.get(values)
        if child is None:
            with _LOCK:
                child = self._children.setdefault(
                    values, _CHILD_FACTORY[self.kind](self))
        return child

    def _delegate(self):
        if self._default is None:
            raise ValueError("%s has labels %s: call .labels(...) first"
                             % (self.name, self.labelnames))
        return self._default

    # unlabelled convenience surface
    def inc(self, amount=1.0):
        self._delegate().inc(amount)

    def dec(self, amount=1.0):
        self._delegate().dec(amount)

    def set(self, v):
        self._delegate().set(v)

    def observe(self, v):
        self._delegate().observe(v)

    @property
    def value(self):
        return self._delegate().value

    @property
    def count(self):
        return self._delegate().count

    @property
    def sum(self):
        return self._delegate().sum

    def _samples(self):
        """[(labelvalues tuple, child), ...] including the default child.

        The children dict is snapshotted under the lock: exporters (and
        the periodic log thread) iterate while labels() inserts."""
        with _LOCK:
            items = list(self._children.items())
        out = []
        if self._default is not None:
            out.append(((), self._default))
        out.extend(sorted(items))
        return out

    def _reset(self):
        # zero IN PLACE: instrumentation sites hold direct child refs
        # (e.g. TRANSFER_H2D), so replacing children would orphan them
        with _LOCK:
            children = list(self._children.values())
            if self._default is not None:
                children.append(self._default)
            for child in children:
                if self.kind == "histogram":
                    child._counts = [0] * (len(self.buckets) + 1)
                    child._sum = 0.0
                    child._count = 0
                else:
                    child._value = 0.0


def _register(kind, name, help, labelnames, buckets=None):
    # registration is cold-path: always validate under the lock so a
    # racing mis-typed registration raises instead of silently returning
    # a metric of the wrong kind
    with _LOCK:
        m = _REGISTRY.get(name)
        if m is not None:
            if m.kind != kind or m.labelnames != tuple(labelnames):
                raise ValueError(
                    "metric %r already registered as %s%s"
                    % (name, m.kind, m.labelnames))
            if kind == "histogram" and buckets is not None \
                    and tuple(sorted(buckets)) != m.buckets:
                raise ValueError(
                    "histogram %r already registered with buckets %s"
                    % (name, m.buckets))
            return m
        m = Metric(kind, name, help, labelnames, buckets)
        _REGISTRY[name] = m
    return m


def counter(name, help="", labelnames=()):
    """Get-or-create a monotonically increasing counter."""
    return _register("counter", name, help, labelnames)


def gauge(name, help="", labelnames=()):
    """Get-or-create a gauge (set/inc/dec)."""
    return _register("gauge", name, help, labelnames)


def histogram(name, help="", labelnames=(), buckets=None):
    """Get-or-create a histogram with fixed upper-bound buckets."""
    return _register("histogram", name, help, labelnames, buckets)


def get_metric(name):
    """Look up a registered metric (None if absent)."""
    return _REGISTRY.get(name)


def reset():
    """Zero every registered metric (registrations are kept)."""
    for m in list(_REGISTRY.values()):
        m._reset()


# ---------------------------------------------------------------------------
# timers
# ---------------------------------------------------------------------------

class span:
    """Monotonic-clock timing context: observes ``<name>_seconds`` (or the
    given histogram) and opens a ``jax.profiler.TraceAnnotation``, so the
    span lands in the profiler's own trace whenever a profiler session is
    live (that sink does not look at ``ENABLED``).

    >>> with telemetry.span("train_step"):
    ...     step()
    """

    __slots__ = ("name", "_hist", "_start", "_ann")

    def __init__(self, name, hist=None):
        self.name = name
        self._hist = hist
        self._start = None
        self._ann = None

    def __enter__(self):
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        # disabled-at-enter spans stay dead for their whole lifetime:
        # no clock read here, and __exit__ is a single None check (a
        # span that straddles an enable() observes nothing — half a
        # duration would be a lie)
        self._start = time.perf_counter() if ENABLED else None
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        if self._start is None or not ENABLED:
            return False
        dur = time.perf_counter() - self._start
        hist = self._hist
        if hist is None:
            hist = histogram(self.name + "_seconds",
                             "duration of %s spans" % self.name)
        hist.observe(dur)
        return False


def timed(name, hist=None):
    """Decorator form of ``span``: time every call of fn."""
    def deco(fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with span(name, hist):
                return fn(*args, **kwargs)
        return wrapper
    return deco


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def _labels_dict(metric, values):
    return dict(zip(metric.labelnames, values))


def snapshot():
    """JSON-ready view: {name: {type, help, samples: [...]}}.

    Counter/gauge samples: {"labels": {...}, "value": v}; histogram
    samples: {"labels": {...}, "count": n, "sum": s, "buckets": {le: n}}
    with cumulative bucket counts ("+Inf" last).
    """
    out = {}
    for name, m in list(_REGISTRY.items()):
        samples = []
        for values, child in m._samples():
            labels = _labels_dict(m, values)
            if m.kind == "histogram":
                count, total, cum = child.read()
                samples.append({
                    "labels": labels, "count": count, "sum": total,
                    "buckets": {_fmt_le(ub): c for ub, c in cum}})
            else:
                samples.append({"labels": labels, "value": child.value})
        out[name] = {"type": m.kind, "help": m.help, "samples": samples}
    return out


def _bucket_quantile(cum, count, q):
    """Estimate the q-quantile from cumulative bucket counts (linear
    interpolation within the covering bucket, Prometheus
    histogram_quantile style).  Observations in the +Inf overflow
    bucket clamp to the last finite bound — the estimate never invents
    a value beyond what the buckets can resolve."""
    if count <= 0:
        return 0.0
    target = q * count
    lo, prev_c, last_finite = 0.0, 0, 0.0
    for ub, c in cum:
        if ub != float("inf"):
            last_finite = ub
        if c >= target:
            if ub == float("inf"):
                return last_finite
            width = c - prev_c
            if width <= 0:
                return ub
            return lo + (target - prev_c) / width * (ub - lo)
        prev_c = c
        if ub != float("inf"):
            lo = ub
    return last_finite


def _merged_read(metric, match=None):
    """(count, sum, merged cumulative buckets) across the label
    children of a histogram family (all children share the family's
    bucket edges).  ``match`` restricts the merge to children whose
    labels contain it — the per-tenant SLO view reads one tenant's
    samples out of a shared histogram."""
    want = {k: str(v) for k, v in (match or {}).items()}
    reads = [c.read() for values, c in metric._samples()
             if all(_labels_dict(metric, values).get(k) == v
                    for k, v in want.items())]
    count = sum(r[0] for r in reads)
    total = sum(r[1] for r in reads)
    cum = [(ub, sum(r[2][i][1] for r in reads))
           for i, (ub, _) in enumerate(reads[0][2])] if reads else []
    return count, total, cum


def histogram_quantiles(name, qs=(0.5, 0.95, 0.99)):
    """Bucket-estimated quantiles of a histogram family, merged over
    its label children: {q: seconds}.  {} for unknown/empty/non-
    histogram names — SLO-ish latency without scraping Prometheus."""
    m = _REGISTRY.get(name)
    if m is None or m.kind != "histogram":
        return {}
    count, _, cum = _merged_read(m)
    if not count:
        return {}
    return {q: _bucket_quantile(cum, count, q) for q in qs}


def totals(nonzero=False, quantiles=False):
    """Flat {name: summed value} over all label children; histograms
    contribute ``<name>_count`` and ``<name>_sum`` — plus bucket-
    estimated ``_p50``/``_p95``/``_p99`` when ``quantiles`` is set (the
    periodic log line asks for them).  The compact form bench rows and
    the periodic log line carry."""
    out = {}
    for name, m in list(_REGISTRY.items()):
        if m.kind == "histogram":
            count, total, cum = _merged_read(m)
            out[name + "_count"] = count
            out[name + "_sum"] = round(total, 6)
            if quantiles and count:
                for q, label in ((0.5, "_p50"), (0.95, "_p95"),
                                 (0.99, "_p99")):
                    out[name + label] = round(
                        _bucket_quantile(cum, count, q), 6)
        else:
            out[name] = sum(c.value for _, c in m._samples())
    if nonzero:
        out = {k: v for k, v in out.items() if v}
    return out


def value(name, labels=None):
    """Sum of a counter/gauge's samples whose labels contain ``labels``."""
    m = _REGISTRY.get(name)
    if m is None:
        return 0.0
    want = {k: str(v) for k, v in (labels or {}).items()}
    tot = 0.0
    for values, child in m._samples():
        have = _labels_dict(m, values)
        if all(have.get(k) == v for k, v in want.items()):
            tot += child.value if m.kind != "histogram" else child.count
    return tot


def dump(path):
    """Write the JSON snapshot to ``path``; returns the path."""
    with open(path, "w") as f:
        json.dump({"time": time.time(), "enabled": ENABLED,
                   "metrics": snapshot()}, f, indent=2, sort_keys=True)
    return path


def _fmt_le(ub):
    return "+Inf" if ub == float("inf") else repr(float(ub))


def _esc(v):
    return str(v).replace("\\", r"\\").replace('"', r'\"') \
        .replace("\n", r"\n")


def _esc_help(v):
    # HELP text escapes only backslash and newline (the exposition
    # format spec) — quotes stay literal, unlike label values
    return str(v).replace("\\", r"\\").replace("\n", r"\n")


def _labelstr(metric, values, extra=()):
    pairs = list(zip(metric.labelnames, values)) + list(extra)
    if not pairs:
        return ""
    return "{%s}" % ",".join('%s="%s"' % (k, _esc(v)) for k, v in pairs)


def prometheus():
    """Prometheus text exposition format (one # HELP/# TYPE pair plus
    sample lines per registered metric)."""
    lines = []
    for name, m in list(_REGISTRY.items()):
        lines.append("# HELP %s %s" % (name, _esc_help(m.help or name)))
        lines.append("# TYPE %s %s" % (name, m.kind))
        for values, child in m._samples():
            if m.kind == "histogram":
                count, total, cum = child.read()
                for ub, c in cum:
                    lines.append("%s_bucket%s %d" % (
                        name, _labelstr(m, values, [("le", _fmt_le(ub))]),
                        c))
                lines.append("%s_sum%s %s"
                             % (name, _labelstr(m, values), repr(total)))
                lines.append("%s_count%s %d"
                             % (name, _labelstr(m, values), count))
            else:
                lines.append("%s%s %s" % (name, _labelstr(m, values),
                                          repr(float(child.value))))
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# device-memory sampler
# ---------------------------------------------------------------------------

def sample_device_memory(device=None):
    """Refresh ``device_memory_bytes`` gauges from profiler.memory_info()
    (PJRT memory_stats; CPU backends report nothing).  Returns the raw
    report for convenience."""
    if not ENABLED:
        return {}
    from . import profiler

    try:
        report = profiler.memory_info(device)
    except Exception:  # backend down: telemetry must never raise
        return {}
    for dev, stats in report.items():
        for stat, v in stats.items():
            DEVICE_MEMORY.labels(device=dev, stat=stat).set(v)
    return report


# ---------------------------------------------------------------------------
# periodic log line
# ---------------------------------------------------------------------------

_logger_started = False


def log_line():
    """One compact 'telemetry k=v ...' line over the nonzero totals
    (histograms carry their bucket-estimated p50/p95/p99).  Registered
    SLOs are evaluated first so their state/burn gauges are fresh in
    the same line."""
    try:
        from .obs import slo_engine as _slo

        if _slo.registered():
            _slo.evaluate()
    except Exception:  # noqa: BLE001 - the log line must never fail
        pass
    tot = totals(nonzero=True, quantiles=True)
    body = " ".join(
        "%s=%s" % (k, ("%d" % v) if float(v).is_integer() else
                   ("%.6g" % v))
        for k, v in sorted(tot.items()))
    return "telemetry " + (body or "(all zero)")


def _log_loop(interval):
    while True:
        time.sleep(interval)
        try:
            if ENABLED:
                sample_device_memory()
                _LOGGER.info(log_line())
        except Exception:  # noqa: BLE001 - the log thread must survive
            _LOGGER.exception("telemetry log tick failed")


def start_logger(interval=None):
    """Start the periodic telemetry log thread (idempotent).  With no
    argument, reads MXNET_TELEMETRY_LOG_INTERVAL (seconds; 0 = off)."""
    global _logger_started
    if interval is None:
        interval = get_env("MXNET_TELEMETRY_LOG_INTERVAL", float, 0.0)
    if not interval or interval <= 0 or _logger_started:
        return False
    t = threading.Thread(target=_log_loop, args=(float(interval),),
                         daemon=True, name="mxnet-telemetry-log")
    t.start()
    _logger_started = True
    return True


# ---------------------------------------------------------------------------
# canonical framework metrics (registered at import so every exporter
# emits a stable, documented set — see README "Telemetry & observability")
# ---------------------------------------------------------------------------

CACHEDOP_BUILD = counter(
    "cachedop_build_total",
    "hybridize cache compiles (one jit trace per new signature)",
    ("block",))
CACHEDOP_HIT = counter(
    "cachedop_hit_total", "hybridize cache hits", ("block",))
CACHEDOP_RECOMPILE = counter(
    "cachedop_recompile_total",
    "cache builds that added a signature to an already-warm block "
    "(shape/dtype/mode churn)", ("block",))
CACHEDOP_BUILD_SECONDS = histogram(
    "cachedop_build_seconds", "hybridize trace+compile latency")
ENGINE_PUSH = counter(
    "engine_push_total", "ops pushed through the engine facade")
ENGINE_NAIVE_WAIT = counter(
    "engine_naive_wait_total",
    "blocking waits forced by NaiveEngine mode")
TRANSFER_BYTES = counter(
    "transfer_bytes_total", "host<->device transfer volume",
    ("direction",))
TRANSFER_D2H = TRANSFER_BYTES.labels(direction="d2h")
TRANSFER_H2D = TRANSFER_BYTES.labels(direction="h2d")
COLLECTIVE_CALLS = counter(
    "collective_calls_total", "collective programs dispatched", ("op",))
COLLECTIVE_BYTES = counter(
    "collective_bytes_total", "bytes moved by collectives", ("op",))
COLLECTIVE_SECONDS = histogram(
    "collective_seconds", "collective dispatch+assembly latency")
ALLREDUCE_BUCKET_FILL = histogram(
    "allreduce_bucket_fill",
    "fill fraction of each fused all-reduce bucket relative to "
    "MXNET_KVSTORE_BUCKET_BYTES (>1 = one oversized array)",
    buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 1.0, 1.5, 2.0))
# imperative Trainer multi-tensor update engine (optimizer/
# multi_tensor.py): one fused, buffer-donated program per parameter
# group per step; eager per-parameter updates are the fallback path
TRAINER_FUSED_GROUPS = gauge(
    "trainer_fused_groups",
    "multi-tensor update groups in the last imperative Trainer step")
TRAINER_FUSED_APPLY = counter(
    "trainer_fused_apply_total",
    "fused multi-tensor update programs launched", ("optimizer",))
TRAINER_FUSED_BUILDS = counter(
    "trainer_fused_builds_total",
    "multi-tensor group program builds (trace + compile)",
    ("optimizer",))
TRAINER_EAGER_UPDATES = counter(
    "trainer_eager_updates_total",
    "per-parameter eager optimizer updates (multi-tensor fallback)",
    ("reason",))
TRAINER_UPDATE_SECONDS = histogram(
    "trainer_update_seconds",
    "imperative Trainer optimizer-apply dispatch latency per step")
DATALOADER_WAIT_SECONDS = histogram(
    "dataloader_batch_wait_seconds",
    "time the training loop blocked waiting for the next batch")
DEVICE_MEMORY = gauge(
    "device_memory_bytes", "PJRT device memory stats "
    "(sample_device_memory refreshes)", ("device", "stat"))
# mx.checkpoint (checkpoint/manager.py + writer.py): snapshot is the
# only critical-path phase of an async save; serialize/commit run on
# the background writer
CHECKPOINT_SNAPSHOT_SECONDS = histogram(
    "checkpoint_snapshot_seconds",
    "device->host snapshot time (critical path of an async save)")
CHECKPOINT_SERIALIZE_SECONDS = histogram(
    "checkpoint_serialize_seconds",
    "background shard serialize+durable-write (streamed) time")
CHECKPOINT_COMMIT_SECONDS = histogram(
    "checkpoint_commit_seconds",
    "background manifest/marker write + atomic-publish time")
CHECKPOINT_BYTES = counter(
    "checkpoint_bytes_total", "checkpoint shard bytes moved",
    ("direction",))
CHECKPOINT_QUEUE_DEPTH = gauge(
    "checkpoint_async_queue_depth",
    "async saves snapshotted but not yet committed")
CHECKPOINT_RETRIES = counter(
    "checkpoint_retries_total",
    "commit attempts retried after a transient I/O error")
CHECKPOINT_SAVES = counter(
    "checkpoint_saves_total", "checkpoint commits by outcome",
    ("result",))
CHECKPOINT_RESTORES = counter(
    "checkpoint_restores_total", "checkpoint restore calls")
# mx.serve (serve/): dynamic-batching inference serving.  Queue wait is
# the time a request sat in the BatchQueue before its micro-batch was
# dispatched; pad waste is the zero-fill the bucket table forced.
SERVE_REQUESTS = counter(
    "serve_requests_total", "serving requests by outcome "
    "(ok/rejected/timeout/error/cancelled/quarantined/poisoned)",
    ("result",))
SERVE_REQUEST_SECONDS = histogram(
    "serve_request_seconds",
    "end-to-end request latency (enqueue -> result set)")
SERVE_QUEUE_WAIT_SECONDS = histogram(
    "serve_queue_wait_seconds",
    "time a request waited in the batch queue before dispatch")
SERVE_QUEUE_DEPTH = gauge(
    "serve_queue_depth", "requests currently waiting in the batch queue")
SERVE_BATCHES = counter(
    "serve_batches_total", "micro-batches dispatched to the model runner")
SERVE_BATCH_SIZE = histogram(
    "serve_batch_size", "requests coalesced per dispatched micro-batch",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256))
SERVE_PAD_ELEMENTS = counter(
    "serve_pad_elements_total",
    "zero elements added by bucket padding (batch + shape fill)")
SERVE_PAD_FRACTION = histogram(
    "serve_pad_fraction",
    "padded/total element fraction per dispatched micro-batch",
    buckets=(0.01, 0.05, 0.1, 0.2, 0.3, 0.5, 0.7, 0.9))
SERVE_COMPILES = counter(
    "serve_compile_total",
    "hybridize compiles triggered by serving, by bucket "
    "(steady state: one per bucket, all during warm-up)", ("bucket",))
SERVE_SWAPS = counter(
    "serve_model_swaps_total", "hot model swaps (atomic runner "
    "replacement pointing at a new checkpoint step)")
# mx.compile (compile/): persistent compilation cache + AOT warm-start.
# A hit means a stored XLA executable was loaded and the compile was
# skipped; a miss means the lookup ran but nothing usable was stored.
COMPILE_CACHE_HIT = counter(
    "compile_cache_hit_total",
    "persistent compile-cache artifact loads (XLA compile skipped)")
COMPILE_CACHE_MISS = counter(
    "compile_cache_miss_total",
    "persistent compile-cache lookups with no usable artifact "
    "(fresh compile follows, then a commit)")
COMPILE_CACHE_COMMIT = counter(
    "compile_cache_commit_total",
    "compiled executables durably committed to the persistent cache")
COMPILE_CACHE_EVICT = counter(
    "compile_cache_evict_total",
    "cache entries evicted by the LRU size cap")
COMPILE_CACHE_QUARANTINE = counter(
    "compile_cache_quarantine_total",
    "corrupt cache entries quarantined (renamed *.corrupt, never "
    "loaded again)")
COMPILE_CACHE_FALLBACK = counter(
    "compile_cache_fallback_total",
    "AOT executable calls that failed and fell back to the in-memory "
    "jit path (aval drift etc.)")
COMPILE_CACHE_LOAD_SECONDS = histogram(
    "compile_cache_load_seconds",
    "artifact read + checksum-verify latency")
COMPILE_CACHE_COMMIT_SECONDS = histogram(
    "compile_cache_commit_seconds",
    "artifact serialize + durable-commit latency")
# mx.trace (trace/): flight-recorder dumps and watchdog activity —
# reason is manual / crash / exit / slow_step / deadline_burst /
# divergence / hang / dry_run (export.py), scope names the watch that
# stalled (watchdog.py)
TRACE_DUMPS = counter(
    "trace_dumps_total",
    "flight-recorder dumps written, by trigger reason", ("reason",))
TRACE_WATCHDOG_FIRES = counter(
    "trace_watchdog_fires_total",
    "hang-watchdog reports (no progress past the scope timeout)",
    ("scope",))
# mx.monitor (monitor/): on-device training-health numerics.  One
# fused stat reduction program per multi-tensor parameter group per
# step (grad/weight L2 norm, max|x|, nonfinite counts); values reach
# the gauges through the async host-fetch ring, so a lag of a step or
# two behind the live device state is expected.
MONITOR_STAT_BUILDS = counter(
    "monitor_stat_builds_total",
    "stat reduction program builds (trace + compile; steady state: "
    "one per parameter group, zero per-step retraces)")
MONITOR_STAT_PROGRAMS = counter(
    "monitor_stat_programs_total",
    "stat reduction programs dispatched (groups x observed steps)")
MONITOR_GRAD_NORM = gauge(
    "monitor_grad_norm", "last observed per-group gradient L2 norm",
    ("group",))
MONITOR_WEIGHT_NORM = gauge(
    "monitor_weight_norm", "last observed per-group weight L2 norm",
    ("group",))
MONITOR_GRAD_MAX = gauge(
    "monitor_grad_max_abs", "last observed per-group max |grad|",
    ("group",))
MONITOR_WEIGHT_MAX = gauge(
    "monitor_weight_max_abs", "last observed per-group max |weight|",
    ("group",))
MONITOR_GRAD_GLOBAL_NORM = gauge(
    "monitor_grad_global_norm",
    "last observed global gradient L2 norm (sqrt of the per-group "
    "squared-norm sum)")
MONITOR_GRAD_GLOBAL_NORM_HIST = histogram(
    "monitor_grad_global_norm_hist",
    "distribution of the global gradient L2 norm over observed steps",
    buckets=(0.001, 0.01, 0.1, 0.5, 1.0, 2.0, 5.0, 10.0, 50.0, 100.0,
             1000.0))
MONITOR_NONFINITE = counter(
    "monitor_nonfinite_total",
    "nonfinite (NaN/Inf) elements observed, saturating at ~2^24 per "
    "program (f32 on-device count)", ("kind", "group"))
MONITOR_NONFINITE_STEPS = counter(
    "monitor_nonfinite_steps_total",
    "observed steps with at least one nonfinite gradient element")
MONITOR_SKIPPED_STEPS = counter(
    "monitor_skipped_steps_total",
    "trainer steps skipped whole by the nonfinite sentinel "
    "(policy=skip_step; params/optimizer state untouched)")
MONITOR_SENTINEL_TRIPS = counter(
    "monitor_sentinel_trips_total",
    "nonfinite sentinel trips by the policy in force", ("policy",))
MONITOR_DROPS = counter(
    "monitor_dropped_total",
    "stat entries displaced from the bounded host-fetch ring before "
    "the publisher drained them")
MONITOR_FETCH_SECONDS = histogram(
    "monitor_fetch_seconds",
    "device->host stat vector fetch latency (synchronous only when "
    "the sentinel policy needs the value to gate the step)")
SERVE_NONFINITE_OUTPUTS = counter(
    "serve_nonfinite_outputs_total",
    "nonfinite (NaN/Inf) elements in served model outputs "
    "(mx.monitor output guard; surfaced at /statz)")
SERVE_NONFINITE_BATCHES = counter(
    "serve_nonfinite_batches_total",
    "dispatched micro-batches containing at least one nonfinite "
    "output element")
# mx.step (step/): whole-program training-step capture — forward,
# loss, backward, bucketed allreduce, fused optimizer apply and the
# monitor stat reductions traced into ONE donated XLA program per
# step.  The stitched imperative path stays the always-correct
# fallback; every degradation is counted by reason, never a lost step.
STEP_CAPTURE_BUILDS = counter(
    "step_capture_builds_total",
    "whole-step captured program builds (trace + compile; steady "
    "state: one per (input-signature, optimizer-hparams, monitor "
    "mode) — zero per-step retraces)")
STEP_CAPTURE_STEPS = counter(
    "step_capture_steps_total",
    "training steps executed through mx.step, by path "
    "(captured = one whole-step XLA program; stitched = the "
    "imperative fwd/bwd/allreduce/apply sequence)", ("path",))
STEP_CAPTURE_FALLBACKS = counter(
    "step_capture_fallback_total",
    "captured-step degradations to the stitched path, by reason "
    "(capture/compile/dispatch failure, kill switch, unsupported "
    "trainer shape) — the step is still applied", ("reason",))
STEP_PROGRAM_SECONDS = histogram(
    "step_program_seconds",
    "captured whole-step program host latency per step (slot eval + "
    "dispatch + writeback; the program itself runs async)")
# mx.shard (shard/): global-mesh SPMD training with ZeRO-1/2/3
# cross-replica weight-update sharding.  The gauges record the LIVE
# per-device residency after mesh placement — the memory contract the
# bench rows and acceptance tests bound (state ~1/dp for zero>=1,
# params ~1/dp for zero=3).
SHARD_DEVICE_BYTES = gauge(
    "shard_device_bytes",
    "bytes resident on ONE device after mx.shard mesh placement, by "
    "array kind (params / optimizer_state)", ("kind",))
SHARD_ZERO_LEVEL = gauge(
    "shard_zero_level",
    "ZeRO weight-update sharding level of the most recently placed "
    "captured step program (0 = replicated data-parallel)")
SHARD_COLLECTIVE_BYTES = counter(
    "shard_collective_bytes_total",
    "priced wire bytes of mesh collectives issued by captured "
    "programs, by mesh axis (dp / mdl) and collective op — the "
    "per-axis comms bill the first live TPU window calibrates "
    "against measured step time", ("axis", "op"))
SHARD_TP_MODE = gauge(
    "shard_tp_mode",
    "tensor-parallel execution mode of the most recently placed "
    "captured step program (0 = gather [bit-exact storage sharding], "
    "1 = compute [Megatron sharded matmuls])")
# mx.resilience (resilience/): deterministic fault injection,
# preemption handling, and the hardened restart supervisor — plus the
# serve-side graceful-degradation counters (bisect/poison/breakers).
RESILIENCE_FAULTS = counter(
    "resilience_faults_injected_total",
    "planned faults fired, by injection site (MXNET_FAULTS / "
    "resilience.plan())", ("site",))
RESILIENCE_RESTARTS = counter(
    "resilience_restarts_total",
    "supervisor recovery events by kind (transient / divergence / "
    "fatal / budget_exhausted / unhealthy)", ("kind",))
RESILIENCE_BACKOFF_SECONDS = histogram(
    "resilience_backoff_seconds",
    "jittered exponential backoff slept between restarts")
RESILIENCE_PREEMPTIONS = counter(
    "resilience_preemptions_total",
    "preemption requests observed (SIGTERM or resilience.request())")
RESILIENCE_EMERGENCY_SAVES = counter(
    "resilience_emergency_saves_total",
    "emergency checkpoints flushed during preemption shutdown")
SERVE_POISON = counter(
    "serve_poison_requests_total",
    "requests whose failure was isolated by bisect retry while their "
    "batch-mates were served independently")
SERVE_BISECT_SPLITS = counter(
    "serve_bisect_splits_total",
    "failed micro-batches split in half for retry (poison isolation)")
SERVE_BREAKER_STATE = gauge(
    "serve_breaker_state",
    "per-bucket circuit breaker state (0=closed 1=half-open 2=open)",
    ("bucket",))
SERVE_BREAKER_TRIPS = counter(
    "serve_breaker_trips_total",
    "circuit breaker openings (bucket quarantined after repeated "
    "dispatch failures)", ("bucket",))
# mx.serve.decode (serve/decode.py + kvcache.py): paged KV-cache +
# continuous batching for autoregressive serving.  One decode-step
# program per (batch-bucket, page-config) runs every iteration over
# whichever sequences are live; buckets label compiles like the
# vision path's serve_compile_total.
SERVE_DECODE_TOKENS = counter(
    "serve_decode_tokens_total", "tokens generated by the decode loop")
SERVE_DECODE_STEPS = counter(
    "serve_decode_steps_total",
    "continuous-batching decode iterations dispatched")
SERVE_DECODE_PREFILLS = counter(
    "serve_decode_prefills_total",
    "sequences prefilled through the prompt bucket path")
SERVE_DECODE_BATCH = histogram(
    "serve_decode_batch_size",
    "live sequences per decode iteration (varies step to step as "
    "sequences join and leave the running batch)",
    buckets=(1, 2, 4, 8, 16, 32, 64))
SERVE_DECODE_LIVE = gauge(
    "serve_decode_live_sequences",
    "sequences currently decoding in the running batch")
SERVE_DECODE_WAITING = gauge(
    "serve_decode_waiting_sequences",
    "sequences queued for admission (slots or KV pages exhausted)")
SERVE_DECODE_TTFT_SECONDS = histogram(
    "serve_decode_ttft_seconds",
    "time to first token: submit -> the prefill-produced token, by "
    "prefix-cache outcome (hit / partial / miss)",
    ("cache",))
SERVE_DECODE_TOKEN_SECONDS = histogram(
    "serve_decode_token_seconds",
    "per-token decode latency (one continuous-batching iteration)")
SERVE_DECODE_COMPILES = counter(
    "serve_decode_compile_total",
    "decode/prefill program builds by bucket (steady state: at most "
    "one per bucket, all during warm-up; mx.compile restores count 0)",
    ("bucket",))
SERVE_DECODE_EVICTIONS = counter(
    "serve_decode_evictions_total",
    "sequences evicted from the running batch, by reason (finished / "
    "timeout / poisoned / error / quarantined / cancelled)",
    ("reason",))
SERVE_KV_PAGES_IN_USE = gauge(
    "serve_kv_pages_in_use",
    "KV-cache pool pages currently reserved by live sequences")
SERVE_KV_PAGES_HIGH_WATER = gauge(
    "serve_kv_pages_high_water",
    "high-water mark of reserved KV-cache pool pages")
# mx.serve.cache (serve/cache.py): the radix prefix cache — identical
# prompt prefixes prefill once per replica, not once per request.
SERVE_PREFIX_LOOKUPS = counter(
    "serve_prefix_lookups_total",
    "prefix-cache admissions by outcome (hit = every cacheable prompt "
    "block matched, partial = some, miss = none)",
    ("result",))
SERVE_PREFIX_HIT_TOKENS = counter(
    "serve_prefix_hit_tokens_total",
    "prompt tokens served from cached prefix pages (prefill work "
    "avoided)")
SERVE_PREFIX_SHARED_PAGES = gauge(
    "serve_prefix_shared_pages",
    "KV pool pages in the shared refcounted segment (prefix trie + "
    "live readers)")
SERVE_PREFIX_EVICTIONS = counter(
    "serve_prefix_evictions_total",
    "prefix trie nodes dropped (LRU pool pressure, corrupt-drill "
    "invalidation, or clear)")
SERVE_DECODE_PREFILL_TOKENS = counter(
    "serve_decode_prefill_tokens_total",
    "prompt tokens actually run through a prefill/chunk program (the "
    "uncached suffix only; the fleet drill asserts one full prefill "
    "per shared prompt fleet-wide)")
# mx.tenant (tenant/): multi-tenant serving — batched LoRA adapter
# multiplexing, WFQ admission, per-tenant quotas/isolation.  The
# tenant label is the registered tenant name; base (un-tenanted)
# traffic never touches these families.
TENANT_REQUESTS = counter(
    "tenant_requests_total",
    "tenant-attributed serving requests by outcome "
    "(ok/rejected/timeout/error/cancelled/quarantined/poisoned)",
    ("tenant", "result"))
TENANT_TTFT_SECONDS = histogram(
    "tenant_ttft_seconds",
    "time to first token per tenant (the per-tenant SLO feed)",
    ("tenant",))
TENANT_TOKENS = counter(
    "tenant_tokens_total", "tokens emitted per tenant", ("tenant",))
TENANT_QUOTA_REJECTS = counter(
    "tenant_quota_rejects_total",
    "submissions rejected by a per-tenant quota, by reason "
    "(queue / pages) — per-tenant 503s, never head-of-line blocking",
    ("tenant", "reason"))
TENANT_WFQ_PICKS = counter(
    "tenant_wfq_picks_total",
    "admissions granted by the weighted-fair-queueing picker",
    ("tenant",))
TENANT_ADAPTER_SWAPS = counter(
    "tenant_adapter_swaps_total",
    "adapter bank slot swaps (hot load/unload; compile count stays "
    "flat — slot content is data, not program)")
TENANT_ADAPTER_POISON = counter(
    "tenant_adapter_poison_total",
    "nonfinite evictions attributed to a tenant's adapter (feeds the "
    "per-adapter breaker that quarantines ONLY that slot)",
    ("tenant",))
TENANT_SLOTS = gauge(
    "tenant_adapter_slots",
    "adapter bank capacity of the serving process")
TENANT_ADAPTERS_RESIDENT = gauge(
    "tenant_adapters_resident",
    "adapter slots currently holding a loaded adapter")
# mx.serve.spec (serve/spec.py): speculative decoding — draft-propose,
# target-verify, greedy acceptance (bit-identical to single-step).
SERVE_SPEC_ROUNDS = counter(
    "serve_spec_rounds_total",
    "speculative rounds reaching the verify dispatch")
SERVE_SPEC_PROPOSED = counter(
    "serve_spec_proposed_total",
    "draft tokens proposed to the target verifier")
SERVE_SPEC_ACCEPTED = counter(
    "serve_spec_accepted_total",
    "draft tokens accepted by greedy verification (accepted/proposed "
    "is the acceptance rate; accepted tokens cost no extra target "
    "step)")
SERVE_SPEC_FALLBACKS = counter(
    "serve_spec_fallbacks_total",
    "sequences degraded to non-speculative decode, by reason "
    "(draft_pool / draft_prefill / draft_nonfinite / draft_error / "
    "draft_lost / injected)",
    ("reason",))
# mx.dist (dist/): coordinated multi-host fault tolerance —
# collective deadlines, membership, pod-consistent checkpoints.
DIST_COLLECTIVE_TIMEOUTS = counter(
    "dist_collective_timeouts_total",
    "collectives that missed MXNET_DIST_COLLECTIVE_TIMEOUT (a peer "
    "rank unreachable), by site", ("site",))
DIST_WORLD_STOPS = counter(
    "dist_world_stops_total",
    "coordinated world-stop flags this rank posted first, by reason "
    "(failure / preempt / drill)", ("reason",))
DIST_POD_COMMITS = counter(
    "dist_pod_commits_total",
    "pod-level checkpoint barrier outcomes (ok = POD marker "
    "published after all ranks acked; timeout = torn pod commit, "
    "step unselectable at restore)", ("result",))
DIST_LEAVES = counter(
    "dist_member_leaves_total",
    "clean membership departures by reason", ("reason",))
# mx.data (data/): sharded streaming input pipeline.  The ring gauges
# are the H3 health signal: steady state is occupancy ~ depth and a
# flat stall counter — a climbing stall count means reads/decode (not
# H2D) bound the pipeline, so raise MXNET_DATA_WORKERS first.  The
# loop-blocked time itself lands in dataloader_batch_wait_seconds,
# shared with the classic DataLoader.
DATA_RING_DEPTH = gauge(
    "data_ring_depth",
    "configured prefetch ring depth (batches staged ahead; "
    "MXNET_DATA_PREFETCH)")
DATA_RING_OCCUPANCY = gauge(
    "data_ring_occupancy",
    "device-staged batches currently waiting in the prefetch ring")
DATA_RING_STALLS = counter(
    "data_ring_stalls_total",
    "times the training loop arrived at an EMPTY prefetch ring "
    "(the reader/decode stage fell behind the step program)")
DATA_READ_SECONDS = histogram(
    "data_read_seconds",
    "shard record-read time per batch (worker-side, after retries)")
DATA_DECODE_SECONDS = histogram(
    "data_decode_seconds",
    "decode + batchify time per batch (worker-side)")
DATA_STAGE_SECONDS = histogram(
    "data_stage_seconds",
    "host batch -> device/mesh staging dispatch time (the transfer "
    "itself runs async under PJRT)")
DATA_BATCHES = counter(
    "data_batches_total", "batches staged through the prefetch ring")
DATA_RECORDS = counter(
    "data_records_total", "records read + decoded by reader workers")
DATA_READ_RETRIES = counter(
    "data_read_retries_total",
    "reader IO attempts retried after an OSError (incl. injected "
    "data_read io faults)")
DATA_RESUMES = counter(
    "data_resumes_total",
    "mid-epoch cursor restores (checkpoint resume of the stream)")
# mx.obs (obs/): the fleet-wide observability plane — cross-rank
# snapshot publishing over the membership KV, straggler detection,
# SLO burn rates, and per-step attribution.  Publish failures are the
# "fleet view degraded to local-only" signal.
OBS_PUBLISHES = counter(
    "obs_publish_total",
    "per-rank obs payloads published into the membership KV")
OBS_PUBLISH_FAILURES = counter(
    "obs_publish_failures_total",
    "obs payload publishes that failed (dead/partitioned KV; the "
    "fleet view degrades to local-only until it recovers)")
OBS_STRAGGLERS = counter(
    "obs_stragglers_total",
    "straggler episodes flagged per rank (step p50 above "
    "MXNET_OBS_STRAGGLER_FACTOR x the fleet median)", ("rank",))
OBS_SLO_STATE = gauge(
    "obs_slo_state",
    "per-objective SLO state (0=OK 1=WARN 2=PAGE, multi-window "
    "burn-rate evaluation)", ("slo",))
OBS_SLO_BURN = gauge(
    "obs_slo_burn_rate",
    "error-budget burn rate per objective and window (1.0 = burning "
    "exactly the budget)", ("slo", "window"))
OBS_STEP_SECONDS = histogram(
    "obs_step_seconds",
    "training-step wall time as seen by the obs cadence hook",
    buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0, 2.5, 5.0, 10.0, 30.0, 60.0))
OBS_ATTRIB_RECORDS = counter(
    "obs_attribution_records_total",
    "per-step attribution records written (JSONL stream when "
    "MXNET_OBS_ATTRIBUTION is set)")
OBS_FLEET_RANKS = gauge(
    "obs_fleet_ranks",
    "ranks visible in the last fleet-view refresh (1 + local_only "
    "means the membership KV is unreachable)")
# mx.fleet (fleet/): the multi-replica serving fleet — KV-backed
# service discovery, the load-aware router front-end, prefill/decode
# page handoff, and zero-drop failover.
FLEET_PUBLISHES = counter(
    "fleet_publish_total",
    "replica discovery records published into the membership KV "
    "(heartbeat-piggybacked, rate-limited)")
FLEET_PUBLISH_FAILURES = counter(
    "fleet_publish_failures_total",
    "discovery record publishes that failed (dead/partitioned KV; "
    "the replica ages out of the router's view until it recovers)")
FLEET_REQUESTS = counter(
    "fleet_router_requests_total",
    "router-fronted requests by outcome (ok / rejected = whole-fleet "
    "saturation or no routable replica / failed / poisoned)",
    ("result",))
FLEET_DISPATCHES = counter(
    "fleet_router_dispatch_total",
    "upstream dispatch attempts by pool plane (micro / prefill / "
    "decode; retries count again)", ("plane",))
FLEET_AFFINITY_HITS = counter(
    "fleet_prefix_affinity_total",
    "decode dispatches routed by prefix-cache affinity (the prompt's "
    "first block was already cached on the chosen replica)")
FLEET_ADAPTER_AFFINITY = counter(
    "fleet_adapter_affinity_total",
    "decode dispatches routed by tenant-adapter residency (the "
    "tenant's adapter was already resident on the chosen replica)")
FLEET_FAILOVERS = counter(
    "fleet_failover_total",
    "mid-request re-routes after a replica death or connection "
    "failure (the zero-drop replay path)")
FLEET_HANDOFFS = counter(
    "fleet_handoff_total",
    "prefill->decode KV page handoffs by result (ok / "
    "checksum_mismatch / error)", ("result",))
FLEET_HANDOFF_BYTES = histogram(
    "fleet_handoff_bytes",
    "serialized page-handoff blob size (pages + cursor + sampler "
    "state, one checksummed blob)",
    buckets=(1024, 4096, 16384, 65536, 262144, 1048576, 4194304,
             16777216))
FLEET_ROUTER_OVERHEAD_SECONDS = histogram(
    "fleet_router_overhead_seconds",
    "router-added time per request (refresh + replica pick + "
    "bookkeeping, excluding upstream serving time)",
    buckets=(1e-5, 3e-5, 1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 0.1))
FLEET_ROUTER_REQUEST_SECONDS = histogram(
    "fleet_router_request_seconds",
    "end-to-end latency of router-fronted requests (the fleet SLO "
    "objective's feed)",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
             0.5, 1.0, 2.5, 5.0, 10.0, 30.0))
FLEET_REPLICAS = gauge(
    "fleet_replicas_live",
    "fresh, non-draining replicas in the router's last discovery "
    "refresh")
FLEET_ROLLOUTS = counter(
    "fleet_rollout_replicas_total",
    "replicas drained and swapped by fleet.rollout() (one at a time, "
    "riding Server's graceful drain)")
FLEET_POISON_VERDICTS = counter(
    "fleet_poison_verdicts_total",
    "poison verdicts published to the KV (first writer wins; every "
    "router stops retrying the sequence fleet-wide)")

start_logger()
