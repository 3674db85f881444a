"""Deterministic fault injection — the drill half of mx.resilience.

Failure handling that is only ever exercised by real outages is
failure handling that does not work (the r04–r05 bench windows died to
exactly that).  This module lets every recovery path in the stack be
driven on CPU, deterministically, from a *fault plan*:

- a plan is a list of ``(site, key)`` entries, armed via the
  ``MXNET_FAULTS`` env var or the ``plan()`` API;
- code registers **named injection sites** by calling ``fire(site,
  seq=...)`` at the interesting spots — trainer step launch
  (``trainer_step``), collective ``pushpull_all`` (``collective``),
  checkpoint writer IO (``checkpoint_commit`` at commit entry,
  ``checkpoint_marker`` just before the COMMITTED marker lands),
  compile-cache commit (``compile_commit``), serve batch dispatch
  (``serve_dispatch``; ``serve_poison`` marks individual request ids),
  and streaming reader IO (``data_read``, keyed by batch index —
  ``io`` kind engages the reader's bounded retry loop);
- a fault fires **iff** the plan holds a matching entry for that
  (site, sequence) pair — so every drill replays identically, run
  after run, and an empty plan costs one dict probe per site.

Plan grammar (comma-separated entries)::

    MXNET_FAULTS="site@key[:kind][*count]"

    trainer_step@5              one transient fault at step 5
    collective@*:transient*2    first two collective calls fail
    checkpoint_commit@0:io      first commit attempt raises OSError
                                (the manager's retry loop recovers)
    checkpoint_marker@0:abort   hard-kill (os._exit) right before the
                                COMMITTED marker -> torn checkpoint
    serve_poison@req-7          request id "req-7" poisons any batch
                                it rides in (the bisect drill)

Kinds: ``transient`` (default, ``InjectedFault`` — classified
transient by the supervisor), ``io`` (``InjectedIOError``, an
``OSError`` so retry-with-backoff paths engage), ``fatal``
(``InjectedFault`` the classification refuses to retry), ``abort``
(``os._exit`` — simulates SIGKILL mid-operation; cleanup handlers
never run, exactly like a preempted node).

Every firing is counted in ``resilience_faults_injected_total{site}``
and recorded as a trace instant, so a drill's dump/metrics artifacts
say precisely which faults were injected where.
"""
from __future__ import annotations

import threading

from .. import telemetry, trace
from ..base import MXNetError, get_env

__all__ = ["InjectedFault", "InjectedIOError", "FaultPlan", "SITES",
           "KINDS", "plan", "clear", "active", "armed", "refresh_env",
           "fire", "poisoned", "record_firing", "state",
           "ABORT_EXIT_CODE"]

# the registered site names (fire() accepts others — a drill may probe
# a site added later — but these are the ones wired into the stack)
SITES = ("trainer_step", "collective", "checkpoint_commit",
         "checkpoint_marker", "compile_commit", "serve_dispatch",
         "serve_poison", "serve_cache", "spec_verify", "data_read")
KINDS = ("transient", "io", "fatal", "abort")

# distinct from any real exit status the drills assert on (SIGKILL
# would be -9; preemption uses MXNET_PREEMPT_EXIT_CODE)
ABORT_EXIT_CODE = 77


class InjectedFault(MXNetError):
    """A planned fault.  ``kind`` is ``transient`` or ``fatal`` — the
    supervisor's classification routes on it."""

    def __init__(self, msg, kind="transient", site=None, key=None):
        super().__init__(msg)
        self.kind = kind
        self.site = site
        self.key = key


class InjectedIOError(OSError):
    """A planned IO fault — an ``OSError`` so the existing
    retry-with-backoff paths (checkpoint commit, compile-cache commit)
    handle it exactly like a real storage hiccup."""

    def __init__(self, msg, site=None, key=None):
        super().__init__(msg)
        self.site = site
        self.key = key


class _Entry:
    __slots__ = ("site", "key", "kind", "count", "fired")

    def __init__(self, site, key, kind="transient", count=1):
        if kind not in KINDS:
            raise MXNetError("unknown fault kind %r (one of %s)"
                             % (kind, ", ".join(KINDS)))
        self.site = site
        self.key = str(key)
        self.kind = kind
        # count=None from the grammar means "no explicit *N": one-shot
        # for fault sites, UNLIMITED for serve_poison — a poisoned
        # request stays poisoned for its whole drill (re-checked on
        # every bisect retry and later dispatch); an explicit *N still
        # bounds it.  A stored count of None means unlimited.
        if count is None:
            count = None if site == "serve_poison" else 1
        self.count = None if count is None else int(count)
        self.fired = 0

    def matches(self, site, key):
        if site != self.site:
            return False
        if self.count is not None and self.fired >= self.count:
            return False
        return self.key == "*" or self.key == str(key)

    def describe(self):
        return {"site": self.site, "key": self.key, "kind": self.kind,
                "count": self.count, "fired": self.fired}


class FaultPlan:
    """A parsed, armed set of fault entries (see module grammar)."""

    def __init__(self, entries=()):
        self.entries = list(entries)

    @classmethod
    def parse(cls, spec):
        """``"site@key[:kind][*count],..."`` -> FaultPlan.  Whitespace
        around entries is ignored; an empty spec is an empty plan.

        A trailing ``*<digits>`` ALWAYS parses as the repeat count, so
        a literal key may not end in ``*<digits>`` — pick drill
        request ids accordingly.  The bare wildcard key ``site@*`` is
        unambiguous: the split below requires a non-empty prefix
        before the ``*``."""
        entries = []
        for raw in (spec or "").split(","):
            raw = raw.strip()
            if not raw:
                continue
            if "@" not in raw:
                raise MXNetError(
                    "bad MXNET_FAULTS entry %r: expected "
                    "site@key[:kind][*count]" % raw)
            site, _, rest = raw.partition("@")
            count = None        # no explicit *N: _Entry picks default
            head, star, tail = rest.rpartition("*")
            if star and head and tail.isdigit():
                rest, count = head, int(tail)
            kind = "transient"
            if ":" in rest:
                rest, _, kind = rest.rpartition(":")
            entries.append(_Entry(site.strip(), rest.strip(), kind,
                                  count))
        return cls(entries)

    def take(self, site, key):
        """Consume-and-return the first matching entry (or None).
        Caller holds the module lock."""
        for e in self.entries:
            if e.matches(site, key):
                e.fired += 1
                return e
        return None

    def match(self, site, key):
        """Non-consuming probe (poison checks fire on every retry of a
        bisected batch, so they must not burn a count)."""
        for e in self.entries:
            if e.matches(site, key):
                return e
        return None


_LOCK = threading.Lock()
_PLAN = None          # None = MXNET_FAULTS not read yet
_SEQ = {}             # per-site call counters (for seq=None sites)
# lock-free hot-path flag: None = plan not loaded yet, else
# bool(plan.entries).  fire()/poisoned() read it WITHOUT the lock, so
# an unarmed production process pays one attribute load per site —
# never a lock acquisition on the trainer step or serve dispatch path.
# (Entries can only appear via plan()/refresh_env(), which reset it.)
_ARMED = None


def _load_locked():
    global _PLAN, _ARMED
    if _PLAN is None:
        _PLAN = FaultPlan.parse(get_env("MXNET_FAULTS", str, ""))
        _ARMED = bool(_PLAN.entries)
    return _PLAN


def armed():
    """Cheap is-any-fault-planned probe (see ``_ARMED``)."""
    a = _ARMED
    if a is None:
        with _LOCK:
            a = bool(_load_locked().entries)
    return a


def plan(spec):
    """Arm a fault plan (a grammar string, or a prebuilt FaultPlan).
    Resets every per-site sequence counter so drills replay from a
    clean origin.  Returns the armed plan."""
    global _PLAN, _ARMED
    p = spec if isinstance(spec, FaultPlan) else FaultPlan.parse(spec)
    with _LOCK:
        _PLAN = p
        _ARMED = bool(p.entries)
        _SEQ.clear()
    return p


def clear():
    """Disarm: no faults fire until ``plan()`` or ``refresh_env()``."""
    with _LOCK:
        global _PLAN, _ARMED
        _PLAN = FaultPlan()
        _ARMED = False
        _SEQ.clear()


def refresh_env():
    """Re-read ``MXNET_FAULTS`` (the armed-at-import path reads it
    lazily on first ``fire``; tests that set the env later call
    this)."""
    global _PLAN
    with _LOCK:
        _PLAN = None
        _SEQ.clear()
        return _load_locked()


def active():
    return armed()


def record_firing(site, key=None, consume=False):
    """Count one logical firing (telemetry + trace instant).  ``fire``
    calls this itself after ``take`` already consumed the entry; the
    serve bisect path calls it with ``consume=True`` at the moment a
    poisoned request is isolated, so the plan's ``fired`` bookkeeping
    agrees with the telemetry counter (and retries of the same request
    during one dispatch count once)."""
    if consume:
        with _LOCK:
            e = _load_locked().match(site, key)
            if e is not None:
                e.fired += 1
    if telemetry.ENABLED:
        telemetry.RESILIENCE_FAULTS.labels(site=site).inc()
    trace.instant("fault_injected", cat="resilience",
                  args={"site": site, "key": None if key is None
                        else str(key)})


def fire(site, seq=None):
    """Fire the planned fault for ``(site, seq)`` — a no-op unless the
    armed plan holds a matching live entry.  With ``seq=None`` the
    site's own call counter is used (incremented only while a plan is
    armed, so sequences are deterministic from ``plan()``)."""
    if not armed():                 # lock-free production fast path
        return
    with _LOCK:
        p = _load_locked()
        if not p.entries:
            return
        if seq is None:
            seq = _SEQ.get(site, 0)
            _SEQ[site] = seq + 1
        entry = p.take(site, seq)
    if entry is None:
        return
    record_firing(site, seq)
    msg = ("injected %s fault at site %r (key %s, firing %d/%s)"
           % (entry.kind, site, entry.key, entry.fired,
              entry.count if entry.count is not None else "inf"))
    if entry.kind == "abort":
        import os
        import sys

        sys.stderr.write("mx.resilience: %s — hard exit %d\n"
                         % (msg, ABORT_EXIT_CODE))
        sys.stderr.flush()
        os._exit(ABORT_EXIT_CODE)
    if entry.kind == "io":
        raise InjectedIOError(msg, site=site, key=entry.key)
    raise InjectedFault(msg, kind=entry.kind, site=site, key=entry.key)


def poisoned(request_id):
    """True when the plan marks ``request_id`` as a poison request
    (site ``serve_poison``).  Non-consuming: a poisoned request stays
    poisoned through every bisect retry of its batch."""
    if request_id is None or not armed():
        return False
    with _LOCK:
        p = _load_locked()
        if not p.entries:
            return False
        return p.match("serve_poison", request_id) is not None


def state():
    """Snapshot for ``tools/diagnose.py --resilience``."""
    with _LOCK:
        p = _load_locked()
        return {"active": bool(p.entries),
                "entries": [e.describe() for e in p.entries],
                "seq": dict(_SEQ)}
