"""mx.resilience — deterministic fault injection, preemption-aware
shutdown, hardened restart supervision.

The stack can see itself (telemetry / trace / monitor) and persist
itself (checkpoint); this subsystem makes it *survive* itself:

- ``resilience.inject`` — a step/site-keyed fault plan
  (``MXNET_FAULTS`` or ``resilience.plan()``) with named injection
  sites at trainer step launch, collective ``pushpull_all``,
  checkpoint writer IO, compile-cache commit, and serve batch
  dispatch.  Faults fire deterministically by (site, sequence), so
  every recovery drill replays identically on CPU under Tier-1.
- ``resilience.preempt`` — SIGTERM handling with a grace budget
  (``MXNET_PREEMPT_GRACE_SECONDS``): the supervisor stops at the next
  step boundary, flushes an emergency checkpoint, drains serve, and
  exits with the distinct ``MXNET_PREEMPT_EXIT_CODE``.
- ``resilience.supervisor`` — transient-vs-fatal exception classification,
  exponential backoff with jitter, a restart budget over a sliding
  step window, wall-clock-bounded device health checks, and
  restore-on-divergence wired to the mx.monitor feed.  It absorbs
  (and deprecates) ``elastic.FaultTolerantRunner``.

Serve-side graceful degradation (bisect-isolate poisoned requests,
per-bucket circuit breakers) lives in ``mx.serve`` and is counted in
the same ``resilience_*``/``serve_*`` telemetry family.  Drills:
``tools/faults_smoke.py`` / ``make faults-smoke``.
"""
from __future__ import annotations

from ..base import get_env
from . import inject, preempt, supervisor
from .inject import (FaultPlan, InjectedFault, InjectedIOError, clear,
                     fire, plan, poisoned, refresh_env)
from .preempt import (graceful_shutdown, install, preemption_imminent,
                      request, requested)
from .supervisor import (Backoff, GluonStepLoop, RestartBudget,
                         Supervisor, classify, health_check,
                         recent_restarts, register_fatal,
                         register_transient)

__all__ = [
    "inject", "preempt", "supervisor",
    "FaultPlan", "InjectedFault", "InjectedIOError",
    "plan", "clear", "fire", "poisoned", "refresh_env",
    "install", "request", "requested", "preemption_imminent",
    "graceful_shutdown",
    "Supervisor", "GluonStepLoop", "Backoff", "RestartBudget",
    "classify", "health_check", "recent_restarts",
    "register_transient", "register_fatal",
]

# arm the SIGTERM handler at import when asked, so a preempted run
# leaves an emergency checkpoint
if get_env("MXNET_PREEMPT_INSTALL", bool, False):  # pragma: no cover
    install()
