"""mx.dist — coordinated multi-host fault tolerance.

PR 8's ``mx.resilience`` taught one process to survive itself; this
package makes the *world* survivable (the robustness half of ROADMAP
item 1).  Four pieces, each drillable on CPU with 2 local processes:

- :mod:`~mxnet_tpu.dist.membership` — rank membership over the same
  rendezvous ``tools/launch.py`` stands up (shared-directory backend
  for CPU drills, jax coordination-service backend on pods):
  heartbeats, generation numbers (world incarnations), and a
  first-writer-wins world-stop flag every rank polls at its step
  boundary.
- :mod:`~mxnet_tpu.dist.timeouts` — ``MXNET_DIST_COLLECTIVE_TIMEOUT``
  deadlines around collective dispatch: a dead peer turns the
  classic forever-hang in ``psum`` into a classified
  :class:`DistTimeout` the supervisor classification retries via the
  coordinated world-restart path, with the trace watchdog armed
  around every collective.
- :mod:`~mxnet_tpu.dist.podckpt` — pod-consistent checkpoints: every
  rank commits its shard (PR 2 machinery untouched), rank 0 publishes
  the POD marker only after all ranks ack, and restore selects the
  max COMMON committed step — a torn pod commit is unselectable by
  construction.
- the ``Supervisor(membership=...)`` dist mode (``mx.resilience``) —
  any rank's transient failure or SIGTERM propagates through the stop
  flag; all ranks stop at the step boundary, emergency-checkpoint the
  same step through the pod protocol, and exit with the preempt code
  so ``tools/launch.py --restarts`` relaunches the world (possibly
  smaller: restore-with-resharding carries the shrink).

Drills: ``tools/dist_faults_smoke.py`` / ``make dist-faults-smoke``.
"""
from __future__ import annotations

from . import membership as membership_mod
from . import podckpt, timeouts
from .membership import (CoordKV, FileKV, MemKV, Membership,
                         default_backend, member_dir)
from .podckpt import PodCheckpointManager, pod_latest_step
from .timeouts import DistTimeout, collective_timeout, run_with_deadline

__all__ = [
    "Membership", "FileKV", "MemKV", "CoordKV", "default_backend",
    "member_dir",
    "DistTimeout", "collective_timeout", "run_with_deadline",
    "PodCheckpointManager", "pod_latest_step",
    "join", "current",
]

# the process-global membership the supervisor / kvstore consult
_MEMBERSHIP = None


def join(**kwargs):
    """Create + join the process-global :class:`Membership` (rank and
    world size default to the launcher's ``MXNET_DIST_*`` env).
    Idempotent: a second call returns the existing membership."""
    global _MEMBERSHIP
    if _MEMBERSHIP is None:
        m = Membership(**kwargs)
        m.join()
        _MEMBERSHIP = m
    return _MEMBERSHIP


def current():
    """The process-global membership, or None before :func:`join`."""
    return _MEMBERSHIP


def _reset():
    """Tests only: drop the process-global membership."""
    global _MEMBERSHIP
    if _MEMBERSHIP is not None:
        _MEMBERSHIP.stop_heartbeat()
    _MEMBERSHIP = None


def state():
    """Snapshot for ``tools/diagnose.py --dist``."""
    return {
        "member_dir": member_dir(),
        "collective_timeout": collective_timeout(),
        "membership": None if _MEMBERSHIP is None
        else _MEMBERSHIP.state(),
    }
