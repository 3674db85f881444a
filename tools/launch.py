#!/usr/bin/env python
"""Multi-process training launcher + whole-world restart supervisor.

Reference: tools/launch.py (dmlc-core tracker spawning scheduler + server +
worker processes for the ps-lite kvstore, /root/reference/tools/launch.py:
25-60).  The TPU-native stack has NO server role (SURVEY §5.8: collectives
replace push/pull), so the launcher's job shrinks to: start N worker
processes with a shared rendezvous address and rank, and let
``jax.distributed.initialize`` + the collective kvstore do the rest.

On top of that, this is the *world supervisor* of mx.dist:

- a shared **membership directory** (``MXNET_DIST_MEMBER_DIR``) is
  created and exported so every rank's ``dist.Membership`` heartbeats
  and world-stop flags share one place;
- **SIGTERM/SIGINT are forwarded to every child** (the pod scheduler
  preempts the HOST; children must see it to emergency-checkpoint),
  and workers still alive ``--term-grace`` seconds later are SIGKILLed
  — a preemption drill kills the whole world, it never leaks rank
  processes past the launcher;
- the same escalation reaps the world when ONE rank dies: peers get
  SIGTERM (they are already stopping via the membership flag or a
  collective timeout), then SIGKILL after the grace;
- ``--restarts K`` relaunches the WHOLE world up to K times when it
  exits non-zero (rank crash, coordinated preemption exit) — each
  attempt exports ``MXNET_DIST_ATTEMPT`` so membership generations
  are deterministic, and ranks resume from the pod-consistent
  checkpoint (``dist.PodCheckpointManager``).  An operator-initiated
  SIGTERM/SIGINT never restarts.

**A rank is one process per HOST.**  Every child opens JAX's default
backend and no chip is assigned to a rank, and a chip belongs to one
process at a time — so on a machine with chips ``-n`` is the number of
hosts you run this on, not of chips: one process drives all the chips of
its host (``make_mesh`` / ``shard.GlobalMesh`` over ``jax.devices()``).
``-n > 1`` on ONE host is for ``--backend cpu`` drills only.

Ports are picked **deterministically** from (pid, attempt) and probed
for availability, so parallel launchers (pytest workers) never race a
shared ephemeral port the way bind-then-release selection did.

Usage::

    python tools/launch.py -n 4 python train.py --my-args
    python tools/launch.py -n 2 --backend cpu --restarts 1 \
        python tests/nightly/dist_fault_drill.py train ...

``--backend cpu`` forces the XLA CPU platform in children (the multi-
process CI path per SURVEY §4: N local processes, Gloo collectives); the
default inherits the environment (TPU pods use one process per host).
"""
from __future__ import annotations

import argparse
import os
import shutil
import signal
import socket
import sys
import subprocess
import tempfile
import time


def pick_port(seed, host="127.0.0.1"):
    """Deterministic port selection: probe candidates derived from
    ``seed`` (pid*1000+attempt) until one binds.  Parallel launchers
    (pytest workers) walk DIFFERENT candidate sequences instead of all
    racing the kernel's shared ephemeral range — the close-then-rebind
    gap still exists in principle, but only an unrelated process
    landing on this seed's exact candidate can hit it.  The probe
    binds WITHOUT ``SO_REUSEADDR``, matching how the child's
    coordinator will bind: a port a previous world left in TIME_WAIT
    must fail the probe here, not the rendezvous later."""
    for i in range(64):
        port = 20000 + (int(seed) * 7919 + i * 131) % 20000
        s = socket.socket()
        try:
            s.bind((host, port))
            return port
        except OSError:
            continue
        finally:
            s.close()
    # pathological exhaustion: fall back to the kernel's choice
    s = socket.socket()
    s.bind((host, 0))
    port = s.getsockname()[1]
    s.close()
    return port


# retained for callers that imported the old helper
def find_free_port():
    return pick_port(os.getpid())


def _spawn_world(args, coord, member_dir, attempt):
    procs = []
    for rank in range(args.num_workers):
        env = dict(os.environ)
        if args.rendezvous == "jax":
            env["MXNET_DIST_COORDINATOR"] = coord
        env["MXNET_DIST_NUM_WORKERS"] = str(args.num_workers)
        env["MXNET_DIST_RANK"] = str(rank)
        env["MXNET_DIST_MEMBER_DIR"] = member_dir
        env["MXNET_DIST_ATTEMPT"] = str(attempt)
        # unique per (launcher, attempt): membership join matches it
        # exactly, so a REUSED --member-dir can never hand a rank a
        # stale previous-incarnation world record
        env["MXNET_DIST_WORLD_NONCE"] = "%d-%d" % (os.getpid(), attempt)
        if args.backend == "cpu":
            env["JAX_PLATFORMS"] = "cpu"
        procs.append(subprocess.Popen(args.command, env=env))
    return procs


def _signal_world(procs, sig):
    for p in procs:
        if p.poll() is None:
            try:
                p.send_signal(sig)
            except OSError:
                pass


def _reap_world(procs, grace):
    """SIGTERM -> wait up to ``grace`` -> SIGKILL survivors.  Always
    returns with every child reaped (no orphaned rank processes)."""
    _signal_world(procs, signal.SIGTERM)
    deadline = time.monotonic() + max(0.0, float(grace))
    while time.monotonic() < deadline:
        if all(p.poll() is not None for p in procs):
            break
        time.sleep(0.1)
    for p in procs:
        if p.poll() is None:
            try:
                p.kill()
            except OSError:
                pass
    for p in procs:
        try:
            p.wait(timeout=30)
        except Exception:  # noqa: BLE001 - unkillable (D-state) child
            pass


def _world_rc(codes, preempt_code):
    """One exit status for a finished world: 0 when every rank was
    clean; the distinct preemption code when the only failures are
    clean preemptions (or teardown signals the launcher itself
    delivered); else the first hard failure."""
    if all(c == 0 for c in codes):
        return 0
    benign = {0, preempt_code, -signal.SIGTERM, -signal.SIGKILL}
    hard = [c for c in codes if c not in benign]
    if hard:
        return hard[0]
    if any(c == preempt_code for c in codes):
        return preempt_code
    return next(c for c in codes if c != 0)


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="launch N distributed worker processes")
    parser.add_argument("-n", "--num-workers", type=int, required=True)
    parser.add_argument("--backend", default=None, choices=[None, "cpu"],
                        help="force JAX_PLATFORMS in children")
    parser.add_argument("--coordinator", default=None,
                        help="host:port rendezvous (default: "
                             "deterministic free local port)")
    parser.add_argument("--rendezvous", default="jax",
                        choices=["jax", "none"],
                        help="'jax' (default) exports "
                             "MXNET_DIST_COORDINATOR so children join "
                             "a jax.distributed process group; 'none' "
                             "skips it — membership/pod-checkpoint "
                             "drills on backends whose XLA cannot run "
                             "multi-process collectives (CPU)")
    parser.add_argument("--restarts", type=int, default=0,
                        help="relaunch the whole world up to K times "
                             "when it exits non-zero (coordinated "
                             "restart drills; default 0)")
    parser.add_argument("--term-grace", type=float, default=30.0,
                        help="seconds between forwarding SIGTERM and "
                             "SIGKILLing surviving workers — keep it "
                             "above MXNET_DIST_COLLECTIVE_TIMEOUT + "
                             "MXNET_DIST_BARRIER_TIMEOUT so a rank "
                             "rescued from a dead collective can "
                             "finish its emergency pod publish")
    parser.add_argument("--member-dir", default=None,
                        help="shared membership dir exported as "
                             "MXNET_DIST_MEMBER_DIR (default: a fresh "
                             "temp dir, removed at exit)")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)
    if not args.command:
        parser.error("no command given")

    member_dir = args.member_dir
    own_member_dir = member_dir is None
    if own_member_dir:
        member_dir = tempfile.mkdtemp(prefix="mxdist-")
    else:
        os.makedirs(member_dir, exist_ok=True)

    # the preemption code children exit with on a clean coordinated stop
    preempt_code = int(os.environ.get("MXNET_PREEMPT_EXIT_CODE", "85"))

    sig_flag = {"sig": None}

    def _on_signal(signum, _frame):
        sig_flag["sig"] = signum

    signal.signal(signal.SIGINT, _on_signal)
    signal.signal(signal.SIGTERM, _on_signal)

    rc = 0
    try:
        for attempt in range(max(0, args.restarts) + 1):
            coord = args.coordinator or "127.0.0.1:%d" % pick_port(
                os.getpid() * 1000 + attempt)
            procs = _spawn_world(args, coord, member_dir, attempt)
            # poll ALL workers: a crash in any rank (while peers block
            # in a collective waiting for it) must tear the job down,
            # not hang behind a rank-order wait
            tearing_down = False
            live = list(procs)
            while live:
                if sig_flag["sig"] is not None and not tearing_down:
                    # operator/scheduler preemption: forward ONCE (a
                    # second SIGTERM would hard-exit the children past
                    # their emergency checkpoint), then escalate
                    tearing_down = True
                    sys.stderr.write(
                        "launch.py: signal %s — forwarding SIGTERM to "
                        "%d workers (SIGKILL after %.0fs)\n"
                        % (sig_flag["sig"], len(live), args.term_grace))
                    _reap_world(procs, args.term_grace)
                for p in list(live):
                    code = p.poll()
                    if code is None:
                        continue
                    live.remove(p)
                    if code != 0 and not tearing_down:
                        # one rank failed: reap the rest of the world
                        # (peers are already stopping via the
                        # membership flag / collective timeout —
                        # SIGTERM lets them finish the emergency
                        # checkpoint, SIGKILL bounds the wait)
                        tearing_down = True
                        _reap_world(procs, args.term_grace)
                if live:
                    time.sleep(0.2)
            rc = _world_rc([p.returncode for p in procs], preempt_code)
            if rc == 0 or sig_flag["sig"] is not None \
                    or attempt >= args.restarts:
                break
            sys.stderr.write(
                "launch.py: world exited rc=%d — coordinated restart "
                "%d/%d\n" % (rc, attempt + 1, args.restarts))
        return rc
    finally:
        if own_member_dir:
            shutil.rmtree(member_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
