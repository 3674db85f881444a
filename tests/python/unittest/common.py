"""Shared test fixtures (reference tests/python/unittest/common.py).

``with_seed`` is the reference's reproducible-randomness decorator
(common.py:164): every decorated test draws a fresh seed (or honors
MXNET_TEST_SEED), seeds both numpy and the framework RNG, and on failure
prints the seed so the exact tensor draw can be replayed with
``MXNET_TEST_SEED=<n> pytest <test>``.
"""
from __future__ import annotations

import functools
import os
import random as _pyrandom

import numpy as np


def with_seed(seed=None):
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            env = os.environ.get("MXNET_TEST_SEED")
            this = int(env) if env else (
                seed if seed is not None
                else _pyrandom.SystemRandom().randint(0, 2 ** 31 - 1))
            np.random.seed(this)
            import mxnet_tpu as mx

            mx.random.seed(this)
            try:
                return fn(*args, **kwargs)
            except Exception:
                print("*** test failed with MXNET_TEST_SEED=%d — rerun "
                      "with that env var to reproduce the draw ***" % this)
                raise

        return wrapper

    return deco


def xplane_host_lines(trace_dir):
    """What a finished ``jax.profiler`` session wrote under ``trace_dir``,
    host side: ``[[(start_ns, end_ns, name, stats), ...], ...]``, one list
    a thread's line of a ``/host:`` plane (a line is named by the OS thread,
    which Python's thread names do not reach, so lines are told apart by
    position), events sorted by start."""
    import glob

    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    assert files, "no *.xplane.pb under %s" % trace_dir
    lines = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            lines.append(sorted(
                (e.start_ns, e.start_ns + e.duration_ns, e.name,
                 {k: v for k, v in e.stats}) for e in line.events))
    return lines


def xplane_find(lines, name):
    """``[(line index, start_ns, end_ns, stats)]`` of the events called
    ``name``."""
    return [(i, s, e, stats) for i, line in enumerate(lines)
            for s, e, n, stats in line if n == name]
