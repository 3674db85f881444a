"""mx.compile — persistent compilation cache + AOT warm-start.

The north-star execution model compiles ONE fused XLA program per
(shapes, dtypes, mode) signature — but until now every process paid
the full trace+compile cost again.  This subsystem amortizes XLA
compilation ACROSS processes:

- ``HybridBlock._get_cached_op`` consults the disk cache on every
  in-memory miss (artifacts keyed by a fingerprint of the lowered
  StableHLO text + platform/topology/versions/XLA flags) and commits
  the serialized executable after every fresh build;
- ``precompile(block, signatures)`` builds + persists a signature set
  ahead of time;
- ``warm_start(block)`` repopulates the hybridize cache from disk with
  ZERO tracing and ZERO compiling, so a second process — or a
  restarted ``mx.serve`` server — reaches steady state immediately;
- storage follows the ``mx.checkpoint`` durability discipline
  (write-to-temp + fsync + COMMITTED marker + atomic rename, CRC32
  manifests, corrupt-entry quarantine, LRU size cap).

Enablement: OFF by default (a training notebook should not silently
grow ``~/.mxnet``).  Turn it on with ``MXNET_COMPILE_CACHE=1``, by
pointing ``MXNET_COMPILE_CACHE_DIR`` somewhere, or programmatically
via ``mxnet_tpu.compile.enable(dir=...)``.  Every cache failure —
missing dir, corrupt artifact, version drift — degrades to a normal
in-memory compile; the hot path never raises because of the cache.

Telemetry: ``compile_cache_{hit,miss,commit,evict,quarantine,
fallback}_total`` counters and ``compile_cache_{load,commit}_seconds``
histograms, visible in the Prometheus/JSON exporters and serve
``/statz``.
"""
from __future__ import annotations

import os
import threading

from ..base import get_env
from .aot import attach_from_cache, precompile, warm_start
from .cache import CompileCache, block_signature, default_cache_dir

__all__ = ["enable", "disable", "is_enabled", "configure", "get_cache",
           "cache_dir", "stats", "clear", "jax_cache_dir",
           "precompile", "warm_start", "attach_from_cache",
           "CompileCache", "block_signature", "default_cache_dir"]

_LOCK = threading.Lock()
_CACHE = None


def jax_cache_dir():
    """Turn JAX's own persistent compilation cache on for this process
    and return its directory — the first call of ``chip_smoke.py``,
    ``bench.py`` and the ``benchmark/`` scripts (mx.compile above is the
    repo's store for hybridize/captured programs and stays off there).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already keeps its
    cache there and no other path is named here.  Where it is not, the
    cache goes to ``.jax_cache`` at the root of the checkout: the path is
    part of every entry's key, so it is fixed — never a temp dir, a pid
    or a time."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # cache the small programs too (an eager op is a compile on the chip)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def _env_enabled():
    """Initial enablement from the environment.  An explicitly-set
    MXNET_COMPILE_CACHE always wins; _DIR implies on only while the
    boolean knob is unset — a fleet-wide _DIR (relocating the store)
    must not make an explicit MXNET_COMPILE_CACHE=0 opt-out
    impossible."""
    flag = get_env("MXNET_COMPILE_CACHE", bool, None)
    if flag is not None:
        return bool(flag)
    return bool(get_env("MXNET_COMPILE_CACHE_DIR", str, None))


_ENABLED = _env_enabled()


def is_enabled():
    """One cheap boolean — the hot-path gate in _get_cached_op."""
    return _ENABLED


def enable(dir=None, max_bytes=None):  # noqa: A002 - mirrors configure
    """Turn the persistent cache on (optionally repointing it)."""
    global _ENABLED
    if dir is not None or max_bytes is not None:
        configure(dir=dir, max_bytes=max_bytes)
    _ENABLED = True


def disable():
    """Turn the persistent cache off; entries on disk are kept."""
    global _ENABLED
    _ENABLED = False


def configure(dir=None, max_bytes=None):  # noqa: A002
    """(Re)build the process-wide cache with an explicit directory
    and/or size cap; returns the new CompileCache.  An omitted argument
    keeps the current cache's setting — ``configure(max_bytes=...)``
    after ``configure(dir=...)`` must not silently repoint the cache at
    the default directory."""
    global _CACHE
    with _LOCK:
        if _CACHE is not None:
            if dir is None:
                dir = _CACHE.root
            if max_bytes is None:
                max_bytes = _CACHE.max_bytes
        _CACHE = CompileCache(root=dir, max_bytes=max_bytes)
    return _CACHE


def get_cache():
    """The process-wide CompileCache (built on first use from the env
    knobs), or None when construction fails (degrade, don't raise)."""
    global _CACHE
    if _CACHE is None:
        with _LOCK:
            if _CACHE is None:
                try:
                    _CACHE = CompileCache()
                except Exception:
                    return None
    return _CACHE


def cache_dir():
    """Directory of the active cache."""
    c = get_cache()
    return c.root if c is not None else default_cache_dir()


def stats():
    """{dir, entries, total_bytes, max_bytes, quarantined} of the
    active cache."""
    c = get_cache()
    if c is None:
        return {"dir": default_cache_dir(), "entries": 0,
                "total_bytes": 0, "max_bytes": 0, "quarantined": []}
    return c.stats()


def clear():
    """Drop every cached artifact."""
    c = get_cache()
    if c is not None:
        c.clear()
