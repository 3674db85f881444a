#!/usr/bin/env python
"""Environment doctor (reference tools/diagnose.py — prints platform, deps,
env vars, and connectivity so bug reports carry reproducible context).

TPU additions over the reference: PJRT backend/device table, a timed MXU
matmul smoke (run with a watchdog, so a backend that never answers is
reported as a hang instead of hanging the doctor), native host
runtime availability, and the framework env-var registry with effective
values.

Usage::

    python tools/diagnose.py [--no-device-check]
"""
from __future__ import annotations

import argparse
import os
import platform
import sys
import threading
import time

# runnable from a checkout: python tools/diagnose.py
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def section(title):
    print("\n----------%s----------" % title)


def python_info():
    section("Python Info")
    print("Version      :", platform.python_version())
    print("Compiler     :", platform.python_compiler())
    print("Build        :", platform.python_build())
    print("Arch         :", platform.architecture())


def platform_info():
    section("Platform Info")
    print("Platform     :", platform.platform())
    print("system       :", platform.system())
    print("node         :", platform.node())
    print("release      :", platform.release())
    print("version      :", platform.version())


def deps_info():
    section("Dependencies")
    for mod in ("numpy", "jax", "jaxlib", "flax", "optax"):
        try:
            m = __import__(mod)
            print("%-12s : %s" % (mod, getattr(m, "__version__", "?")))
        except ImportError:
            print("%-12s : NOT FOUND" % mod)


def framework_info(device_check=True):
    section("MXNet-TPU Info")
    t0 = time.time()
    import mxnet_tpu as mx

    print("import time  : %.3fs" % (time.time() - t0))
    print("location     :", os.path.dirname(mx.__file__))
    from mxnet_tpu import runtime

    feats = [f for f in runtime.feature_list() if f.enabled]
    print("features     :", ", ".join(f.name for f in feats))
    from mxnet_tpu import native

    print("native rt    :", "available" if native.available()
          else "unavailable (pure-python fallbacks active)")
    from mxnet_tpu.ops.registry import list_ops

    print("ops          : %d registered" % len(list_ops()))

    if not device_check:
        return
    section("Device Info")
    import jax

    print("backend      :", jax.default_backend())
    for d in jax.devices():
        print("device       : id=%d kind=%s process=%d"
              % (d.id, d.device_kind, d.process_index))

    # watchdog: do the smoke in a daemon thread and report a hang
    # instead of hanging the doctor
    result = {}

    def smoke():
        import jax.numpy as jnp

        x = jnp.ones((256, 256))
        t = time.time()
        float((x @ x).sum())  # device round-trip hard-syncs
        result["first"] = time.time() - t
        t = time.time()
        float((x @ x).sum())
        result["steady"] = time.time() - t

    th = threading.Thread(target=smoke, daemon=True)
    th.start()
    th.join(timeout=120)
    if "steady" in result:
        print("matmul smoke : first=%.2fs steady=%.4fs OK"
              % (result["first"], result["steady"]))
    else:
        print("matmul smoke : HUNG (>120s) — is another process holding "
              "the chip?  JAX_PLATFORMS=cpu checks the host side alone")


def _snapshot_quantiles(fam, qs=(0.5, 0.95, 0.99)):
    """Bucket-estimated quantiles computed FROM a snapshot family dict
    (merging its label children) — works on synthetic/offline
    snapshots, not just the live registry."""
    from mxnet_tpu.telemetry import _bucket_quantile

    count = sum(s.get("count", 0) for s in fam.get("samples", ()))
    if not count:
        return {}
    merged = {}
    for s in fam.get("samples", ()):
        for le, c in (s.get("buckets") or {}).items():
            merged[le] = merged.get(le, 0) + c
    cum = sorted((float("inf") if le == "+Inf" else float(le), c)
                 for le, c in merged.items())
    return {q: _bucket_quantile(cum, count, q) for q in qs}


def _quantile_lines(snap):
    """The quantile-table lines for a snapshot dict (pure — golden
    tests feed a synthetic snapshot and compare output verbatim)."""
    lines = []
    for name, m in sorted(snap.items()):
        if m.get("type") != "histogram":
            continue
        qs = _snapshot_quantiles(m)
        if not qs:
            continue
        lines.append("  %-38s p50=%.6g p95=%.6g p99=%.6g"
                     % (name, qs[0.5], qs[0.95], qs[0.99]))
    return lines


def telemetry_info():
    """Live mx.telemetry snapshot (counters accumulated by this process —
    the matmul smoke and import path already populate transfer/engine
    metrics), plus a fresh device-memory sample and bucket-estimated
    latency quantiles per histogram."""
    section("Telemetry")
    import json

    from mxnet_tpu import telemetry

    telemetry.sample_device_memory()
    snap = telemetry.snapshot()
    print("enabled      :", telemetry.ENABLED)
    print(json.dumps(snap, indent=2, sort_keys=True))
    print("totals       :", telemetry.totals(nonzero=True))
    lines = _quantile_lines(snap)
    if lines:
        print("quantiles (bucket-estimated, seconds):")
        for line in lines:
            print(line)
    else:
        print("quantiles    : (no histogram observations)")


def _fleet_lines(doc):
    """The --fleet section lines for a ``/fleetz``-shaped doc (pure —
    golden tests feed a synthetic doc and compare output verbatim)."""
    lines = ["enabled      : %s" % doc.get("enabled")]
    if not doc.get("enabled"):
        lines.append("(set MXNET_OBS=1 or mxnet_tpu.obs.enable())")
        return lines
    if doc.get("error"):
        lines.append("error        : %s" % doc["error"])
        return lines
    lines.append("generation   : %s" % doc.get("generation"))
    lines.append("view rank    : %s%s" % (
        doc.get("rank"),
        "  (LOCAL-ONLY: KV unreachable or nothing published)"
        if doc.get("local_only") else ""))
    rows = doc.get("ranks") or []
    if rows:
        lines.append("%-5s %-8s %-7s %-8s %-10s %-12s %-9s %s"
                     % ("rank", "pid", "age_s", "step", "steps_seen",
                        "step_p50_s", "monitor", "straggler"))
        for r in rows:
            p50 = r.get("step_p50_s")
            lines.append("%-5s %-8s %-7s %-8s %-10s %-12s %-9s %s"
                         % (r.get("rank"), r.get("pid"),
                            r.get("age_s"), r.get("step"),
                            r.get("steps_observed"),
                            "-" if p50 is None else "%.6g" % p50,
                            r.get("monitor"),
                            "YES" if r.get("straggler") else "-"))
    stragglers = doc.get("stragglers") or []
    lines.append("stragglers   : %s"
                 % (", ".join(str(r) for r in stragglers)
                    if stragglers else "(none)"))
    for name, state in sorted((doc.get("slo") or {}).items()):
        lines.append("slo          : %-24s %s" % (name, state))
    totals = doc.get("totals") or {}
    if totals:
        lines.append("fleet totals (nonzero):")
        for k in sorted(totals):
            lines.append("  %-40s %s" % (k, totals[k]))
    return lines


def fleet_info(src="live"):
    """mx.obs fleet view: the merged per-rank table, straggler flags,
    SLO states, and fleet-summed totals.  ``src`` is "live" (the
    attached membership / local-only world) or a path to a saved
    ``/fleetz`` JSON document."""
    section("Fleet (mx.obs)")
    import json

    if src and src != "live":
        with open(src) as f:
            doc = json.load(f)
    else:
        from mxnet_tpu import obs

        doc = obs.fleetz()
    for line in _fleet_lines(doc):
        print(line)


def _fleet_router_lines(doc):
    """The --fleet-router section lines for a router-``/statz``-shaped
    doc (pure — golden tests feed a synthetic doc and compare output
    verbatim)."""
    lines = ["generation   : %s" % doc.get("generation"),
             "disaggregated: %s" % bool(doc.get("disaggregated"))]
    reps = doc.get("replicas") or {}
    if reps:
        lines.append("%-10s %-8s %-6s %-6s %-7s %-8s %-8s %-9s %-7s %s"
                     % ("replica", "role", "ready", "drain", "age_s",
                        "q_age_s", "waiting", "pages", "breaker",
                        "endpoint"))
        for rid in sorted(reps):
            r = reps[rid]
            load = r.get("load") or {}
            br_open = int(load.get("breakers_open") or 0)
            br_half = int(load.get("breakers_half_open") or 0)
            breaker = "open" if br_open else (
                "half" if br_half else "closed")
            lines.append(
                "%-10s %-8s %-6s %-6s %-7s %-8s %-8s %-9s %-7s %s"
                % (rid, r.get("role"),
                   "yes" if r.get("ready") else "NO",
                   "YES" if r.get("draining") else "-",
                   r.get("age_s"),
                   load.get("queue_age_s"),
                   load.get("decode_waiting"),
                   "%s/%s" % (load.get("pages_free"),
                              load.get("pages_total")),
                   breaker, r.get("endpoint")))
    else:
        lines.append("(no live replicas)")
    for pool in ("prefill", "decode"):
        p = (doc.get("pools") or {}).get(pool) or {}
        lines.append("pool %-8s: replicas=%s waiting=%s live=%s "
                     "pages=%s/%s"
                     % (pool, p.get("replicas"),
                        p.get("decode_waiting"), p.get("decode_live"),
                        p.get("pages_free"), p.get("pages_total")))
    req = doc.get("requests") or {}
    lines.append("requests     : %s"
                 % (", ".join("%s=%s" % (k, req[k])
                              for k in sorted(req)) or "(none)"))
    lines.append("failovers    : %s   handoffs: %s   inflight: %s"
                 % (doc.get("failovers"), doc.get("handoffs"),
                    doc.get("inflight")))
    draining = doc.get("draining") or []
    lines.append("draining     : %s"
                 % (", ".join(str(r) for r in draining)
                    if draining else "(none)"))
    poison = doc.get("poison") or []
    lines.append("poison       : %s"
                 % (", ".join(str(p) for p in poison)
                    if poison else "(none)"))
    return lines


def fleet_router_info(src):
    """mx.fleet router view: the live replica table (role / load /
    breaker / drain), per-pool depth, request + failover + handoff
    counters, poison verdicts.  ``src`` is a router URL
    (http://host:port — reads its /statz), a KV root directory (the
    discovery records are rendered straight from the KV, no router
    process needed), or a saved router-/statz/ JSON file."""
    section("Fleet router (mx.fleet)")
    import json

    if src.startswith("http://") or src.startswith("https://"):
        import urllib.request

        with urllib.request.urlopen(src.rstrip("/") + "/statz",
                                    timeout=10) as resp:
            doc = json.loads(resp.read())
    elif os.path.isdir(src):
        from mxnet_tpu.dist.membership import FileKV
        from mxnet_tpu.fleet import kv_doc

        doc = kv_doc(FileKV(src))
    else:
        with open(src) as f:
            doc = json.load(f)
    for line in _fleet_router_lines(doc):
        print(line)


def trace_info():
    """Dump the mx.trace plane: flag, ring occupancy, watchdog state,
    dump destinations, and the dumps this process has written."""
    section("Trace / flight recorder")
    from mxnet_tpu import trace

    print("enabled      :", trace.is_enabled())
    ring = trace.RECORDER
    print("ring         : %d / %d events buffered (%d displaced)"
          % (len(ring), ring.capacity, ring.dropped))
    print("dump dir     :", trace.dump_dir())
    wd = trace.watchdog.get()
    if wd is None:
        print("watchdog     : not armed "
              "(MXNET_TRACE_WATCHDOG=1 or trace.watchdog.install())")
    else:
        print("watchdog     : %s  timeout=%.1fs poll=%.1fs fires=%d"
              % ("alive" if wd.alive else "stopped", wd.timeout,
                 wd.poll, wd.fires))
        if wd.last_report:
            print("last report  : scope=%s stacks=%s trace=%s"
                  % wd.last_report)
        active = wd.active()
        print("active scopes:", ", ".join(sorted(set(active)))
              if active else "(none)")
    p99 = trace.anomaly.STEP_DETECTOR.trailing_p99()
    print("slow-step    : factor=%.1f trailing_p99=%s"
          % (trace.anomaly.STEP_DETECTOR.factor,
             ("%.6gs" % p99) if p99 else "(warming up)"))
    dumps = trace.last_dumps()
    if dumps:
        print("dumps written:")
        for reason, path in dumps:
            print("  [%s] %s" % (reason, path))
    else:
        print("dumps written: none this process")


def checkpoints_info(root):
    """Audit a checkpoint root: one line per step with size, shard
    count, and checksum status (mx.checkpoint.validate, read-only —
    nothing is quarantined)."""
    section("Checkpoints")
    import os as _os

    from mxnet_tpu import checkpoint as ckpt

    if not _os.path.isdir(root):
        print("root         : %s (missing)" % root)
        return
    # recover=False: auditing must not promote/sweep anything in a root
    # another process may be actively writing
    mgr = ckpt.CheckpointManager(root, recover=False)
    report = mgr.validate()
    if not report:
        print("root         : %s (no checkpoint directories)" % root)
        return
    print("root         : %s" % root)
    ok_steps = [s for s in report if report[s]["ok"]]
    latest = max(ok_steps) if ok_steps else None
    for step in sorted(report):
        info = report[step]
        if info["ok"]:
            status = "legacy-ok" if info.get("legacy") else "ok"
        else:
            status = "CORRUPT: " + "; ".join(info["errors"])
        d = mgr._dir_for(step)
        shards = len([n for n in _os.listdir(d)
                      if n.endswith((".npy", ".npz"))]) \
            if _os.path.isdir(d) else 0
        print("step %8d : %10.1f KiB  %3d shard(s)  %s%s"
              % (step, info["nbytes"] / 1024.0, shards, status,
                 "  <- latest restorable" if step == latest else ""))


def _serve_decode_table(dec, breakers=None):
    """The decode plane's operator table: live sequences, page-pool
    occupancy/high-water, per-bucket compile provenance and breaker
    state (the /statz ``decode`` block)."""
    if not dec:
        return
    print("decode plane :")
    runner = dec.get("runner", {})
    pool = runner.get("pool", {})
    pc = pool.get("config", {})
    print("  pool       : %d/%d pages in use (high water %d, %.1f%% "
          "occupied)  page_size=%s  max_context=%s"
          % (pool.get("in_use_pages", 0), pool.get("capacity_pages", 0),
             pool.get("high_water_pages", 0),
             100.0 * pool.get("occupancy", 0.0),
             pc.get("page_size"), pc.get("max_context")))
    print("  traffic    : %d live  %d waiting  %d admitted  %d steps  "
          "oom_rejects=%d"
          % (len(dec.get("live", [])), dec.get("waiting", 0),
             dec.get("admitted", 0), dec.get("steps", 0),
             pool.get("oom_rejects", 0)))
    for seq in dec.get("live", []):
        print("    seq %-16s prompt=%-4d generated=%d/%d  pages=%d  "
              "joined@%s"
              % (seq.get("request_id") or "(anon)",
                 seq.get("prompt_tokens", 0), seq.get("generated", 0),
                 seq.get("max_new_tokens", 0), seq.get("pages", 0),
                 seq.get("joined_step")))
    board = dict(dec.get("breakers") or {})
    if breakers:
        board.update({k: v for k, v in breakers.items()
                      if "decode" in k or "prefill" in k})
    print("  buckets    :")
    for label, prov in sorted(runner.get("buckets", {}).items()):
        kind, _, size = label.partition(":")
        key = str((kind, int(size.lstrip("bt") or 0)))
        state = (board.get(key) or {}).get("state", "closed")
        print("    %-14s compile=%-10s breaker=%s"
              % (label, prov, state))
    ev = dec.get("evictions", {})
    if ev:
        print("  evictions  : %s" % ", ".join(
            "%s=%d" % kv for kv in sorted(ev.items())))


def serve_info(src):
    """Dump the serving plane: scheduler config, bucket table, queue
    depth and rejection/outcome counters.  ``src`` is either a RUNNING
    server's base URL (http://host:port — reads its /statz endpoint)
    or a telemetry JSON snapshot path (as written by
    ``telemetry.dump``)."""
    section("Serving")
    import json

    if src.startswith("http://") or src.startswith("https://"):
        import urllib.request

        with urllib.request.urlopen(src.rstrip("/") + "/statz",
                                    timeout=10) as resp:
            stats = json.load(resp)
        print("source       : %s/statz (live)" % src.rstrip("/"))
        print("ready        : %s   healthy: %s"
              % (stats.get("ready"), stats.get("healthy")))
        cfg = stats.get("config", {})
        for k in ("max_batch_size", "max_wait_us", "queue_depth",
                  "timeout_ms", "batch_sizes", "dtype"):
            print("%-12s : %r" % (k, cfg.get(k)))
        runner = stats.get("runner", {})
        runner = runner or {}
        print("model        : step=%r root=%r warmed=%r compiled=%r"
              % (runner.get("step"), runner.get("root"),
                 runner.get("warmed"), runner.get("compiled_signatures")))
        print("buckets      : %s"
              % (", ".join(runner.get("buckets", [])) or "(exact shapes)"))
        print("queue depth  : %r" % stats.get("queue_depth"))
        _serve_decode_table(stats.get("decode"),
                            stats.get("breakers", {}))
        totals = dict(stats.get("totals", {}))
        totals.pop("serve_requests_total", None)
        for result, v in sorted(stats.get("requests", {}).items()):
            totals["serve_requests_total{result=%s}" % result] = v
    else:
        with open(src) as f:
            snap = json.load(f)
        metrics = snap.get("metrics", snap)
        print("source       : %s (snapshot)" % src)
        depth = metrics.get("serve_queue_depth", {}).get("samples", [])
        print("queue depth  : %r"
              % (depth[0]["value"] if depth else "n/a"))
        compiles = metrics.get("serve_compile_total", {}).get("samples", [])
        if compiles:
            print("buckets      : %s" % ", ".join(
                "%s (%d compiles)" % (s["labels"].get("bucket"),
                                      s["value"]) for s in compiles))
        totals = {}
        for name, m in sorted(metrics.items()):
            if not name.startswith("serve_"):
                continue
            for s in m.get("samples", []):
                if m.get("type") == "histogram":
                    totals[name + "_count"] = \
                        totals.get(name + "_count", 0) + s.get("count", 0)
                else:
                    key = name if not s.get("labels") else \
                        "%s{%s}" % (name, ",".join(
                            "%s=%s" % kv
                            for kv in sorted(s["labels"].items())))
                    totals[key] = totals.get(key, 0) + s.get("value", 0)
    print("requests     :")
    shown = False
    for k in sorted(totals):
        if k.startswith("serve_requests_total"):
            print("  %-36s %g" % (k, totals[k]))
            shown = True
    if not shown:
        print("  (no serve_requests_total samples)")
    print("other serve_* totals:")
    for k in sorted(totals):
        if not k.startswith("serve_requests_total") and totals[k]:
            print("  %-36s %g" % (k, totals[k]))


def cache_info(src):
    """Dump the per-token-cost plane (mx.serve.cache + mx.serve.spec):
    prefix-trie size, hit/partial/miss counters, shared pages,
    evictions, and the speculative plane's acceptance economics.
    ``src`` is a running server's base URL (http://host:port — reads
    its /statz v2 ``cache`` / ``spec`` blocks) or a saved /statz JSON
    document."""
    section("Prefix cache / speculative decode (mx.serve.cache)")
    import json

    if src.startswith("http://") or src.startswith("https://"):
        import urllib.request

        with urllib.request.urlopen(src.rstrip("/") + "/statz",
                                    timeout=10) as resp:
            doc = json.loads(resp.read())
        print("source       : %s/statz (live)" % src.rstrip("/"))
    else:
        with open(src) as f:
            doc = json.load(f)
        print("source       : %s (saved /statz)" % src)
    cache = doc.get("cache") or {"enabled": False}
    if not cache.get("enabled"):
        print("prefix cache : disabled (DecodeConfig(prefix_cache="
              "True) or MXNET_SERVE_PREFIX_CACHE=1)")
    else:
        looks = (cache.get("hits", 0) + cache.get("partials", 0)
                 + cache.get("misses", 0))
        print("prefix cache : enabled, block=%d tokens"
              % cache.get("block_tokens", 0))
        print("  trie       : %d node(s), %d shared page(s)"
              % (cache.get("nodes", 0), cache.get("shared_pages", 0)))
        print("  lookups    : %d  (hit %d / partial %d / miss %d"
              "%s)" % (looks, cache.get("hits", 0),
                       cache.get("partials", 0), cache.get("misses", 0),
                       ", %.0f%% hit" % (100.0 * cache["hits"] / looks)
                       if looks else ""))
        print("  hit tokens : %d total   inserted pages: %d   "
              "evictions: %d" % (cache.get("hit_tokens_total", 0),
                                 cache.get("inserted_pages", 0),
                                 cache.get("evictions", 0)))
    spec = doc.get("spec") or {"enabled": False}
    if not spec.get("enabled"):
        print("speculative  : disabled (DecodeRunner(draft=...))")
    else:
        print("speculative  : enabled, K=%d draft=%s epoch=%d"
              % (spec.get("k", 0), spec.get("draft_model"),
                 spec.get("epoch", 0)))
        print("  rounds     : %d  verify steps: %d"
              % (spec.get("rounds", 0), spec.get("verify_steps", 0)))
        print("  acceptance : %.2f (%d / %d proposed)   accepted per "
              "target step: %.2f"
              % (spec.get("acceptance_rate", 0.0),
                 spec.get("accepted", 0), spec.get("proposed", 0),
                 spec.get("accepted_per_step", 0.0)))
        fb = spec.get("fallbacks") or {}
        print("  fallbacks  : %s"
              % (", ".join("%s=%d" % kv for kv in sorted(fb.items()))
                 or "(none)"))
        dp = spec.get("draft_pool") or {}
        print("  draft pool : %s/%s pages in use"
              % (dp.get("in_use", "?"), dp.get("capacity", "?")))


def tenant_info(src):
    """Dump the multi-tenant serving plane (mx.tenant): adapter bank
    residency, per-tenant weights / quotas / live usage, WFQ virtual
    clock, and quota-reject counters.  ``src`` is a running server's
    base URL (reads its /statz v2 ``tenants`` block) or a saved /statz
    JSON document."""
    section("Multi-tenant serving (mx.tenant)")
    import json

    if src.startswith("http://") or src.startswith("https://"):
        import urllib.request

        with urllib.request.urlopen(src.rstrip("/") + "/statz",
                                    timeout=10) as resp:
            doc = json.loads(resp.read())
        print("source       : %s/statz (live)" % src.rstrip("/"))
    else:
        with open(src) as f:
            doc = json.load(f)
        print("source       : %s (saved /statz)" % src)
    ten = doc.get("tenants") or {"enabled": False}
    if not ten.get("enabled"):
        print("tenant plane : disabled (DecodeRunner(tenant="
              "TenantPlane()); arm with MXNET_TENANT=1)")
        return
    cfg = ten.get("config") or {}
    bank = ten.get("bank") or {}
    print("tenant plane : enabled, %d adapter slot(s) x max_rank %d"
          % (cfg.get("slots", 0), cfg.get("max_rank", 0)))
    print("  bank       : %d/%d resident, %d swap(s), targets=%s"
          % (bank.get("resident", 0), bank.get("n_slots", 0),
             bank.get("swaps", 0),
             ",".join(bank.get("targets") or []) or "(none)"))
    wfq = ten.get("wfq") or {}
    print("  wfq clock  : %.3f  picks: %s"
          % (wfq.get("clock", 0.0),
             ", ".join("%s=%d" % kv
                       for kv in sorted((wfq.get("picks") or {})
                                        .items())) or "(none)"))
    rejects = ten.get("rejects") or {}
    print("  rejects    : %s"
          % (", ".join("%s=%d" % kv for kv in sorted(rejects.items()))
             or "(none)"))
    tenants = ten.get("tenants") or {}
    if not tenants:
        print("  tenants    : (none registered)")
    for name in sorted(tenants):
        t = tenants[name]
        usage = t.get("usage") or {}
        quota = t.get("quota") or {}
        print("  - %-12s w=%-5g adapter=%-14s live %d/%s  pages %d/%s"
              "  waiting %d/%s  served %d tok"
              % (name, t.get("weight", 1.0),
                 t.get("adapter") or "(base)",
                 usage.get("live", 0), quota.get("max_live") or "inf",
                 usage.get("pages", 0), quota.get("max_pages") or "inf",
                 usage.get("waiting", 0), quota.get("queue_depth", "?"),
                 t.get("served_tokens", 0)))


def trainer_info():
    """Audit the imperative Trainer's multi-tensor update engine by
    training a representative mixed-group model for 2 steps: group
    table (params-per-group, bytes, programs/step, provenance) plus the
    collective bucket plan (programs and fill % at the current
    MXNET_KVSTORE_BUCKET_BYTES)."""
    section("Trainer / multi-tensor")
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd, telemetry
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.kvstore import collective
    from mxnet_tpu.optimizer import multi_tensor

    from mxnet_tpu.base import get_env

    enabled = get_env("MXNET_MULTI_TENSOR", bool, True)
    print("multi-tensor :", "enabled" if enabled else
          "DISABLED (MXNET_MULTI_TENSOR=0 — eager per-param updates)")
    mx.random.seed(0)
    net = nn.HybridSequential()
    for _ in range(8):
        net.add(nn.Dense(32, in_units=32))
    net.initialize()
    params = net.collect_params()
    # a distinct lr_mult splits a group — makes the table representative
    list(params.values())[-1].lr_mult = 0.5
    trainer = gluon.Trainer(params, "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    x = nd.array(np.random.RandomState(0).rand(4, 32).astype(np.float32))
    for _ in range(2):
        with autograd.record():
            loss = (net(x) ** 2).mean()
        loss.backward()
        trainer.step(4)
    rows = multi_tensor.group_table(trainer)
    print("groups       : %d  (demo model: %d params)"
          % (len(rows), len(trainer._params)))
    for r in rows:
        shard_col = "w=%s s=%s" % (r["placement"]["params"],
                                   r["placement"]["state"])
        print("  %-10s %3d params  %10.1f KiB  %d program/step  "
              "%s%s  shard[%s]  (%d host scalars)"
              % (r["optimizer"], r["params"], r["bytes"] / 1024.0,
                 r["programs_per_step"], r["provenance"],
                 "  [zero%d]" % r["zero"] if r["zero"] else "",
                 shard_col, r["host_scalar_slots"]))
    grads = [(p.grad().size * p.grad().dtype.itemsize,
              str(p.grad().dtype)) for p in trainer._params]
    plan = collective.plan_buckets(grads)
    total = sum(n for n, _ in grads)
    print("bucket plan  : %d collective program(s) for %.1f KiB grads "
          "(bucket=%.1f MiB)"
          % (len(plan), total / 1024.0,
             collective.default_bucket_bytes() / 1048576.0))
    for b, idxs in enumerate(plan):
        nbytes = sum(grads[i][0] for i in idxs)
        print("  bucket %d   : %3d key(s)  %10.1f KiB  fill %5.1f%%"
              % (b, len(idxs), nbytes / 1024.0,
                 100.0 * nbytes / collective.default_bucket_bytes()))
    tot = {k: v for k, v in telemetry.totals(nonzero=True).items()
           if k.startswith("trainer_")}
    print("telemetry    : %s" % (tot or "(telemetry disabled)"))


def step_info():
    """Print the mx.step capture report by capturing a representative
    whole-step program (tiny MLP + Adam + monitor fused in) and
    running it for 2 steps: segment list, donation map, remat policy,
    provenance (fresh vs compile-cache hit), bucket plan, path counts
    and fallback reasons if degraded."""
    section("Whole-step capture (mx.step)")
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, monitor, nd, step, telemetry
    from mxnet_tpu.gluon import nn

    print("capture      :", "enabled" if step.is_enabled() else
          "DISABLED (MXNET_STEP_CAPTURE=0 — stitched path)")
    print("remat policy :", step.remat_mode())
    mon_was = monitor.core.ENABLED
    monitor.enable()
    try:
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(32, activation="relu", in_units=32),
                nn.Dense(8, in_units=32))
        net.initialize()
        net.hybridize()
        trainer = gluon.Trainer(net.collect_params(), "adam",
                                {"learning_rate": 0.01})
        program = trainer.capture(net, gluon.loss.L2Loss())
        rs = np.random.RandomState(0)
        x = nd.array(rs.rand(4, 32).astype(np.float32))
        y = nd.array(rs.rand(4, 8).astype(np.float32))
        for _ in range(2):
            program(x, y)
        rep = program.report()
    finally:
        if not mon_was:
            monitor.disable()
    print("paths        : captured=%d stitched=%d skipped=%d"
          % (rep["paths"]["captured"], rep["paths"]["stitched"],
             rep["skipped_steps"]))
    mesh = rep.get("mesh")
    print("mesh         : %s" % (
        "dp=%(dp)d mdl=%(mdl)d over %(devices)d device(s), "
        "%(processes)d process(es)" % mesh if mesh
        else "(none — single-device capture)"))
    if rep.get("zero"):
        print("zero         : level %d (mx.shard weight-update "
              "sharding)" % rep["zero"])
    for prog in rep["programs"]:
        print("program      : provenance=%s  remat=%s  monitor=%s  "
              "gate=%s  zero=%s  host-scalar slots=%d"
              % (prog["provenance"], prog["remat"],
                 prog["monitor_fused"], prog["gate"],
                 prog.get("zero", 0), prog["host_scalar_slots"]))
        if prog.get("wire"):
            print("  wire/step  : grads %s B  param gather %s B"
                  % (prog["wire"]["grads"], prog["wire"]["param_gather"]))
        print("  fingerprint: %s" % (prog["fingerprint"] or
                                     "(cache disabled / no lowering)"))
        print("  segments   :")
        for seg in prog["segments"]:
            extras = {k: v for k, v in seg.items() if k != "segment"}
            print("    %-10s %s" % (seg["segment"], extras))
        print("  donation   :")
        for name, d in prog["donation"].items():
            print("    %-20s %s" % (name, d))
        print("  bucket plan: %d bucket(s) %s  bucket_bytes=%.1f MiB"
              % (len(prog["bucket_plan"]),
                 [len(b) for b in prog["bucket_plan"]],
                 prog.get("bucket_bytes", 0) / 1048576.0))
    if rep["fallbacks"]:
        print("fallbacks    :")
        for f in rep["fallbacks"]:
            print("  step %-5s %-24s %s"
                  % (f["step"], f["reason"], f["detail"]))
    else:
        print("fallbacks    : (none)")
    tot = {k: v for k, v in telemetry.totals(nonzero=True).items()
           if k.startswith("step_")}
    print("telemetry    : %s" % (tot or "(telemetry disabled)"))


def _monitor_table(rows):
    """Print one aligned row per parameter group from {label: stats}
    dicts carrying grad/weight norm, max|x|, nonfinite counts."""
    if not rows:
        print("groups       : (no per-group stats observed)")
        return
    print("groups       :")
    print("  %-28s %12s %12s %12s %12s %6s %6s"
          % ("group", "grad_norm", "grad_max", "w_norm", "w_max",
             "nf_g", "nf_w"))
    for label in sorted(rows):
        st = rows[label]
        print("  %-28s %12.6g %12.6g %12.6g %12.6g %6d %6d"
              % (label, st.get("g_norm", 0.0), st.get("g_max_abs", 0.0),
                 st.get("w_norm", 0.0), st.get("w_max_abs", 0.0),
                 int(st.get("g_nonfinite", 0)),
                 int(st.get("w_nonfinite", 0))))


def monitor_info(src):
    """The mx.monitor stat plane.  ``src`` is ``live`` (default: train
    a tiny monitored model for a few steps and read the live
    registry), a telemetry JSON snapshot (``telemetry.dump``), or a
    ``MXNET_MONITOR_STREAM`` JSONL file."""
    section("Monitor / training health")
    import json

    if src != "live":
        with open(src) as f:
            content = f.read()
        first, _, rest = content.partition("\n")
        try:
            head = json.loads(first)
        except ValueError:
            head = {}
        if isinstance(head, dict) and "groups" in head:
            # MXNET_MONITOR_STREAM JSONL: one line per observed step.
            # A crashed run leaves a torn final line — report the
            # intact steps instead of dying on the tear (the stream's
            # whole point is the post-mortem)
            lines, torn = [head], 0
            for ln in rest.splitlines():
                if not ln.strip():
                    continue
                try:
                    lines.append(json.loads(ln))
                except ValueError:
                    torn += 1
            print("source       : %s (JSONL stream, %d step(s)%s)"
                  % (src, len(lines),
                     ", %d torn line(s) skipped" % torn if torn else ""))
            last = lines[-1]
            skipped = sum(1 for ln in lines if ln.get("skipped"))
            nonfinite = sum(
                1 for ln in lines
                if any(g.get("nonfinite_grad") for g in
                       ln.get("groups", {}).values()))
            norms = [ln.get("grad_global_norm", 0.0) for ln in lines]
            print("steps        : %d  (nonfinite %d, skipped %d)"
                  % (len(lines), nonfinite, skipped))
            print("grad norm    : last=%.6g max=%.6g"
                  % (norms[-1], max(norms)))
            print("last step    : %s  policy=%s%s"
                  % (last.get("step"), last.get("policy"),
                     "  [SKIPPED]" if last.get("skipped") else ""))
            _monitor_table({
                label: {"g_norm": g.get("grad_norm", 0.0),
                        "g_max_abs": g.get("grad_max_abs", 0.0),
                        "w_norm": g.get("weight_norm", 0.0),
                        "w_max_abs": g.get("weight_max_abs", 0.0),
                        "g_nonfinite": g.get("nonfinite_grad", 0),
                        "w_nonfinite": g.get("nonfinite_weight", 0)}
                for label, g in last.get("groups", {}).items()})
            return
        # telemetry snapshot (telemetry.dump JSON)
        try:
            snap = json.loads(content)
        except ValueError:
            # not a snapshot either — e.g. a stream whose FIRST line
            # is the torn one; say so instead of dying in a traceback
            print("source       : %s (unparseable: neither a telemetry "
                  "snapshot nor an intact JSONL stream)" % src)
            return
        metrics = snap.get("metrics", snap)
        print("source       : %s (telemetry snapshot)" % src)

        def _gauge(name):
            out = {}
            for s in metrics.get(name, {}).get("samples", []):
                out[s["labels"].get("group", "")] = s.get("value", 0.0)
            return out

        rows = {}
        for label, v in _gauge("monitor_grad_norm").items():
            rows.setdefault(label, {})["g_norm"] = v
        for label, v in _gauge("monitor_weight_norm").items():
            rows.setdefault(label, {})["w_norm"] = v
        for label, v in _gauge("monitor_grad_max_abs").items():
            rows.setdefault(label, {})["g_max_abs"] = v
        for label, v in _gauge("monitor_weight_max_abs").items():
            rows.setdefault(label, {})["w_max_abs"] = v
        for s in metrics.get("monitor_nonfinite_total",
                             {}).get("samples", []):
            key = "g_nonfinite" if s["labels"].get("kind") == "grad" \
                else "w_nonfinite"
            rows.setdefault(s["labels"].get("group", ""),
                            {})[key] = s.get("value", 0)
        _monitor_table(rows)
        for name in ("monitor_grad_global_norm",
                     "monitor_nonfinite_steps_total",
                     "monitor_skipped_steps_total",
                     "monitor_stat_builds_total",
                     "monitor_dropped_total"):
            samples = metrics.get(name, {}).get("samples", [])
            if samples:
                print("%-26s : %g" % (name, samples[0].get("value", 0)))
        trips = metrics.get("monitor_sentinel_trips_total",
                            {}).get("samples", [])
        for s in trips:
            print("sentinel trips (%s)     : %g"
                  % (s["labels"].get("policy"), s.get("value", 0)))
        return

    # live: train a tiny monitored model (mirrors trainer_info's demo)
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, monitor, nd, telemetry
    from mxnet_tpu.gluon import nn

    telemetry.enable()
    monitor.enable()
    print("enabled      :", monitor.is_enabled())
    print("sentinel     :", monitor.sentinel.policy())
    print("stream       :", monitor.stream_path() or "(off)")
    mx.random.seed(0)
    net = nn.HybridSequential()
    for _ in range(6):
        net.add(nn.Dense(16, in_units=16))
    net.initialize()
    params = net.collect_params()
    list(params.values())[-1].lr_mult = 0.5  # a second group
    trainer = gluon.Trainer(params, "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    x = nd.array(np.random.RandomState(0).rand(4, 16).astype(np.float32))
    for _ in range(3):
        with autograd.record():
            loss = (net(x) ** 2).mean()
        loss.backward()
        trainer.step(4)
        monitor.observe_loss(float(loss.asnumpy()))
    monitor.flush(timeout=10.0)
    s = monitor.summary()
    print("steps        : %d  (nonfinite %d, skipped %d, dropped %d)"
          % (s["steps"], s["nonfinite_steps"], s["skipped_steps"],
             s["dropped"]))
    print("grad norm    : last=%.6g max=%.6g"
          % (s["grad_global_norm_last"], s["grad_global_norm_max"]))
    print("stat programs: %d compiled (builds=%g, dispatches=%g)"
          % (monitor.stats.programs(),
             telemetry.value("monitor_stat_builds_total"),
             telemetry.value("monitor_stat_programs_total")))
    _monitor_table(monitor.group_values())
    det = monitor.DETECTOR.state()
    print("detector     : spikes=%d nonfinite_grad_steps=%d "
          "loss_nonfinite=%d plateaus=%d"
          % (det["spikes"], det["nonfinite_grad_steps"],
             det["loss_nonfinite"], det["plateaus"]))
    print("               spike_factor=%.1f window=%d (fill %d) "
          "trailing_max=%.6g"
          % (det["spike_factor"], det["window"], det["window_fill"],
             det["trailing_max"]))
    tot = {k: v for k, v in telemetry.totals(nonzero=True).items()
           if k.startswith("monitor_")}
    print("telemetry    : %s" % (tot or "(no monitor_* activity)"))


def data_info():
    """Audit the mx.data streaming input plane: live loaders (shard
    assignment, ring depth/occupancy/stalls, per-worker read rates,
    cursor position) plus this process's data_* telemetry — the H3
    health check (steady state: occupancy ~ depth, flat stalls)."""
    section("Data Pipeline")
    from mxnet_tpu import data as mxdata
    from mxnet_tpu import telemetry

    print("ring depth   :", mxdata.default_depth(),
          "(MXNET_DATA_PREFETCH)")
    print("workers      :", mxdata.default_workers(),
          "(MXNET_DATA_WORKERS)")
    num_hosts, host = mxdata.world_coords()
    print("world        : host %d/%d" % (host, num_hosts))
    loaders = mxdata.state()
    print("live loaders : %d" % len(loaders))
    for i, st in enumerate(loaders):
        cur = st["cursor"]
        print("  [%d] %s shards=%d records=%d/%d local_batch=%d "
              "batches/epoch=%d" % (i, st["assignment"], st["shards"],
                                    st["records_local"],
                                    st["records_total"],
                                    st["local_batch"],
                                    st["batches_per_epoch"]))
        print("      ring depth=%d occupancy=%d staged=%d stalls=%d"
              % (st["ring_depth"], st["ring_occupancy"],
                 st["ring_staged"], st["ring_stalls"]))
        print("      cursor epoch=%d batch=%d shard=%d offset=%d "
              "samples_seen=%d" % (cur["epoch"], cur["batch"],
                                   cur["shard_index"],
                                   cur["record_offset"],
                                   cur["samples_seen"]))
        if st["worker_records"]:
            print("      worker records:",
                  " ".join("w%d=%d" % (w, n) for w, n in
                           sorted(st["worker_records"].items())))
        if st["mesh"]:
            print("      mesh:", st["mesh"])
    tot = {k: v for k, v in telemetry.totals(nonzero=True).items()
           if k.startswith(("data_", "dataloader_"))}
    print("telemetry    : %s" % (tot or "(no data-plane activity "
                                 "this process)"))
    for name in ("data_read_seconds", "data_decode_seconds",
                 "data_stage_seconds", "dataloader_batch_wait_seconds"):
        try:
            qs = telemetry.histogram_quantiles(name)
        except Exception:
            qs = None
        if qs:
            print("  %-32s p50=%.6f p95=%.6f p99=%.6f"
                  % (name, qs.get(0.5, 0.0), qs.get(0.95, 0.0),
                     qs.get(0.99, 0.0)))


def compile_cache_info():
    """Audit the mx.compile persistent compilation cache: directory,
    entry count, total bytes, per-entry age/size, quarantined entries,
    and this process's hit/miss/commit telemetry."""
    section("Compile Cache")
    import time as _time

    from mxnet_tpu import compile as mxcompile
    from mxnet_tpu import telemetry

    print("enabled      :", mxcompile.is_enabled(),
          "" if mxcompile.is_enabled() else
          "(set MXNET_COMPILE_CACHE=1 / MXNET_COMPILE_CACHE_DIR)")
    cache = mxcompile.get_cache()
    # one directory walk serves the summary AND the per-entry listing
    # (a cache near its cap holds hundreds of dirs, stat'd per file)
    entries = cache.entries() if cache is not None else []
    quarantined = cache.quarantined() if cache is not None else []
    print("dir          :", mxcompile.cache_dir())
    print("entries      : %d  (%.1f KiB total, cap %.1f MiB)"
          % (len(entries), sum(e[2] for e in entries) / 1024.0,
             (cache.max_bytes if cache is not None else 0) / 1048576.0))
    now = _time.time()
    for fp, _d, nbytes, mtime in sorted(entries, key=lambda e: -e[3]):
        print("entry %s : %8.1f KiB  last-used %.0fs ago"
              % (fp[:12], nbytes / 1024.0, now - mtime))
    if quarantined:
        print("quarantined  :")
        for q in quarantined:
            print("  %s" % q)
    else:
        print("quarantined  : none")
    tot = {k: v for k, v in telemetry.totals(nonzero=True).items()
           if k.startswith("compile_cache_")}
    print("telemetry    : %s" % (tot or "(no compile_cache_* activity "
                                        "in this process)"))


def resilience_info():
    """mx.resilience state: the armed fault plan, preemption handler,
    recent supervisor restarts, serve breaker gauges, and the
    injected-fault / restart / poison counters."""
    section("Resilience")
    from mxnet_tpu import telemetry
    from mxnet_tpu.resilience import inject, preempt, supervisor

    plan = inject.state()
    print("fault plan   : %s" % ("armed (%d entries)"
                                 % len(plan["entries"])
                                 if plan["active"] else "none"))
    for e in plan["entries"]:
        print("  %s@%s kind=%s fired=%d/%s"
              % (e["site"], e["key"], e["kind"], e["fired"],
                 e["count"] if e["count"] is not None else "inf"))
    pre = preempt.state()
    print("preemption   : handler %s, %s (exit code %d, hooks: %s)"
          % ("installed" if pre["installed"] else "not installed",
             "REQUESTED (%.1fs grace left)" % pre["grace_remaining"]
             if pre["requested"] else "idle",
             pre["exit_code"], ", ".join(pre["hooks"]) or "none"))
    restarts = supervisor.recent_restarts()
    if restarts:
        print("restarts     : %d recorded (newest last)" % len(restarts))
        for r in restarts[-8:]:
            print("  step %-6d %-16s restored=%-6s backoff=%-6s %s"
                  % (r["step"], r["kind"], r["restored_step"],
                     "%.2fs" % r["backoff_seconds"]
                     if r["backoff_seconds"] else "-",
                     (r["error"] or "")[:60]))
    else:
        print("restarts     : none in this process")
    breakers = {}
    m = telemetry.get_metric("serve_breaker_state")
    if m is not None:
        for values, child in m._samples():
            if values:
                breakers[values[0]] = int(child.value)
    if breakers:
        names = {0: "closed", 1: "half-open", 2: "open"}
        print("breakers     :")
        for bucket, st in sorted(breakers.items()):
            print("  %-24s %s" % (bucket, names.get(st, st)))
    else:
        print("breakers     : none registered in this process")
    tot = {k: v for k, v in telemetry.totals(nonzero=True).items()
           if k.startswith(("resilience_", "serve_poison",
                            "serve_bisect", "serve_breaker"))}
    print("telemetry    : %s" % (tot or "(no resilience_* activity in "
                                        "this process)"))


def shard_info():
    """mx.shard phase 2 state: the configured mesh, tensor-parallel
    mode, layout-rule table, a per-parameter layout resolution for a
    representative MLP on a dp=2 x mdl=2 mesh (virtual devices are
    fine — same specs as a pod), and the per-axis collective-byte
    counters."""
    section("Shard (model parallelism)")
    import jax

    from mxnet_tpu import shard, telemetry
    from mxnet_tpu.shard.policy import ShardPolicy

    st = shard.state()
    print("mesh         : %s" % (st["mesh"] or "(none configured — "
                                 "set MXNET_SHARD_DP/MXNET_SHARD_MDL "
                                 "or pass mesh= to the Trainer)"))
    print("tp mode      : %s %s"
          % (st["tp_mode"],
             "(bit-exact storage sharding; weights re-gathered "
             "in-program)" if st["tp_mode"] == "gather"
             else "(Megatron sharded matmuls; tolerance parity)"))
    rules = st["layout"]
    if not rules:
        print("layout table : (empty — every array resolves via the "
              "implicit '* -> auto' tail rule)")
    else:
        print("layout table : %d rule(s), first match wins" % len(rules))
        for r in rules:
            print("  %-24s -> %s%s"
                  % (r["pattern"], r["kind"],
                     "" if r["dim"] is None else ":%d" % r["dim"]))
    devs = jax.devices()
    if len(devs) >= 4:
        gm = shard.GlobalMesh(dp=2, mdl=2, devices=devs[:4])
        pol = ShardPolicy(3, gm)
        print("resolution   : dp=2 x mdl=2, zero=3 (representative "
              "MLP shapes)")
        for name, shape in (("dense0.weight", (16, 12)),
                            ("dense0.bias", (16,)),
                            ("dense1.weight", (4, 16)),
                            ("dense1.bias", (4,))):
            lo = pol.layout_of(name, shape)
            print("  %-14s %-9s kind=%-9s mdl_dim=%-4s %s"
                  % (name, "x".join(map(str, shape)), lo["kind"],
                     lo["mdl_dim"], lo["spec"]))
    else:
        print("resolution   : skipped (%d device(s); need >= 4 for "
              "the dp=2 x mdl=2 sample mesh)" % len(devs))
    mode_gauge = telemetry.value("shard_tp_mode")
    print("telemetry    : shard_tp_mode=%s zero_level=%s"
          % (mode_gauge, telemetry.value("shard_zero_level")))
    total = 0
    for axis in ("dp", "mdl"):
        for op in ("reduce_scatter", "all_reduce", "all_gather"):
            v = telemetry.value("shard_collective_bytes_total",
                                {"axis": axis, "op": op})
            total += v
            if v:
                print("  wire       : axis=%-3s %-14s %d B" % (axis, op,
                                                               v))
    if not total:
        print("  wire       : no collective bytes counted this "
              "process (counters fill as captured sharded steps run)")


def dist_info(root=None):
    """mx.dist state: membership backend + world view, collective
    deadline, pod-checkpoint discovery for an optional ROOT."""
    section("Dist")
    from mxnet_tpu import dist, telemetry

    st = dist.state()
    print("member dir   : %s" % (st["member_dir"] or "(not exported — "
                                 "FileKV backend inactive)"))
    print("collective   : deadline %s"
          % ("%.1fs" % st["collective_timeout"]
             if st["collective_timeout"] else "DISARMED "
             "(set MXNET_DIST_COLLECTIVE_TIMEOUT on multi-host runs)"))
    mem = st["membership"]
    if mem is None and st["member_dir"]:
        # peek at the shared dir without joining (read-only view)
        m = dist.Membership(heartbeat=0)
        rec = m.kv.get("world")
        if rec is not None:
            m.generation = int(rec.get("generation", 0))
            m.world_size = int(rec.get("world_size", m.world_size))
            mem = m.state()
    if mem is None:
        print("membership   : not joined in this process")
    elif not mem.get("joined"):
        print("membership   : rank %d / world %d (not joined)"
              % (mem["rank"], mem["world_size"]))
    else:
        print("membership   : rank %d / world %d, generation %d"
              % (mem["rank"], mem["world_size"], mem["generation"]))
        print("  alive      : %s" % (mem["alive"] or "(none fresh)"))
        print("  dead       : %s" % (mem["dead"] or "none"))
        stop = mem.get("stop")
        print("  stop flag  : %s"
              % ("none" if stop is None else
                 "reason=%s rank=%s step=%s %s"
                 % (stop.get("reason"), stop.get("rank"),
                    stop.get("step"), (stop.get("error") or "")[:60])))
    if root:
        from mxnet_tpu.dist import podckpt

        steps = podckpt._scan_pod_markers(root)
        print("pod ckpts    : %s" % (("%d pod-committed step(s), "
                                      "latest %d" % (len(steps),
                                                     steps[-1]))
                                     if steps else "none under %s"
                                     % root))
    tot = {k: v for k, v in telemetry.totals(nonzero=True).items()
           if k.startswith("dist_")}
    print("telemetry    : %s" % (tot or "(no dist_* activity in this "
                                        "process)"))


def env_info():
    section("Environment")
    from mxnet_tpu import config

    for name, val in sorted(config.current().items()):
        mark = "*" if name in os.environ else " "
        print("%s %-38s = %r" % (mark, name, val))
    print("(* = set in this environment)")
    for var in ("JAX_PLATFORMS", "XLA_FLAGS", "PYTHONPATH", "http_proxy",
                "https_proxy"):
        if os.environ.get(var):
            print("  %s=%s" % (var, os.environ[var]))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--no-device-check", action="store_true",
                    help="skip the on-device matmul smoke")
    ap.add_argument("--telemetry", action="store_true",
                    help="print the live mx.telemetry snapshot")
    ap.add_argument("--checkpoints", metavar="ROOT",
                    help="audit a checkpoint root: steps, sizes, "
                         "checksum status (read-only; skips the "
                         "environment sections, honors --telemetry)")
    ap.add_argument("--serve", metavar="SRC",
                    help="dump serving-plane state (scheduler config, "
                         "bucket table, queue/rejection counters) from "
                         "a running server URL (http://host:port) or a "
                         "telemetry JSON snapshot file")
    ap.add_argument("--compile-cache", action="store_true",
                    help="audit the mx.compile persistent compilation "
                         "cache: dir, entries, bytes, quarantined "
                         "entries, hit/miss telemetry")
    ap.add_argument("--trainer", action="store_true",
                    help="audit the imperative Trainer's multi-tensor "
                         "update engine: group table, programs/step, "
                         "collective bucket fill")
    ap.add_argument("--step", action="store_true",
                    help="audit mx.step whole-step capture: capture a "
                         "representative program and print segments, "
                         "donation map, remat policy, provenance, "
                         "bucket plan and fallback reasons")
    ap.add_argument("--trace", action="store_true",
                    help="dump the mx.trace plane: flight-recorder "
                         "occupancy, watchdog state, anomaly "
                         "detectors, dumps written")
    ap.add_argument("--monitor", nargs="?", const="live", metavar="SRC",
                    help="mx.monitor training-health stats: per-group "
                         "norms, nonfinite totals, sentinel "
                         "policy/trips, detector state — live (train "
                         "a tiny monitored model; the default), or "
                         "from a telemetry JSON snapshot / "
                         "MXNET_MONITOR_STREAM JSONL file")
    ap.add_argument("--resilience", action="store_true",
                    help="dump the mx.resilience plane: armed fault "
                         "plan, preemption handler state, recent "
                         "supervisor restarts, serve breaker states, "
                         "injected-fault counters")
    ap.add_argument("--data", action="store_true",
                    help="audit the mx.data streaming input plane: "
                         "live loaders, ring depth/occupancy/stalls, "
                         "per-worker read rates, cursor state, data_* "
                         "telemetry")
    ap.add_argument("--shard", action="store_true",
                    help="mx.shard model-parallel plane: configured "
                         "mesh, tp mode (gather/compute), layout-rule "
                         "table, per-parameter spec resolution on a "
                         "sample dp=2 x mdl=2 mesh, per-axis "
                         "collective-byte counters")
    ap.add_argument("--dist", nargs="?", const="", metavar="CKPT_ROOT",
                    help="dump the mx.dist plane: membership/world "
                         "view, collective deadline, world-stop flag, "
                         "and (with a root) pod-committed checkpoint "
                         "steps")
    ap.add_argument("--fleet", nargs="?", const="live", metavar="SRC",
                    help="mx.obs fleet view: per-rank table (publish "
                         "age, step cadence, straggler flags), SLO "
                         "states, fleet-summed totals — live (the "
                         "attached membership or a local-only world; "
                         "the default), or from a saved /fleetz JSON "
                         "document")
    ap.add_argument("--cache", metavar="SRC",
                    help="per-token-cost plane: prefix-trie size, "
                         "hit/partial/miss, shared pages, evictions, "
                         "speculative acceptance rate — SRC is a "
                         "server URL (reads its /statz) or a saved "
                         "/statz JSON document")
    ap.add_argument("--fleet-router", metavar="SRC",
                    help="mx.fleet router view: live replica table "
                         "(role, load, breaker, drain), per-pool "
                         "depth, request/failover/handoff counters, "
                         "poison verdicts — SRC is a router URL "
                         "(reads its /statz), a membership KV root "
                         "directory, or a saved /statz JSON document")
    ap.add_argument("--tenant", metavar="SRC",
                    help="multi-tenant serving plane: adapter bank "
                         "residency, per-tenant weights / quotas / "
                         "live usage, WFQ clock, quota rejects — SRC "
                         "is a server URL (reads its /statz) or a "
                         "saved /statz JSON document")
    args = ap.parse_args()
    # section flags compose: --compile-cache --serve URL prints both
    # (each skips the environment dump, all honor --telemetry)
    if args.compile_cache or args.serve or args.checkpoints or \
            args.trainer or args.step or args.trace or args.monitor or \
            args.resilience or args.data or \
            args.dist is not None or args.fleet or args.fleet_router \
            or args.cache or args.tenant or args.shard:
        if args.compile_cache:
            compile_cache_info()
        if args.data:
            data_info()
        if args.resilience:
            resilience_info()
        if args.shard:
            shard_info()
        if args.dist is not None:
            dist_info(args.dist or None)
        if args.fleet:
            fleet_info(args.fleet)
        if args.fleet_router:
            fleet_router_info(args.fleet_router)
        if args.trainer:
            trainer_info()
        if args.step:
            step_info()
        if args.monitor:
            monitor_info(args.monitor)
        if args.serve:
            serve_info(args.serve)
        if args.cache:
            cache_info(args.cache)
        if args.tenant:
            tenant_info(args.tenant)
        if args.checkpoints:
            checkpoints_info(args.checkpoints)
        if args.trace:
            trace_info()
        if args.telemetry:
            telemetry_info()
        print()
        return
    python_info()
    platform_info()
    deps_info()
    framework_info(device_check=not args.no_device_check)
    if args.telemetry:
        telemetry_info()
    env_info()
    print()


if __name__ == "__main__":
    main()
