"""Environment-variable configuration registry.

Reference: the ~102 documented ``MXNET_*`` env vars
(/root/reference/docs/static_site/src/pages/api/faq/env_var.md) read via
``dmlc::GetEnv`` across the codebase.  The TPU-native runtime needs far
fewer knobs (XLA owns scheduling/fusion/memory planning), but the ones
that DO exist are declared here in one typed registry — so ``mx.config.
describe()`` is the env_var.md equivalent and unknown ``MXNET_*`` vars
can be flagged instead of silently ignored.
"""
from __future__ import annotations

import os

from .base import get_env

__all__ = ["ENV_VARS", "describe", "current", "check_unknown"]

# name -> (type, default, doc)
ENV_VARS = {
    "MXNET_HOME": (
        str, "~/.mxnet",
        "Cache root for pretrained weights and datasets "
        "(model_zoo/model_store.py; reference base.data_dir())."),
    "MXNET_ENGINE_TYPE": (
        str, "ThreadedEnginePerDevice",
        "Accepted for reference compatibility (engine.py facade); device "
        "scheduling is XLA/PJRT's regardless."),
    "MXNET_KVSTORE_BUCKET_BYTES": (
        int, 4 << 20,
        "Collective kvstore gradient-fusion bucket size in bytes "
        "(kvstore/collective.py; replaces MXNET_KVSTORE_BIGARRAY_BOUND)."),
    "MXNET_MULTI_TENSOR": (
        bool, True,
        "Multi-tensor fused optimizer apply in the imperative Trainer "
        "(optimizer/multi_tensor.py): one jitted, buffer-donated update "
        "program per parameter group per step.  Set 0 to force the "
        "classic per-parameter eager updates (automatic for row_sparse "
        "grads and non-fusable optimizers)."),
    "MXNET_TPU_NO_NATIVE": (
        bool, False,
        "Disable the C++ native host runtime (pure-python fallbacks for "
        "recordio/jpeg/loader)."),
    "MXNET_DIST_COORDINATOR": (
        str, None,
        "host:port rendezvous address; set by tools/launch.py — presence "
        "triggers jax.distributed.initialize at import."),
    "MXNET_DIST_NUM_WORKERS": (
        int, None, "World size for the process group (tools/launch.py)."),
    "MXNET_DIST_RANK": (
        int, None, "This process's rank (tools/launch.py)."),
    "MXNET_DIST_COLLECTIVE_TIMEOUT": (
        float, 0.0,
        "Deadline (seconds) on collective dispatch (gradient pushpull, "
        "init broadcast): a dead peer raises a transient-classified "
        "DistTimeout into the supervisor instead of hanging this rank "
        "forever (dist/timeouts.py; 0 = no deadline).  Arm it on every "
        "multi-host run."),
    "MXNET_DIST_MEMBER_DIR": (
        str, None,
        "Shared membership directory (exported by tools/launch.py): "
        "rank heartbeats, world generation records, and the "
        "first-writer-wins world-stop flag live here "
        "(dist/membership.py FileKV backend)."),
    "MXNET_DIST_HEARTBEAT_SECONDS": (
        float, 2.0,
        "Interval of each rank's background membership heartbeat."),
    "MXNET_DIST_DEAD_AFTER_SECONDS": (
        float, 10.0,
        "Heartbeat staleness bound: a rank silent this long is "
        "reported dead by Membership.alive()/dead_ranks()."),
    "MXNET_DIST_BARRIER_TIMEOUT": (
        float, 20.0,
        "Pod checkpoint barrier bound: how long rank 0 waits for all "
        "ranks' shard acks before declaring the pod commit torn (and "
        "non-zero ranks wait for the pod marker; dist/podckpt.py).  "
        "Under a pending preemption the wait is additionally clipped "
        "to the remaining grace budget; keep this below "
        "MXNET_PREEMPT_GRACE_SECONDS and launch.py --term-grace so an "
        "emergency publish can finish before the SIGKILL."),
    "MXNET_DIST_ATTEMPT": (
        int, 0,
        "World launch attempt, exported by tools/launch.py --restarts; "
        "pins the membership generation deterministically across "
        "whole-world restarts."),
    "MXNET_DIST_WORLD_NONCE": (
        str, None,
        "Unique (launcher, attempt) token exported by tools/launch.py; "
        "Membership.join matches it exactly so a reused member dir "
        "never hands a rank a stale previous-incarnation world "
        "record."),
    "MXNET_PROFILER_AUTOSTART": (
        bool, False,
        "Start the profiler at import (reference env_var.md)."),
    "MXNET_TRACE_DISABLE": (
        bool, False,
        "Disable mx.trace recording (spans still feed telemetry "
        "histograms; the flight-recorder ring stops filling)."),
    "MXNET_TRACE_RING_EVENTS": (
        int, 8192,
        "Flight-recorder capacity: the last N trace events kept in "
        "memory for dump-on-demand/-crash/-anomaly (trace/core.py)."),
    "MXNET_TRACE_DUMP_DIR": (
        str, None,
        "Where flight-record dumps (mxtrace-<pid>-<reason>-*.json) and "
        "watchdog stack reports land (default <tempdir>/mxnet_trace)."),
    "MXNET_TRACE_DUMP_ON_CRASH": (
        bool, True,
        "Dump the flight record from sys/threading excepthook on an "
        "uncaught exception (trace/export.py)."),
    "MXNET_TRACE_DUMP_AT_EXIT": (
        bool, False,
        "Also dump the flight record at normal interpreter exit."),
    "MXNET_TRACE_DUMP_MIN_SECONDS": (
        float, 30.0,
        "Rate limit between anomaly-triggered dumps of the same reason "
        "(slow_step / deadline_burst / hang / straggler)."),
    "MXNET_TRACE_DUMP_MAX_EVENTS": (
        int, 0,
        "Cap chrome-trace dumps at the newest N ring events (0 = the "
        "full ring); a clipped dump records truncated_events in its "
        "mx.trace.dump metadata block."),
    "MXNET_TRACE_SLOW_STEP_FACTOR": (
        float, 3.0,
        "Dump the flight record when a trainer step exceeds this "
        "factor x the trailing p99 step latency (0 disables)."),
    "MXNET_TRACE_DEADLINE_BURST": (
        int, 8,
        "Serve deadline misses within MXNET_TRACE_DEADLINE_WINDOW that "
        "trigger a flight-record dump (0 disables)."),
    "MXNET_TRACE_DEADLINE_WINDOW": (
        float, 5.0,
        "Sliding window (seconds) for the serve deadline-miss burst "
        "detector."),
    "MXNET_TRACE_WATCHDOG": (
        bool, False,
        "Arm the hang watchdog lazily on the first watched scope "
        "(trainer step / serve dispatch / checkpoint commit): no "
        "progress for MXNET_TRACE_WATCHDOG_SECONDS dumps all-thread "
        "stacks + the flight record."),
    "MXNET_TRACE_WATCHDOG_SECONDS": (
        float, 120.0,
        "Default no-progress timeout per watched scope."),
    "MXNET_OBS": (
        bool, False,
        "Arm mx.obs, the fleet-wide observability plane: per-rank "
        "telemetry snapshots published into the membership KV "
        "(heartbeat-piggybacked), merged fleet views (/fleetz, "
        "diagnose --fleet), straggler detection, SLO burn rates, and "
        "per-step attribution (obs/).  Off = one cached flag check "
        "per hook."),
    "MXNET_OBS_PUBLISH_SECONDS": (
        float, 5.0,
        "Minimum interval between per-rank obs payload publishes into "
        "the membership KV."),
    "MXNET_OBS_STRAGGLER_FACTOR": (
        float, 2.0,
        "Flag a rank as a straggler when its step p50 exceeds this "
        "factor x the median p50 of its peers (needs >= 2 ranks; one "
        "obs_stragglers_total count + one rate-limited "
        "reason=straggler flight-record dump per episode; 0 "
        "disables)."),
    "MXNET_OBS_SLO_FAST_SECONDS": (
        float, 300.0,
        "Fast burn-rate window for SLO evaluation (the standard SRE "
        "multi-window formulation; PAGE/WARN require BOTH windows "
        "over threshold)."),
    "MXNET_OBS_SLO_SLOW_SECONDS": (
        float, 3600.0,
        "Slow burn-rate window for SLO evaluation."),
    "MXNET_OBS_ATTRIBUTION": (
        str, None,
        "Append one JSON line of per-step time attribution (phase "
        "shares, data-wait, MFU estimate) to this path."),
    "MXNET_OBS_PEAK_TFLOPS": (
        float, None,
        "Per-chip peak TFLOP/s for the attribution MFU estimate, "
        "overriding the built-in device-kind table (unknown kinds "
        "report mfu null)."),
    "MXNET_OBS_REGRESSION_PCT": (
        float, 10.0,
        "tools/bench_gate.py failure threshold: fresh bench metrics "
        "worse than baseline by more than this percentage (trimmed "
        "mean) exit non-zero."),
    "MXNET_MONITOR": (
        bool, False,
        "Arm mx.monitor training-health numerics: one fused stat "
        "reduction program per multi-tensor parameter group per step "
        "(grad/weight norms, max|x|, nonfinite counts) feeding "
        "telemetry gauges, the divergence detector, and the nonfinite "
        "sentinel (monitor/)."),
    "MXNET_MONITOR_SENTINEL": (
        str, "warn",
        "Nonfinite-gradient sentinel policy: warn (async, log only), "
        "skip_step (drop the whole step before any state mutates — "
        "bit-identical to never calling step()), raise (MXNetError at "
        "the first bad step), off.  Gates the imperative update path "
        "only; inert (with a warning) under update_on_kvstore=True, "
        "where the kvstore applies updates itself."),
    "MXNET_MONITOR_STREAM": (
        str, None,
        "Append one JSON line of per-group health stats per observed "
        "step to this path (the numerics post-mortem artifact of a "
        "run)."),
    "MXNET_MONITOR_INTERVAL": (
        int, 1,
        "Observe every Nth trainer step (1 = every step; the sentinel "
        "only gates observed steps)."),
    "MXNET_MONITOR_RING": (
        int, 256,
        "Bounded host-fetch ring capacity: stat entries awaiting the "
        "async publisher; oldest are dropped (monitor_dropped_total) "
        "under pressure so Trainer.step never blocks."),
    "MXNET_MONITOR_SPIKE_FACTOR": (
        float, 10.0,
        "Divergence detector: dump when the global grad norm exceeds "
        "this factor x the trailing-window max (0 disables)."),
    "MXNET_MONITOR_SPIKE_WINDOW": (
        int, 64,
        "Trailing window length (observed steps) for the grad-norm "
        "spike detector."),
    "MXNET_MONITOR_PLATEAU_WINDOW": (
        int, 0,
        "Loss observations without a new best before a loss_plateau "
        "divergence dump (0 disables; fed via monitor.observe_loss / "
        "the estimator TrainingHealthHandler)."),
    "MXNET_FAULTS": (
        str, None,
        "Deterministic fault plan for mx.resilience drills: comma-"
        "separated site@key[:kind][*count] entries (sites: "
        "trainer_step / collective / checkpoint_commit / "
        "checkpoint_marker / compile_commit / serve_dispatch / "
        "serve_poison / step_capture / data_read; kinds: transient / "
        "io / fatal / "
        "abort).  Faults fire by (site, sequence), so every drill "
        "replays identically (resilience/inject.py).  The "
        "serve_dispatch and serve_poison sites also fire on the "
        "serve decode plane: a poisoned request id evicts that "
        "SEQUENCE alone from the continuous batch (pages reclaimed, "
        "batch-mates keep decoding).  The "
        "step_capture site fires twice per captured step lifecycle: "
        "at capture/build time (poisons the capture -> clean stitched "
        "fallback) and at program dispatch (exercises the supervisor "
        "rewind path)."),
    "MXNET_SHARD_DP": (
        int, 0,
        "Data-parallel axis size for the auto-configured mx.shard "
        "GlobalMesh (0 = unset; with MXNET_SHARD_MDL also unset, no "
        "mesh is auto-built).  When set, Trainer(zero=...) and mesh-"
        "aware step capture adopt a GlobalMesh(dp=N) over the global "
        "device list without any code change (shard/mesh.py)."),
    "MXNET_SHARD_MDL": (
        int, 0,
        "Optional inner model-parallel axis size of the auto-"
        "configured GlobalMesh (0/1 = pure data parallelism).  The "
        "mdl axis is carved from the fast (ICI) end of the device "
        "order."),
    "MXNET_SHARD_DATA": (
        str, "dp",
        "Input-batch placement inside a mesh-captured step program: "
        "'dp' (default) splits the global batch along the dp axis — "
        "each replica's slice feeds its devices; 'replicate' gives "
        "every replica the whole batch (drill/debug mode).  A batch "
        "not divisible by dp falls back to replicate."),
    "MXNET_DATA_PREFETCH": (
        int, 2,
        "mx.data prefetch ring depth: batches asynchronously staged "
        "onto their device/mesh shardings ahead of the training loop "
        "(data/ring.py; the PERF_PLAN H3 fix).  >= 2 keeps captured-"
        "step dispatch off the H2D critical path."),
    "MXNET_DATA_WORKERS": (
        int, 2,
        "Reader worker threads per host in mx.data.StreamLoader "
        "(shard read + decode + batchify; data/reader.py).  Raise it "
        "when data_ring_stalls_total climbs."),
    "MXNET_DATA_ALLOW_UNSHARDED": (
        bool, False,
        "Allow legacy whole-dataset iterators (io.ImageRecordIter, "
        "contrib.io.DataLoaderIter) in a multi-host world, where each "
        "host would read the FULL dataset and silently duplicate "
        "every sample world-times per epoch.  Off by default: those "
        "iterators raise and name mx.data.StreamLoader instead."),
    "MXNET_STEP_CAPTURE": (
        bool, True,
        "Kill switch for mx.step whole-program training-step capture: "
        "0 makes every StepProgram call run the stitched imperative "
        "sequence (fwd/bwd/allreduce/apply as separate programs) "
        "instead of the one donated whole-step XLA program "
        "(step/capture.py).  Checked per call."),
    "MXNET_STEP_REMAT": (
        str, "off",
        "Rematerialization policy inside the captured step program: "
        "off (default) keeps activations live for backward; all wraps "
        "forward+loss in one jax.checkpoint; blocks checkpoints each "
        "direct-child Block boundary (best effort).  Trades backward "
        "recompute for activation memory (step/capture.py)."),
    "MXNET_PREEMPT_INSTALL": (
        bool, False,
        "Arm the SIGTERM preemption handler at import: the supervisor "
        "stops at the next step boundary, flushes an emergency "
        "checkpoint, drains serve, and exits with "
        "MXNET_PREEMPT_EXIT_CODE (resilience/preempt.py)."),
    "MXNET_PREEMPT_GRACE_SECONDS": (
        float, 30.0,
        "Grace budget after SIGTERM: shutdown hooks are skipped (the "
        "emergency checkpoint is not) once it is exhausted; a second "
        "SIGTERM exits immediately."),
    "MXNET_PREEMPT_EXIT_CODE": (
        int, 85,
        "Exit status of a clean preemption shutdown — distinct from "
        "crash codes so the pod scheduler knows to simply resume."),
    "MXNET_RESTART_BUDGET": (
        int, 3,
        "Supervisor restart budget: max transient-failure restarts "
        "within MXNET_RESTART_WINDOW_STEPS (resilience/supervisor.py)."),
    "MXNET_RESTART_WINDOW_STEPS": (
        int, 0,
        "Sliding step window the restart budget applies over (0 = "
        "whole-run lifetime, the old FaultTolerantRunner semantics)."),
    "MXNET_RESTART_BACKOFF_BASE": (
        float, 1.0,
        "First-restart backoff delay in seconds (doubles per restart, "
        "jittered, capped at MXNET_RESTART_BACKOFF_MAX)."),
    "MXNET_RESTART_BACKOFF_MAX": (
        float, 60.0,
        "Backoff delay ceiling between supervisor restarts."),
    "MXNET_HEALTH_TIMEOUT": (
        float, 60.0,
        "Wall-clock bound on the post-failure device health probe; a "
        "hung transfer reports 'error: timeout' instead of blocking "
        "the supervisor forever."),
    "MXNET_SERVE_BREAKER_THRESHOLD": (
        int, 5,
        "Consecutive failed dispatches that open a serve bucket's "
        "circuit breaker (serve/breaker.py; <= 0 disables breakers)."),
    "MXNET_SERVE_BREAKER_COOLDOWN": (
        float, 30.0,
        "Seconds a tripped bucket stays quarantined before the "
        "half-open trial dispatch."),
    "MXNET_SERVE_RETRY_AFTER": (
        float, 1.0,
        "Retry-After seconds the HTTP front-end advertises on "
        "overload 503 responses."),
    "MXNET_SERVE_DECODE_PAGE_SIZE": (
        int, 16,
        "Token slots per KV-cache page of the serve decode plane "
        "(serve/kvcache.py): every sequence's context is stored as "
        "fixed-size pages addressed through its page table."),
    "MXNET_SERVE_DECODE_POOL_PAGES": (
        int, 256,
        "Total pages in the decode plane's device-resident KV pool; "
        "admission reserves a sequence's whole worst case up front, so "
        "this bounds concurrent context tokens (pages x page_size)."),
    "MXNET_SERVE_DECODE_MAX_LIVE": (
        int, 8,
        "Max sequences decoding concurrently in the running batch "
        "(serve/decode.py DecodeScheduler); also caps the decode "
        "batch-bucket table the runner pre-compiles."),
    "MXNET_SERVE_DECODE_MAX_NEW": (
        int, 64,
        "Default and hard cap on generated tokens per decode request "
        "(requests may ask for less; more is clamped)."),
    "MXNET_SERVE_DECODE_STREAM": (
        bool, True,
        "Serve chunked per-token streaming on /predict?stream=1; 0 "
        "forces collect mode (the streamed and collected token "
        "sequences are bit-identical either way)."),
    "MXNET_SERVE_PREFIX_CACHE": (
        bool, False,
        "Enable the radix prefix cache (serve/cache.py): identical "
        "prompt prefixes prefill once per replica and admission "
        "charges only the uncached suffix; cached-prefix output is "
        "bit-identical to cold decode."),
    "MXNET_SERVE_SPEC_K": (
        int, 0,
        "Speculative decoding draft proposal count per round "
        "(serve/spec.py; needs DecodeRunner(draft=...)); 0 means "
        "the built-in default of 4.  Greedy acceptance keeps output "
        "bit-identical to single-step decode."),
    "MXNET_FLEET_PUBLISH_SECONDS": (
        float, 1.0,
        "Min seconds between a replica's discovery-record publishes "
        "into the membership KV (fleet/discovery.py; the publish "
        "rides the membership heartbeat thread)."),
    "MXNET_FLEET_DEAD_AFTER_SECONDS": (
        float, 10.0,
        "Discovery-record age beyond which the fleet router stops "
        "routing to a replica (mirrors the membership liveness rule "
        "MXNET_DIST_DEAD_AFTER_SECONDS)."),
    "MXNET_FLEET_REFRESH_SECONDS": (
        float, 0.5,
        "Min seconds between the fleet router's discovery refreshes "
        "(replica records + draining flags + poison verdicts are "
        "re-read from the KV at most this often)."),
    "MXNET_FLEET_RETRIES": (
        int, 2,
        "Max mid-request re-routes (zero-drop failover replays) the "
        "fleet router attempts after replica deaths before failing "
        "the request."),
    "MXNET_FLEET_SATURATION": (
        float, 1.0,
        "Queue-fill fraction at which a replica counts as saturated; "
        "when EVERY routable replica is saturated the router "
        "rejects early with 503 + Retry-After instead of queueing."),
    "MXNET_FLEET_UPSTREAM_TIMEOUT": (
        float, 30.0,
        "Socket timeout in seconds for router->replica upstream "
        "requests (connect and per-read)."),
    "MXNET_FLEET_SLO_TARGET_S": (
        float, 0.25,
        "Latency target (seconds) of the fleet_router_p99_ms SLO the "
        "router registers with mx.obs when the obs plane is armed."),
    "MXNET_FLEET_ROLE": (
        str, "both",
        "Pool role a serve replica registers under when none is "
        "passed explicitly: both | prefill | decode (disaggregated "
        "prefill/decode pools; fleet/pools.py)."),
    "MXNET_TENANT": (
        bool, False,
        "Arm the mx.tenant multi-tenant serving plane: batched LoRA "
        "adapter multiplexing (one compiled decode program serves "
        "mixed-adapter batches), virtual-time weighted fair queuing "
        "before admission, and per-tenant quotas/isolation "
        "(tenant/)."),
    "MXNET_TENANT_SLOTS": (
        int, 8,
        "Adapter bank capacity: how many LoRA adapters are "
        "device-resident per decode runner (tenant/adapters.py).  "
        "Changing it re-specializes the decode programs (one-time "
        "recompile, then hot add/remove swaps slots with zero "
        "recompiles)."),
    "MXNET_TENANT_MAX_RANK": (
        int, 8,
        "Max LoRA rank the adapter bank accepts; lower-rank adapters "
        "are zero-padded into the bank (tenant/adapters.py)."),
    "MXNET_TENANT_DEFAULT_WEIGHT": (
        float, 1.0,
        "WFQ weight assigned to tenants registered without an "
        "explicit weight, and charged to un-tenanted (base-model) "
        "traffic so it cannot starve tenants (tenant/fairsched.py)."),
    "MXNET_TENANT_MAX_LIVE": (
        int, 0,
        "Default per-tenant cap on concurrently decoding sequences "
        "(0 = unlimited); exceeding it is a per-tenant 503 + "
        "Retry-After, never head-of-line blocking (tenant/quota.py)."),
    "MXNET_TENANT_MAX_PAGES": (
        int, 0,
        "Default per-tenant cap on reserved KV-cache pages (0 = "
        "unlimited), enforced against the PagePool reservation at "
        "admission (tenant/quota.py)."),
    "MXNET_TENANT_QUEUE_DEPTH": (
        int, 16,
        "Default per-tenant waiting-queue depth; a tenant whose "
        "backlog reaches it gets 503 + Retry-After while other "
        "tenants keep flowing (tenant/quota.py)."),
    "MXNET_TELEMETRY_DISABLE": (
        bool, False,
        "Disable the runtime telemetry registry (mx.telemetry); hooks "
        "reduce to one boolean check."),
    "MXNET_TELEMETRY_LOG_INTERVAL": (
        float, 0.0,
        "Seconds between periodic 'telemetry k=v ...' log lines "
        "(mxnet_tpu.telemetry logger; 0 disables)."),
    "MXNET_COMPILE_CACHE": (
        bool, False,
        "Enable the mx.compile persistent compilation cache: hybridize "
        "builds consult/commit serialized XLA executables on disk "
        "(compile/cache.py).  Also implied by setting "
        "MXNET_COMPILE_CACHE_DIR."),
    "MXNET_COMPILE_CACHE_DIR": (
        str, None,
        "Directory for persistent compiled artifacts (default "
        "<MXNET_HOME>/compile_cache).  Setting it enables the cache."),
    "MXNET_COMPILE_CACHE_MAX_BYTES": (
        int, 1 << 30,
        "LRU size cap for the compile cache; least-recently-loaded "
        "entries are evicted after each commit (<=0 disables the cap)."),
    "MXNET_EAGER_VJP_CACHE": (
        bool, True,
        "Reuse jitted forward+vjp pairs for repeated eager recorded-op "
        "signatures (ops/registry.py); 0 retraces jax.vjp every call."),
    "MXNET_EAGER_VJP_CACHE_MAX_ELEMS": (
        int, 1 << 16,
        "Input-size ceiling (total elements) for the eager vjp cache; "
        "above it the cached recompute-backward would cost more device "
        "time than the retrace it saves."),
    "MXNET_NP_FALLBACK_LOG_VERBOSE": (
        bool, True,
        "Warn (once per name) when mx.np resolves a function via host "
        "numpy instead of jax.numpy — host fallbacks run off-device and "
        "outside autograd (numpy/__init__.py)."),
    "MXNET_STORAGE_FALLBACK_LOG_VERBOSE": (
        bool, False,
        "Log when a sparse op densifies (the storage-fallback path, "
        "ndarray/sparse.py)."),
    "MXNET_TEST_LARGE": (
        bool, False,
        "Run the gated large-tensor nightly checks (2^31-element shapes; "
        "tests/python/unittest/test_large_array.py)."),
}


def describe():
    """Human-readable table of every supported env var (the env_var.md
    equivalent)."""
    lines = ["%-38s %-8s %-22s %s" % ("Variable", "Type", "Default", "Doc")]
    for name, (typ, default, doc) in sorted(ENV_VARS.items()):
        lines.append("%-38s %-8s %-22s %s"
                     % (name, typ.__name__, repr(default), doc))
    return "\n".join(lines)


def current():
    """{name: effective value} for every registered var."""
    return {name: get_env(name, typ, default)
            for name, (typ, default, _doc) in ENV_VARS.items()}


def check_unknown(warn=True):
    """Return MXNET_* vars set in the environment but NOT registered —
    typo'd or reference-only knobs that silently do nothing here."""
    unknown = sorted(k for k in os.environ
                     if k.startswith("MXNET_") and k not in ENV_VARS)
    if unknown and warn:
        import warnings

        warnings.warn(
            "unrecognized MXNET_* environment variables (no effect in "
            "mxnet_tpu): %s — see mxnet_tpu.config.describe()" % unknown,
            stacklevel=2)
    return unknown
