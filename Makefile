# Native host runtime (src/native): recordio, threaded dependency engine,
# pooled allocator, libjpeg image pipeline.  `make native` builds the
# shared library the mxnet_tpu.native ctypes bindings load (the bindings
# also build it on demand at import).
CXX ?= g++
CXXFLAGS ?= -std=c++17 -O2 -fPIC -Wall -pthread
LDLIBS ?= -ljpeg -lz

SRCS := $(wildcard src/native/*.cc)
SO := build/libmxtpu_native.so

.PHONY: native test cpptest telemetry-smoke checkpoint-smoke serve-smoke \
	decode-smoke compile-cache-smoke trainer-smoke step-smoke \
	trace-smoke monitor-smoke faults-smoke dist-faults-smoke \
	zero-smoke shard-smoke data-smoke obs-smoke \
	fleet-smoke cache-smoke tenant-smoke smoke-all clean

native: $(SO)

$(SO): $(SRCS) $(wildcard src/native/*.h)
	@mkdir -p build
	$(CXX) $(CXXFLAGS) -shared $(SRCS) -o $@ $(LDLIBS)

# in-process C++ unit tests (reference tests/cpp/ engine/storage suites)
CPPTEST := build/test_native
cpptest: $(CPPTEST)
	$(CPPTEST)

$(CPPTEST): tests/cpp/test_native_main.cc $(SRCS) $(wildcard src/native/*.h)
	@mkdir -p build
	$(CXX) $(CXXFLAGS) tests/cpp/test_native_main.cc $(SRCS) -o $@ $(LDLIBS)

# cpptest runs inside the pytest suite (test_cpp_native.py)
test: native
	python -m pytest tests/ -q

# fast telemetry smoke (tier-1 exercises the mx.telemetry registry,
# the cross-stack instrumentation hooks, and the profiler Counter fix)
telemetry-smoke:
	JAX_PLATFORMS=cpu python -m pytest \
	  tests/python/unittest/test_telemetry.py \
	  tests/python/unittest/test_profiler.py -q -m 'not slow'

# mx.checkpoint crash-consistency smoke: save -> corrupt one shard ->
# validate flags + quarantines it -> restore falls back to the previous
# good step; then the full pytest suite for the subsystem
checkpoint-smoke:
	JAX_PLATFORMS=cpu python tools/checkpoint_smoke.py
	JAX_PLATFORMS=cpu python -m pytest \
	  tests/python/unittest/test_checkpoint.py \
	  tests/python/unittest/test_elastic.py -q -m 'not slow'

# mx.serve smoke: serve a tiny checkpointed model, concurrent requests
# across 2 shape buckets (<=1 compile per bucket), clean ServerOverloaded
# rejection beyond queue_depth, serve_* metrics in the Prometheus export;
# then the subsystem's pytest suite
serve-smoke:
	JAX_PLATFORMS=cpu python tools/serve_smoke.py
	JAX_PLATFORMS=cpu python -m pytest \
	  tests/python/unittest/test_serve.py -q -m 'not slow'

# mx.serve.decode smoke: paged KV-cache + continuous batching — tiny
# decoder on CPU, concurrent mixed prefill/decode clients (stream +
# collect over HTTP), sequences verifiably join/leave the running batch
# mid-flight, <=1 compile per (bucket, page-config), streamed tokens
# bit-identical to collect mode + X-Request-Id echo, serve_poison drill
# evicts one sequence alone with pages reclaimed, clean drain audits the
# pool to zero; then the subsystem's pytest suite
decode-smoke:
	JAX_PLATFORMS=cpu python tools/decode_smoke.py
	JAX_PLATFORMS=cpu python -m pytest \
	  tests/python/unittest/test_serve_decode.py -q -m 'not slow'

# mx.compile smoke: compile in process A -> process B warm-starts from
# the persistent cache with 0 fresh jax.jit builds (verified through
# cachedop_build / compile_cache_hit telemetry deltas) -> a corrupted
# artifact is quarantined and the run degrades to an in-memory compile;
# then the subsystem's pytest suite
compile-cache-smoke:
	JAX_PLATFORMS=cpu python tools/compile_cache_smoke.py
	JAX_PLATFORMS=cpu python -m pytest \
	  tests/python/unittest/test_compile_cache.py -q -m 'not slow'

# multi-tensor Trainer smoke: 3-step CPU train asserting ONE fused
# update program per parameter group (no per-step retraces), zero eager
# fallbacks, fused-vs-eager parity, and the collective bucket-count
# bound; then the subsystem's pytest suite
trainer-smoke:
	JAX_PLATFORMS=cpu python tools/trainer_smoke.py
	JAX_PLATFORMS=cpu python -m pytest \
	  tests/python/unittest/test_trainer_fused.py -q -m 'not slow'

# mx.trace smoke: traced CPU train step + serve request (>=4 nested
# phase spans each, one trace id, distinct thread tracks), parseable
# Perfetto dump, X-Request-Id echo, watchdog dry-run writing stacks +
# flight record; then the subsystem's pytest suite
trace-smoke:
	JAX_PLATFORMS=cpu python tools/trace_smoke.py
	JAX_PLATFORMS=cpu python -m pytest \
	  tests/python/unittest/test_trace.py -q -m 'not slow'

# mx.monitor smoke: 5-step CPU train with an Inf gradient injected on
# step 3 under MXNET_MONITOR_SENTINEL=skip_step — the step is skipped
# bit-identically, exactly one divergence flight-record dump names the
# offending group, the JSONL health stream parses, and stat programs
# build once per group (zero per-step retraces); then the subsystem's
# pytest suite
monitor-smoke:
	JAX_PLATFORMS=cpu python tools/monitor_smoke.py
	JAX_PLATFORMS=cpu python -m pytest \
	  tests/python/unittest/test_monitor.py -q -m 'not slow'

# mx.step whole-step capture: capture -> ONE executable (no cachedop/
# fused-group/monitor-stat builds during captured steps), bit-identical
# params + optimizer state vs the stitched path, skip_step inside the
# program mutates nothing, and a fault at the step_capture site
# degrades cleanly to a stitched (still applied) step; then the
# subsystem's pytest suite
step-smoke:
	JAX_PLATFORMS=cpu python tools/step_smoke.py
	JAX_PLATFORMS=cpu python -m pytest \
	  tests/python/unittest/test_step_capture.py -q -m 'not slow'

# mx.resilience fault drills: writer killed mid-commit -> recover;
# collective fault mid-run -> backoff + bit-identical resume; real
# SIGTERM -> emergency checkpoint -> cross-process bit-identical
# resume; save on 4 virtual devices -> restore-with-resharding on 2;
# then the subsystem's pytest suite
faults-smoke:
	JAX_PLATFORMS=cpu python tools/faults_smoke.py
	JAX_PLATFORMS=cpu python -m pytest \
	  tests/python/unittest/test_resilience.py \
	  tests/python/unittest/test_elastic.py -q -m 'not slow'

# mx.shard ZeRO-2/3 global-mesh drills (single process, 8 virtual CPU
# devices): ZeRO-3 captured step = ONE program with 10-step bit parity
# vs the unsharded mesh reference and ~1/4 per-device param+state
# residency; sharded pod checkpoint saved at dp=4 resumes on dp=2
# bit-identically; injected collective hang -> DistTimeout ->
# supervisor resume from the pod checkpoint; then the subsystem's
# pytest suite
zero-smoke:
	JAX_PLATFORMS=cpu python tools/zero_smoke.py
	JAX_PLATFORMS=cpu python -m pytest \
	  tests/python/unittest/test_shard.py -q -m 'not slow'

# mx.shard phase 2 model-parallel drills (single process, 8 virtual
# CPU devices): dp=2 x mdl=2 gather-mode captured step = ONE program
# with 10-step bit parity vs the mdl=1 mesh reference and ~1/2 (x
# zero3: ~1/4) per-device param residency + priced mdl all-gather;
# mid-run stage kill fences the 1F1B pipeline step at the membership
# envelope before any donated buffer is consumed; mdl=2 sharded
# decode emits the byte-identical token stream with half-resident KV
# pages and zero compiles after warm_up; then the subsystem's pytest
# suite
shard-smoke:
	JAX_PLATFORMS=cpu python tools/shard_smoke.py
	JAX_PLATFORMS=cpu python -m pytest \
	  tests/python/unittest/test_shard_mp.py -q -m 'not slow'

# mx.data streaming input pipeline drills: loader-fed captured-step
# loop with the prefetch ring armed runs within 5% of the pre-staged
# reference (batch-wait p99 <= 5% of step, telemetry-asserted — the
# PERF_PLAN H3 bound); mid-epoch trainer-checkpoint resume replays
# the exact remaining sample order; injected data_read io fault
# retried with the stream intact; preemption drain reaps loader
# threads AND gluon worker processes; 2-rank launch.py world killed
# mid-epoch relaunches and resumes the stream bit-identically from
# the max-common-committed pod step; then the subsystem's pytest
# suite
data-smoke:
	JAX_PLATFORMS=cpu python tools/data_smoke.py
	JAX_PLATFORMS=cpu python -m pytest \
	  tests/python/unittest/test_data_stream.py -q -m 'not slow'

# mx.dist coordinated fault drills (2 local CPU processes over
# tools/launch.py): rank SIGKILLed mid-step -> DistTimeout within the
# deadline -> whole-world restart resumes bit-identically from the max
# common committed pod step; SIGTERM to ONE rank -> every rank
# emergency-commits the SAME step + exits with the preempt code ->
# shrink-world (2->1) lossless resume; torn pod commit (rank killed
# before its shard ack) never selected at restore; then the subsystem's
# pytest suite
dist-faults-smoke:
	JAX_PLATFORMS=cpu python tools/dist_faults_smoke.py
	JAX_PLATFORMS=cpu python -m pytest \
	  tests/python/unittest/test_dist_ft.py -q -m 'not slow'

# mx.obs observability-plane smoke: 2-rank fleet drill (cross-rank
# aggregation merged on BOTH ranks + seeded slow rank fires exactly one
# straggler episode), serve SLO burn-rate OK -> PAGE -> OK round trip
# (/healthz degraded + /statz + /fleetz + gauge agree), captured-step
# attribution JSONL schema check (span shares + FLOPs + MFU), and the
# bench_gate regression drill (fails a seeded 30% slowdown, passes an
# unchanged run); then the subsystem's pytest suite
obs-smoke:
	JAX_PLATFORMS=cpu python tools/obs_smoke.py
	JAX_PLATFORMS=cpu python -m pytest \
	  tests/python/unittest/test_obs.py -q -m 'not slow'

# mx.fleet smoke: disaggregated prefill/decode handoff round-trip
# (byte-identical two-hop stream, corrupt blob rejected by checksum,
# pools empty + scrub-clean after), then a 3-replica CPU world under
# tools/launch.py: fleet.rollout() drains every replica in turn under
# client load with ZERO rejects, and a replica SIGKILLed mid-stream
# still yields a byte-identical client stream (router re-prefills on a
# survivor, splices at the emitted-token cursor); then the subsystem's
# pytest suite
fleet-smoke:
	JAX_PLATFORMS=cpu python tools/fleet_smoke.py
	JAX_PLATFORMS=cpu python -m pytest \
	  tests/python/unittest/test_fleet.py -q -m 'not slow'

# mx.serve.cache smoke: per-token-cost plane — cached-prefix decode
# bit-identical to cold and speculative decode bit-identical to
# single-step with ZERO compiles as sessions churn; serve_cache /
# spec_verify drills degrade one sequence alone; then a 2-replica CPU
# world shares one 2k-token system prompt that prefills exactly ONCE
# fleet-wide (router prefix affinity, telemetry-asserted), the hot
# replica is SIGKILLed mid-stream and the survivor repopulates its own
# cache with a byte-identical client stream; then the subsystem's
# pytest suite
cache-smoke:
	JAX_PLATFORMS=cpu python tools/cache_smoke.py
	JAX_PLATFORMS=cpu python -m pytest \
	  tests/python/unittest/test_serve_cache.py -q -m 'not slow'

# mx.tenant smoke: multi-tenant serving plane — a mixed 8-adapter
# batch decodes on the ONE program warm-up built (compile delta 0
# across adapter hot add/remove), gathered-LoRA output bit-identical
# to the dense-merged per-tenant reference, WFQ admission honours
# weights exactly, and the isolation drill (NaN'ing adapter + quota
# buster) degrades each offending tenant ALONE with batch-mate
# streams byte-identical; then the subsystem's pytest suite
tenant-smoke:
	JAX_PLATFORMS=cpu python tools/tenant_smoke.py
	JAX_PLATFORMS=cpu python -m pytest \
	  tests/python/unittest/test_tenant.py -q -m 'not slow'

# every subsystem smoke in sequence — the one-command pre-flight before
# a chip run.  Ordered CHEAP-FIRST (approx wall time on the CPU
# container in the comment column) so a broken build fails in seconds,
# not after the multi-process drills.  Runs as ONE shell loop so the
# first failing smoke's exit code propagates even under `make -k`
# (prerequisite-list smoke-all + -k used to keep going and could mask
# an earlier failure behind a later green target).
SMOKES := \
	telemetry-smoke \
	trace-smoke \
	compile-cache-smoke \
	trainer-smoke \
	monitor-smoke \
	checkpoint-smoke \
	step-smoke \
	serve-smoke \
	obs-smoke \
	zero-smoke \
	shard-smoke \
	decode-smoke \
	tenant-smoke \
	cache-smoke \
	faults-smoke \
	data-smoke \
	fleet-smoke \
	dist-faults-smoke
# approx wall time:        telemetry ~15s, trace ~25s, compile-cache
# ~35s, trainer ~35s, monitor ~40s, checkpoint ~45s, step ~45s,
# serve ~60s, obs ~75s, zero ~90s, shard ~90s,
# decode ~100s, tenant ~100s, cache ~2min, faults ~2min, data ~3min,
# fleet ~3min, dist-faults ~4min (multi-process drills last; total
# ~20min cold)
smoke-all:
	@set -e; for t in $(SMOKES); do \
	  echo "== $$t =="; \
	  $(MAKE) --no-print-directory $$t || exit $$?; \
	done; echo "smoke-all OK ($(words $(SMOKES)) smokes)"

# suite summary artifact (TESTS_r{N}.json) — round-2 advisor contract
test-report:
	python tools/test_report.py TESTS_r04.json

# LoC diagnostic — the EXACT command the round metrics use (round-2
# advisor asked for reproducibility; excludes tests, includes native src)
loc:
	@find mxnet_tpu src include bench.py __graft_entry__.py tools \
	  benchmark \( -name '*.py' -o -name '*.cc' -o -name '*.h' \) \
	  -not -path '*test*' | xargs wc -l | tail -1
	@echo "tests:" && find tests -name '*.py' -o -name '*.cc' \
	  | xargs wc -l | tail -1

clean:
	rm -rf build
