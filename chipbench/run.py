#!/usr/bin/env python3
"""chipbench/run.py — one cell of the benchmark, one run, one process.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of ``workloads`` in BENCHMARK.json) is a configuration
(``configs/<config>.json`` + its builder ``configs/<builder>.py`` + its plain
reference ``reference/<reference>.py``) under a traffic mix
(``traffic/<traffic>.json``), judged against ``limits/<cell>.json``; each
per-layer metric is a reader ``metrics/<name>.py``.  Everything is found by
the name in BENCHMARK.json: a new cell, configuration or metric adds files
and edits none.

Every cell drives ``parallel.FusedTrainer.step(x, y)`` in the loop MXNet
users write (the loss fetched every ``log_every`` steps).  Set-up makes
weights and a pool of batches on the device from ``--seed``, builds the
trainer, drives its first steps (they compile, warm up, and are what the
plain reference follows afterwards), and hands the same trainer to the
measured window.  The window lasts ``--seconds``, or until the traffic's
``min_steps`` steps are done if that is later (how a cell with a long step
gets enough samples under its percentile); a traced run stops at
``trace_steps``.  After the window: counts of compiles, where the state
lives, peak memory; then the trainer is freed and the reference runs.

A run that finds no TPU, or fewer chips than the cell needs, exits non-zero
and prints no result.  ``--rehearse`` runs a tiny size on whatever backend
JAX has and reports no metric (a CPU number is never a device metric).
"""
from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, as near as Python can see it

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
STEP_SPAN, FETCH_SPAN = "chipbench.step", "chipbench.fetch"
SAMPLE = 4096  # elements of a leaf's first gradient kept for check.grad_diff
# The fewest samples a whole run's step_ms_p95 may be taken over.
# statistics.quantiles(samples, n=20)[-1] stands at position 0.95 * (n + 1),
# counted from 1: from n = 39 on that is the second largest sample or below,
# so one stalled step of the shared host is not the reading (at n = 29 it is
# the mean of the two largest).
MIN_SAMPLES = 40


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold dots;
    ``kind`` "" is the harness's own directory)."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_%s_%s" % (kind, name.replace(".", "_")), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(workload, rehearse):
    """(manifest, cell, config, traffic, limits) of a cell, by name."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit("chipbench: no cell %r in BENCHMARK.json (have %s)"
                         % (workload, sorted(cells)))
    cell = cells[workload]
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    with open(os.path.join(ROOT, files[cell["config"]])) as f:
        cfg = json.load(f)
    traffic = load_json("traffic", cell["traffic"] + ".json")
    if rehearse:  # the tiny sizes live beside the real ones
        cfg.update(cfg.get("rehearse", {}))
        traffic.update(traffic.get("rehearse", {}))
    limits = load_json("limits", workload + ".json")
    tiny = limits.pop("rehearse", {})
    if rehearse:
        limits.update(tiny)
    return manifest, cell, cfg, traffic, limits


class Compiles:
    """Count of XLA backend compiles (a persistent-cache hit counts too),
    from JAX's own monitoring events.  Copied from chip_smoke.py."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


def off_device(tree, devices):
    """Array leaves of ``tree`` that do not live on exactly ``devices``."""
    import jax

    leaves = [a for a in jax.tree_util.tree_leaves(tree)
              if isinstance(a, jax.Array)]
    if not leaves:
        return ["nothing to check"]
    return [str((a.shape, a.devices())) for a in leaves
            if a.devices() != devices]


def enough_samples(workload, n):
    """A whole run's percentile needs MIN_SAMPLES samples behind it."""
    if n < MIN_SAMPLES:
        raise SystemExit(
            "chipbench: cell %s: step_ms_p95 over %d samples would be all "
            "but the largest of them; a window has to hold %d (a longer "
            "step wants `min_steps` in the cell's traffic file)"
            % (workload, n, MIN_SAMPLES))


def seed_key(seed):
    """A key from any whole number up to 2**63 (the driver's seeds pass
    2**31): two 31-bit words, folded."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(int(seed) >> 31),
                              int(seed) & 0x7FFFFFFF)


def first_gradient_readings(builder, cfg, trainer, names):
    """After step 1, from the trainer's own optimizer state: the first
    gradient as the optimizer got it, a norm and an evenly spaced SAMPLE of
    elements a leaf (on the host)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def read(opt_state):
        g = {n: builder.first_gradient(cfg, opt_state[names[n]])
             .astype(jnp.float32) for n in names if names[n] in opt_state}
        return ({n: jnp.linalg.norm(a) for n, a in g.items()},
                {n: a.ravel()[::max(1, a.size // SAMPLE)][:SAMPLE]
                 for n, a in g.items()})

    norms, samples = jax.device_get(read(trainer.state_dict()["opt_state"]))
    return {n: float(v) for n, v in norms.items()}, samples


def change_readings(builder, cfg, trainer, names, key):
    """After the followed steps: the norm of every leaf's change from the
    seed's weights (made again from ``key``, not kept)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def read(params, key):
        w0 = builder.make_weights(cfg, key)
        return {n: jnp.linalg.norm(
            params[names[n]].astype(jnp.float32) - w0[n]) for n in names}

    out = read(trainer.state_dict()["params"], key)
    return {n: float(v) for n, v in jax.device_get(out).items()}


class Cell:
    """A cell opened for a run: its files, the devices, the mesh."""

    def __init__(self, workload, rehearse=False):
        import jax

        self.workload, self.rehearse = workload, rehearse
        (self.manifest, self.cell, self.cfg, self.traffic,
         self.limits) = load_cell(workload, rehearse)
        self.chips = self.cell["chips"]
        devices = jax.devices()
        if not rehearse and devices[0].platform != "tpu":
            raise SystemExit(
                "chipbench: no TPU (JAX has %s); nothing is measured on "
                "another platform" % devices[0].platform)
        if len(devices) < self.chips:
            raise SystemExit("chipbench: cell %s needs %d chip(s), JAX has "
                             "%d" % (workload, self.chips, len(devices)))
        self.devices = devices[:self.chips]
        self.kind = self.devices[0].device_kind
        peaks = load_json("peaks.json")
        if not rehearse and self.kind.lower() not in peaks:
            raise SystemExit(
                "chipbench: no published peaks for device_kind %r; add it to "
                "chipbench/peaks.json with its source" % self.kind)
        self.peaks = peaks.get(self.kind.lower())

        from mxnet_tpu.compile import jax_cache_dir

        jax_cache_dir()
        self.builder = load_module("configs", self.cfg["builder"])
        self.reference = load_module("reference", self.cfg["reference"])
        self.mesh = None
        if self.traffic.get("mesh"):
            from mxnet_tpu import parallel

            self.mesh = parallel.make_mesh(dict(self.traffic["mesh"]),
                                           devices=self.devices)
        self.n_follow = self.traffic["reference"]["steps"]
        if self.n_follow > self.traffic["pool"]:
            raise SystemExit("chipbench: the reference follows %d steps, the "
                             "pool holds %d batches"
                             % (self.n_follow, self.traffic["pool"]))

    def make(self, seed):
        """(weights, pool of batches) on the device(s) from the seed, in
        one jitted call; batches laid over the mesh as the trainer wants.
        Batch ``i`` (from 1) is ``make_batch`` under ``fold_in(key, i)``,
        traced once for the whole pool, however many it holds."""
        import jax
        import jax.numpy as jnp

        b, cfg, traffic = self.builder, self.cfg, self.traffic
        key, pool = seed_key(seed), traffic["pool"]

        def make_all(key):
            stacked = jax.vmap(lambda i: b.make_batch(
                cfg, traffic, jax.random.fold_in(key, i)))(
                    1 + jnp.arange(pool))
            return (b.make_weights(cfg, jax.random.fold_in(key, 0)),
                    [jax.tree_util.tree_map(lambda a: a[i], stacked)
                     for i in range(pool)])

        if self.mesh is None:
            return jax.jit(make_all)(key)
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(self.mesh, P())
        rows = NamedSharding(self.mesh, P(tuple(self.mesh.axis_names)))
        shapes = jax.eval_shape(make_all, key)
        out_sh = (jax.tree_util.tree_map(lambda _: rep, shapes[0]),
                  jax.tree_util.tree_map(lambda _: rows, shapes[1]))
        return jax.jit(make_all, out_shardings=out_sh)(key)

    def first_steps(self, seed):
        """Build the trainer on the seed's weights and drive its first
        steps through the window's own call and feed: they compile or load
        the step, warm it up, and are what the plain reference follows once
        the window has closed.  Returns (trainer, pool, readings)."""
        import jax

        weights, pool = self.make(seed)
        trainer = self.builder.make_trainer(self.cfg, weights, self.mesh)
        names = self.builder.program_names(weights)
        del weights
        key = jax.random.fold_in(seed_key(seed), 0)
        program = {"losses": []}
        for i in range(self.n_follow):
            x, y = pool[i]
            program["losses"].append(float(trainer.step(x, y).asnumpy()))
            if i == 0:
                program["grad_norms"], program["grad_samples"] = \
                    first_gradient_readings(self.builder, self.cfg, trainer,
                                            names)
        program["delta_norms"] = change_readings(
            self.builder, self.cfg, trainer, names, key)
        return trainer, pool, program

    def follow(self, seed, pool, **how):
        """The plain reference over the same first steps (``how``: the
        control's precision, a planted fault)."""
        import jax

        weights = jax.jit(lambda k: self.builder.make_weights(self.cfg, k))(
            jax.random.fold_in(seed_key(seed), 0))
        batches = pool[:self.n_follow]
        if self.mesh is not None:  # the reference knows of one chip
            batches = jax.device_put(batches, self.devices[0])
        return self.reference.run(self.cfg, self.traffic, weights, batches,
                                  **how)


def run_cell(workload, seed, seconds, trace, rehearse=False):
    """One run; returns the result line as a dict.  The tests call it with
    ``rehearse`` (tiny sizes, no look for a chip), break the timed path
    underneath and look at ``correct``."""
    import jax

    phases = {"imports": time.perf_counter() - T0}
    compiles = Compiles()
    cell = Cell(workload, rehearse)
    traffic, builder, devices = cell.traffic, cell.builder, cell.devices
    phases["open"] = time.perf_counter() - T0
    trainer, pool, program = cell.first_steps(seed)
    phases["first_steps"] = time.perf_counter() - T0
    pool_n, step_i = traffic["pool"], cell.n_follow
    for _ in range(traffic["warmup_steps"]):
        x, y = pool[step_i % pool_n]
        float(trainer.step(x, y).asnumpy())
        step_i += 1

    # ---- the measured window ----
    log_every = traffic["log_every"]
    trace_dir = os.path.join(ROOT, ".chipbench_trace", workload)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    max_steps = traffic["trace_steps"] if trace else None
    min_steps = 0 if trace else traffic.get("min_steps", 0)
    compiles_before = compiles.count
    arrivals, losses, steps_done = [], [], 0
    setup_s = time.perf_counter() - T0
    t_start = time.perf_counter()
    while True:
        x, y = pool[step_i % pool_n]
        with jax.profiler.TraceAnnotation(STEP_SPAN):
            loss = trainer.step(x, y)
        step_i += 1
        steps_done += 1
        if steps_done % log_every == 0:
            with jax.profiler.TraceAnnotation(FETCH_SPAN):
                losses.append(float(loss.asnumpy()))
            now = time.perf_counter()
            arrivals.append((steps_done, now))
            if (max_steps and steps_done >= max_steps) or \
                    (now - t_start >= seconds and steps_done >= min_steps):
                break
    window_s = arrivals[-1][1] - t_start
    if trace:
        jax.profiler.stop_trace()
    compiled_in_window = compiles.count - compiles_before

    # ---- after the window: state, memory, then free the program ----
    state = trainer.state_dict()
    strays = off_device((state["params"], state["opt_state"]), set(devices))
    # live buffers at their peak plus what the runtime reserved for the
    # step program's temporaries (peak_bytes_in_use alone leaves those out:
    # 2.0 GB against the compiler's 11.3 GB of temporaries, PERF.md)
    memory_peak = max(
        s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
        for s in (d.memory_stats() or {} for d in devices))
    del state, trainer, loss
    gc.collect()

    # ---- the plain reference follows the first steps ----
    t_ref = time.perf_counter()
    want = cell.follow(seed, pool)
    reference_s = time.perf_counter() - t_ref

    check = load_module("", "check")
    numbers, leaves = check.compare(program, want, cell.limits)
    numbers["compiles_in_window"] = {"value": compiled_in_window, "limit": 0}
    numbers["arrays_off_device"] = {"value": len(strays), "limit": 0}
    finite = all(v == v and abs(v) != float("inf") for v in losses)
    numbers["losses_not_finite"] = {"value": 0 if finite else 1, "limit": 0}
    correct = check.passed(numbers)

    # ---- metrics ----
    # a sample is the time per step between two arrivals of a loss on the
    # host, `steps_per_sample` arrivals apart where a step is shorter than
    # the 250 ms that a reading of the host's clock should span
    marks = ([(0, t_start)] + arrivals)[::traffic["steps_per_sample"]]
    samples = [(t1 - t0) / (s1 - s0) * 1e3
               for (s0, t0), (s1, t1) in zip(marks, marks[1:])]
    metrics, device_extra, breakdown = {}, {}, None
    if trace and not rehearse:
        reduced = load_module("", "trace").reduce_dir(trace_dir, len(devices))
        ctx = {"trace": reduced, "chips": cell.chips, "cfg": cell.cfg,
               "traffic": traffic, "builder": builder, "peaks": cell.peaks,
               "readers": load_module("", "readers"),
               "ops_per_step": builder.ops_per_step(cell.cfg, traffic)}
        for m in cell.manifest["per_layer"]:
            if "workloads" in m and workload not in m["workloads"]:
                continue
            value = load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device_extra = {"busy_s": reduced["busy_s"],
                        "window_s": reduced["window_s"]}
        breakdown = reduced["breakdown"]
    elif not rehearse:
        enough_samples(workload, len(samples))
        rate = "train_%s_per_s" % builder.UNIT
        metrics[rate] = {
            "value": steps_done * builder.units_per_step(cell.cfg, traffic)
            / window_s, "unit": manifest_unit(cell.manifest, rate)}
        metrics["step_ms_p95"] = {
            "value": statistics.quantiles(samples, n=20)[-1], "unit": "ms"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)

    device = {"platform": devices[0].platform, "kind": cell.kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    device.update(device_extra)
    result = {"correct": bool(correct), "attempted": steps_done, "failed": 0,
              "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result.update({
        "workload": workload, "seed": seed, "rehearsal": bool(rehearse),
        "window_s": window_s, "step_ms_median": statistics.median(samples),
        "step_samples": len(samples),
        "setup_phases_s": phases, "reference_s": reference_s,
        "compile_s": compiles.seconds, "first_losses": program["losses"],
        "worst_leaves": leaves, "numbers": numbers})
    return result


def manifest_unit(manifest, name):
    for m in manifest["end_to_end"]:
        if m["name"] == name:
            return m["unit"]
    raise SystemExit("chipbench: BENCHMARK.json has no end-to-end metric %r"
                     % name)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    result = run_cell(args.workload, args.seed, args.seconds, args.trace,
                      args.rehearse)
    for name, n in result["numbers"].items():
        print("%s %s limit %s%s" % (
            name, n["value"], n["limit"],
            "" if n["value"] <= n["limit"] else "  <-- OVER"),
            file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
