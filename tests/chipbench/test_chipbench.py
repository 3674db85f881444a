"""Tests of the benchmark under chipbench/, on the CPU.

What a CPU can show: that the manifest is well formed and everything it
names is a file of its own; that a rehearsal of each cell drives the whole
run and prints the result line; the operation counts against hand counts;
each plain reference against the program at a tiny size, and a run in lower
precision failing that comparison; that breaking the timed path underneath
makes ``correct`` false; and that the trace reduction repeats exactly on a
trace recorded on the chip.  No speed is read here.
"""
import gzip
import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "chipbench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + name, os.path.join(BENCH, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


bench = _load("run")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
CONFIGS = {c["name"]: c for c in MANIFEST["configs"]}


def _cpu_env(devices=4):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=%d" % devices)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


# ---------------------------------------------------------------- manifest
def test_manifest_is_well_formed_and_everything_is_a_file():
    assert sorted(MANIFEST) == sorted(
        ["command", "paths", "run_seconds", "configs", "workloads",
         "end_to_end", "per_layer"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert (group, entry["name"]) not in names
            names.add((group, entry["name"]))
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in MANIFEST["end_to_end"]:
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock",
                                                          "device_trace")
    # every configuration, traffic mix, limit, reference and reader is a
    # file of its own, found by the name in the manifest
    used = set()
    for w in MANIFEST["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cfg_entry = CONFIGS[w["config"]]
        used.add(w["config"])
        with open(os.path.join(ROOT, cfg_entry["file"])) as f:
            cfg = json.load(f)
        for kind, key in (("configs", "builder"), ("reference", "reference")):
            assert os.path.isfile(os.path.join(BENCH, kind,
                                               cfg[key] + ".py"))
        assert os.path.isfile(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(
            BENCH, "limits", w["name"] + ".json"))
    assert used == set(CONFIGS)
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 4)
    # a per-layer metric lists only cells that report the metric it moves
    for m in MANIFEST["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py")), m["name"]
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", CELLS)) <= \
            set(moved.get("workloads", CELLS)), m["name"]
    # every cell reports setup_s, another end-to-end metric and a per-layer
    for cell in CELLS:
        mine = [m for m in MANIFEST["end_to_end"]
                if cell in m.get("workloads", CELLS)]
        assert len(mine) >= 2
        assert any(cell in m.get("workloads", CELLS)
                   for m in MANIFEST["per_layer"])
    assert 1 <= MANIFEST["run_seconds"] <= 51
    for entry in MANIFEST["configs"] + MANIFEST["workloads"] \
            + MANIFEST["per_layer"]:
        for key in ("why", "source", "layer"):
            text = entry.get(key, "x")
            if key == "source" and entry in MANIFEST["per_layer"]:
                continue
            assert 1 <= len(text) <= 200 and "\n" not in text \
                and "\t" not in text, (entry["name"], key)
    # files under the benchmark's directories are named from a name's
    # characters
    for path in MANIFEST["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f
    for path in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))


def test_peaks_table_names_its_source_and_v5e():
    peaks = bench.load_json("peaks.json")
    assert "Google Cloud" in peaks["_source"]
    v5e = peaks["tpu v5 lite"]
    assert (v5e["bf16_flops_per_s"], v5e["hbm_bytes_per_s"],
            v5e["hbm_bytes"]) == (197e12, 819e9, 16e9)


# --------------------------------------------------------- operation counts
def test_bert_operation_count_against_a_hand_count():
    cfg = bench.load_json("configs", "bert_base.json")
    traffic = bench.load_json("traffic", "t512_b64.json")
    b = bench.load_module("configs", "bert_base")
    # by hand, 64 x 512 tokens, 76 masked slots a row: a layer is
    # 4 projections (4 x 2 x 768^2) + scores and values (2 x 2 x 512 x 768)
    # + the FFN (2 x 2 x 768 x 3072) = 15,728,640 operations a token
    per_token = 12 * 15_728_640
    per_slot = 2 * 768 * 768 + 2 * 768 * 30522
    hand = 3 * (per_token * 32768 + per_slot * 64 * 76)
    assert b.ops_per_step(cfg, traffic) == hand == 19_255_571_251_200
    assert b.units_per_step(cfg, traffic) == 32768
    assert b.attention_shape(cfg, traffic, 1) == (768, 512, 64)


def test_resnet_operation_count_against_a_hand_count():
    cfg = bench.load_json("configs", "resnet50_v1.json")
    b = bench.load_module("configs", "resnet50_v1")
    # by hand, stage by stage (multiply-adds an image): a bottleneck at
    # width c, mid m = c/4, on an h x h output costs m*c_in + 9*m*m + c*m
    # a pixel, its first block a c*c_in projection more
    def stage(c_in, c, h, blocks):
        m = c // 4
        first = (m * c_in + 9 * m * m + c * m + c * c_in) * h * h
        return first + (blocks - 1) * (m * c + 9 * m * m + c * m) * h * h

    hand = 64 * 3 * 49 * 112 * 112 + stage(64, 256, 56, 3) \
        + stage(256, 512, 28, 4) + stage(512, 1024, 14, 6) \
        + stage(1024, 2048, 7, 3) + 2048 * 1000
    assert b.forward_macs(cfg) == hand == 3_857_973_248
    traffic = bench.load_json("traffic", "img224_b256.json")
    assert b.ops_per_step(cfg, traffic) == 6.0 * hand * 256


def test_flash_kernel_operations_and_bytes():
    r = bench.load_module("", "readers")
    ops, nbytes = r.flash_ops_bytes("flash_fwd", 768, 512, 64)
    assert ops == 4 * 768 * 512 * 512 * 64          # QK^T and PV
    assert nbytes == 4 * 768 * 512 * 64 * 2 + 768 * 512 * 4
    assert r.flash_ops_bytes("flash_bwd_dkv", 768, 512, 64)[0] == 2 * ops
    peaks = bench.load_json("peaks.json")["tpu v5 lite"]
    seconds, bound = r.least_seconds(ops, nbytes, peaks)
    assert bound == "compute" and abs(seconds - ops / 197e12) < 1e-12


# ------------------------------------------------- rehearsal of every cell
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_drives_a_whole_run(cell):
    result = bench.run_cell(cell, 3_000_000_019, 0.5, 0, rehearse=True)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "numbers"
    assert result["correct"] is True, result["numbers"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["metrics"] == {}  # no device metric from a CPU
    assert result["device"]["platform"] == "cpu"
    chips = [w for w in MANIFEST["workloads"] if w["name"] == cell][0]["chips"]
    assert result["device"]["count"] == chips
    for n in result["numbers"].values():
        assert set(n) == {"value", "limit"}


def test_command_line_prints_the_result_last_and_fails_without_a_tpu():
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
           CELLS[0], "--seed", "7", "--seconds", "0.5", "--trace", "0"]
    done = subprocess.run(cmd + ["--rehearse"], env=_cpu_env(), cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    # each number compared, beside its limit, ends standard error
    tail = done.stderr.strip().splitlines()[-len(last["numbers"]):]
    assert [line.split()[0] for line in tail] == list(last["numbers"])
    assert all(" limit " in line for line in tail)
    # without --rehearse a run that finds no TPU prints no result
    done = subprocess.run(cmd, env=_cpu_env(), cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode != 0
    assert "{" not in done.stdout


# ------------------------- the references against the program, and controls
def _first_steps(cell_name, dtype):
    cell = bench.Cell(cell_name, rehearse=True)
    cell.cfg["compute_dtype"] = dtype
    trainer, pool, program = cell.first_steps(11)
    del trainer
    return cell, pool, program


@pytest.mark.parametrize("cell_name", ["bert_base_t512", "bert_base_t128",
                                       "resnet50_b256"])
def test_reference_agrees_with_the_program_in_float32(cell_name):
    """In float32 the program and the plain reference are the same
    mathematics: every number agrees to rounding.  And the reference in a
    lower precision (the control) fails the cell's own limits."""
    check = bench.load_module("", "check")
    cell, pool, program = _first_steps(cell_name, "float32")
    want = cell.follow(11, pool)
    # 1e-3: a change a millionth of its weight is a difference of float32s
    tight = dict.fromkeys(cell.limits, 1e-3)
    numbers, _ = check.compare(program, want, tight)
    assert check.passed(numbers), numbers
    control = cell.follow(11, pool, precision=cell.cfg["controls"][0])
    numbers, _ = check.compare(control, want, cell.limits)
    assert not check.passed(numbers), numbers


# ----------------------- the timed path broken underneath: correct is false
def _break_step(monkeypatch, fault, chips, first_timed_call):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import parallel

    real = parallel.FusedTrainer.step
    calls = {"n": 0}

    def rows(tree, n):
        return jax.tree_util.tree_map(lambda a: a[:a.shape[0] // n], tree)

    def step(self, x, y):
        calls["n"] += 1
        if fault == "state_unchanged":
            if self._params is None:
                return real(self, x, y)
            kept = jax.tree_util.tree_map(
                jnp.copy, (self._params, self._opt_state))
            loss = real(self, x, y)
            self._params, self._opt_state = kept
            return loss
        if fault == "half_batch":
            return real(self, rows(x, 2), rows(y, 2))
        if fault == "no_exchange":
            # what every chip would apply had the gradients not been
            # exchanged: the mean over the first chip's rows alone
            def own(tree):
                return jax.tree_util.tree_map(
                    lambda a: jnp.concatenate([a[:a.shape[0] // chips]]
                                              * chips), tree)

            return real(self, own(x), own(y))
        if fault == "compile_in_window" and calls["n"] == first_timed_call:
            jax.jit(lambda a: a * 3 + calls["n"])(jnp.ones((3,)))
        return real(self, x, y)

    monkeypatch.setattr(parallel.FusedTrainer, "step", step)


FAULTS = [(c, f) for c in CELLS
          for f in ("state_unchanged", "half_batch", "compile_in_window")] \
    + [(w["name"], "no_exchange") for w in MANIFEST["workloads"]
       if w["chips"] > 1]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_a_broken_timed_path_is_not_correct(monkeypatch, cell, fault):
    entry = [w for w in MANIFEST["workloads"] if w["name"] == cell][0]
    traffic = bench.load_cell(cell, rehearse=True)[3]
    # the window's first step comes after the followed and the warm-up steps
    # (however few steps a loaded machine fits into the window)
    _break_step(monkeypatch, fault, entry["chips"],
                traffic["reference"]["steps"] + traffic["warmup_steps"] + 1)
    result = bench.run_cell(cell, 5, 0.3, 0, rehearse=True)
    assert result["correct"] is False
    over = [n for n, v in result["numbers"].items()
            if not v["value"] <= v["limit"]]
    assert over, result["numbers"]
    if fault == "compile_in_window":
        assert over == ["compiles_in_window"]


# ------------------------------------------------------ the trace reduction
def _fixture(name):
    with gzip.open(os.path.join(BENCH, "fixtures", name), "rt") as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(os.path.join(BENCH, "fixtures"))
    if f.endswith(".trace.json.gz")))
def test_trace_reduction_repeats_on_a_recorded_trace(name):
    """A trace recorded on the chip in the PR that added the cell, trimmed
    to a few steps: the reduction gives the recorded numbers exactly."""
    trace = bench.load_module("", "trace")
    fixture = _fixture(name)
    loaded = {"host": [tuple(e) for e in fixture["loaded"]["host"]],
              "devices": {int(n): {k: [tuple(e) for e in v]
                                   for k, v in d.items()}
                          for n, d in fixture["loaded"]["devices"].items()}}
    got = json.loads(json.dumps(trace.reduce(loaded)))
    want = fixture["reduced"]
    assert got["window_s"] == want["window_s"]
    assert got["busy_s"] == want["busy_s"]
    assert got["breakdown"] == want["breakdown"]
    for n, dev in want["devices"].items():
        mine = got["devices"][n]
        for key in ("busy_s", "gaps", "op_seconds", "op_counts",
                    "launch_gaps_ms", "collective_s",
                    "collective_exposed_s", "step_count"):
            assert mine[key] == dev[key], (n, key)
    assert 0 < got["busy_s"] <= got["window_s"]


def test_trace_arithmetic_on_a_made_up_trace():
    trace = bench.load_module("", "trace")
    ms = 1_000_000
    loaded = {
        "host": [(0, 1 * ms, "chipbench.step"), (1 * ms, 10 * ms,
                                                 "chipbench.fetch")],
        "devices": {0: {
            "modules": [(1 * ms, 4 * ms, "jit_step(1)"),
                        (6 * ms, 9 * ms, "jit_step(1)")],
            "ops": [(1 * ms, 3 * ms, "%fusion.1 = f32[8]{0} fusion(f32[8] "
                     "%p), kind=kOutput, calls=%c"),
                    (2 * ms, 4 * ms, "%all-reduce.1 = f32[8]{0} "
                     "all-reduce(f32[8] %fusion.1), to_apply=%add"),
                    (6 * ms, 9 * ms, "%flash_fwd.3 = bf16[8]{0} "
                     "custom-call(bf16[8] %q)")]}}}
    got = trace.reduce(loaded)
    dev = got["devices"][0]
    assert got["window_s"] == 0.010 and dev["busy_s"] == 0.006
    assert dev["launch_gaps_ms"] == [2.0]
    assert dev["collective_s"] == 0.002
    assert dev["collective_exposed_s"] == 0.001  # 3..4 ms runs alone
    assert dev["op_seconds"]["flash_fwd"] == 0.003
    assert dev["class_seconds"]["matmul_conv_fusion"] == 0.002
    assert got["breakdown"]["idle_gaps"][0] == ["chipbench.fetch@0.004s",
                                                0.002]
