"""Tests of what the ``laguna_xs2`` configuration adds to the benchmark, on
the CPU at the rehearsal size: the configuration's file against the published
config; hand counts of the step's operations and of the rule readers' pairs
and bytes; the plain reference against the program in float32 (logits, loss,
every leaf's gradient and change) and its fp8 control and the planted faults
failing the cell's limits; the share of one chip tied to the whole layer (8
shares add up to the uncut reference's layer, the shared expert counted
once); the new readers on a made-up trace, silent without one, and repeating
the numbers of the trace recorded on the chip.  No speed is read here."""
import gzip
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "chipbench")
CELL = "laguna_xs2_t8k"
NEW = ["flash_win_fwd_roofline", "flash_win_bwd_roofline",
       "flash_causal_fwd_roofline", "flash_causal_bwd_roofline",
       "attn_full_ms", "attn_window_ms", "moe_shared_ms"]
JOINED = ["step_mfu_pct.tokens", "launch_gap_ms.tokens",
          "device_idle_pct.tokens", "step_pre_dispatch_ms",
          "step_dispatch_ms", "step_forward_ms", "step_backward_ms",
          "step_optimizer_ms", "moe_route_ms", "moe_experts_ms"]


def _load(name, kind=""):
    spec = importlib.util.spec_from_file_location(
        "laguna_test_" + name, os.path.join(BENCH, kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


bench = _load("run")
# the readers say `import spans` / `import block_readers` (run.py's directory
# is on sys.path when it runs as a script): put them where that finds them
spans = sys.modules.setdefault("spans", _load("spans"))
block_readers = sys.modules.setdefault("block_readers", _load("block_readers"))
rule_readers = sys.modules.setdefault("rule_readers", _load("rule_readers"))
builder = bench.load_module("configs", "laguna_xs2")
reference = bench.load_module("reference", "laguna_xs2")
CFG = bench.load_json("configs", "laguna_xs2.json")
TRAFFIC = bench.load_json("traffic", "t8k_b1.json")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)


# ------------------------------------------------ the configuration's file
def test_configuration_keeps_every_published_number_but_the_reduced():
    row = bench.load_json("fixtures", "laguna_xs2.published.json")
    entry = [c for c in MANIFEST["configs"] if c["name"] == "laguna_xs2"][0]
    assert entry["source"] == row["source_url"] == CFG["source"]
    differs = sorted(k for k, v in row["config"].items()
                     if CFG.get(k, "missing") != v)
    assert differs == sorted(entry["reduced"]) == [
        "layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert CFG["published"] == {k: row["config"][k] for k in differs}
    # depth: the leading dense full layer and one whole period after it;
    # the per-layer lists are the published ones' first five entries
    assert CFG["num_hidden_layers"] == 5 >= 4
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert CFG[key] == row["config"][key][:5]
    assert CFG["layer_types"] == ["full_attention"] \
        + ["sliding_attention"] * 3 + ["full_attention"]
    assert CFG["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert CFG["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    # an eighth of experts and vocabulary, the router as wide as published
    assert CFG["num_experts"] * 8 == CFG["router_experts"] == 256
    assert CFG["vocab_size"] * 8 == 100352
    assert CFG["num_experts_per_tok"] == 8 and CFG["first_expert"] == 0
    assert "8 chips" in CFG["deployment"]
    assert len(CFG["assumed"]) >= 8
    # the state the deployment string states: 691.6 M parameters
    n = sum(int(np.prod(shape))
            for shape, _ in builder.weight_shapes(CFG).values())
    assert round(n / 1e6, 1) == 691.6 and "691.6 M" in CFG["deployment"]
    # the rehearsal keeps what makes the configuration: both layer kinds,
    # different head counts, a dense first layer, held < routed experts,
    # a window shorter than the sequence, partial YaRN rotary
    tiny = dict(CFG, **CFG["rehearse"])
    assert set(tiny["layer_types"]) == {"full_attention", "sliding_attention"}
    assert len(set(tiny["num_attention_heads_per_layer"])) == 2
    assert tiny["mlp_layer_types"][0] == "dense"
    assert tiny["num_experts"] < tiny["router_experts"]
    assert tiny["sliding_window"] < TRAFFIC["rehearse"]["seq"]
    freq, factor = reference.rotary_frequencies(
        tiny["rope_parameters"]["full_attention"], tiny["head_dim"])
    assert len(freq) * 2 == tiny["head_dim"] // 2 and factor > 1


def test_the_manifest_gains_the_cell_at_the_end_of_every_list():
    assert MANIFEST["configs"][-1]["name"] == "laguna_xs2"
    cell = MANIFEST["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "laguna_xs2", "t8k_b1", 1)
    assert len(MANIFEST["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert [m["name"] for m in MANIFEST["per_layer"][-7:]] == NEW
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
    for name in JOINED:
        assert by_name[name]["workloads"][-1] == CELL
    rate = [m for m in MANIFEST["end_to_end"]
            if m["name"] == "train_tokens_per_s"][0]
    assert rate["workloads"][-1] == CELL
    assert (TRAFFIC["batch"], TRAFFIC["seq"], TRAFFIC["pool"],
            TRAFFIC["min_steps"], TRAFFIC["warmup_steps"],
            TRAFFIC["reference"]["steps"], TRAFFIC["trace_steps"],
            TRAFFIC["log_every"], TRAFFIC["steps_per_sample"]) \
        == (1, 8192, 128, 100, 2, 2, 8, 1, 1)


# ------------------------------------------------------- operation counts
def test_laguna_operation_count_against_a_hand_count():
    t = 8192
    causal, window = t * (t + 1) // 2, 512 * t - 512 * 511 // 2
    assert builder.allowed_pairs("causal", t, 512) == causal == 33_558_528
    assert builder.allowed_pairs("window", t, 512) == window == 4_063_488
    # by hand, one position, forward.  A full layer: q, k, v, o and the gate
    full = 2 * 2048 * (48 * 128 + 2 * 8 * 128) + 2 * 48 * 128 * 2048 \
        + 2 * 2048 * 48
    slide = 2 * 2048 * (64 * 128 + 2 * 8 * 128) + 2 * 64 * 128 * 2048 \
        + 2 * 2048 * 64
    attn_full = 2 * 2 * 48 * 128 * causal / t        # QK^T and PV
    attn_slide = 2 * 2 * 64 * 128 * window / t
    dense = 3 * 2 * 2048 * 8192
    # router, one expected expert row (8 x 32 / 256), the shared expert
    sparse = 2 * 2048 * 256 + 1.0 * 3 * 2 * 2048 * 512 + 3 * 2 * 2048 * 512
    layers = [full + attn_full + dense] + [slide + attn_slide + sparse] * 3 \
        + [full + attn_full + sparse]
    for i, want in enumerate(layers):
        assert builder.forward_ops_per_position(CFG, TRAFFIC, i) == want
    head = 2 * 2048 * 12544
    hand = 3 * t * (sum(layers) + head)
    assert builder.ops_per_step(CFG, TRAFFIC) == hand
    assert 19.6e12 < hand < 19.8e12                 # ISSUE 32: 19.7 TFLOP
    # attention's part of the forward: 2.05 of 6.57 TFLOP
    attention = t * (2 * attn_full + 3 * attn_slide)
    assert 2.04e12 < attention < 2.06e12 and 6.5e12 < hand / 3 < 6.6e12
    assert builder.units_per_step(CFG, TRAFFIC) == 8192
    # the balanced expectation of held rows: 8,192 a sparse layer
    assert 8 * 32 / 256 * t == 8192


def test_rule_readers_pairs_operations_and_bytes_against_a_hand_count():
    calls = builder.attention_calls(CFG, TRAFFIC, 1)
    assert calls == {
        "causal": {"batch": 1, "heads": 48, "kv_heads": 8, "seq": 8192,
                   "head_dim": 128, "layers": 2, "window": None,
                   "pairs": 8192 * 8193 // 2},
        "window": {"batch": 1, "heads": 64, "kv_heads": 8, "seq": 8192,
                   "head_dim": 128, "layers": 3, "window": 512,
                   "pairs": 512 * 8192 - 512 * 511 // 2}}
    assert (calls["causal"]["pairs"], calls["window"]["pairs"]) \
        == (33_558_528, 4_063_488)
    # a window longer than T is the causal rule
    assert builder.allowed_pairs("window", 100, 512) == 100 * 101 // 2
    ops, nbytes = rule_readers.flash_ops_bytes("flash_fwd", calls["window"])
    assert ops == 2 * 2 * 64 * 4_063_488 * 128        # QK^T and PV
    # q and o a query head, k and v ONCE a KV head, bf16; lse rows float32
    assert nbytes == (2 * 64 + 2 * 8) * 8192 * 128 * 2 + 64 * 8192 * 4
    dq = rule_readers.flash_ops_bytes("flash_bwd_dq", calls["causal"])
    dkv = rule_readers.flash_ops_bytes("flash_bwd_dkv", calls["causal"])
    fwd = rule_readers.flash_ops_bytes("flash_fwd", calls["causal"])
    assert fwd[0] == 2 * 2 * 48 * 33_558_528 * 128
    assert (dq[0], dkv[0]) == (1.5 * fwd[0], 2 * fwd[0])
    assert dkv[1] == (2 * 48 + 4 * 8) * 8192 * 128 * 2 + 2 * 48 * 8192 * 4
    peaks = bench.load_json("peaks.json")["tpu v5 lite"]
    readers = bench.load_module("", "readers")
    for kind in calls:                  # both compute-bound at D = 128
        o, n = rule_readers.flash_ops_bytes("flash_fwd", calls[kind])
        assert readers.least_seconds(o, n, peaks)[1] == "compute"
    # kernels on 512 x 512 tiles visit 31 and 136 tiles a head: the share
    # of their work that is allowed pairs bounds their roofline under 100
    assert 4_063_488 / (31 * 512 * 512) == pytest.approx(0.50003, abs=1e-4)
    assert 33_558_528 / (136 * 512 * 512) < 0.95


def _made_up_trace():
    ms = 1_000_000
    win = "jit(mx_step)/jvp(mx.step.forward)/checkpoint/mx.attn.window/" \
        "mx.attn.window/pallas_call"
    win_b = "jit(mx_step)/transpose(jvp(mx.step.forward))/checkpoint/" \
        "mx.attn.window/mx.attn.window/pallas_call"
    full = "jit(mx_step)/jvp(mx.step.forward)/checkpoint/mx.attn.causal/" \
        "mx.attn.causal/pallas_call"
    shared = "jit(mx_step)/jvp(mx.step.forward)/checkpoint/mx.moe.shared/dot"
    ops, modules = [], []
    for t in (0, 40 * ms):
        modules.append([t, t + 30 * ms, "jit_mx_step(1)"])
        ops += [[t, t + 2 * ms, "flash_fwd.1", win, ""],
                [t + 2 * ms, t + 8 * ms, "flash_fwd.2", full, ""],
                [t + 8 * ms, t + 9 * ms, "fusion.7", win, ""],     # rotary
                [t + 9 * ms, t + 12 * ms, "flash_bwd_dq.1", win_b, ""],
                [t + 12 * ms, t + 16 * ms, "flash_bwd_dkv.1", win_b, ""],
                [t + 16 * ms, t + 17 * ms, "fusion.9", shared, ""],
                [t + 17 * ms, t + 30 * ms, "fusion.3", "jit(mx_step)/x", ""]]
    return {"host": [], "devices": {0: {"ops": ops, "modules": modules}}}


def test_rule_readers_on_a_made_up_trace():
    loaded = _made_up_trace()
    assert rule_readers.kernel_calls(loaded, "window", "flash_fwd") \
        == (2, 0.004)
    assert rule_readers.kernel_calls(loaded, "causal", "flash_fwd") \
        == (2, 0.012)
    assert rule_readers.kernel_calls(loaded, "causal", "flash_bwd_dq") \
        == (0, 0.0)
    readers = bench.load_module("", "readers")
    peaks = bench.load_json("peaks.json")["tpu v5 lite"]
    calls = builder.attention_calls(CFG, TRAFFIC, 1)
    least = readers.least_seconds(
        *rule_readers.flash_ops_bytes("flash_fwd", calls["window"]), peaks)[0]
    got = rule_readers.flash_roofline_pct_of(
        loaded, calls, peaks, readers, "window", ["flash_fwd"])
    assert got == pytest.approx(100 * 2 * least / 0.004)
    both = rule_readers.flash_roofline_pct_of(
        loaded, calls, peaks, readers, "window",
        ["flash_bwd_dq", "flash_bwd_dkv"])
    least_b = sum(readers.least_seconds(
        *rule_readers.flash_ops_bytes(k, calls["window"]), peaks)[0]
        for k in ("flash_bwd_dq", "flash_bwd_dkv"))
    assert both == pytest.approx(100 * 2 * least_b / 0.014)
    assert rule_readers.flash_roofline_pct_of(
        loaded, calls, peaks, readers, "causal", ["flash_bwd_dq"]) is None
    # the scopes' time a step: kernels and the work around them
    assert block_readers.scope_ms_of(loaded, "mx.attn.window") == 10.0
    assert block_readers.scope_ms_of(loaded, "mx.attn.causal") == 6.0
    assert block_readers.scope_ms_of(loaded, "mx.moe.shared") == 1.0


def test_every_new_reader_is_silent_without_a_trace_or_the_builder(
        tmp_path, monkeypatch):
    """Outside a traced run there is no .chipbench_trace/: every reader
    this PR adds returns None and does not raise; nor does a roofline
    reader given a builder that knows no ``attention_calls`` (the parent's
    cells) or a program without the scopes."""
    monkeypatch.setattr(spans, "ROOT", str(tmp_path))
    for name in NEW:
        assert _load(name, "metrics").read({}) is None
    assert rule_readers.flash_roofline_pct(
        {"builder": bench.load_module("configs", "bert_base")}, "window",
        ["flash_fwd"]) is None
    unscoped = _made_up_trace()
    for op in unscoped["devices"][0]["ops"]:
        op[3] = "jit(mx_step)/x"
    readers = bench.load_module("", "readers")
    peaks = bench.load_json("peaks.json")["tpu v5 lite"]
    assert rule_readers.flash_roofline_pct_of(
        unscoped, builder.attention_calls(CFG, TRAFFIC, 1), peaks, readers,
        "window", ["flash_fwd"]) is None
    assert block_readers.scope_ms_of(unscoped, "mx.attn.window") is None


def test_readers_repeat_on_the_recorded_trace():
    """The cell's trace recorded on the chip (PR 32), trimmed to two steps:
    the scope and roofline readers give the recorded numbers exactly, both
    rules' calls are the three flash kernels, and no share passes 100."""
    with gzip.open(os.path.join(BENCH, "fixtures",
                                CELL + ".spans.json.gz"), "rt") as f:
        fixture = json.load(f)
    loaded = fixture["loaded"]
    for scope, want in fixture["rule_scopes"].items():
        assert want is not None and want > 0
        assert block_readers.scope_ms_of(loaded, scope) == want
    assert sorted(fixture["rule_scopes"]) == [
        "mx.attn.causal", "mx.attn.window", "mx.moe.experts", "mx.moe.route",
        "mx.moe.shared"]
    readers = bench.load_module("", "readers")
    peaks = bench.load_json("peaks.json")["tpu v5 lite"]
    calls = builder.attention_calls(CFG, TRAFFIC, 1)
    assert sorted(fixture["rule_rooflines"]) == sorted(NEW[:4])
    for name, want in fixture["rule_rooflines"].items():
        kind = "window" if "_win_" in name else "causal"
        kernels = ["flash_fwd"] if "_fwd_" in name \
            else ["flash_bwd_dq", "flash_bwd_dkv"]
        got = rule_readers.flash_roofline_pct_of(loaded, calls, peaks,
                                                 readers, kind, kernels)
        assert got == want and 0 < got < 100
    ops = loaded["devices"]["0"]["ops"]
    for kind, layers in (("window", 3), ("causal", 2)):
        mine = [op for op in ops if "mx.attn.%s" % kind in op[3]
                and "flash" in op[2]]
        assert {op[2].split(".")[0] for op in mine} == {
            "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
        # two steps; a layer calls the forward twice (recomputed)
        assert len([op for op in mine if op[2].startswith("flash_fwd")]) \
            == 2 * 2 * layers
        assert any("transpose(" in op[3] for op in mine)


# ------------------------------ the reference against the program, float32
@pytest.fixture(scope="module")
def float32_steps():
    cell = bench.Cell(CELL, rehearse=True)
    cell.cfg["compute_dtype"] = "float32"
    trainer, pool, program = cell.first_steps(11)
    del trainer
    return cell, pool, program, cell.follow(11, pool)


def test_reference_agrees_with_the_program_in_float32(float32_steps):
    """In float32 the program (flash kernels in interpret mode under both
    rules, sorted grouped products, a sigmoid router) and the plain
    reference (chunked dense attention, a loop over experts) are the same
    mathematics: loss, every leaf's gradient and every leaf's change agree
    to rounding."""
    check = bench.load_module("", "check")
    cell, pool, program, want = float32_steps
    numbers, _ = check.readings(program, want)
    assert all(v < 1e-3 for v in numbers.values()), numbers
    assert set(program["grad_norms"]) == set(want["grad_norms"]) \
        == set(builder.weight_shapes(cell.cfg))
    for name, norm in want["grad_norms"].items():       # EVERY gradient
        assert abs(program["grad_norms"][name] - norm) \
            <= 1e-3 * max(norm, 1e-6), name
        got, ref = program["grad_samples"][name], want["grad_samples"][name]
        assert np.abs(got - ref).max() <= 2e-3 * max(np.abs(ref).max(),
                                                     1e-6), name


def test_control_and_planted_faults_fail_the_cells_limits(float32_steps):
    check = bench.load_module("", "check")
    cell, pool, _, want = float32_steps
    for how in ({"precision": cell.cfg["controls"][0]},   # fp8
                {"rows": 1},            # half of a batch of two sequences
                {"rows": 0}):           # half of ONE sequence's positions
        numbers, _ = check.compare(cell.follow(11, pool, **how), want,
                                   cell.limits)
        assert not check.passed(numbers), (how, numbers)


def test_program_logits_are_the_references(float32_steps):
    """The zoo's model on the seed's weights against the reference's
    forward, position by position; the step program holds no (T, T)
    operand: both rules run in the flash kernels."""
    from mxnet_tpu import nd

    cell, pool, _, _ = float32_steps
    key = jax.random.fold_in(bench.seed_key(11), 0)
    weights = builder.make_weights(cell.cfg, key)
    trainer = builder.make_trainer(cell.cfg, dict(weights), None)
    x, y = pool[0]
    got = trainer._block(nd.NDArray(x)).asnumpy()
    with jax.default_matmul_precision("highest"):
        rest, groups = reference._pack(weights, cell.cfg)
        h = reference.hidden_states(cell.cfg, cell.traffic, None, rest,
                                    groups, x)
        want = reference._rms(h, rest["norm.gamma"],
                              cell.cfg["rms_norm_eps"]) \
            @ rest["head.weight"].T
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(np.abs(want).max()) > 1e-3
    t = x.shape[1]
    text = trainer._lower(x, y).as_text()
    # dense attention would hold (B, H, T, T) scores; the interpreted
    # kernels work on tiles
    assert "x%dx%dx" % (t, t) not in text


# --------------------------------------- the share tied to the whole layer
def _layer(n=48, c=16, hidden=24, experts=16, seed=3):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    return {"x": jax.random.normal(k[0], (n, c)),
            "moe.gate": jax.random.normal(k[1], (experts, c)),
            "moe.w1": 0.3 * jax.random.normal(k[2], (experts, c, hidden)),
            "moe.wg": 0.3 * jax.random.normal(k[3], (experts, c, hidden)),
            "moe.w2": 0.3 * jax.random.normal(k[4], (experts, hidden, c)),
            "moe.shared_w1": 0.3 * jax.random.normal(k[5], (c, hidden)),
            "moe.shared_wg": 0.3 * jax.random.normal(k[6], (c, hidden)),
            "moe.shared_w2": 0.3 * jax.random.normal(k[7], (hidden, c))}


def _share(p, first, count, top_k=4):
    from mxnet_tpu.gluon.nn.moe import moe_forward

    held = slice(first, first + count)
    return moe_forward(
        p["x"], p["moe.gate"], p["moe.w1"][held], p["moe.w2"][held],
        wg=p["moe.wg"][held], shared_w1=p["moe.shared_w1"],
        shared_wg=p["moe.shared_wg"], shared_w2=p["moe.shared_w2"],
        top_k=top_k, first=first, activation="silu", score="sigmoid",
        scale=2.5)


def _reference_layer(p, first, count, top_k=4, shared=True):
    cfg = {"num_experts_per_tok": top_k, "first_expert": first,
           "moe_routed_scaling_factor": 2.5}
    held = slice(first, first + count)
    lw = {n: (a[held] if n in ("moe.w1", "moe.wg", "moe.w2") else a)
          for n, a in p.items()
          if n != "x" and (shared or "shared" not in n)}
    with jax.default_matmul_precision("highest"):
        return reference._moe(cfg, None, p["x"], lw)


def test_eight_shares_add_up_to_the_uncut_layer_shared_expert_once():
    """Each chip's share holds the shared expert whole (it is replicated);
    what the 8 chips' EXPERTS add, plus the shared expert counted once, is
    the uncut layer of the reference."""
    p = _layer()
    whole = _reference_layer(p, 0, 16)                 # all experts held
    shared = whole - _reference_layer(p, 0, 16, shared=False)
    assert float(jnp.abs(shared).max()) > 0.1
    parts = [_share(p, first, 2) for first in range(0, 16, 2)]
    for first, part in zip(range(0, 16, 2), parts):
        np.testing.assert_allclose(part, _reference_layer(p, first, 2),
                                   atol=3e-5)
    np.testing.assert_allclose(sum(part - shared for part in parts) + shared,
                               whole, atol=1e-4)
    # the routed parts alone are 2.5 x a convex mix: not small
    assert float(jnp.abs(whole - shared).max()) > 0.1
