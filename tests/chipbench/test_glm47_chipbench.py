"""Tests of what the ``glm47_flash`` configuration adds to the benchmark, on
the CPU at the rehearsal size: the configuration's file against the published
config; hand counts of the step's operations and of the readers' pairs and
bytes at a head of 256; the plain reference against the program in float32
(both losses, every leaf's gradient with the embedding's and the head's two
paths summed, every leaf's change) and its fp8 control and the planted
faults failing the cell's limits; the selection bias in the reference; the
share of one chip tied to the whole layer (8 shares add up to the uncut
reference's layer, the shared expert counted once); the new readers on a
made-up trace, silent without one, and repeating the numbers of the trace
recorded on the chip.  No speed is read here."""
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
CELL = "glm47_flash_t8k"
NEW = ["flash_mla_fwd_roofline", "flash_mla_bwd_roofline", "attn_mla_ms",
       "mtp_ms"]
JOINED = ["step_mfu_pct.tokens", "launch_gap_ms.tokens",
          "device_idle_pct.tokens", "step_pre_dispatch_ms",
          "step_dispatch_ms", "step_forward_ms", "step_backward_ms",
          "step_optimizer_ms", "moe_route_ms", "moe_experts_ms",
          "moe_shared_ms"]
LAGUNA_NEW = ["flash_win_fwd_roofline", "flash_win_bwd_roofline",
              "flash_causal_fwd_roofline", "flash_causal_bwd_roofline",
              "attn_full_ms", "attn_window_ms", "moe_shared_ms"]


def _load(name, kind=""):
    spec = importlib.util.spec_from_file_location(
        "glm47_test_" + name, os.path.join(BENCH, kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


bench = _load("run")
# the readers say `import spans` / `import block_readers` (run.py's directory
# is on sys.path when it runs as a script): put them where that finds them
spans = sys.modules.setdefault("spans", _load("spans"))
block_readers = sys.modules.setdefault("block_readers", _load("block_readers"))
rule_readers = sys.modules.setdefault("rule_readers", _load("rule_readers"))
builder = bench.load_module("configs", "glm47_flash")
reference = bench.load_module("reference", "glm47_flash")
CFG = bench.load_json("configs", "glm47_flash.json")
TRAFFIC = bench.load_json("traffic", "t8k_b1.json")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)


# ------------------------------------------------ the configuration's file
def test_configuration_keeps_every_published_number_but_the_reduced():
    row = bench.load_json("fixtures", "glm47_flash.published.json")
    entry = [c for c in MANIFEST["configs"] if c["name"] == "glm47_flash"][0]
    assert entry["source"] == row["source_url"] == CFG["source"]
    differs = sorted(k for k, v in row["config"].items()
                     if CFG.get(k, "missing") != v)
    assert differs == sorted(entry["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert CFG["published"] == {k: row["config"][k] for k in differs}
    # no width is among them
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "num_attention_heads",
                "num_experts_per_tok", "num_nextn_predict_layers"):
        assert CFG[key] == row["config"][key], key
    # depth: the leading dense layer and four sparse layers (one layer is a
    # whole period; the floor of four decides), the module kept
    assert CFG["num_hidden_layers"] - CFG["first_k_dense_replace"] == 4
    assert CFG["num_nextn_predict_layers"] == 1
    # an eighth of experts and vocabulary, the router as wide as published
    assert CFG["n_routed_experts"] * 8 == CFG["router_experts"] == 64
    assert CFG["n_routed_experts"] >= 8 and CFG["vocab_size"] * 8 == 154880
    assert CFG["num_experts_per_tok"] == 4 and CFG["first_expert"] == 0
    assert CFG["qk_nope_head_dim"] + CFG["qk_rope_head_dim"] \
        == CFG["v_head_dim"] == 256
    assert "8 chips" in CFG["deployment"]
    assert len(CFG["assumed"]) >= 10 and CFG["mtp_loss_weight"] == 0.1
    # the state the deployment string states: 706.5 M parameters, by part
    shapes = builder.weight_shapes(CFG)

    def count(prefix):
        return sum(int(np.prod(s)) for n, (s, _) in shapes.items()
                   if n.startswith(prefix))

    attention = count("layers.0.attention.")
    assert attention == 1_572_864 + 768 + 3_932_160 + 1_179_648 + 512 \
        + 4_587_520 + 10_485_760
    assert count("embed.") + count("head.") == 2 * 19360 * 2048
    assert round(count("layers.0.") / 1e6, 2) == 84.68
    assert round(count("layers.1.") / 1e6, 2) == 106.83
    assert round(count("mtp.") / 1e6, 2) == 115.22
    n = count("")
    assert round(n / 1e6, 1) == 706.5 and "706.5 M" in CFG["deployment"]
    assert round(n * 16 / 1e9, 2) == 11.30 and "11.30 GB" in CFG["deployment"]
    # the rehearsal keeps what makes the configuration: a dense first layer
    # and sparse ones, held < routed experts, a head of nope + rope = v,
    # ranks under the hidden size, the module
    tiny = dict(CFG, **CFG["rehearse"])
    assert tiny["first_k_dense_replace"] == 1 < tiny["num_hidden_layers"]
    assert tiny["n_routed_experts"] < tiny["router_experts"]
    assert tiny["qk_nope_head_dim"] + tiny["qk_rope_head_dim"] \
        == tiny["v_head_dim"] == 32
    assert tiny["kv_lora_rank"] < tiny["q_lora_rank"] < tiny["hidden_size"]
    assert tiny["num_nextn_predict_layers"] == 1
    assert tiny["topk_method"] == "noaux_tc"


def test_the_manifest_gains_the_cell_at_the_end_of_every_list():
    assert [c["name"] for c in MANIFEST["configs"]][-2:] \
        == ["laguna_xs2", "glm47_flash"]
    assert len(MANIFEST["configs"]) == 5
    cell = MANIFEST["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "glm47_flash", "t8k_b1", 1)
    # Laguna's cell as it was, straight before this one, under the SAME
    # traffic file: two configurations, one traffic
    laguna = MANIFEST["workloads"][-2]
    assert (laguna["name"], laguna["config"], laguna["traffic"],
            laguna["chips"]) == ("laguna_xs2_t8k", "laguna_xs2", "t8k_b1", 1)
    assert len(MANIFEST["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[-4:] == NEW and names[-11:-4] == LAGUNA_NEW
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
    for name in LAGUNA_NEW[:-1]:
        assert by_name[name]["workloads"] == ["laguna_xs2_t8k"]
    for name in JOINED:
        assert by_name[name]["workloads"][-2:] == ["laguna_xs2_t8k", CELL]
    for name in NEW[:2]:
        assert (by_name[name]["unit"], by_name[name]["layer"],
                by_name[name]["moves"], by_name[name]["source"]) \
            == ("%", "kernels", "train_tokens_per_s", "device_trace")
    for name in NEW[2:]:
        assert (by_name[name]["unit"], by_name[name]["layer"],
                by_name[name]["moves"], by_name[name]["better"]) \
            == ("ms", "step programs", "step_ms_p95", "lower")
    rate = [m for m in MANIFEST["end_to_end"]
            if m["name"] == "train_tokens_per_s"][0]
    assert rate["workloads"][-2:] == ["laguna_xs2_t8k", CELL]
    assert (TRAFFIC["batch"], TRAFFIC["seq"], TRAFFIC["pool"],
            TRAFFIC["min_steps"], TRAFFIC["warmup_steps"],
            TRAFFIC["reference"]["steps"], TRAFFIC["trace_steps"],
            TRAFFIC["log_every"], TRAFFIC["steps_per_sample"]) \
        == (1, 8192, 128, 100, 2, 2, 8, 1, 1)
    limits = bench.load_json("limits", CELL + ".json")
    assert {"loss_gap_1", "delta_norm_gap", "grad_diff"} <= set(limits)


# ------------------------------------------------------- operation counts
def test_glm_operation_count_against_a_hand_count():
    t = 8192
    pairs = t * (t + 1) // 2
    assert builder.allowed_pairs(t) == pairs == 33_558_528
    # by hand, one position, forward.  The latent block's five products
    latent = 2 * (2048 * 768 + 768 * 20 * 256 + 2048 * (512 + 64)
                  + 512 * 20 * (192 + 256) + 20 * 256 * 2048)
    assert round(latent / 1e6, 1) == 43.5
    attn = 2 * 2 * 20 * 256 * pairs / t                # QK^T and PV
    assert round(attn / 1e6, 1) == 83.9
    dense = 3 * 2 * 2048 * 10240
    # router, half an expected expert row (4 x 8 / 64), the shared expert
    sparse = 2 * 2048 * 64 + 0.5 * 3 * 2 * 2048 * 1536 + 3 * 2 * 2048 * 1536
    assert round(dense / 1e6, 1) == 125.8 and round(sparse / 1e6, 1) == 28.6
    assert builder.forward_ops_per_position(CFG, TRAFFIC, False) \
        == latent + attn + dense
    assert builder.forward_ops_per_position(CFG, TRAFFIC, True) \
        == latent + attn + sparse
    w_eh, head = 2 * 4096 * 2048, 2 * 2048 * 19360
    position = 6 * (latent + attn) + dense + 5 * sparse + w_eh + 2 * head
    assert 1.207e9 < position < 1.210e9            # ISSUE 34: 1.208 G
    hand = 3 * t * position
    assert builder.ops_per_step(CFG, TRAFFIC) == pytest.approx(hand,
                                                               rel=1e-12)
    assert abs(hand / 29.7e12 - 1) < 0.01          # ISSUE 34: 29.7 TFLOP
    # the distinctive part: allowed pairs 42% of the model's operations,
    # the latent block as a whole 63%; a sparse layer is 82% latent attention
    assert round(6 * attn / position, 2) == 0.42
    assert round(6 * (attn + latent) / position, 2) == 0.63
    assert round((attn + latent) / (attn + latent + sparse), 2) == 0.82
    # tokens are counted once, whatever the module predicts
    assert builder.units_per_step(CFG, TRAFFIC) == 8192
    # the balanced expectation of held rows: 4,096 a sparse layer, a third
    # into the second chunk of the 32,768-row buffer's chunks of 3,072
    from mxnet_tpu.gluon.nn import moe
    assert 4 * 8 / 64 * t == 4096 and moe.chunk_rows(4 * t) == 3072
    assert moe.loops(4, 8, 64) == {"positions": True, "buffer": True}
    x, y = jax.eval_shape(
        lambda k: builder.make_batch(CFG, TRAFFIC, k), jax.random.PRNGKey(0))
    assert x.shape == y.shape == (1, t + 1)       # T + 2 ids a sequence


def test_readers_pairs_operations_and_bytes_at_a_head_of_256():
    calls = builder.attention_calls(CFG, TRAFFIC, 1)
    assert calls == {"mla": {
        "batch": 1, "heads": 20, "kv_heads": 20, "seq": 8192,
        "head_dim": 256, "layers": 6, "window": None,
        "pairs": 8192 * 8193 // 2}}
    call = calls["mla"]
    fwd = rule_readers.flash_ops_bytes("flash_fwd", call)
    dq = rule_readers.flash_ops_bytes("flash_bwd_dq", call)
    dkv = rule_readers.flash_ops_bytes("flash_bwd_dkv", call)
    assert fwd[0] == 2 * 2 * 20 * 33_558_528 * 256 == 687_278_653_440
    assert (dq[0], dkv[0]) == (1.5 * fwd[0], 2 * fwd[0])
    # q, o, k and v a head (as many KV heads as query heads; the shared
    # rotary key is counted a head, as the kernel reads it), bf16; lse rows
    assert fwd[1] == 4 * 20 * 8192 * 256 * 2 + 20 * 8192 * 4
    assert dkv[1] == 6 * 20 * 8192 * 256 * 2 + 2 * 20 * 8192 * 4
    peaks = bench.load_json("peaks.json")["tpu v5 lite"]
    readers = bench.load_module("", "readers")
    least, bound = readers.least_seconds(*fwd, peaks)
    assert bound == "compute" and 3.4e-3 < least < 3.6e-3   # 688 GFLOP
    # kernels on 512 x 512 tiles visit 136 tiles a head: the share of their
    # work that is allowed pairs bounds their roofline under 100
    assert 33_558_528 / (136 * 512 * 512) < 0.95


def _made_up_trace():
    ms = 1_000_000
    fwd = "jit(mx_step)/jvp(mx.step.forward)/checkpoint/mx.attn.mla/" \
        "mx.attn.causal/pallas_call"
    bwd = "jit(mx_step)/transpose(jvp(mx.step.forward))/checkpoint/" \
        "mx.attn.mla/mx.attn.causal/pallas_call"
    low_rank = "jit(mx_step)/jvp(mx.step.forward)/checkpoint/mx.attn.mla/dot"
    module = "jit(mx_step)/jvp(mx.step.forward)/checkpoint/mx.mtp/"
    other = "jit(mx_step)/jvp(mx.step.forward)/checkpoint/mx.attn.causal/" \
        "mx.attn.causal/pallas_call"               # another model's call
    ops, modules = [], []
    for t in (0, 40 * ms):
        modules.append([t, t + 30 * ms, "jit_mx_step(1)"])
        ops += [[t, t + 6 * ms, "flash_fwd.1", fwd, ""],
                [t + 6 * ms, t + 7 * ms, "fusion.7", low_rank, ""],
                [t + 7 * ms, t + 10 * ms, "flash_bwd_dq.1", bwd, ""],
                [t + 10 * ms, t + 14 * ms, "flash_bwd_dkv.1", bwd, ""],
                [t + 14 * ms, t + 16 * ms, "flash_fwd.2",
                 module + "mx.attn.mla/mx.attn.causal/pallas_call", ""],
                [t + 16 * ms, t + 17 * ms, "fusion.9", module + "dot", ""],
                [t + 17 * ms, t + 19 * ms, "flash_fwd.3", other, ""],
                [t + 19 * ms, t + 30 * ms, "fusion.3", "jit(mx_step)/x", ""]]
    return {"host": [], "devices": {0: {"ops": ops, "modules": modules}}}


def test_new_readers_on_a_made_up_trace():
    loaded = _made_up_trace()
    # the module's call is one of the latent block's; a call under the
    # causal scope alone (another model's) is not
    assert rule_readers.kernel_calls(loaded, "mla", "flash_fwd") \
        == (4, 0.016)
    assert rule_readers.kernel_calls(loaded, "causal", "flash_fwd") \
        == (6, 0.020)
    readers = bench.load_module("", "readers")
    peaks = bench.load_json("peaks.json")["tpu v5 lite"]
    calls = builder.attention_calls(CFG, TRAFFIC, 1)
    least = readers.least_seconds(
        *rule_readers.flash_ops_bytes("flash_fwd", calls["mla"]), peaks)[0]
    got = rule_readers.flash_roofline_pct_of(
        loaded, calls, peaks, readers, "mla", ["flash_fwd"])
    assert got == pytest.approx(100 * 4 * least / 0.016)
    least_b = sum(readers.least_seconds(
        *rule_readers.flash_ops_bytes(k, calls["mla"]), peaks)[0]
        for k in ("flash_bwd_dq", "flash_bwd_dkv"))
    both = rule_readers.flash_roofline_pct_of(
        loaded, calls, peaks, readers, "mla",
        ["flash_bwd_dq", "flash_bwd_dkv"])
    assert both == pytest.approx(100 * 2 * least_b / 0.014)
    # the scopes' time a step: kernels and the work around them
    assert block_readers.scope_ms_of(loaded, "mx.attn.mla") == 16.0
    assert block_readers.scope_ms_of(loaded, "mx.mtp") == 3.0


def test_every_new_reader_is_silent_without_a_trace_or_the_builder(
        tmp_path, monkeypatch):
    """Outside a traced run there is no .chipbench_trace/: every reader
    this PR adds returns None and does not raise; nor does a roofline
    reader given a builder that knows no ``mla`` call (Laguna's) or no
    ``attention_calls`` at all (the parent's other cells), or a program
    without the scopes (the parent's)."""
    monkeypatch.setattr(spans, "ROOT", str(tmp_path))
    for name in NEW:
        assert _load(name, "metrics").read({}) is None
    for other in ("bert_base", "laguna_xs2"):
        assert rule_readers.flash_roofline_pct(
            {"builder": bench.load_module("configs", other),
             "cfg": bench.load_json("configs", other + ".json"),
             "traffic": TRAFFIC, "chips": 1}, "mla", ["flash_fwd"]) is None
    unscoped = _made_up_trace()
    for op in unscoped["devices"][0]["ops"]:
        op[3] = "jit(mx_step)/x"
    readers = bench.load_module("", "readers")
    peaks = bench.load_json("peaks.json")["tpu v5 lite"]
    assert rule_readers.flash_roofline_pct_of(
        unscoped, builder.attention_calls(CFG, TRAFFIC, 1), peaks, readers,
        "mla", ["flash_fwd"]) is None
    assert block_readers.scope_ms_of(unscoped, "mx.attn.mla") is None
    assert block_readers.scope_ms_of(unscoped, "mx.mtp") is None


def test_readers_repeat_on_the_recorded_trace():
    """The cell's trace recorded on the chip (PR 34), trimmed to two steps:
    the scope and roofline readers give the recorded numbers exactly, the
    latent block's calls are the three flash kernels, six layers' of them,
    and no share passes 100."""
    with gzip.open(os.path.join(BENCH, "fixtures",
                                CELL + ".spans.json.gz"), "rt") as f:
        fixture = json.load(f)
    loaded = fixture["loaded"]
    for scope, want in fixture["rule_scopes"].items():
        assert want is not None and want > 0
        assert block_readers.scope_ms_of(loaded, scope) == want
    assert sorted(fixture["rule_scopes"]) == [
        "mx.attn.mla", "mx.moe.experts", "mx.moe.route", "mx.moe.shared",
        "mx.mtp"]
    # the module holds a whole decoder layer: more than a sixth of the
    # latent blocks' time lies under it, and less than the whole
    assert fixture["rule_scopes"]["mx.mtp"] \
        > fixture["rule_scopes"]["mx.attn.mla"] / 6
    readers = bench.load_module("", "readers")
    peaks = bench.load_json("peaks.json")["tpu v5 lite"]
    calls = builder.attention_calls(CFG, TRAFFIC, 1)
    assert sorted(fixture["rule_rooflines"]) == sorted(NEW[:2])
    for name, want in fixture["rule_rooflines"].items():
        kernels = ["flash_fwd"] if "_fwd_" in name \
            else ["flash_bwd_dq", "flash_bwd_dkv"]
        got = rule_readers.flash_roofline_pct_of(loaded, calls, peaks,
                                                 readers, "mla", kernels)
        assert got == want and 0 < got < 100
    ops = loaded["devices"]["0"]["ops"]
    mine = [op for op in ops if "mx.attn.mla" in op[3] and "flash" in op[2]]
    assert {op[2].split(".")[0] for op in mine} == {
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    # every kernel call of the step is the latent block's, under the causal
    # rule's scope within it
    assert len(mine) == len([op for op in ops if "flash" in op[2]])
    assert all("mx.attn.mla/mx.attn.causal" in op[3] for op in mine)
    # two steps; a layer calls the forward twice (recomputed); six layers,
    # the module's among them
    assert len([op for op in mine if op[2].startswith("flash_fwd")]) \
        == 2 * 2 * 6
    assert len([op for op in mine if op[2].startswith("flash_bwd_dq")]) \
        == 2 * 6
    assert len([op for op in mine if "mx.mtp" in op[3]]) == 2 * 4
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
    """In float32 the program (flash kernels in interpret mode at the latent
    block's expanded heads, sorted grouped products, a sigmoid router under
    a selection bias, two sets of logits from one head) and the plain
    reference (chunked dense attention, a loop over experts) are the same
    mathematics: both steps' losses, every leaf's gradient (the embedding's
    and the head's are sums of two paths) and every leaf's change over two
    steps agree to rounding."""
    check = bench.load_module("", "check")
    cell, pool, program, want = float32_steps
    numbers, _ = check.readings(program, want)
    assert all(v < 1e-3 for v in numbers.values()), numbers
    assert len(program["losses"]) == len(want["losses"]) == 2
    trained = {n for n, (_, kind) in builder.weight_shapes(cell.cfg).items()
               if kind != "bias"}
    assert set(program["grad_norms"]) == set(want["grad_norms"]) == trained
    assert set(want["delta_norms"]) == trained
    assert "layers.1.moe.select_bias" not in trained
    for name, norm in want["grad_norms"].items():       # EVERY gradient
        assert abs(program["grad_norms"][name] - norm) \
            <= 1e-3 * max(norm, 1e-6), name
        got, ref = program["grad_samples"][name], want["grad_samples"][name]
        assert np.abs(got - ref).max() <= 2e-3 * max(np.abs(ref).max(),
                                                     1e-6), name
    for name in ("embed.weight", "head.weight", "mtp.proj.weight",
                 "mtp.layer.moe.w1", "layers.1.attention.kv_b_proj.weight"):
        assert want["grad_norms"][name] > 0 and want["delta_norms"][name] > 0


def test_control_and_planted_faults_fail_the_cells_limits(float32_steps):
    check = bench.load_module("", "check")
    cell, pool, _, want = float32_steps
    for how in ({"precision": cell.cfg["controls"][0]},   # fp8
                {"rows": 1},            # half of a batch of two sequences
                {"rows": 0}):           # half of ONE sequence's positions
        numbers, _ = check.compare(cell.follow(11, pool, **how), want,
                                   cell.limits)
        assert not check.passed(numbers), (how, numbers)


def _reference_logits(cfg, traffic, weights, x):
    """(main logits, module logits) of the reference's forward, position by
    position, from its own pieces."""
    eps = cfg["rms_norm_eps"]
    rest, stacks, module = reference._pack(weights, cfg)
    h = rest["embed.weight"][x[:, :-1]]
    for stacked in stacks:
        for i in range(next(iter(stacked.values())).shape[0]):
            h = reference._layer(cfg, traffic, None, h,
                                 {n: a[i] for n, a in stacked.items()})
    h = reference._rms(h, rest["norm.gamma"], eps)
    joined = jnp.concatenate(
        [reference._rms(h, module["hidden_norm.gamma"], eps),
         reference._rms(rest["embed.weight"][x[:, 1:]],
                        module["embed_norm.gamma"], eps)], -1)
    lw = {n[len("layer."):]: a for n, a in module.items()
          if n.startswith("layer.")}
    h2 = reference._rms(
        reference._layer(cfg, traffic, None,
                         joined @ module["proj.weight"].T, lw),
        module["norm.gamma"], eps)
    return h @ rest["head.weight"].T, h2 @ rest["head.weight"].T


def test_program_logits_are_the_references(float32_steps):
    """The zoo's model on the seed's weights against the reference's
    forward, position by position, both sets of logits; the step program
    holds no (T, T) operand: all the latent blocks run in the flash
    kernels."""
    from mxnet_tpu import nd

    cell, pool, _, _ = float32_steps
    key = jax.random.fold_in(bench.seed_key(11), 0)
    weights = builder.make_weights(cell.cfg, key)
    trainer = builder.make_trainer(cell.cfg, dict(weights), None)
    x, y = pool[0]
    got = [o.asnumpy() for o in trainer._block(nd.NDArray(x))]
    with jax.default_matmul_precision("highest"):
        want = _reference_logits(cell.cfg, cell.traffic, weights, x)
    for mine, theirs in zip(got, want):
        np.testing.assert_allclose(mine, theirs, atol=2e-5)
        assert float(np.abs(theirs).max()) > 1e-3
    assert np.abs(got[0] - got[1]).max() > 1e-3
    t = x.shape[1] - 1
    text = trainer._lower(x, y).as_text()
    # dense attention would hold (B, H, T, T) scores; the interpreted
    # kernels work on tiles
    assert "x%dx%dx" % (t, t) not in text


def test_the_reference_chooses_by_the_bias_and_weighs_by_the_scores():
    """A bias large enough to flip choices changes which experts the
    reference's layer runs, and the weights stay those of the scores: the
    layer under a bias equals a hand-written mixture over the experts chosen
    by ``score + bias``."""
    p = _layer()
    bias = jnp.zeros(16).at[3].set(5.0)
    cfg = {"num_experts_per_tok": 4, "first_expert": 0,
           "routed_scaling_factor": 1.8}
    lw = {n: a for n, a in p.items() if n != "x"}
    with jax.default_matmul_precision("highest"):
        plain = reference._moe(cfg, None, p["x"], lw)
        biased = reference._moe(cfg, None, p["x"],
                                dict(lw, **{"moe.select_bias": bias}))
        s = jax.nn.sigmoid(p["x"] @ p["moe.gate"].T)
        _, top_e = jax.lax.top_k(s + bias, 4)
        assert bool((top_e == 3).any(-1).all())
        assert not bool((jax.lax.top_k(s, 4)[1] == 3).any(-1).all())
        want = reference._gated(p["x"], p["moe.shared_wg"],
                                p["moe.shared_w1"], p["moe.shared_w2"], None)
        for n in range(p["x"].shape[0]):
            chosen = np.asarray(top_e[n])
            w = 1.8 * s[n, chosen] / jnp.sum(s[n, chosen])
            for e, w_e in zip(chosen, w):
                want = want.at[n].add(w_e * reference._gated(
                    p["x"][n:n + 1], p["moe.wg"][e], p["moe.w1"][e],
                    p["moe.w2"][e], None)[0])
    np.testing.assert_allclose(biased, want, atol=2e-5)
    assert float(jnp.abs(biased - plain).max()) > 1e-2


# --------------------------------------- the share tied to the whole layer
def _layer(n=48, c=16, hidden=24, experts=16, seed=3):
    k = jax.random.split(jax.random.PRNGKey(seed), 9)
    return {"x": jax.random.normal(k[0], (n, c)),
            "moe.gate": jax.random.normal(k[1], (experts, c)),
            "moe.select_bias": 0.3 * jax.random.normal(k[8], (experts,)),
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
        select_bias=p["moe.select_bias"], top_k=top_k, first=first,
        activation="silu", score="sigmoid", scale=1.8)


def _reference_layer(p, first, count, top_k=4, shared=True):
    cfg = {"num_experts_per_tok": top_k, "first_expert": first,
           "routed_scaling_factor": 1.8}
    held = slice(first, first + count)
    lw = {n: (a[held] if n in ("moe.w1", "moe.wg", "moe.w2") else a)
          for n, a in p.items() if n != "x"}
    if not shared:      # a shared expert of zeros adds nothing
        lw["moe.shared_w2"] = jnp.zeros_like(lw["moe.shared_w2"])
    with jax.default_matmul_precision("highest"):
        return reference._moe(cfg, None, p["x"], lw)


def test_eight_shares_add_up_to_the_uncut_layer_shared_expert_once():
    """Each chip's share holds the shared expert whole (it is replicated)
    and chooses under the same selection bias; what the 8 chips' EXPERTS
    add, plus the shared expert counted once, is the uncut layer of the
    reference."""
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
    # the routed parts alone are 1.8 x a convex mix: not small
    assert float(jnp.abs(whole - shared).max()) > 0.1
    # and the bias matters to them: without it the whole layer differs
    no_bias = _reference_layer(
        dict(p, **{"moe.select_bias": jnp.zeros(16)}), 0, 16)
    assert float(jnp.abs(no_bias - whole).max()) > 1e-2
