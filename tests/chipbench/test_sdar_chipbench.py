"""Tests of what the ``sdar_30b_a3b`` configuration adds to the benchmark, on
the CPU at the rehearsal size: the plain reference against the program in
float32 and its fp8 control failing the cell's limits; the share of one chip
tied to the whole layer (8 shares add up to the uncut reference's MoE layer;
nothing is dropped when every pick is a held expert; zeros and finite
gradients when none is); hand counts of the step's and the kernels'
operations and bytes; the scope reader on a made-up trace.  No speed is read
here."""
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
CELL = "sdar_30b_a3b_bd4k"


def _load(name, kind=""):
    spec = importlib.util.spec_from_file_location(
        "sdar_test_" + name, os.path.join(BENCH, kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


bench = _load("run")
# block_readers says `import spans` (run.py's directory is on sys.path when
# it runs as a script): put the module where that import finds it
spans = sys.modules.setdefault("spans", _load("spans"))
block_readers = _load("block_readers")
builder = bench.load_module("configs", "sdar_30b_a3b")
reference = bench.load_module("reference", "sdar_30b_a3b")
CFG = bench.load_json("configs", "sdar_30b_a3b.json")
TRAFFIC = bench.load_json("traffic", "bd4k_b2.json")


# ------------------------------------------------ the configuration's file
def test_configuration_keeps_every_published_number_but_the_reduced():
    # the published config.json, copied from the model-configs guide's
    # catalog: a checkout does not carry the guide
    row = [bench.load_json("fixtures", "sdar_30b_a3b.published.json")]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == "sdar_30b_a3b"][0]
    assert entry["source"] == row[0]["source_url"] == CFG["source"]
    differs = sorted(k for k, v in row[0]["config"].items()
                     if CFG.get(k, "missing") != v)
    assert differs == sorted(entry["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert CFG["published"] == {k: row[0]["config"][k] for k in differs}
    # the cut: depth over the floor, an eighth of experts and vocabulary,
    # the router as wide as published, 8 experts a token
    assert CFG["num_hidden_layers"] >= 4
    assert CFG["num_experts"] * 8 == CFG["router_experts"] == 128
    assert CFG["vocab_size"] * 8 == 151936
    assert CFG["num_experts_per_tok"] == 8
    assert "8 chips" in CFG["deployment"]


# ------------------------------------------------------- operation counts
def test_sdar_operation_count_against_a_hand_count():
    # by hand, one of the 16,384 positions of a step, one layer, forward:
    proj = 2 * 2048 * 4096 + 2 * (2 * 2048 * 512) + 2 * 4096 * 2048
    assert proj == 37_748_736
    # allowed pairs: L^2 + L*b of the 4 L^2, a query sees (L + b) / 2 keys;
    # QK^T and PV, 32 heads of 128
    pairs = 4096 * 4096 + 4096 * 4
    assert builder.allowed_pairs(TRAFFIC) == pairs == 16_793_600
    attn = 2 * 2 * 32 * 128 * pairs / 8192
    assert attn == 33_587_200
    router = 2 * 2048 * 128
    experts = (8 * 16 / 128) * 3 * 2 * 2048 * 768   # one expected row
    layer = proj + attn + router + experts
    assert builder.forward_ops_per_position_layer(CFG, TRAFFIC) == layer \
        == 81_297_408
    head = 2 * 2048 * 18992 * 2 * 4096               # the noisy half only
    hand = 3 * (6 * 16384 * layer + head)
    assert builder.ops_per_step(CFG, TRAFFIC) == hand
    assert 25.8e12 < hand < 26.0e12
    assert builder.units_per_step(CFG, TRAFFIC) == 8192   # tokens, not 2L


def test_masked_flash_kernel_operations_and_bytes():
    call = builder.attention_call(CFG, TRAFFIC, 1)
    assert call == {"batch": 2, "heads": 32, "kv_heads": 4, "seq": 4096,
                    "block": 4, "head_dim": 128}
    pairs = 4096 * 4096 + 4096 * 4
    ops, nbytes = block_readers.flash_bd_ops_bytes("flash_fwd", call)
    assert ops == 2 * 2 * 2 * 32 * pairs * 128        # QK^T and PV
    # q and o a query head, k and v ONCE a KV head, bf16; lse rows float32
    assert nbytes == (2 * 32 + 2 * 4) * 2 * 8192 * 128 * 2 \
        + 2 * 32 * 8192 * 4
    dq = block_readers.flash_bd_ops_bytes("flash_bwd_dq", call)
    dkv = block_readers.flash_bd_ops_bytes("flash_bwd_dkv", call)
    assert (dq[0], dkv[0]) == (1.5 * ops, 2 * ops)
    assert dkv[1] == (2 * 32 + 4 * 4) * 2 * 8192 * 128 * 2 \
        + 2 * 2 * 32 * 8192 * 4
    peaks = bench.load_json("peaks.json")["tpu v5 lite"]
    readers = bench.load_module("", "readers")
    seconds, bound = readers.least_seconds(ops, nbytes, peaks)
    assert bound == "compute" and abs(seconds - ops / 197e12) < 1e-12
    # a kernel that works on 512 x 512 tiles touches 80 of 256: the share
    # of its work that is allowed pairs bounds its roofline under 100%
    assert pairs / (80 * 512 * 512) < 1


def test_roofline_reader_on_made_up_seconds_and_silent_without_the_builder():
    readers = bench.load_module("", "readers")
    peaks = bench.load_json("peaks.json")["tpu v5 lite"]
    call = builder.attention_call(CFG, TRAFFIC, 1)
    ops, nbytes = block_readers.flash_bd_ops_bytes("flash_fwd", call)
    least = readers.least_seconds(ops, nbytes, peaks)[0]
    trace = {"fullest": 0, "devices": {0: {
        "op_seconds": {"flash_fwd": 12 * 4 * least, "fusion": 1.0},
        "op_counts": {"flash_fwd": 12, "fusion": 7}}}}
    ctx = {"trace": trace, "builder": builder, "cfg": CFG,
           "traffic": TRAFFIC, "chips": 1, "peaks": peaks,
           "readers": readers}
    assert block_readers.flash_bd_roofline_pct(ctx, ["flash_fwd"]) \
        == pytest.approx(25.0)
    assert block_readers.flash_bd_roofline_pct(ctx, ["flash_bwd_dq"]) is None
    ctx["builder"] = bench.load_module("configs", "bert_base")
    assert block_readers.flash_bd_roofline_pct(ctx, ["flash_fwd"]) is None


def test_scope_reader_on_a_made_up_trace():
    ms = 1_000_000
    route = "jit(mx_step)/jvp(mx.step.forward)/mx.moe.route/gather"
    route_b = ("jit(mx_step)/transpose(jvp(mx.step.forward))/checkpoint/"
               "mx.moe.route/gather")
    experts = "jit(mx_step)/jvp(mx.step.forward)/mx.moe.experts/ragged_dot"
    ops, modules = [], []
    for t in (0, 20 * ms):
        modules.append([t, t + 10 * ms, "jit_mx_step(1)"])
        ops += [[t, t + 2 * ms, "fusion.1", route, ""],
                [t + 2 * ms, t + 5 * ms, "ragged-dot.1", experts, ""],
                [t + 5 * ms, t + 6 * ms, "fusion.2", route_b, ""],
                [t + 6 * ms, t + 10 * ms, "fusion.3", "jit(mx_step)/x", ""]]
    loaded = {"host": [], "devices": {0: {"ops": ops, "modules": modules}}}
    assert block_readers.scope_ms_of(loaded, "mx.moe.route") == 3.0
    assert block_readers.scope_ms_of(loaded, "mx.moe.experts") == 3.0
    assert block_readers.scope_ms_of(loaded, "mx.attn.") is None  # never 0
    assert block_readers.scope_ms_of({"host": [], "devices": {}},
                                     "mx.moe.route") is None


def test_scope_reader_repeats_on_the_recorded_trace():
    """The cell's trace recorded on the chip (PR 26), trimmed to two steps:
    the scope reader gives the recorded milliseconds exactly, and the
    kernels in it are the masked flash kernels at 8,192 positions."""
    with gzip.open(os.path.join(BENCH, "fixtures",
                                CELL + ".spans.json.gz"), "rt") as f:
        fixture = json.load(f)
    for scope, want in fixture["block_scopes"].items():
        assert want is not None and want > 0
        assert block_readers.scope_ms_of(fixture["loaded"], scope) == want
    ops = fixture["loaded"]["devices"]["0"]["ops"]
    kernels = {op[2].split(".")[0] for op in ops
               if "mx.attn.block_diffusion" in op[3] and "flash" in op[2]}
    assert kernels == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    # forward, recomputation and backward all carry the scopes
    route = [op[3] for op in ops if "mx.moe.route" in op[3]]
    assert any("transpose(" in n for n in route)
    assert any("transpose(" not in n for n in route)


def test_every_reader_this_cell_reports_finds_no_trace(tmp_path, monkeypatch):
    """Outside a traced run there is no .chipbench_trace/: every per-layer
    reader of the cell's that reads the program's spans or scopes (PR 24's
    five and this PR's two) returns None and does not raise."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    monkeypatch.setattr(spans, "ROOT", str(tmp_path))
    names = [m["name"] for m in manifest["per_layer"]
             if CELL in m.get("workloads", [CELL])
             and (m["name"] in spans.METRICS or m["name"].startswith("moe_"))]
    assert sorted(names) == sorted(list(spans.METRICS)
                                   + ["moe_route_ms", "moe_experts_ms"])
    sys.modules.setdefault("block_readers", block_readers)
    for name in names:
        assert _load(name, "metrics").read({}) is None
    # this PR's entries stand straight after PR 24's five, which keep their
    # order, unit and end-to-end metric.  Nothing here says "the last": a
    # later PR appends its own entries, cells and configurations
    order = [m["name"] for m in manifest["per_layer"]]
    at = order.index(list(spans.METRICS)[0])
    theirs = manifest["per_layer"][at:at + 5]
    assert [m["name"] for m in theirs] == list(spans.METRICS)
    assert {(m["moves"], m["unit"]) for m in theirs} == {
        ("step_ms_p95", "ms")}
    assert order[at + 5:at + 9] == [
        "flash_bd_fwd_roofline", "flash_bd_bwd_roofline", "moe_route_ms",
        "moe_experts_ms"]
    assert CELL in [w["name"] for w in manifest["workloads"]]
    assert "sdar_30b_a3b" in [c["name"] for c in manifest["configs"]]


# ------------------------------ the reference against the program, float32
def test_reference_agrees_with_the_program_in_float32():
    """In float32 the program (flash kernels in interpret mode, sorted
    grouped products) and the plain reference (chunked dense attention, a
    loop over experts) are the same mathematics: every number agrees to
    rounding.  And the reference in fp8 fails the cell's own limits."""
    check = bench.load_module("", "check")
    cell = bench.Cell(CELL, rehearse=True)
    cell.cfg["compute_dtype"] = "float32"
    trainer, pool, program = cell.first_steps(11)
    del trainer
    want = cell.follow(11, pool)
    numbers, _ = check.readings(program, want)
    assert all(v < 1e-3 for v in numbers.values()), numbers
    control = cell.follow(11, pool, precision=cell.cfg["controls"][0])
    numbers, _ = check.compare(control, want, cell.limits)
    assert not check.passed(numbers), numbers
    # half a batch fails them too
    numbers, _ = check.compare(cell.follow(11, pool, rows=1), want,
                               cell.limits)
    assert not check.passed(numbers), numbers


# --------------------------------------- the share tied to the whole layer
def _layer(n=48, c=16, hidden=24, experts=16, seed=3):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"x": jax.random.normal(k[0], (n, c)),
            "gate": jax.random.normal(k[1], (experts, c)),
            "w1": 0.3 * jax.random.normal(k[2], (experts, c, hidden)),
            "wg": 0.3 * jax.random.normal(k[3], (experts, c, hidden)),
            "w2": 0.3 * jax.random.normal(k[4], (experts, hidden, c))}


def _share(p, first, count, top_k=4):
    from mxnet_tpu.gluon.nn.moe import moe_forward

    held = slice(first, first + count)
    return moe_forward(p["x"], p["gate"], p["w1"][held], p["w2"][held],
                       wg=p["wg"][held], top_k=top_k, first=first,
                       activation="silu")


def _reference_share(p, first, count, top_k=4):
    cfg = {"num_experts_per_tok": top_k, "first_expert": first,
           "norm_topk_prob": True}
    held = slice(first, first + count)
    with jax.default_matmul_precision("highest"):
        return reference._moe(cfg, None, p["x"], p["gate"], p["w1"][held],
                              p["wg"][held], p["w2"][held])


def test_eight_shares_add_up_to_the_uncut_references_layer():
    p = _layer()
    whole = _reference_share(p, 0, 16)                 # all experts held
    parts = [_share(p, first, 2) for first in range(0, 16, 2)]
    for first, part in zip(range(0, 16, 2), parts):
        np.testing.assert_allclose(part, _reference_share(p, first, 2),
                                   atol=2e-5)
    np.testing.assert_allclose(sum(parts), whole, atol=5e-5)
    assert float(jnp.abs(whole).max()) > 0.1


def test_nothing_is_dropped_when_every_pick_is_a_held_expert():
    """A router biased so that every position's top-k are all held
    experts: the sorted buffer is full to its last row (positions x top_k
    real rows) and the result is the reference's, exactly."""
    from mxnet_tpu.gluon.nn import moe as moe_mod

    p = _layer()
    p["x"] = p["x"].at[:, 0].set(1.0)
    p["gate"] = p["gate"].at[4:8, 0].add(50.0)        # experts 4..8 win
    r = moe_mod.route(p["x"], p["gate"], 4, 4, 4)
    assert bool(r["held"].all())
    assert int(r["rows"]) == 48 * 4 == r["row_token"].shape[0]
    np.testing.assert_allclose(_share(p, 4, 4), _reference_share(p, 4, 4),
                               atol=2e-5)
    np.testing.assert_allclose(_share(p, 4, 4), _reference_share(p, 0, 16),
                               atol=2e-5)              # the whole layer


def test_no_position_picks_a_held_expert_output_zero_gradients_finite():
    p = _layer()
    p["x"] = p["x"].at[:, 0].set(1.0)
    p["gate"] = p["gate"].at[4:8, 0].add(-50.0)       # experts 4..8 lose

    def loss(x, gate, w1, wg, w2):
        q = dict(x=x, gate=gate, w1=w1, wg=wg, w2=w2)
        return jnp.sum(_share(q, 4, 4) ** 2) + jnp.sum(_share(q, 4, 4))

    out = _share(p, 4, 4)
    assert float(jnp.abs(out).max()) == 0.0
    grads = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(
        p["x"], p["gate"], p["w1"], p["wg"], p["w2"])
    assert all(bool(jnp.isfinite(g).all()) for g in grads)
