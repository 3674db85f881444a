"""mx.shard — global-mesh SPMD training with ZeRO-1/2/3 weight-update
sharding of the captured step program (ISSUE 12).

Covers: GlobalMesh construction/spec rules/process-global config, zero
level normalization + Trainer validation, the acceptance block (ZeRO-3
captured = ONE program, 10-step bit parity vs the unsharded captured
reference on the same mesh, per-device optimizer-state bytes <= ~1/dp),
ZeRO-1/2 parity, the unsharded_mesh fallback for meshless multi-process
capture, gather-home on stitched fallback, in-program skip_step on a
mesh, sharded-state pod checkpoints restored across world shrink/grow
(4 -> 2 and 4 -> 8) with bit-identical continued training, the
collective wire-byte pricing, the DistTimeout seam around the sharded
dispatch, and a supervisor fault drill on the ZeRO-3 program.

The "unsharded captured reference" is the captured step on the SAME
mesh with a replicated weight update (zero=0): sharding the update
must change layout and wire bytes, never math.  (A single-device run
is NOT bit-comparable — the cross-replica sum associates differently.)
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, monitor, nd, shard, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.kvstore import collective
from mxnet_tpu.resilience import inject

BATCH, DIN, DOUT = 8, 12, 4


def _jax():
    import jax

    return jax


@pytest.fixture(autouse=True)
def _clean():
    telemetry.enable()
    inject.clear()
    shard.reset()
    monitor.core.reset()
    yield
    inject.clear()
    shard.reset()
    monitor.disable()
    monitor.core.reset()
    for var in ("MXNET_SHARD_DP", "MXNET_SHARD_MDL", "MXNET_SHARD_DATA",
                "MXNET_STEP_CAPTURE", "MXNET_MONITOR_SENTINEL",
                "MXNET_DIST_COLLECTIVE_TIMEOUT"):
        os.environ.pop(var, None)


def _mesh(dp=4):
    return shard.GlobalMesh(dp=dp, devices=_jax().devices()[:dp])


def _make(optname="adam", opt_params=None, zero=0, mesh=None, seed=0):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu", in_units=DIN),
            nn.Dense(DOUT, in_units=16))
    net.initialize()
    net.hybridize()
    trainer = gluon.Trainer(
        net.collect_params(), optname,
        dict(opt_params or {"learning_rate": 0.01}),
        zero=zero, mesh=mesh)
    return net, trainer


def _data(seed=0, nan_at=None):
    rs = np.random.RandomState(seed)
    x = rs.randn(BATCH, DIN).astype(np.float32)
    if nan_at is not None:
        x[nan_at] = np.nan
    y = rs.randn(BATCH, DOUT).astype(np.float32)
    return nd.array(x), nd.array(y)


def _run(prog, steps, x, y):
    for _ in range(steps):
        loss = prog(x, y)
    return loss


def _assert_same_params(net_a, net_b):
    pa, pb = net_a.collect_params(), net_b.collect_params()
    for k in pa:
        np.testing.assert_array_equal(pa[k].data().asnumpy(),
                                      pb[k].data().asnumpy(), err_msg=k)


def _assert_same_states(tr_a, tr_b):
    jax = _jax()
    assert set(tr_a._states) == set(tr_b._states)
    for i in tr_a._states:
        la = jax.tree_util.tree_leaves(tr_a._states[i])
        lb = jax.tree_util.tree_leaves(tr_b._states[i])
        for a, b in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(a._data),
                                          np.asarray(b._data),
                                          err_msg="state %d" % i)


def _state_device_bytes(trainer):
    return shard.device_bytes([trainer._states[i]
                               for i in sorted(trainer._states)])


# ---------------------------------------------------------------------------
# GlobalMesh + policy surface
# ---------------------------------------------------------------------------

def test_global_mesh_shapes_and_specs():
    gm = _mesh(4)
    assert gm.dp == 4 and gm.mdl == 1
    assert gm.describe()["axis_names"] == ["dp"]
    # first dp-divisible dim is sharded; nothing divisible -> replicated
    assert gm.spec_for((8, 3)) == _pspec("dp", None)
    assert gm.spec_for((3, 12)) == _pspec(None, "dp")
    assert gm.spec_for((3, 5)) == _pspec(None, None)
    gm2 = shard.GlobalMesh(dp=2, mdl=2, devices=_jax().devices()[:4])
    assert gm2.describe()["axis_names"] == ["dp", "mdl"]
    with pytest.raises(MXNetError, match="mdl"):
        shard.GlobalMesh(mdl=3, devices=_jax().devices()[:4])
    with pytest.raises(MXNetError, match="devices"):
        shard.GlobalMesh(dp=16, devices=_jax().devices()[:4])


def _pspec(*names):
    from jax.sharding import PartitionSpec as P

    return P(*names)


def test_configure_current_and_as_global():
    import jax.sharding as jsh

    assert shard.current() is None
    raw = jsh.Mesh(np.asarray(_jax().devices()[:4]), ("dp",))
    gm = shard.configure(raw)
    assert isinstance(gm, shard.GlobalMesh) and gm.dp == 4
    assert shard.current() is gm
    with pytest.raises(MXNetError, match="dp"):
        shard.as_global(jsh.Mesh(np.asarray(_jax().devices()[:4]),
                                 ("tp",)))


def test_auto_mesh_from_env():
    os.environ["MXNET_SHARD_DP"] = "2"
    gm = shard.current(auto=True)
    assert gm is not None and gm.dp == 2
    shard.reset()
    assert shard.current(auto=False) is None


def test_normalize_level_and_trainer_validation():
    assert shard.normalize_level(False) == 0
    assert shard.normalize_level(None) == 0
    assert shard.normalize_level(True) == 1
    assert shard.normalize_level(3) == 3
    with pytest.raises(MXNetError, match="ZeRO level"):
        shard.normalize_level(5)
    with pytest.raises(MXNetError, match="mesh"):
        _make(zero=2)
    with pytest.raises(MXNetError, match="update_on_kvstore"):
        net = nn.Dense(DOUT, in_units=DIN)
        net.initialize()
        gluon.Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1}, zero=3, mesh=_mesh().mesh,
                      update_on_kvstore=True)
    # True stays an alias for level 1; raw jax Mesh is adopted
    _, tr = _make(zero=True, mesh=_mesh().mesh)
    assert tr._zero == 1 and tr._zero_gmesh.dp == 4
    # a configured process-global mesh is picked up without mesh=
    shard.configure(_mesh())
    _, tr2 = _make(zero=2)
    assert tr2._zero == 2 and tr2._zero_gmesh.dp == 4


def test_wire_byte_pricing():
    assert collective.all_reduce_wire_bytes(1000, 4) == 1500
    assert collective.reduce_scatter_wire_bytes(1000, 4) == 750
    assert collective.all_reduce_wire_bytes(1000, 1) == 0
    pol = shard.ZeroPolicy(2, _mesh(4))
    assert pol.grad_collective_bytes(1000) == 750
    assert shard.ZeroPolicy(0, _mesh(4)).grad_collective_bytes(1000) \
        == 1500
    assert pol.describe()["grads"] == "reduce-scatter"
    # level 3 gathers params in forward AND backward
    assert shard.ZeroPolicy(3, _mesh(4)).param_gather_bytes(1000) == 1500
    assert shard.ZeroPolicy(1, _mesh(4)).param_gather_bytes(1000) == 750


# ---------------------------------------------------------------------------
# the acceptance block: ZeRO-3 captured on 4 virtual devices
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("optname,opt_params", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01, "wd": 1e-4}),
])
def test_zero3_captured_bit_parity_one_program(optname, opt_params):
    """ISSUE 12 acceptance: on 4 virtual devices the ZeRO-3 captured
    step is ONE program (step_capture_builds_total == 1 across 10
    steps), bit-identical params AND optimizer state vs the unsharded
    captured reference on the same mesh, and per-device optimizer-state
    bytes <= ~1/4 of replicated."""
    gm = _mesh(4)
    x, y = _data()
    net_r, tr_r = _make(optname, opt_params, zero=0, mesh=gm)
    prog_r = tr_r.capture(net_r, gluon.loss.L2Loss())
    loss_r = _run(prog_r, 10, x, y)
    assert prog_r.report()["paths"] == {"captured": 10, "stitched": 0}

    net_z, tr_z = _make(optname, opt_params, zero=3, mesh=gm)
    prog_z = tr_z.capture(net_z, gluon.loss.L2Loss())
    before = telemetry.value("step_capture_builds_total")
    loss_z = _run(prog_z, 10, x, y)
    assert telemetry.value("step_capture_builds_total") - before == 1
    assert prog_z.report()["paths"] == {"captured": 10, "stitched": 0}

    np.testing.assert_array_equal(loss_r.asnumpy(), loss_z.asnumpy())
    _assert_same_params(net_r, net_z)
    _assert_same_states(tr_r, tr_z)
    assert tr_r._step_count == tr_z._step_count == 10

    rep_bytes = _state_device_bytes(tr_r)   # replicated reference
    z3_bytes = _state_device_bytes(tr_z)
    assert z3_bytes <= rep_bytes / 4 + 64, \
        "ZeRO-3 state bytes/device %d vs replicated %d" % (z3_bytes,
                                                           rep_bytes)
    # ZeRO-3 params are dp-sharded between steps too
    p_rep = shard.device_bytes(
        [p.data() for p in net_r.collect_params().values()])
    p_z3 = shard.device_bytes(
        [p.data() for p in net_z.collect_params().values()])
    assert p_z3 <= p_rep / 4 + 64
    prog = prog_z.report()["programs"][0]
    assert prog["zero"] == 3
    allreduce = [s for s in prog["segments"]
                 if s["segment"] == "allreduce"][0]
    assert allreduce["collective"] == "reduce_scatter"
    assert allreduce["wire_bytes"] == collective.reduce_scatter_wire_bytes(
        allreduce["bytes"], 4)


@pytest.mark.parametrize("level", [1, 2])
def test_zero12_captured_bit_parity(level):
    """ZeRO-1 (state sharded; the old zero_trainer refusal now
    captures) and ZeRO-2 (grads reduce-scattered) match the unsharded
    mesh reference bit for bit; params stay replicated."""
    gm = _mesh(4)
    x, y = _data()
    net_r, tr_r = _make(zero=0, mesh=gm)
    prog_r = tr_r.capture(net_r, gluon.loss.L2Loss())
    _run(prog_r, 6, x, y)
    net_z, tr_z = _make(zero=level, mesh=gm)
    prog_z = tr_z.capture(net_z, gluon.loss.L2Loss())
    _run(prog_z, 6, x, y)
    assert prog_z.report()["paths"]["captured"] == 6
    _assert_same_params(net_r, net_z)
    _assert_same_states(tr_r, tr_z)
    assert _state_device_bytes(tr_z) <= _state_device_bytes(tr_r) / 4 + 64
    # params replicated below level 3: full-size on every device
    assert shard.device_bytes(
        [p.data() for p in net_z.collect_params().values()]) == \
        shard.device_bytes(
            [p.data() for p in net_r.collect_params().values()])
    prog = prog_z.report()["programs"][0]
    collective_kind = [s for s in prog["segments"]
                       if s["segment"] == "allreduce"][0]["collective"]
    assert collective_kind == ("reduce_scatter" if level >= 2
                               else "all_reduce")


def test_zero3_scheduler_zero_retrace():
    """Per-step scheduler lr rides the host-scalar slots in the sharded
    program too: one build, bit parity with the unsharded-mesh
    scheduled run."""
    from mxnet_tpu.optimizer import lr_scheduler

    def sched():
        return {"learning_rate": 0.05,
                "lr_scheduler": lr_scheduler.FactorScheduler(step=2,
                                                             factor=0.5)}

    gm = _mesh(4)
    x, y = _data()
    net_r, tr_r = _make("adam", sched(), zero=0, mesh=gm)
    _run(tr_r.capture(net_r, gluon.loss.L2Loss()), 8, x, y)
    net_z, tr_z = _make("adam", sched(), zero=3, mesh=gm)
    before = telemetry.value("step_capture_builds_total")
    _run(tr_z.capture(net_z, gluon.loss.L2Loss()), 8, x, y)
    assert telemetry.value("step_capture_builds_total") - before == 1
    _assert_same_params(net_r, net_z)
    _assert_same_states(tr_r, tr_z)


def test_data_replicate_mode_matches_dp_mode_program_count():
    """MXNET_SHARD_DATA=replicate feeds every replica the whole batch —
    still one captured program, still applied (drill mode)."""
    os.environ["MXNET_SHARD_DATA"] = "replicate"
    gm = _mesh(4)
    x, y = _data()
    net, tr = _make(zero=3, mesh=gm)
    prog = tr.capture(net, gluon.loss.L2Loss())
    _run(prog, 3, x, y)
    assert prog.report()["paths"] == {"captured": 3, "stitched": 0}
    assert tr._step_count == 3


# ---------------------------------------------------------------------------
# degradations: meshless multi-process, stitched gather-home
# ---------------------------------------------------------------------------

def test_multi_process_without_mesh_degrades_unsharded_mesh():
    net, tr = _make()
    prog = tr.capture(net, gluon.loss.L2Loss())
    prog._world = 2  # pretend a peer exists, no GlobalMesh configured
    before = telemetry.value("step_capture_fallback_total",
                             labels={"reason": "unsharded_mesh"})
    x, y = _data()
    prog(x, y)
    rep = prog.report()
    assert rep["paths"] == {"captured": 0, "stitched": 1}
    assert rep["fallbacks"][0]["reason"] == "unsharded_mesh"
    assert telemetry.value("step_capture_fallback_total",
                           labels={"reason": "unsharded_mesh"}) - \
        before == 1
    assert tr._step_count == 1  # degraded, never lost


def test_mesh_with_axis_name_conflicts():
    net, tr = _make(zero=0, mesh=_mesh(4))
    prog = mx.step.capture(net, gluon.loss.L2Loss(), trainer=tr,
                           axis_name="dp")
    x, y = _data()
    prog(x, y)
    assert prog.report()["fallbacks"][0]["reason"] == "mesh_conflict"
    assert tr._step_count == 1


def test_kill_switch_gathers_home_and_recaptures():
    """A stitched step on a ZeRO-3 trainer gathers params back to their
    single-device home (eager math never sees mesh arrays), applies the
    step, and the next captured step re-places + re-captures."""
    gm = _mesh(4)
    x, y = _data()
    net, tr = _make(zero=3, mesh=gm)
    prog = tr.capture(net, gluon.loss.L2Loss())
    prog(x, y)
    w = net.collect_params()["0.weight"].data()._data
    assert len(w.sharding.device_set) == 4
    os.environ["MXNET_STEP_CAPTURE"] = "0"
    prog(x, y)   # stitched: gathered home, still applied
    w = net.collect_params()["0.weight"].data()._data
    assert len(w.sharding.device_set) == 1
    assert tr._step_count == 2
    os.environ.pop("MXNET_STEP_CAPTURE")
    prog(x, y)   # re-placed + re-captured
    w = net.collect_params()["0.weight"].data()._data
    assert len(w.sharding.device_set) == 4
    rep = prog.report()
    assert rep["paths"]["captured"] == 2
    assert tr._step_count == 3


def test_skip_step_in_sharded_program_mutates_nothing():
    os.environ["MXNET_MONITOR_SENTINEL"] = "skip_step"
    monitor.enable()
    gm = _mesh(4)
    net, tr = _make(zero=3, mesh=gm)
    prog = tr.capture(net, gluon.loss.L2Loss())
    x, y = _data()
    prog(x, y)
    params0 = {k: p.data().asnumpy().copy()
               for k, p in net.collect_params().items()}
    counts0 = dict(tr._optimizer._index_update_count)
    sc0 = tr._step_count
    xbad, _ = _data(nan_at=3)
    loss = prog(xbad, y)
    assert np.isnan(loss.asnumpy()).any()
    for k, p in net.collect_params().items():
        np.testing.assert_array_equal(params0[k], p.data().asnumpy(),
                                      err_msg=k)
    assert dict(tr._optimizer._index_update_count) == counts0
    assert tr._step_count == sc0
    prog(x, y)
    assert tr._step_count == sc0 + 1


# ---------------------------------------------------------------------------
# sharded-state pod checkpoints: shrink/grow world
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("new_dp", [2, 8])
def test_pod_checkpoint_reshards_across_world_change(tmp_path, new_dp):
    """Save ZeRO-3 on world(dp)=4 through the pod-consistent protocol,
    restore onto dp=2 and dp=8 meshes: the shard layout changes, the
    math does not — continued training is bit-identical to an unsharded
    trainer restored from the SAME pod checkpoint on the SAME mesh."""
    from mxnet_tpu.dist import PodCheckpointManager, pod_latest_step

    gm4 = _mesh(4)
    x, y = _data()
    net, tr = _make(zero=3, mesh=gm4, seed=2)
    prog = tr.capture(net, gluon.loss.L2Loss())
    _run(prog, 4, x, y)
    pod = PodCheckpointManager(str(tmp_path), rank=0, world_size=1)
    pod.save(tr.step_count, tr.state_dict())
    assert pod.last_pod_commit == (4, True)
    assert pod_latest_step(str(tmp_path)) == 4

    gm_new = _mesh(new_dp) if new_dp <= 4 else shard.GlobalMesh(dp=new_dp)

    def restore_into(zero):
        net2, tr2 = _make(zero=zero, mesh=gm_new, seed=9)
        prog2 = tr2.capture(net2, gluon.loss.L2Loss())
        step, tree = PodCheckpointManager(
            str(tmp_path), rank=0, world_size=1).restore()
        tr2.load_state_dict(tree)
        assert tr2.step_count == 4
        _run(prog2, 3, x, y)
        assert prog2.report()["paths"]["captured"] == 3
        return net2, tr2

    net_z, tr_z = restore_into(3)
    net_u, tr_u = restore_into(0)
    _assert_same_params(net_z, net_u)
    _assert_same_states(tr_z, tr_u)
    assert _state_device_bytes(tr_z) < _state_device_bytes(tr_u)


# ---------------------------------------------------------------------------
# dist/resilience seams
# ---------------------------------------------------------------------------

def test_collective_deadline_wraps_sharded_dispatch():
    """On a GlobalMesh the armed MXNET_DIST_COLLECTIVE_TIMEOUT bounds
    the captured dispatch even in a single-process (virtual-device)
    drill — a hang raises the transient DistTimeout with state marked
    suspect and the count bump rewound."""
    import time

    from mxnet_tpu.dist.timeouts import DistTimeout

    gm = _mesh(4)
    net, tr = _make(zero=3, mesh=gm)
    prog = tr.capture(net, gluon.loss.L2Loss())
    x, y = _data()
    prog(x, y)
    cap = next(iter(prog._programs.values()))
    orig_cfn, orig_jfn = cap.cfn, cap.jfn

    def slow_call(*args):
        # a hang, not a late launch: the abandoned deadline worker must
        # never start the 4-device program beside the main thread's next
        # one (two threads enqueueing collectives in different orders
        # deadlock the CPU backend — it hung the NEXT test)
        time.sleep(1.0)
        raise RuntimeError("abandoned by the deadline")

    cap.cfn = None
    cap.jfn = slow_call
    os.environ["MXNET_DIST_COLLECTIVE_TIMEOUT"] = "0.2"
    nu0 = tr._optimizer.num_update
    with pytest.raises(DistTimeout) as exc_info:
        prog(x, y)
    assert exc_info.value.mx_fault_kind == "transient"
    assert exc_info.value.mx_state_clean is False
    assert tr._optimizer.num_update == nu0
    os.environ.pop("MXNET_DIST_COLLECTIVE_TIMEOUT")
    cap.cfn, cap.jfn = orig_cfn, orig_jfn
    prog(x, y)
    assert tr._step_count == 2


def test_supervisor_drills_zero3_program(tmp_path):
    """A transient fault at the sharded captured dispatch under the
    resilience.Supervisor restores from checkpoint and resumes to the
    same end state as an unfaulted ZeRO-3 run."""
    from mxnet_tpu.resilience.supervisor import (Backoff, GluonStepLoop,
                                                 Supervisor)

    gm = _mesh(4)

    def batches(step):
        rs = np.random.RandomState(step % 5)
        return (rs.rand(BATCH, DIN).astype(np.float32),
                rs.rand(BATCH, DOUT).astype(np.float32))

    def build():
        net, tr = _make("adam", {"learning_rate": 0.01}, zero=3,
                        mesh=gm, seed=3)
        prog = tr.capture(net, gluon.loss.L2Loss())
        return GluonStepLoop(net, tr, gluon.loss.L2Loss(),
                             step_program=prog)

    n = 6
    ref = build()
    for s in range(n):
        ref.step(*batches(s))

    loop = build()
    inject.plan("step_capture@3:transient")
    sup = Supervisor(loop, mx.checkpoint.CheckpointManager(
        str(tmp_path)), checkpoint_every=2,
        backoff=Backoff(base=0.0, jitter=0.0), max_restarts=2)
    losses = sup.run(batches, n)
    assert sup.restarts == 1 and len(losses) == n
    _assert_same_params(ref.block, loop.block)
    _assert_same_states(ref.trainer, loop.trainer)


# ---------------------------------------------------------------------------
# introspection + telemetry
# ---------------------------------------------------------------------------

def test_group_table_shard_placement_column():
    from mxnet_tpu.optimizer import multi_tensor

    gm = _mesh(4)
    os.environ["MXNET_STEP_CAPTURE"] = "0"  # stitched zero path
    net, tr = _make(zero=1, mesh=gm)
    x, y = _data()
    from mxnet_tpu import autograd

    for _ in range(2):
        with autograd.record():
            loss = gluon.loss.L2Loss()(net(x), y)
        loss.backward()
        tr.step(BATCH)
    rows = multi_tensor.group_table(tr)
    assert rows and rows[0]["zero"] == 1
    assert rows[0]["placement"]["state"] == "dp4"
    assert rows[0]["placement"]["params"] == "single"


def test_shard_telemetry_and_report():
    gm = _mesh(4)
    net, tr = _make(zero=3, mesh=gm)
    prog = tr.capture(net, gluon.loss.L2Loss())
    x, y = _data()
    rs_before = telemetry.value("collective_bytes_total",
                                labels={"op": "reduce_scatter"})
    ag_before = telemetry.value("collective_bytes_total",
                                labels={"op": "all_gather"})
    prog(x, y)
    assert telemetry.value("shard_zero_level") == 3
    assert telemetry.value("shard_device_bytes",
                           labels={"kind": "optimizer_state"}) > 0
    assert telemetry.value("collective_bytes_total",
                           labels={"op": "reduce_scatter"}) > rs_before
    assert telemetry.value("collective_bytes_total",
                           labels={"op": "all_gather"}) > ag_before
    rep = prog.report()
    assert rep["mesh"]["dp"] == 4 and rep["zero"] == 3
    assert rep["programs"][0]["wire"]["grads"] > 0


def test_fused_trainer_zero_levels_parity():
    """FusedTrainer accepts levels 2/3: the explicit shard_update
    transform and dp-sharded params leave the training math equal to
    zero=1 (same mesh) and shard the state/params per level."""
    from mxnet_tpu.parallel import FusedTrainer, make_mesh

    rs = np.random.RandomState(0)
    x = rs.randn(BATCH, DIN).astype(np.float32)
    y = rs.randn(BATCH, DOUT).astype(np.float32)

    def build(level):
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu", in_units=DIN),
                nn.Dense(DOUT, in_units=16))
        net.initialize()
        net.hybridize()
        mesh = make_mesh({"dp": 4}, devices=_jax().devices()[:4])
        ft = FusedTrainer(net, loss="l2", optimizer="adam",
                          optimizer_params={"learning_rate": 0.01},
                          mesh=mesh, zero=level)
        for _ in range(4):
            loss = ft.step(x, y)
        return ft, float(loss)

    ft1, l1 = build(1)
    ft2, l2 = build(2)
    ft3, l3 = build(3)
    assert l1 == l2 == l3
    w3 = ft3._params["0.weight"]
    assert "dp" in tuple(ft3._param_specs["0.weight"])
    assert len(w3.sharding.device_set) == 4
    for k in ft1._params:
        np.testing.assert_allclose(np.asarray(ft1._params[k]),
                                   np.asarray(ft3._params[k]),
                                   rtol=1e-6, atol=1e-8, err_msg=k)
