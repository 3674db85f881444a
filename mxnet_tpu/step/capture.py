"""mx.step — whole-program training-step capture.

Hybridize compiles one Block at a time, so the imperative training
step (forward -> loss -> backward -> bucketed allreduce -> fused
optimizer apply) is stitched from separate XLA programs with host
round-trips between them.  Following Relay's whole-model IR argument
(arXiv 1810.00952) and whole-graph capture/optimization (arXiv
2604.16498), ``capture()`` traces the ENTIRE step into ONE jitted,
end-to-end buffer-donated XLA program:

- **forward + loss** through the block's pure export
  (``HybridBlock.export_pure``) — the same pure function hybridize
  compiles, so the math is the stitched math;
- **backward** as one ``jax.vjp`` seeded with ones, exactly the
  cotangent ``autograd.backward`` seeds on a non-scalar loss;
- **per-bucket allreduce** over the ``plan_buckets()`` plan (kvstore/
  collective.py).  Each bucket's reduction depends ONLY on its member
  gradients — bucket-ordered dependency structure, no post-backward
  barrier — so XLA is free to issue early buckets' collectives while
  later layers still differentiate.  In a world of one the sum over
  one replica is the identity; under an SPMD ``axis_name`` each
  bucket is a ``lax.psum``; on an ``mx.shard.GlobalMesh`` with a
  ZeRO-2/3 trainer each bucket REDUCE-SCATTERS straight into the
  update's shard layout ((N-1)/N of the all-reduce wire bytes,
  arXiv 2004.13336) and ZeRO-3 parameters all-gather just in time
  inside forward/backward;
- **fused optimizer apply** replaying the PR 5 multi-tensor groups'
  ``update_multi_precision`` rules in-trace, per-step host values
  (scheduler lr/wd, rescale_grad, Adam bias corrections) flowing
  through the same ``_HostScalar`` slot machinery — zero per-step
  retraces and bit-identical scalar math vs the stitched path;
- **fused health numerics**: the PR 7 monitor stat reductions
  (grad/weight norms, nonfinite counts) computed inside the SAME
  program — monitoring becomes free — and, under a sync sentinel
  policy, a nonfinite predicate that where-selects NO-OP updates on
  device (``skip_step`` without a separate stat fetch);
- an opt-in **rematerialization policy** (``MXNET_STEP_REMAT``:
  ``all`` = ``jax.checkpoint`` around forward+loss, ``blocks`` =
  per direct-child Block boundary) trading backward-pass recompute
  for activation memory.

Parameters and optimizer state are DONATED into the program (the
whole step is in-place at the XLA level), the lowered program
fingerprints into the ``mx.compile`` persistent cache (a fresh
process re-traces cheaply but never re-compiles an unchanged step),
and every capture/compile/dispatch failure degrades to the stitched
imperative path — counted by reason in
``step_capture_fallback_total``, never a lost step.
``MXNET_STEP_CAPTURE=0`` is the kill switch: the same ``StepProgram``
callable then runs the stitched loop, so training scripts adopt it
unconditionally.
"""
from __future__ import annotations

import contextlib
import functools
import logging
import time as _time

import numpy as _np

from .. import obs as _obs
from .. import telemetry as _tel
from .. import trace as _trace
from ..base import MXNetError, get_env
from ..kvstore.collective import (default_bucket_bytes,
                                  observe_bucket_fill,
                                  observe_collective, plan_buckets)
from ..ndarray.ndarray import NDArray
from ..optimizer import multi_tensor as _mt
from ..resilience import inject as _inject

__all__ = ["StepProgram", "capture", "is_enabled", "CaptureError",
           "remat_mode"]

_LOGGER = logging.getLogger("mxnet_tpu.step")

# index of g_nonfinite in monitor.stats.STAT_FIELDS — the gate
# predicate reads it straight out of the fused stat vectors
_G_NONFINITE = 5

REMAT_MODES = ("off", "all", "blocks")


def is_enabled():
    """The ``MXNET_STEP_CAPTURE`` kill switch (default ON).  Checked
    per call, so flipping it mid-run moves the very next step to the
    stitched path."""
    return get_env("MXNET_STEP_CAPTURE", bool, True)


def remat_mode():
    """The armed rematerialization policy (``MXNET_STEP_REMAT``):
    ``off`` (default) keeps every activation live for backward;
    ``all`` wraps forward+loss in one ``jax.checkpoint``; ``blocks``
    checkpoints at each direct-child Block boundary (best effort — a
    block whose forward mutates traced python state degrades to
    ``all`` with a warning)."""
    v = str(get_env("MXNET_STEP_REMAT", str, "off") or "off").lower()
    if v in ("0", "", "none", "false"):
        return "off"
    if v in ("1", "true"):
        return "all"
    if v not in REMAT_MODES:
        raise MXNetError("MXNET_STEP_REMAT=%r is not a remat policy "
                         "(choose from %s)" % (v, "|".join(REMAT_MODES)))
    return v


class CaptureError(MXNetError):
    """Whole-step capture is not possible for this trainer/signature;
    the step runs stitched (``reason`` becomes the telemetry label)."""

    def __init__(self, reason, detail=""):
        super().__init__("step capture unavailable (%s)%s"
                         % (reason, ": " + detail if detail else ""))
        self.reason = reason


def _jax():
    import jax

    return jax


def _bucket_reduce_scatter(grads, plan_pos, grad_shardings):
    """ZeRO-2/3 collective segment: constrain each bucket's member
    gradients to their dp-shard layout (aligned with the optimizer
    state's ``spec_for`` placement, so the sharded update consumes them
    with zero resharding).  Under GSPMD the pending cross-replica sum
    into a sharded consumer lowers to a REDUCE-SCATTER — (N-1)/N of the
    all-reduce wire bytes — and members constrained together within one
    ``plan_buckets()`` bucket fuse into bucket-granular collectives.
    Buckets keep their ordered dependency structure: each depends only
    on its member grads, so early buckets' reduce-scatters overlap the
    still-running backward of later layers, exactly like the all-reduce
    path."""
    import jax

    out = list(grads)
    for idxs in plan_pos:
        for j in idxs:
            out[j] = jax.lax.with_sharding_constraint(
                grads[j], grad_shardings[j])
    return out


def _bucket_allreduce(grads, plan_pos, axis_name):
    """Reduce gradients bucket by bucket inside the captured program.

    ``plan_pos`` is the ``plan_buckets`` output re-indexed to grad-list
    positions.  Each bucket flattens ONLY its members and (under an
    SPMD ``axis_name``) psums them as one collective — no dependency
    on other buckets, so the XLA scheduler can overlap early buckets'
    collectives with the still-running backward of later layers.
    ``axis_name=None`` (a world of one) is the identity: summing one
    replica's gradient is the gradient."""
    if axis_name is None:
        return list(grads)
    import jax
    import jax.numpy as jnp

    out = list(grads)
    for idxs in plan_pos:
        if len(idxs) == 1:
            j = idxs[0]
            out[j] = jax.lax.psum(grads[j], axis_name)
            continue
        flat = jnp.concatenate([jnp.ravel(grads[j]) for j in idxs])
        summed = jax.lax.psum(flat, axis_name)
        off = 0
        for j in idxs:
            n = grads[j].size
            out[j] = summed[off:off + n].reshape(grads[j].shape)
            off += n
    return out


@contextlib.contextmanager
def _remat_block_boundaries(root):
    """Scope: wrap each DIRECT child of ``root`` in ``jax.checkpoint``
    for the duration of one capture trace (``MXNET_STEP_REMAT=blocks``)
    — activations inside a child are rematerialized during backward
    instead of held live across the whole step."""
    import jax

    from ..gluon import block as _blk

    boundaries = {id(c) for c in root._children.values()}
    if not boundaries:
        yield
        return
    orig = _blk.Block.__call__

    def remat_call(self, *args, **kwargs):
        if id(self) not in boundaries:
            return orig(self, *args, **kwargs)
        flat = []
        in_spec = _blk._flatten_nd(list(args), flat)
        nd_pos = [k for k, a in enumerate(flat) if isinstance(a, NDArray)]
        datas = [flat[k]._data for k in nd_pos]
        box = {}

        def f(*ds):
            merged = list(flat)
            for k, d in zip(nd_pos, ds):
                merged[k] = NDArray(d)
            rebuilt = _blk._unflatten_nd(in_spec, iter(merged))
            out = orig(self, *rebuilt, **kwargs)
            flat_out = []
            spec = _blk._flatten_nd(
                out if isinstance(out, (list, tuple)) else [out], flat_out)
            box["spec"] = spec
            box["is_nd"] = [isinstance(o, NDArray) for o in flat_out]
            box["static"] = [o for o in flat_out
                             if not isinstance(o, NDArray)]
            return tuple(o._data for o in flat_out
                         if isinstance(o, NDArray))

        outs = jax.checkpoint(f)(*datas)
        nd_it, st_it = iter(outs), iter(box["static"])
        flat2 = [NDArray(next(nd_it)) if is_nd else next(st_it)
                 for is_nd in box["is_nd"]]
        result = _blk._unflatten_nd(box["spec"], iter(flat2))
        return result[0] if len(result) == 1 else tuple(result)

    _blk.Block.__call__ = remat_call
    try:
        yield
    finally:
        _blk.Block.__call__ = orig


class _Captured:
    """One compiled whole-step signature (the _CachedOp/_Group analog
    for the captured path)."""

    __slots__ = ("sig", "train_idx", "train_names", "other_names",
                 "group_list", "labels", "pos_of", "bucket_plan",
                 "bucket_bytes",
                 "bucket_nbytes", "n_slots", "slot_fns", "jfn", "cfn",
                 "cfn_ok", "fingerprint", "provenance", "gate",
                 "monitor", "remat", "segments", "donation",
                 "gmesh", "level", "param_shardings", "grad_shardings",
                 "state_shardings", "forward_shardings", "tp_mode",
                 "replicated", "wire", "flops")

    def __init__(self):
        self.bucket_bytes = 0
        self.slot_fns = None
        self.jfn = None
        self.cfn = None
        self.cfn_ok = False
        self.fingerprint = None
        self.provenance = "fresh"
        self.gmesh = None
        self.level = 0
        self.flops = None

    def call(self, *args):
        with _mt._quiet_donation():
            if self.cfn is not None:
                try:
                    out = self.cfn(*args)
                    self.cfn_ok = True
                    return out
                except Exception:
                    if self.cfn_ok:
                        raise  # served before: surface the real error
                    self.cfn = None  # aval/placement drift: lazy jit
                    if any(_mt._deleted(a) for a in args[0]):
                        raise MXNetError(
                            "captured step program failed after "
                            "consuming its donated weight buffers")
            return self.jfn(*args)


class StepProgram:
    """The whole training step as one callable.

    ``program(data, label)`` runs forward, loss, backward, bucketed
    allreduce, the fused optimizer apply and the monitor stat
    reductions as ONE donated XLA program (captured lazily per input
    signature) and returns the loss.  On an ``mx.shard.GlobalMesh``
    the same program compiles SPMD over the mesh: the batch lands
    dp-sharded and the trainer's ZeRO level decides what lives sharded
    between steps (state / + reduce-scattered grads / + params).  When
    capture is impossible — kill switch, non-fusable optimizer, sparse
    grads, a multi-process world without a mesh, capture/compile
    failure — the SAME call runs the stitched imperative sequence
    (``autograd.record`` forward, ``backward()``, ``Trainer.step``,
    with mesh-placed arrays first gathered home), so the step is never
    lost and the callable is a drop-in replacement for the classic
    three-line loop either way.
    """

    def __init__(self, block, trainer, loss_fn, axis_name=None):
        from ..gluon.block import HybridBlock

        if not isinstance(block, HybridBlock):
            raise MXNetError(
                "mx.step.capture needs a HybridBlock (whole-step "
                "capture rides the block's pure export); got %r"
                % type(block).__name__)
        if not callable(loss_fn):
            raise MXNetError("loss_fn must be callable")
        self._block = block
        self._trainer = trainer
        self._loss_fn = loss_fn
        self._axis_name = axis_name
        self._programs = {}      # sig -> _Captured
        self._dead = {}          # sig -> fallback reason (stitched for good)
        self._remat_override = None  # blocks-mode failure degrades to all
        self._fallbacks = []     # bounded log of degradations
        self._path_counts = {"captured": 0, "stitched": 0}
        self._skipped = 0
        self._disabled_noted = False
        # mx.shard placement bookkeeping: original (pre-mesh) array
        # placements, restored when a step must run stitched
        self._homes = None
        self._placed = False
        try:
            self._world = _jax().process_count()
        except Exception:
            self._world = 1

    # ---- public surface ---------------------------------------------------
    def __call__(self, data, label=None, batch_size=None):
        datas = tuple(data) if isinstance(data, (list, tuple)) else (data,)
        labels = () if label is None else (
            tuple(label) if isinstance(label, (list, tuple)) else (label,))
        if batch_size is None:
            batch_size = datas[0].shape[0]
        if not is_enabled():
            if not self._disabled_noted:
                self._disabled_noted = True
                self._note_fallback("disabled", "MXNET_STEP_CAPTURE=0")
            return self._stitched(datas, labels, batch_size)
        cap = self._get_program(datas, labels)
        if cap is None:
            return self._stitched(datas, labels, batch_size)
        fall_reason = None
        try:
            return self._run_captured(cap, datas, labels, batch_size)
        except Exception as exc:
            from ..resilience.inject import InjectedFault, InjectedIOError

            if getattr(exc, "mx_step_no_fallback", False):
                # raised AFTER the captured program ran (sentinel
                # policy=raise, publish/bookkeeping errors): the step's
                # device effects already happened (or were gated to
                # no-ops) — a stitched replay would apply it TWICE
                raise
            if isinstance(exc, (InjectedFault, InjectedIOError)) or \
                    getattr(exc, "mx_fault_kind", None) is not None:
                # injected faults and DistTimeout carry resilience
                # semantics — the supervisor owns recovery, a silent
                # stitched replay here would hide the drill/failure
                raise
            if any(_mt._deleted(self._trainer._params[i].data()._data)
                   for i in cap.train_idx):
                raise MXNetError(
                    "captured step failed after its donated weight "
                    "buffers were consumed; parameter state is "
                    "unrecoverable for this step") from exc
            self._programs.pop(cap.sig, None)
            if cap.remat == "blocks":
                # a block whose forward mutates traced python state
                # (BatchNorm running stats) cannot live inside a
                # per-block jax.checkpoint — degrade the POLICY to
                # whole-forward remat and recapture next step
                self._remat_override = "all"
                fall_reason = ("remat_blocks_degraded", repr(exc))
                _LOGGER.warning(
                    "mx.step: MXNET_STEP_REMAT=blocks failed for this "
                    "model; degrading to remat=all", exc_info=True)
            else:
                self._dead[cap.sig] = "dispatch_error"
                fall_reason = ("dispatch_error", repr(exc))
                _LOGGER.warning(
                    "mx.step: captured dispatch failed; step degrades "
                    "to the stitched path", exc_info=True)
        # outside the except block so a stitched failure isn't chained
        # onto (and masked by) the captured one
        self._note_fallback(*fall_reason)
        return self._stitched(datas, labels, batch_size)

    def step(self, data, label=None, batch_size=None):
        """Alias of ``__call__`` (Trainer-protocol spelling)."""
        return self(data, label=label, batch_size=batch_size)

    def invalidate(self):
        """Drop every captured program (checkpoint restore rebinds the
        optimizer-state arrays the programs were traced over; the next
        step re-traces — cheap — and re-hits the persistent cache).
        Restored arrays arrive host-fresh (single-device), so the mesh
        placement is re-laid at the next build too."""
        self._programs.clear()
        self._dead.clear()
        self._placed = False

    def gather(self):
        """Bring parameters (and forward state) back to their original
        pre-mesh placement and invalidate the captured programs — call
        before eager evaluation of a ZeRO-3 model mid-training (the
        sharded arrays would otherwise mix with single-device inputs).
        The next captured step re-places and re-traces (cheap; the
        executable comes back from the persistent cache)."""
        self._gather_home()
        self._programs.clear()

    # ---- mx.shard placement ------------------------------------------------
    def _place(self, items, named, policy):
        """Lay the trainer's arrays out on the GlobalMesh per the ZeRO
        policy: params sharded (level 3) or replicated, optimizer state
        sharded (level >= 1, the trainer's own placement re-asserted),
        forward-only params replicated.  Original placements are
        recorded ONCE so a stitched fallback can gather home."""
        jax = _jax()
        trainer = self._trainer
        if self._homes is None:
            homes = {"params": {}, "states": {}}
            for n, p in named.items():
                if p._data is not None:
                    homes["params"][n] = p._data._data.sharding
            for i, _, _ in items:
                st = trainer._states.get(i)
                if st is not None:
                    homes["states"][i] = jax.tree_util.tree_map(
                        lambda leaf: leaf._data.sharding, st,
                        is_leaf=_mt._is_nd)
            self._homes = homes
        train_ids = {id(p) for _, p, _ in items}
        name_of = {}
        for n, p in named.items():
            name_of.setdefault(id(p), n)
        for _, p, _ in items:
            h = p.data()
            h._data = jax.device_put(
                h._data, policy.param_sharding(
                    h.shape, name=name_of.get(id(p))))
        for n, p in named.items():
            if p._data is not None and id(p) not in train_ids:
                p._data._data = jax.device_put(p._data._data,
                                               policy.gmesh.replicated())
        for i, p, _ in items:
            st = trainer._states.get(i)
            if st is not None:
                pname = name_of.get(id(p))

                def put(leaf, pname=pname):
                    leaf._data = jax.device_put(
                        leaf._data, policy.state_sharding(
                            leaf.shape, name=pname))
                    return leaf
                jax.tree_util.tree_map(put, st, is_leaf=_mt._is_nd)
        self._placed = True
        if _tel.ENABLED:
            from .. import shard as _shard

            _tel.SHARD_DEVICE_BYTES.labels(kind="params").set(
                _shard.device_bytes([p.data() for _, p, _ in items]))
            _tel.SHARD_DEVICE_BYTES.labels(kind="optimizer_state").set(
                _shard.device_bytes([trainer._states[i]
                                     for i, _, _ in items
                                     if trainer._states.get(i)
                                     is not None]))
            _tel.SHARD_ZERO_LEVEL.set(policy.level)
            _tel.SHARD_TP_MODE.set(
                1 if getattr(policy, "mode", "gather") == "compute"
                else 0)

    def _gather_home(self):
        """Undo ``_place``: device_put every placed array back to its
        recorded original placement (no-op when nothing is placed) so
        the eager/stitched engine never mixes mesh-committed arrays
        with single-device ones."""
        if not self._placed or self._homes is None:
            return
        jax = _jax()
        named = self._block.collect_params()
        for n, sh in self._homes["params"].items():
            p = named.get(n)
            if p is not None and p._data is not None:
                p._data._data = jax.device_put(p._data._data, sh)
        for i, tree_sh in self._homes["states"].items():
            st = self._trainer._states.get(i)
            if st is None:
                continue

            def put(leaf, sh):
                leaf._data = jax.device_put(leaf._data, sh)
                return leaf

            jax.tree_util.tree_map(put, st, tree_sh, is_leaf=_mt._is_nd)
        self._placed = False
        # mesh programs were traced over the placed layout; drop them
        # so a later captured step re-places (and re-traces, cheap)
        # instead of feeding home-placed arrays to a mesh executable
        for s in [s for s, c in self._programs.items()
                  if c.gmesh is not None]:
            self._programs.pop(s, None)

    def _stage(self, cap, inputs, labels, hscal, rng):
        """Per-dispatch input staging: on a mesh, the batch lands
        dp-sharded and the scalar vector / rng key replicated.  In a
        multi-process world each process hands its LOCAL batch and the
        global array is assembled across hosts (the per-host data
        feed; gradients then sum over the global batch while
        ``rescale_grad`` divides by the local batch — exactly the
        dist_sync kvstore semantics the stitched path has)."""
        if cap.gmesh is None:
            return inputs, labels, hscal, rng
        jax = _jax()

        def put_batch(a):
            arr = getattr(a, "_data", a)
            if isinstance(arr, jax.Array) and \
                    getattr(arr, "sharding", None) is not None and \
                    arr.sharding == cap.gmesh.batch_sharding(arr.shape):
                # already mesh-placed — the mx.data prefetch ring
                # staged it onto this exact sharding while the
                # previous step ran (the H3 contract: dispatch never
                # pays the H2D here)
                return arr
            sharding = cap.gmesh.batch_sharding(a.shape)
            if cap.gmesh.processes > 1:
                return jax.make_array_from_process_local_data(
                    sharding, _np.asarray(a))
            return jax.device_put(a, sharding)

        inputs = [put_batch(a) for a in inputs]
        labels = [put_batch(a) for a in labels]
        return (inputs, labels,
                jax.device_put(hscal, cap.replicated),
                jax.device_put(rng, cap.replicated))

    def report(self):
        """Capture report for ``tools/diagnose.py --step`` and tests:
        per-signature segment list, donation map, remat policy,
        provenance (fresh vs compile-cache hit), path counts and
        fallback reasons."""
        gm = self._resolve_mesh()
        return {
            "enabled": is_enabled(),
            "world": self._world,
            "axis_name": self._axis_name,
            "mesh": None if gm is None else gm.describe(),
            "zero": int(getattr(self._trainer, "_zero", 0) or 0),
            "paths": dict(self._path_counts),
            "skipped_steps": self._skipped,
            "programs": [{
                "provenance": cap.provenance,
                "fingerprint": cap.fingerprint,
                "remat": cap.remat,
                "monitor_fused": cap.monitor,
                "gate": cap.gate,
                "zero": cap.level,
                "tp_mode": cap.tp_mode,
                "mesh": None if cap.gmesh is None
                else cap.gmesh.describe(),
                "wire": None if cap.wire is None else dict(cap.wire),
                "host_scalar_slots": len(cap.slot_fns or ()),
                "flops": cap.flops,
                "segments": list(cap.segments),
                "donation": dict(cap.donation),
                "bucket_plan": [list(b) for b in cap.bucket_plan],
                "bucket_bytes": int(cap.bucket_bytes),
            } for cap in self._programs.values()],
            "fallbacks": list(self._fallbacks),
        }

    # ---- stitched fallback ------------------------------------------------
    def _stitched(self, datas, labels, batch_size):
        """The classic imperative sequence — always correct, never
        fast-path dependent.  (No ``anomaly=`` on the outer span: the
        nested ``trainer_step`` span already feeds the slow-step
        detector.)"""
        from .. import autograd

        # a mesh-placed model cannot run the eager sequence (sharded
        # arrays never mix with single-device ones): gather home first
        # and drop the mesh programs — the next captured step re-places
        self._gather_home()
        self._path_counts["stitched"] += 1
        if _tel.ENABLED:
            _tel.STEP_CAPTURE_STEPS.labels(path="stitched").inc()
        obs_on = _obs.core.ENABLED
        step = self._trainer._step_count
        t0 = _time.perf_counter() if obs_on else 0.0
        with _trace.span("train_step", hist=False, args={"captured": 0}):
            with _trace.span("forward", hist=False):
                with autograd.record():
                    out = self._block(*datas)
                    loss = self._loss_fn(out, *labels)
            t1 = _time.perf_counter() if obs_on else 0.0
            with _trace.span("backward", hist=False):
                loss.backward()
            t2 = _time.perf_counter() if obs_on else 0.0
            self._trainer.step(batch_size)
        if obs_on:
            # note_step already fired inside trainer.step; attribution
            # is this path's responsibility (never raises)
            t3 = _time.perf_counter()
            _obs.attribution.observe_step(
                step, t3 - t0,
                parts={"forward": t1 - t0, "backward": t2 - t1,
                       "update": t3 - t2},
                path="stitched")
        return loss

    def _note_fallback(self, reason, detail=""):
        if _tel.ENABLED:
            _tel.STEP_CAPTURE_FALLBACKS.labels(reason=reason).inc()
        _trace.instant("step_capture_fallback", cat="step",
                       args={"reason": reason})
        self._fallbacks.append({"reason": reason, "detail": str(detail)[:200],
                                "step": self._trainer._step_count})
        del self._fallbacks[:-32]

    # ---- capture ----------------------------------------------------------
    def _resolve_mesh(self):
        """The GlobalMesh this program shards over: the trainer's own
        (``Trainer(mesh=...)``), else the process-global one
        (``mx.shard.configure`` / ``MXNET_SHARD_DP``), else None —
        the classic single-device capture."""
        from .. import shard as _shard

        gm = getattr(self._trainer, "_zero_gmesh", None)
        if gm is None:
            gm = _shard.current(auto=True)
        return gm

    def _sig(self, datas, labels):
        from .. import monitor as _mon
        from ..contrib import amp as _amp
        from ..monitor import sentinel as _sentinel

        from .. import shard as _shard

        mon_on = _mon.core.ENABLED
        gate = mon_on and _sentinel.policy() in _sentinel.SYNC_POLICIES
        remat = self._remat_override or remat_mode()
        gm = self._resolve_mesh()
        return (tuple((tuple(x.shape), str(x.dtype)) for x in datas),
                tuple((tuple(x.shape), str(x.dtype)) for x in labels),
                mon_on, gate, _mt._hparams_sig(self._trainer._optimizer),
                remat, _amp.is_active(), _amp.target_dtype(),
                None if gm is None else gm.signature(),
                int(getattr(self._trainer, "_zero", 0) or 0),
                str(get_env("MXNET_SHARD_DATA", str, "dp") or "dp"),
                # layout rules + TP mode are part of a mesh program's
                # identity: retrace when either changes mid-process
                None if gm is None else _shard.layout_signature())

    def _get_program(self, datas, labels):
        sig = self._sig(datas, labels)  # typo'd env values fail loud
        reason = self._dead.get(sig)
        if reason is not None:
            return None
        cap = self._programs.get(sig)
        if cap is not None:
            return cap
        try:
            with _trace.span("step_capture", hist=False,
                             args={"step": self._trainer._step_count}):
                cap = self._build(sig, datas, labels)
        except Exception as exc:
            from ..resilience.inject import InjectedFault, InjectedIOError

            reason = getattr(exc, "reason", None) or (
                "injected_fault" if isinstance(
                    exc, (InjectedFault, InjectedIOError))
                else "trace_error")
            self._dead[sig] = reason
            if reason == "trace_error" and sig[5] == "blocks":
                # per-block checkpoints choked on this model's forward:
                # degrade the remat POLICY, not the capture — the next
                # step recaptures with whole-forward remat
                self._remat_override = "all"
                reason = "remat_blocks_degraded"
            self._note_fallback(reason, repr(exc))
            _LOGGER.warning(
                "mx.step: capture failed (%s); this signature runs "
                "stitched", reason, exc_info=True)
            return None
        self._programs[sig] = cap
        return cap

    def _build(self, sig, datas, labels):
        jax = _jax()
        trainer = self._trainer
        opt = trainer._optimizer
        block = self._block
        # mx.resilience drill site: a planned fault here poisons the
        # CAPTURE — the step must cleanly degrade to the stitched path
        _inject.fire("step_capture", seq=trainer._step_count)
        if not trainer._kv_initialized:
            trainer._init_kvstore()
        if trainer._update_on_kvstore:
            raise CaptureError("update_on_kvstore")
        gmesh = self._resolve_mesh()
        level = int(getattr(trainer, "_zero", 0) or 0)
        if gmesh is not None and self._axis_name is not None:
            raise CaptureError(
                "mesh_conflict",
                "axis_name=%r (the shard_map spelling) and a GlobalMesh "
                "are both armed; pick one" % (self._axis_name,))
        if self._world > 1 and gmesh is None and self._axis_name is None:
            # cross-process collectives need the program to be SPMD
            # over the global mesh; without one configured the step
            # degrades (counted) instead of silently dropping the
            # cross-replica reduction
            raise CaptureError(
                "unsharded_mesh",
                "multi-process capture needs a GlobalMesh: call "
                "mx.shard.configure(mx.shard.GlobalMesh()) or pass "
                "mesh= to the Trainer")
        if level and gmesh is None:  # trainer validation makes this dead
            raise CaptureError("unsharded_mesh", "zero=%d without mesh"
                               % level)
        if gmesh is not None and self._world > 1 and \
                gmesh.processes < self._world:
            raise CaptureError(
                "unsharded_mesh",
                "GlobalMesh spans %d process(es) of a %d-process world"
                % (gmesh.processes, self._world))
        block._ensure_initialized(datas)  # resolve deferred shapes
        items = []
        for i, param in enumerate(trainer._params):
            if param.grad_req == "null" or param._data is None:
                continue
            trainer._maybe_init_states(i, param)
            items.append((i, param, param.grad()))
        if not items:
            raise CaptureError("no_trainable_params")
        groups, eager = _mt.partition(trainer, items)
        if eager:
            raise CaptureError("eager_members", eager[0][3])
        named = block.collect_params()
        name_of = {}
        for n, p in named.items():
            name_of.setdefault(id(p), n)
        missing = [i for i, p, _ in items if id(p) not in name_of]
        if missing:
            raise CaptureError("params_not_in_block",
                               "trainer indices %s" % missing[:5])

        from ..monitor.core import _group_label

        policy = None
        if gmesh is not None:
            from .. import shard as _shard

            policy = _shard.ShardPolicy(level, gmesh)
            self._place(items, named, policy)

        cap = _Captured()
        cap.sig = sig
        cap.gmesh = gmesh
        cap.level = level
        cap.train_idx = tuple(i for i, _, _ in items)
        cap.pos_of = {i: j for j, i in enumerate(cap.train_idx)}
        cap.train_names = [name_of[id(p)] for _, p, _ in items]
        train_set = set(cap.train_names)
        cap.other_names = [n for n in named if n not in train_set]
        cap.group_list = [
            (_group_label(trainer, key, members),
             tuple(i for i, _, _ in members))
            for key, members in groups.items()]
        cap.labels = [label for label, _ in cap.group_list]
        cap.monitor = bool(sig[2])
        cap.gate = bool(sig[3])
        cap.remat = sig[5]
        grad_arrs = [g._data for _, _, g in items]
        grad_sizes = [(a.size * a.dtype.itemsize, str(a.dtype))
                      for a in grad_arrs]
        # the plan's bucket size: recorded in report() and threaded
        # through every fill observation this program feeds
        cap.bucket_bytes = default_bucket_bytes()
        cap.bucket_plan = plan_buckets(
            grad_sizes, bucket_bytes=cap.bucket_bytes)
        cap.bucket_nbytes = [
            sum(grad_arrs[j].size * grad_arrs[j].dtype.itemsize
                for j in bucket)
            for bucket in cap.bucket_plan]
        cap.n_slots = 12 * len(items) + 8
        if policy is None:
            cap.param_shardings = None
            cap.grad_shardings = None
            cap.state_shardings = None
            cap.forward_shardings = None
            cap.replicated = None
            cap.wire = None
            cap.tp_mode = None
        else:
            cap.param_shardings = [
                policy.param_sharding(p.data().shape,
                                      name=name_of[id(p)])
                for _, p, _ in items]
            cap.grad_shardings = [
                policy.grad_sharding(g.shape, name=name_of[id(p)])
                for _, p, g in items]
            cap.state_shardings = [
                jax.tree_util.tree_map(
                    lambda a, n=name_of[id(p)]:
                    policy.state_sharding(a.shape, name=n),
                    _mt._unwrap_state(trainer._states[i]))
                for i, p, _ in items]
            cap.replicated = gmesh.replicated()
            cap.tp_mode = policy.mode
            # what each weight is constrained to INSIDE fwd/bwd:
            # replicated (gather mode / ZeRO-3 jit gather) or its mdl
            # layout (compute mode — GSPMD shards the matmuls).  None
            # when params are stored replicated anyway: no constraint,
            # the classic level<3 pure-dp program.
            cap.forward_shardings = [
                policy.forward_sharding(p.data().shape,
                                        name=name_of[id(p)])
                for _, p, _ in items] \
                if policy.needs_forward_constraint else None
        w_bytes = sum(p.data()._data.size * p.data()._data.dtype.itemsize
                      for _, p, _ in items)
        s_leaves = [leaf for i in cap.train_idx
                    for leaf in jax.tree_util.tree_leaves(
                        _mt._unwrap_state(trainer._states[i]))]
        s_bytes = sum(a.size * a.dtype.itemsize for a in s_leaves)
        if policy is not None:
            # wire bytes per step, the reduce-scatter-vs-all-reduce
            # price (fed to collective telemetry each dispatch)
            cap.wire = {
                "grads": policy.grad_collective_bytes(
                    int(sum(cap.bucket_nbytes))),
                "param_gather": policy.param_gather_bytes(int(w_bytes)),
                "mdl_gather": policy.mdl_param_bytes(int(w_bytes)),
            }
        cap.donation = {
            "params": {"arrays": len(items), "bytes": int(w_bytes),
                       "donated": True},
            "optimizer_state": {"arrays": len(s_leaves),
                                "bytes": int(s_bytes), "donated": True},
            "forward_only_params": {"arrays": len(cap.other_names),
                                    "donated": False},
        }
        cap.segments = [
            {"segment": "forward", "params": len(named),
             "remat": cap.remat,
             "gather": "jit-per-layer" if level >= 3 else None},
            {"segment": "loss", "fn": type(self._loss_fn).__name__},
            {"segment": "backward", "grads": len(items)},
            {"segment": "allreduce", "buckets": len(cap.bucket_plan),
             "world": self._world,
             "bytes": int(sum(cap.bucket_nbytes)),
             "collective": "reduce_scatter" if (
                 gmesh is not None and level >= 2) else "all_reduce",
             "dp": None if gmesh is None else gmesh.dp,
             "zero": level,
             "wire_bytes": None if cap.wire is None
             else int(cap.wire["grads"]),
             "axis": self._axis_name},
        ]
        if gmesh is not None and gmesh.mdl > 1:
            cap.segments.append({
                "segment": "tensor_parallel", "mdl": gmesh.mdl,
                "mode": cap.tp_mode,
                "wire_bytes": int(cap.wire["mdl_gather"])})
        if cap.monitor:
            cap.segments.append({"segment": "stats",
                                 "groups": len(cap.group_list)})
        cap.segments.append({"segment": "apply",
                             "groups": len(cap.group_list),
                             "optimizer": type(opt).__name__})
        if cap.gate:
            cap.segments.append({"segment": "gate",
                                 "policy": "sync-sentinel"})
        for seg in cap.segments:
            _trace.instant("step_segment", cat="step", args=seg)

        step_fn = self._make_step_fn(cap)
        cap.jfn = jax.jit(step_fn, donate_argnums=(0, 1))
        train_datas = [p.data()._data for _, p, _ in items]
        state_trees = [_mt._unwrap_state(trainer._states[i])
                       for i in cap.train_idx]
        other_datas = [named[n]._data._data for n in cap.other_names]
        hscal0 = _np.zeros((cap.n_slots,), _np.float32)
        rng0 = jax.random.PRNGKey(0)
        input_datas, label_datas, hscal0, rng0 = self._stage(
            cap, [x._data for x in datas], [y._data for y in labels],
            hscal0, rng0)
        args = (train_datas, state_trees, other_datas, hscal0, rng0,
                input_datas, label_datas)
        lowered = None
        with _mt._quiet_donation():
            with _trace.span("step_trace", hist=False):
                try:
                    lowered = cap.jfn.lower(*args)
                except Exception:
                    # no AOT lowering on this backend: one abstract
                    # trace still discovers the slot closures; jfn
                    # compiles lazily on first call
                    jax.eval_shape(step_fn, *args)
            if cap.slot_fns is None:
                raise CaptureError("trace_error",
                                   "no host state recorded")
            if lowered is not None:
                try:
                    # XLA's own FLOP count for the whole-step program
                    # — the numerator of the mx.obs MFU estimate
                    cost = lowered.cost_analysis()
                    if isinstance(cost, (list, tuple)):
                        cost = cost[0] if cost else {}
                    cap.flops = float(cost.get("flops")) \
                        if cost.get("flops") else None
                except Exception:  # noqa: BLE001 - optional metadata
                    cap.flops = None
                from ..compile.aot import attach_lowered

                with _trace.span("step_compile", hist=False):
                    cap.cfn, cap.fingerprint, cap.provenance = \
                        attach_lowered(
                            lowered, "_StepProgram",
                            "step:%s:%s:%d" % (type(block).__name__,
                                               type(opt).__name__,
                                               len(items)))
        if _tel.ENABLED:
            _tel.STEP_CAPTURE_BUILDS.inc()
        _LOGGER.info(
            "mx.step: captured whole-step program (%d params, %d "
            "groups, %d buckets, remat=%s, monitor=%s, provenance=%s)",
            len(items), len(cap.group_list), len(cap.bucket_plan),
            cap.remat, cap.monitor, cap.provenance)
        return cap

    def _make_step_fn(self, cap):
        """The pure whole-step function ONE signature jit-compiles."""
        jax = _jax()
        import jax.numpy as jnp

        from ..monitor import stats as _mstats

        trainer = self._trainer
        opt = trainer._optimizer
        loss_fn = self._loss_fn
        block = self._block
        apply_fn, _ = block.export_pure(training=True)
        train_names = list(cap.train_names)
        other_names = list(cap.other_names)
        pos_of = dict(cap.pos_of)
        group_list = list(cap.group_list)
        train_idx = cap.train_idx
        plan_pos = [[pos_of[train_idx[j]] for j in bucket]
                    for bucket in cap.bucket_plan]
        axis_name = self._axis_name
        remat = cap.remat
        monitor_on = cap.monitor
        gate = cap.gate
        gmesh = cap.gmesh
        level = cap.level
        param_shardings = cap.param_shardings
        grad_shardings = cap.grad_shardings
        state_shardings = cap.state_shardings
        forward_shardings = cap.forward_shardings
        replicated = cap.replicated
        if gmesh is None:
            rows = contextlib.nullcontext
        else:
            # Pallas kernels cannot be partitioned automatically: tell
            # them, while forward and backward trace, that dp splits the
            # batch
            from ..ops.pallas_attention import mesh_rows

            rows = functools.partial(mesh_rows, gmesh.mesh, ("dp",))

        def step_fn(train_datas, state_trees, other_datas, hscal, rng,
                    input_datas, label_datas):
            base = dict(zip(other_names, other_datas))

            def fwd(tds):
                if forward_shardings is not None:
                    # Pin each weight's IN-PROGRAM layout.  Gather
                    # mode (and ZeRO-3): the constraint is replicated
                    # — each weight is re-materialized (one
                    # all-gather per array, scheduled by XLA right
                    # before first use and freed after) INSIDE
                    # forward+backward, which also pins the fwd/bwd
                    # math to the replicated program's exact
                    # contraction order — sharded params change
                    # layout, not bits — and its transpose hands the
                    # cotangent back toward the sharded layout.
                    # Under remat the gathers replay in backward, so
                    # peak parameter memory stays ~1/(dp*mdl) + live
                    # layer.  Compute mode: the constraint is the mdl
                    # layout itself — GSPMD shards the consuming
                    # matmuls (Megatron TP) and activation parity
                    # becomes tolerance, not bitwise.
                    tds = [jax.lax.with_sharding_constraint(t, s)
                           for t, s in zip(tds, forward_shardings)]
                pd = dict(base)
                pd.update(zip(train_names, tds))
                ctx = contextlib.nullcontext() if remat != "blocks" \
                    else _remat_block_boundaries(block)
                with ctx:
                    outs, states = apply_fn(pd, rng, *input_datas)
                outs_nd = [NDArray(o) for o in outs]
                out = outs_nd[0] if len(outs_nd) == 1 else tuple(outs_nd)
                loss = loss_fn(out, *[NDArray(y) for y in label_datas])
                if not isinstance(loss, NDArray):
                    raise CaptureError("loss_not_ndarray",
                                       type(loss).__name__)
                return loss._data, states

            fwd2 = jax.checkpoint(fwd) if remat == "all" else fwd
            # ones cotangent == autograd.backward's seed on a
            # non-scalar loss: grads are d(sum(loss))/dw
            with rows():
                loss, vjp, states = jax.vjp(fwd2, list(train_datas),
                                            has_aux=True)
                (grads,) = vjp(jnp.ones_like(loss))
            grads = _bucket_allreduce(list(grads), plan_pos, axis_name)
            if gmesh is not None and gmesh.dp > 1 and level >= 2:
                # ZeRO-2/3: the pending cross-replica sum lands
                # directly in the update's shard layout — a
                # reduce-scatter per bucket, never a replicated grad
                grads = _bucket_reduce_scatter(grads, plan_pos,
                                               grad_shardings)
            statvecs = []
            if monitor_on:
                for _label, idxs in group_list:
                    w = [train_datas[pos_of[i]] for i in idxs]
                    g = [grads[pos_of[i]] for i in idxs]
                    statvecs.append(_mstats._stat_fn(w, g))
            ok = None
            if gate:
                nf = jnp.float32(0.0)
                for vec in statvecs:
                    nf = nf + vec[_G_NONFINITE]
                ok = nf == 0
            tr = _mt._Trace(hscal)
            new_w = list(train_datas)
            new_s = list(state_trees)
            with _mt._trace_hparams(opt, tr):
                for _label, idxs in group_list:
                    for i in idxs:
                        j = pos_of[i]
                        w = NDArray(train_datas[j])
                        g = NDArray(grads[j])
                        st = jax.tree_util.tree_map(NDArray,
                                                    state_trees[j])
                        opt.update_multi_precision(i, w, g, st)
                        new_w[j] = w._data
                        new_s[j] = _mt._unwrap_state(st)
            cap.slot_fns = tr.fns
            if ok is not None:
                # skip_step INSIDE the program: a nonfinite grad
                # where-selects the untouched inputs — bit-identical
                # to never launching the update, no separate fetch
                new_w = [jnp.where(ok, n, o)
                         for n, o in zip(new_w, train_datas)]
                new_s = [jax.tree_util.tree_map(
                    lambda n, o: jnp.where(ok, n, o), n, o)
                    for n, o in zip(new_s, state_trees)]
            if gmesh is not None:
                # pin the output layout: params stay dp-sharded between
                # steps under ZeRO-3 (levels 0-2: the post-update
                # all-gather of the weight-update-sharding transform),
                # optimizer state stays dp-sharded (levels >= 1), and
                # everything host-facing (loss, forward state, stat
                # vectors) comes back replicated
                wsc = jax.lax.with_sharding_constraint
                new_w = [wsc(a, param_shardings[j])
                         for j, a in enumerate(new_w)]
                new_s = [jax.tree_util.tree_map(wsc, ns, ssh)
                         for ns, ssh in zip(new_s, state_shardings)]
                states = {k: wsc(v, replicated)
                          for k, v in states.items()}
                loss = wsc(loss, replicated)
                statvecs = [wsc(v, replicated) for v in statvecs]
            return new_w, new_s, states, loss, statvecs

        return step_fn

    # ---- captured dispatch ------------------------------------------------
    def _run_captured(self, cap, datas, labels, batch_size):
        jax = _jax()
        from .. import monitor as _mon
        from .. import random as _mxrandom

        trainer = self._trainer
        opt = trainer._optimizer
        step = trainer._step_count
        obs_on = _obs.core.ENABLED
        t0 = _time.perf_counter() if (_tel.ENABLED or obs_on) else 0.0
        _m = [0.0] * 6  # obs phase marks: slots/stage/dispatch/wb/pub
        with _trace.span("train_step", hist=False, anomaly=True,
                         args={"step": step, "captured": 1}), \
                _trace.watchdog.watch("train_step"):
            opt.rescale_grad = trainer._scale / batch_size
            named = self._block.collect_params()
            w_handles = [trainer._params[i].data() for i in cap.train_idx]
            train_datas = [h._data for h in w_handles]
            state_trees = [_mt._unwrap_state(trainer._states[i])
                           for i in cap.train_idx]
            other_datas = [named[n]._data._data for n in cap.other_names]
            rng = _mxrandom.take_key()
            # the real host bookkeeping the traced no-ops stand in for;
            # snapshot first so a failed/vetoed launch rewinds exactly
            # once (Adam bias-correction t must not advance for a step
            # that never applied)
            counts = opt._index_update_count
            prev_counts = {i: counts.get(i) for i in cap.train_idx}
            prev_num_update = opt.num_update
            for i in cap.train_idx:
                opt._update_count(i)
            try:
                # mx.resilience drill site, AFTER the count bump: a
                # transient here exercises the supervisor rewind path
                _inject.fire("step_capture", seq=step)
                if obs_on:
                    _m[0] = _time.perf_counter()
                with _trace.span("step_slots", hist=False):
                    vals = _np.zeros((cap.n_slots,), _np.float32)
                    for k, f in enumerate(cap.slot_fns):
                        vals[k] = f()
                if obs_on:
                    _m[1] = _time.perf_counter()
                inputs, lbls, vals, rng = self._stage(
                    cap, [x._data for x in datas],
                    [y._data for y in labels], vals, rng)
                if obs_on:
                    _m[2] = _time.perf_counter()
                with _trace.span("step_dispatch", hist=False,
                                 args={"groups": len(cap.group_list),
                                       "buckets": len(cap.bucket_plan)}):
                    out = self._dispatch(
                        cap, train_datas, state_trees, other_datas,
                        vals, rng, inputs, lbls)
                if obs_on:
                    _m[3] = _time.perf_counter()
            except Exception:
                self._rewind(prev_counts, prev_num_update)
                raise
            # from here on the program RAN: its device effects are
            # real (or were gated to no-ops), so any error below must
            # surface as-is — a stitched replay would apply the step
            # twice.  __call__ honors the mx_step_no_fallback tag.
            try:
                new_w, new_s, aux_states, loss, statvecs = out
                with _trace.span("step_writeback", hist=False):
                    for j, i in enumerate(cap.train_idx):
                        w_handles[j]._data = new_w[j]
                        st = trainer._states[i]
                        if st is not None:
                            jax.tree_util.tree_map(_wb, st, new_s[j],
                                                   is_leaf=_mt._is_nd)
                    # functionalized forward state (BatchNorm running
                    # stats etc.) updates on EVERY step, skipped or
                    # not — exactly like the stitched path, whose
                    # forward ran before the sentinel verdict
                    for pkey, val in aux_states.items():
                        p = named.get(pkey)
                        if p is not None:
                            p._data._data = val
                if obs_on:
                    _m[4] = _time.perf_counter()
                applied = True
                if cap.monitor:
                    entries = list(zip(cap.labels, statvecs))
                    with _trace.span("step_publish", hist=False):
                        try:
                            verdict = _mon.core.observe_captured(
                                trainer, step, entries)
                        except MXNetError:
                            # policy=raise: the program gated updates
                            # to no-ops on device; rewind the host
                            # counters before surfacing
                            self._rewind(prev_counts, prev_num_update)
                            raise
                    if obs_on:
                        _m[5] = _time.perf_counter()
                    if verdict == "skip":
                        self._rewind(prev_counts, prev_num_update)
                        self._skipped += 1
                        applied = False
                if applied:
                    trainer._step_count += 1
                self._path_counts["captured"] += 1
                mesh_reduces = cap.gmesh is not None and cap.gmesh.dp > 1
                if self._world > 1 or self._axis_name is not None \
                        or mesh_reduces:
                    # the stitched path only observes bucket fill when
                    # collectives actually run; mirror that so the two
                    # paths stay comparable (a world of one reduces
                    # nothing).  Payload bytes feed the SAME
                    # collective_* series the eager kvstore path does
                    # ("allreduce"), or "reduce_scatter" under a
                    # ZeRO-2/3 mesh — plus the params "all_gather" a
                    # sharded update pays to re-materialize weights.
                    # Priced WIRE bytes live in cap.wire / report().
                    observe_bucket_fill(
                        cap.bucket_nbytes,
                        op="reduce_scatter" if (
                            mesh_reduces and cap.level >= 2)
                        else "allreduce",
                        bucket_bytes=cap.bucket_bytes)
                    if mesh_reduces and cap.level >= 1:
                        observe_collective(
                            "all_gather",
                            cap.donation["params"]["bytes"])
                if _tel.ENABLED and cap.wire is not None:
                    # per-axis priced wire bytes: what the first live
                    # TPU window compares against measured step time
                    if mesh_reduces:
                        _tel.SHARD_COLLECTIVE_BYTES.labels(
                            axis="dp",
                            op="reduce_scatter" if cap.level >= 2
                            else "all_reduce").inc(
                            int(cap.wire["grads"]))
                        _tel.SHARD_COLLECTIVE_BYTES.labels(
                            axis="dp", op="all_gather").inc(
                            int(cap.wire["param_gather"]))
                    if cap.gmesh is not None and cap.gmesh.mdl > 1:
                        _tel.SHARD_COLLECTIVE_BYTES.labels(
                            axis="mdl", op="all_gather").inc(
                            int(cap.wire.get("mdl_gather", 0) or 0))
                if _tel.ENABLED:
                    _tel.STEP_CAPTURE_STEPS.labels(path="captured").inc()
                    _tel.STEP_PROGRAM_SECONDS.observe(
                        _time.perf_counter() - t0)
                if obs_on:
                    try:
                        total = _time.perf_counter() - t0
                        parts = {"slots": _m[1] - _m[0],
                                 "stage": _m[2] - _m[1],
                                 "dispatch": _m[3] - _m[2],
                                 "writeback": _m[4] - _m[3]}
                        if _m[5]:
                            parts["host_publish"] = _m[5] - _m[4]
                        _obs.core.note_step(total)
                        _obs.attribution.observe_step(
                            step, total, parts=parts,
                            flops=cap.flops, path="captured")
                    except Exception:  # noqa: BLE001 - obs never
                        pass            # raises into the step
            except Exception as exc:
                exc.mx_step_no_fallback = True
                raise
        return NDArray(loss)

    def _dispatch(self, cap, *args):
        """Launch the captured program, bounded by the mx.dist
        collective deadline when one is armed in a multi-process world
        OR on a GlobalMesh (the whole captured dispatch IS the
        collective phase — and the mesh case is how the single-process
        virtual-device drills exercise the DistTimeout seam)."""
        if self._world <= 1 and cap.gmesh is None:
            return cap.call(*args)
        from ..dist import timeouts as _dt

        timeout = _dt.collective_timeout()
        if not timeout or timeout <= 0:
            return cap.call(*args)
        try:
            return _dt.run_with_deadline(lambda: cap.call(*args),
                                         site="step_capture",
                                         timeout=timeout)
        except _dt.DistTimeout as exc:
            # unlike the stitched allreduce (which times out BEFORE any
            # optimizer mutation), a captured program may have consumed
            # its donated buffers mid-flight: the state is suspect and
            # must not be emergency-saved
            exc.mx_state_clean = False
            raise

    def _rewind(self, prev_counts, prev_num_update):
        opt = self._trainer._optimizer
        counts = opt._index_update_count
        for i, v in prev_counts.items():
            if v is None:
                counts.pop(i, None)
            else:
                counts[i] = v
        opt.num_update = prev_num_update


def _wb(old, new):
    old._data = new
    return old


def capture(block_or_trainer, loss_fn, trainer=None, block=None,
            axis_name=None):
    """Capture the whole training step — ``block`` forward, ``loss_fn``
    loss, backward, bucketed allreduce, fused optimizer apply and the
    monitor stat reductions — into one donated XLA program.

    Accepts the block or the trainer first (``capture(net, loss_fn,
    trainer=t)`` / ``capture(t, loss_fn, block=net)``); both must be
    supplied.  Returns a :class:`StepProgram`; each call of it runs one
    full training step (``program(data, label)`` -> loss) and degrades
    to the stitched imperative path whenever capture cannot apply.
    ``axis_name`` names the SPMD mesh axis bucket allreduces psum over
    (a world of one needs none).  The program registers with the
    trainer so checkpoint restores invalidate captured traces."""
    from ..gluon.trainer import Trainer

    obj = block_or_trainer
    if isinstance(obj, Trainer):
        if trainer is not None and trainer is not obj:
            raise MXNetError("capture: two different trainers supplied")
        trainer = obj
    else:
        if block is not None and block is not obj:
            raise MXNetError("capture: two different blocks supplied")
        block = obj
    if trainer is None:
        raise MXNetError(
            "mx.step.capture needs the gluon.Trainer that owns the "
            "parameters: capture(net, loss_fn, trainer=trainer)")
    if block is None:
        raise MXNetError(
            "mx.step.capture needs the HybridBlock to capture: "
            "capture(trainer, loss_fn, block=net)")
    prog = StepProgram(block, trainer, loss_fn, axis_name=axis_name)
    register = getattr(trainer, "_register_step_program", None)
    if register is not None:
        register(prog)
    return prog
