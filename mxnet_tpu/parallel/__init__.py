"""Parallelism over the TPU device mesh.

This package provides what the reference NEVER had (SURVEY §2.3): tensor /
sequence / expert parallelism and sharded training as first-class features,
plus the data-parallel capability the reference implemented with kvstore +
ps-lite/NCCL (src/kvstore/) — all expressed as jax.sharding Meshes and XLA
collectives over ICI:

- ``make_mesh``: name→size device mesh ('dp','tp','sp','pp','ep'...).
- ``FusedTrainer``: fwd+bwd+grad-psum+optimizer as ONE pjit-compiled XLA
  program over the mesh; parameters sharded by their Parameter.sharding
  hints (TP/FSDP), batch sharded over dp×sp.  This is the TPU equivalent of
  the entire dist-kvstore training stack (kvstore_dist.h push/pull overlap,
  server-side optimizer, CommDevice tree reduce) AND of CachedOp bulking.
- ``ring_attention`` / ``ulysses_attention``: context parallelism for long
  sequences (SURVEY §5.7 — absent in the reference).
"""
from __future__ import annotations

import contextlib
import functools

import numpy as _np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError
from ..ndarray.ndarray import NDArray
from .optim import make_optimizer, shard_update
from .ring import ring_attention, ulysses_attention

__all__ = ["make_mesh", "make_hybrid_mesh", "FusedTrainer",
           "PipelineTrainer", "make_train_step",
           "ring_attention", "ulysses_attention", "P", "Mesh",
           "NamedSharding", "shard_params", "param_pspec", "SUPPORTS_ZERO"]

# feature gate for the driver dryrun: FusedTrainer(zero=True) shards
# optimizer state over dp (ZeRO-1)
SUPPORTS_ZERO = True


def make_mesh(axes=None, devices=None):
    """Build a named device mesh.

    axes: dict name->size; a single axis size may be -1 (filled with the
    remaining devices).  Default: {'dp': n_devices}.
    """
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if axes is None:
        axes = {"dp": n}
    names = list(axes.keys())
    sizes = list(axes.values())
    if -1 in sizes:
        known = 1
        for s in sizes:
            if s != -1:
                known *= s
        sizes[sizes.index(-1)] = n // known
    total = 1
    for s in sizes:
        total *= s
    if total > n:
        raise MXNetError("mesh %s needs %d devices, have %d"
                         % (dict(zip(names, sizes)), total, n))
    dev_array = _np.asarray(devices[:total]).reshape(sizes)
    return Mesh(dev_array, tuple(names))


def make_hybrid_mesh(dcn_axes, ici_axes):
    """Multi-slice mesh: outer axes ride the slow DCN (inter-slice network),
    inner axes the fast ICI — the TPU rendering of the reference's
    two-tier ps-lite/NCCL hierarchy (docs/.../distributed_training.md:
    rack-local allreduce then cross-rack push/pull).

    dcn_axes / ici_axes: dict name->size, e.g.
    ``make_hybrid_mesh({'dp_dcn': 2}, {'dp': 2, 'tp': 2})`` on 8 devices.
    Slice boundaries come from ``device.slice_index`` when the runtime
    exposes it (multi-slice TPU); otherwise devices are grouped by
    process (multi-host) or split contiguously (single host / CPU mesh) —
    contiguous blocks keep intra-axis collectives on neighboring devices,
    which is what mesh_utils.create_hybrid_device_mesh optimizes for.

    Shardings over the combined mesh then place DCN-crossing collectives
    on the outer axes only: e.g. grads psum over ('dp', 'dp_dcn') run as a
    fast ICI reduce-scatter + a single small DCN allreduce.
    """
    devices = jax.devices()
    n_dcn = 1
    for s in dcn_axes.values():
        n_dcn *= s
    n_ici = 1
    for s in ici_axes.values():
        n_ici *= s
    if n_dcn * n_ici > len(devices):
        raise MXNetError("hybrid mesh needs %d devices, have %d"
                         % (n_dcn * n_ici, len(devices)))
    devices = devices[:n_dcn * n_ici]
    key = (lambda d: getattr(d, "slice_index", None)) \
        if getattr(devices[0], "slice_index", None) is not None \
        else (lambda d: d.process_index)
    groups = {}
    for d in devices:
        groups.setdefault(key(d), []).append(d)
    if len(groups) == n_dcn and all(
            len(g) == n_ici for g in groups.values()):
        ordered = [d for k in sorted(groups) for d in groups[k]]
    else:  # single host / CPU mesh: contiguous split
        ordered = list(devices)
    shape = tuple(dcn_axes.values()) + tuple(ici_axes.values())
    names = tuple(dcn_axes.keys()) + tuple(ici_axes.keys())
    return Mesh(_np.asarray(ordered).reshape(shape), names)


def param_pspec(param, mesh):
    """PartitionSpec from a Parameter.sharding hint, dropping axes the mesh
    does not have (so the same model runs on any mesh shape)."""
    hint = getattr(param, "sharding", None)
    if hint is None:
        return P()
    spec = []
    for ax in hint:
        if ax is not None and ax in mesh.axis_names and \
                mesh.shape[ax] > 1:
            spec.append(ax)
        else:
            spec.append(None)
    return P(*spec)


def shard_params(block, mesh):
    """Device-put every initialized parameter according to its hint."""
    out = {}
    for name, param in block.collect_params().items():
        spec = param_pspec(param, mesh)
        sharding = NamedSharding(mesh, spec)
        if param._data is not None:
            param._data._data = jax.device_put(param._data._data, sharding)
        out[name] = spec
    return out


class FusedTrainer:
    """One-XLA-program training over a mesh.

    Usage::

        net = model_zoo.vision.resnet50_v1()
        net.initialize()
        trainer = parallel.FusedTrainer(
            net, loss="softmax_ce", optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            mesh=parallel.make_mesh({"dp": 8}))
        loss = trainer.step(x, y)          # jax or NDArray batches

    The step runs forward, backward, cross-dp gradient reduction (implicit:
    XLA inserts psum from the shardings) and the optimizer update inside a
    single compiled program with donated buffers (the reference's
    static_alloc + inplace memory planning, done by XLA).
    """

    def __init__(self, block, loss=None, optimizer="sgd",
                 optimizer_params=None, mesh=None, loss_fn=None,
                 batch_axes=("dp",), dtype=None, grad_accum=1, zero=False):
        self._block = block
        self._mesh = mesh
        # mixed precision: fp32 master weights; compute in dtype (bf16 is
        # the TPU-native mode — MXU bf16 matmuls accumulate f32, no loss
        # scaling needed; reference contrib/amp did fp16 + LossScaler)
        if dtype in (None, "float32", "fp32"):
            self._dtype = None
        elif dtype in ("bfloat16", "bf16", jnp.bfloat16):
            self._dtype = jnp.bfloat16
        elif dtype in ("float16", "fp16", jnp.float16):
            self._dtype = jnp.float16
        else:
            raise MXNetError("unsupported FusedTrainer dtype %r" % (dtype,))
        self._batch_axes = tuple(a for a in batch_axes
                                 if mesh is not None and
                                 a in mesh.axis_names)
        if grad_accum < 1:
            raise MXNetError("grad_accum must be >= 1, got %r" % grad_accum)
        self._grad_accum = int(grad_accum)
        # ZeRO: shard the weight update over dp (PAPERS.md cross-replica
        # weight-update sharding / mx.shard levels).  True/1 shards
        # optimizer state (XLA derives the collectives from the state
        # shardings); 2 additionally constrains gradients to the shard
        # layout EXPLICITLY (optim.shard_update — a reduce-scatter, never
        # a replicated grad); 3 also dp-shards the parameters between
        # steps (forward all-gathers on demand).
        from ..shard import normalize_level as _zero_level

        level = _zero_level(zero)
        if level and (mesh is None or "dp" not in mesh.axis_names):
            raise MXNetError("zero=%r requires a mesh with a dp axis"
                             % (zero,))
        self._zero = level if (level and mesh.shape["dp"] > 1) else 0
        optimizer_params = dict(optimizer_params or {})
        self._lr, self._lr_scheduler = _pop_lr_schedule(optimizer_params)
        self._opt_init, self._opt_update = make_optimizer(
            optimizer, learning_rate=self._lr, **optimizer_params)
        # a user loss_fn receives ALL model outputs and ALL labels:
        # loss_fn(outputs_list, *labels) -> scalar/per-example loss
        # (multi-input models pass x as a tuple, multi-label as y tuple)
        self._user_loss = loss_fn is not None
        self._loss_fn = loss_fn or _make_loss(loss)
        self._apply = None
        self._params = None
        self._opt_state = None
        self._step_fn = None
        self._step_count = 0
        self._param_specs = None

    # -- param plumbing -----------------------------------------------------
    def _setup(self, *example_inputs):
        block = self._block
        # resolve deferred shapes with an eager probe
        from .. import autograd

        if any(p._data is None for p in block.collect_params().values()):
            with autograd.pause():
                block(*[NDArray(x) for x in example_inputs])
        apply_fn, params = block.export_pure(training=True)
        self._apply = apply_fn
        named = block.collect_params()
        self._trainable = {n for n, p in named.items()
                           if p.grad_req != "null"}
        if self._mesh is not None:
            self._param_specs = {n: param_pspec(p, self._mesh)
                                 for n, p in named.items()}
            if self._zero >= 3:
                # ZeRO-3: trainable params live dp-sharded BETWEEN
                # steps (same first-divisible-dim rule as the state
                # shards); the step program all-gathers them on demand
                self._param_specs = {
                    n: (self._dp_extend(s, params[n].shape)
                        if n in self._trainable else s)
                    for n, s in self._param_specs.items()}
            params = {
                n: jax.device_put(v, NamedSharding(self._mesh,
                                                   self._param_specs[n]))
                for n, v in params.items()}
        self._params = params
        self._opt_state = self._opt_init(
            {n: v for n, v in params.items() if n in self._trainable})
        if self._zero:
            self._state_specs = self._make_zero_specs(self._opt_state)
            self._opt_state = jax.tree_util.tree_map(
                lambda v, s: jax.device_put(
                    v, NamedSharding(self._mesh, s)),
                self._opt_state, self._state_specs)
        else:
            self._state_specs = None
        self._build_step()
        pending = getattr(self, "_pending_state", None)
        if pending is not None:
            self._pending_state = None
            self._apply_state(pending)

    def _dp_extend(self, spec, shape):
        """Add ``dp`` on the first divisible, unsharded axis of ``spec``
        (no-op when dp already appears — a user FSDP hint wins)."""
        dp = self._mesh.shape["dp"]
        base = list(spec) + [None] * (len(shape) - len(spec))
        if "dp" in base:
            return P(*base)
        for ax, dim in enumerate(shape):
            if base[ax] is None and dim > 0 and dim % dp == 0:
                base[ax] = "dp"
                break
        return P(*base)

    def _make_zero_specs(self, opt_state):
        """Per-leaf PartitionSpecs sharding optimizer state over dp.

        Each state leaf mirrors its parameter's shape: keep the param's own
        (tp) sharding and additionally split the first dp-divisible
        unsharded axis across dp.  Leaves with no divisible axis stay
        replicated (biases etc. — negligible memory)."""
        dp = self._mesh.shape["dp"]

        def spec_for(name, leaf):
            return self._dp_extend(self._param_specs.get(name, P()),
                                   leaf.shape)

        specs = {k: jax.tree_util.tree_map(lambda v: spec_for(k, v), leaf)
                 for k, leaf in opt_state.items()}
        flat_specs = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda s: isinstance(s, P))
        if flat_specs and not any("dp" in s for s in flat_specs):
            import warnings

            warnings.warn(
                "zero=True had no effect: no optimizer-state dimension is "
                "divisible by dp=%d, so every shard is a full replica "
                "(pad the model dims or lower dp to actually shard)" % dp,
                stacklevel=3)
        return specs

    def _build_step(self):
        apply_fn = self._apply
        loss_fn = self._loss_fn
        trainable = self._trainable
        opt_update = self._opt_update
        if self._zero >= 2 and self._state_specs is not None:
            # ZeRO-2/3: explicit weight-update-sharding transform — the
            # grads entering the update are constrained to the state
            # shard layout (reduce-scatter, never a replicated grad)
            # and the fresh params to their forward layout
            opt_update = shard_update(
                opt_update, self._mesh, self._state_specs,
                {n: self._param_specs[n] for n in self._trainable})
        accum = self._grad_accum
        compute_dtype = self._dtype
        from ..contrib.amp import FP32_PARAM_SUFFIXES as _fp32_sufs

        user_loss = self._user_loss
        mesh = self._mesh
        if mesh is None:
            rows = contextlib.nullcontext
        else:
            # Pallas kernels cannot be partitioned automatically: tell
            # them, while the step traces, which axes split the batch
            from ..ops.pallas_attention import mesh_rows

            rows = functools.partial(mesh_rows, mesh, self._batch_axes)

        def cast_in(full, xs):
            """Mixed-precision boundary: cast f32 weights + inputs to the
            compute dtype; normalization params/statistics stay f32 (the
            per-op safety list — batch_norm/layer_norm then normalize in
            f32 and emit the compute dtype)."""
            if compute_dtype is None:
                return full, xs
            full = {n: (v.astype(compute_dtype)
                        if v.dtype == jnp.float32 and
                        not n.split(".")[-1] in _fp32_sufs else v)
                    for n, v in full.items()}
            xs = tuple(x.astype(compute_dtype)
                       if jnp.issubdtype(x.dtype, jnp.floating) else x
                       for x in xs)
            return full, xs

        # device scopes (metadata only: the compiled program is the
        # same).  JAX names the backward after the forward's scope by
        # itself: a step's operations read ``jvp(mx.step.forward)``,
        # ``transpose(jvp(mx.step.forward))`` and ``mx.step.optimizer``
        # in the profiler's trace (chipbench/spans.py sums them)
        @jax.named_scope("mx.step.forward")
        def loss_of(tp, frozen, rng, xs, ys):
            full = dict(frozen)
            full.update(tp)
            full, xs = cast_in(full, xs)
            outs, new_states = apply_fn(full, rng, *xs)
            if user_loss:
                loss = loss_fn(outs, *ys)
            else:
                if len(ys) > 1:
                    raise MXNetError(
                        "built-in losses take ONE label array; pass a "
                        "custom loss_fn(outputs, *labels) for multi-label "
                        "training (got %d label arrays)" % len(ys))
                loss = loss_fn(outs[0], ys[0])
            return jnp.mean(loss), new_states

        # the program's NAME carries the scopes' version: JAX's persistent
        # compile cache keys a program by its name and its stripped IR, not
        # by op_name metadata, so under an old name an executable cached
        # before the scopes existed (or under other scope names) is loaded
        # with its old op_names and the trace shows those (seen on the chip,
        # PR 24: ResNet-50's step came from PR 23's cache without a scope).
        # Whoever renames a scope renames the program.
        def mx_step(*args):
            with rows():
                return step_body(*args)

        def step_body(params, opt_state, step_i, lr_t, rng, xs, ys):
            train_p = {n: v for n, v in params.items() if n in trainable}
            frozen = {n: v for n, v in params.items() if n not in trainable}
            vg = jax.value_and_grad(loss_of, has_aux=True)

            if accum == 1:
                (loss, new_states), grads = vg(train_p, frozen, rng, xs, ys)
            else:
                if xs[0].shape[0] % accum != 0:
                    raise MXNetError(
                        "batch size %d not divisible by grad_accum=%d"
                        % (xs[0].shape[0], accum))
                # k microbatches through ONE jitted scan: grads averaged
                # across microbatches (mean-of-means == mean over the full
                # batch for equal microbatch sizes), a single optimizer
                # update at the end.  Peak activation memory drops ~k×.
                def mb(a):
                    return a.reshape((accum, a.shape[0] // accum)
                                     + a.shape[1:])

                xm = tuple(mb(x) for x in xs)
                ym = tuple(mb(y) for y in ys)
                # ALL k microbatches inside one scan (the fwd+bwd XLA code
                # appears once in the program, not twice): the state-dict
                # structure is discovered with eval_shape (zero FLOPs) and
                # the carry starts from the current running stats.
                state_struct = jax.eval_shape(
                    lambda: vg(train_p, frozen, rng,
                               tuple(x[0] for x in xm),
                               tuple(y[0] for y in ym)))[0][1]
                states0 = {k: (frozen[k] if k in frozen else train_p[k])
                           for k in state_struct}
                g0 = jax.tree_util.tree_map(jnp.zeros_like, train_p)

                def body(carry, xy):
                    acc_loss, acc_g, states, i = carry
                    xi, yi = xy
                    # thread running stats (BN etc.) sequentially through
                    # the microbatches, like k small steps with no param
                    # update in between; independent dropout per microbatch
                    fz = dict(frozen)
                    fz.update(states)
                    (li, si), gi = vg(train_p, fz,
                                      jax.random.fold_in(rng, i), xi, yi)
                    acc_g = jax.tree_util.tree_map(jnp.add, acc_g, gi)
                    return (acc_loss + li, acc_g, si, i + 1), None

                (loss, grads, new_states, _i), _ = jax.lax.scan(
                    body, (jnp.float32(0), g0, states0, jnp.uint32(0)),
                    (xm, ym))
                loss = loss / accum
                grads = jax.tree_util.tree_map(
                    lambda g: g / accum, grads)

            with jax.named_scope("mx.step.optimizer"):
                new_train, new_opt = opt_update(step_i, train_p, grads,
                                                opt_state, lr_t)
            new_params = dict(frozen)
            new_params.update(new_train)
            new_params.update(new_states)  # running stats etc.
            return new_params, new_opt, loss

        if self._mesh is not None:
            batch_spec = P(self._batch_axes if self._batch_axes else None)
            self._batch_sharding = NamedSharding(self._mesh, batch_spec)
            param_sh = {n: NamedSharding(self._mesh, self._param_specs[n])
                        for n in self._params}
            state_sh = None
            out_state_sh = None
            if self._zero:
                state_sh = jax.tree_util.tree_map(
                    lambda s: NamedSharding(self._mesh, s),
                    self._state_specs,
                    is_leaf=lambda s: isinstance(s, P))
                out_state_sh = state_sh
            self._step_fn = jax.jit(
                mx_step,
                in_shardings=(param_sh, state_sh, None, None, None,
                              NamedSharding(self._mesh, batch_spec),
                              NamedSharding(self._mesh, batch_spec)),
                out_shardings=(param_sh, out_state_sh, None),
                donate_argnums=(0, 1))
        else:
            self._step_fn = jax.jit(mx_step, donate_argnums=(0, 1))
        # for mx.step.dispatch's and mx.step.recompile's args
        self._n_leaves = 3 + len(jax.tree_util.tree_leaves(
            (self._params, self._opt_state)))
        self._programs = 0

    # -- public -------------------------------------------------------------
    def _stage(self, x, y):
        """(xs, ys) tuples of jax arrays for the step program, built (and
        the program with them) on first use, batch-sharded on a mesh."""
        def as_jax(v):
            return v._data if isinstance(v, NDArray) else jnp.asarray(v)

        xs = tuple(as_jax(v) for v in x) if isinstance(x, (tuple, list)) \
            else (as_jax(x),)
        ys = tuple(as_jax(v) for v in y) if isinstance(y, (tuple, list)) \
            else (as_jax(y),)
        if self._step_fn is None:
            self._setup(*xs)
        if self._mesh is not None:
            # committed single-device arrays (NDArray _data) would clash
            # with the jitted in_shardings; reshard onto the batch axes
            xs = tuple(jax.device_put(v, self._batch_sharding) for v in xs)
            ys = tuple(jax.device_put(v, self._batch_sharding) for v in ys)
        return xs, ys

    def step(self, x, y):
        """One fused training step.  ``x``/``y`` may each be a single array
        or a tuple (multi-input models / multi-label losses); all leading
        dims are the batch."""
        from .. import random as mxrandom
        from .. import trace as _trace
        from ..resilience import inject as _inject

        # host spans: ring events under one root, and annotations in the
        # profiler's own trace whenever a session is live (hist=False: a
        # dotted name is no Prometheus name)
        with _trace.span("mx.step", hist=False, step_num=self._step_count):
            # mx.resilience drill site: fires BEFORE the donated launch,
            # so a faulted step leaves params/opt_state untouched and the
            # supervisor's restore-and-replay is exact
            _inject.fire("trainer_step", seq=self._step_count)
            with _trace.span("mx.step.stage", hist=False) as staged:
                xs, ys = self._stage(x, y)
                staged.note(
                    arrays=len(xs) + len(ys),
                    put_bytes=sum(v.nbytes for v in xs + ys)
                    if self._mesh is not None else 0)
            with _trace.span("mx.step.rng", hist=False):
                rng = mxrandom.take_key()
            with _trace.span("mx.step.scalars", hist=False):
                # reference num_update starts at 1 (_update_count
                # increments before _get_lr, optimizer.py:100) — keep the
                # same phase
                lr_t = (self._lr_scheduler(self._step_count + 1)
                        if self._lr_scheduler is not None else self._lr)
                step_i = jnp.uint32(self._step_count)
                lr_t = jnp.float32(lr_t)
            with _trace.span("mx.step.dispatch", hist=False,
                             args={"leaves": self._n_leaves
                                   + len(xs) + len(ys)}):
                self._params, self._opt_state, loss = self._step_fn(
                    self._params, self._opt_state, step_i, lr_t, rng,
                    xs, ys)
            programs = self._step_fn._cache_size()
            if programs != self._programs:
                if self._programs:  # the first program is no RE-compile
                    _trace.instant("mx.step.recompile", args={
                        "step_num": self._step_count,
                        "shapes": ";".join(
                            "x".join(map(str, v.shape)) for v in xs + ys)})
                self._programs = programs
            self._step_count += 1
            return NDArray(loss)

    def _lower(self, x, y):
        """The step program lowered for a batch like ``(x, y)`` — a
        ``jax.stages.Lowered`` for ``chip_smoke.py`` to read (the kernels
        and collectives in it) without taking a step."""
        xs, ys = self._stage(x, y)
        return self._step_fn.lower(
            self._params, self._opt_state, jnp.uint32(0), jnp.float32(0),
            jax.random.PRNGKey(0), xs, ys)

    def sync_block(self):
        """Write the trained params back into the Gluon block (gathering
        mesh-sharded values onto one device for eager use)."""
        named = self._block.collect_params()
        for n, v in self._params.items():
            if n in named and named[n]._data is not None:
                if self._mesh is not None:
                    v = jnp.asarray(_np.asarray(v))
                named[n]._data._data = v

    # -- checkpoint/resume (mxnet_tpu.elastic contract) ---------------------
    def state_dict(self):
        """Full training state as a jax pytree (params + optimizer state +
        step counter) for CheckpointManager.  Returns None before the
        first step (structure unknown until _setup)."""
        if self._params is None:
            return None
        return {"params": self._params, "opt_state": self._opt_state,
                "step": jnp.uint32(self._step_count)}

    def load_state_dict(self, state):
        """Restore training state.  Safe BEFORE the first step too: the
        state is parked and applied after _setup builds the program (a
        fresh-process resume must not be overwritten by _setup's fresh
        init)."""
        if self._params is None:
            self._pending_state = state
            return
        self._apply_state(state)

    def _apply_state(self, state):
        params, opt_state = state["params"], state["opt_state"]
        if self._mesh is not None and self._param_specs is not None:
            params = {n: jax.device_put(
                v, NamedSharding(self._mesh, self._param_specs[n]))
                for n, v in params.items()}
            if self._zero and self._state_specs is not None:
                opt_state = jax.tree_util.tree_map(
                    lambda v, s: jax.device_put(
                        v, NamedSharding(self._mesh, s)),
                    opt_state, self._state_specs)
        self._params = params
        self._opt_state = opt_state
        self._step_count = int(state["step"])

    def _checkpoint_manager(self, root, **manager_kwargs):
        from ..checkpoint import cached_manager

        return cached_manager(self, root, **manager_kwargs)

    def save_checkpoint(self, root, step=None, block=True,
                        manager=None, **manager_kwargs):
        """Persist the full training state (params + optimizer state +
        step) through ``mx.checkpoint``.  ``block=False`` returns a
        ``SaveFuture`` after only the device->host snapshot — the step
        loop keeps running while the background writer commits.  Pass
        ``manager`` to share one ``CheckpointManager`` across trainers;
        otherwise one is cached per root on this trainer."""
        state = self.state_dict()
        if state is None:
            raise MXNetError(
                "save_checkpoint before the first step: the trainer has "
                "no state yet")
        mgr = manager or self._checkpoint_manager(root, **manager_kwargs)
        step = self._step_count if step is None else int(step)
        fut = mgr.save_async(step, state)
        return fut.result() if block else fut

    def load_checkpoint(self, root, step=None, manager=None):
        """Restore a ``save_checkpoint`` step (default latest).  Leaves
        land back on THIS trainer's current mesh/sharding — restarting
        on a different replica count reshards transparently.  Returns
        the restored step."""
        mgr = manager or self._checkpoint_manager(root)
        step, state = mgr.restore(self.state_dict(), step=step)
        self.load_state_dict(state)
        return step

    @property
    def params(self):
        return self._params


def _pop_lr_schedule(optimizer_params):
    """Shared Fused/Pipeline trainer LR plumbing.  Reference Optimizer
    contract (optimizer.py:65): an EXPLICIT learning_rate re-bases the
    schedule; a defaulted one must not clobber the scheduler's own
    base_lr.  The schedule itself is evaluated host-side each step and
    fed into the compiled program as a scalar argument (no recompiles)."""
    explicit = "learning_rate" in optimizer_params
    lr = optimizer_params.pop("learning_rate", 0.01)
    scheduler = optimizer_params.pop("lr_scheduler", None)
    if scheduler is not None and explicit and hasattr(scheduler, "base_lr"):
        scheduler.base_lr = lr
    return lr, scheduler


def _make_loss(loss):
    from ..gluon import loss as gloss

    if loss in (None, "softmax_ce", "softmax_cross_entropy"):
        def fn(pred, label):
            # loss math in f32 regardless of compute dtype (bf16 logits
            # lose ~3 decimal digits in the log-sum-exp otherwise)
            logp = jax.nn.log_softmax(pred.astype(jnp.float32), axis=-1)
            lbl = label.astype(jnp.int32)
            return -jnp.take_along_axis(logp, lbl[..., None],
                                        axis=-1)[..., 0]

        return fn
    if loss == "l2":
        return lambda pred, label: 0.5 * jnp.square(
            pred.astype(jnp.float32) - label.astype(jnp.float32))
    if callable(loss):
        return loss
    raise MXNetError("unknown fused loss %r" % loss)


def make_train_step(block, loss="softmax_ce", optimizer="sgd",
                    optimizer_params=None, mesh=None, **kwargs):
    return FusedTrainer(block, loss=loss, optimizer=optimizer,
                        optimizer_params=optimizer_params, mesh=mesh,
                        **kwargs)


# imported last: pipeline.py pulls _make_loss from this module
from .pipeline import PipelineTrainer  # noqa: E402
from .moe import moe_apply  # noqa: E402

__all__ += ["moe_apply"]
