"""Collective KVStore — the dist_sync/dist_device_sync/nccl replacement.

Reference: KVStoreDist over ps-lite (src/kvstore/kvstore_dist.h — workers
ZPush/ZPull key shards to server processes, optional server-side optimizer)
and KVStoreNCCL (kvstore_nccl.h ncclAllReduce).

TPU-native redesign (SURVEY §5.8 north star): NO servers.  `pushpull` is a
synchronous all-reduce over the ICI mesh:
- single-host multi-chip: one jitted psum across local devices,
- multi-host (jax.distributed initialized): a psum over ALL devices in the
  global mesh — XLA routes it over ICI within a slice and DCN across
  slices, replacing both the NCCL ring and the ps-lite scheduler/server
  topology.  The optimizer always runs worker-side (update_on_kvstore is
  refused, like the reference's NCCL store).
"""
from __future__ import annotations

import time as _time

import jax
import jax.numpy as jnp
import numpy as _np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import telemetry as _tel
from .. import trace as _trace
from ..base import MXNetError, get_env
from ..ndarray.ndarray import NDArray
from ..resilience import inject as _inject
from .base import KVStoreBase
from .kvstore import _pair, _reduce


def default_bucket_bytes():
    """The hand-set gradient-fusion bucket size: how many bytes of
    keys fuse into one collective program (reference:
    MXNET_KVSTORE_BIGARRAY_BOUND splits big arrays; here the knob
    bounds how many small keys fuse into one psum launch).  Re-read
    from the environment per call: nothing caches it at import."""
    return int(get_env("MXNET_KVSTORE_BUCKET_BYTES", int, 4 << 20))


def plan_buckets(sizes_dtypes, bucket_bytes=None):
    """Pure bucket planner: ``[(nbytes, dtype_str), ...]`` (in push
    order) -> list of index buckets, each reduced by ONE collective
    program.

    Deterministic and order-preserving — every rank pushes the same
    keys in the same order, so identical plans (and therefore identical
    program sequences) fall out on all processes.  Buckets are
    per-dtype (the flat concat needs one dtype) and close once they
    reach ``bucket_bytes``; a single array larger than the bound gets
    its own bucket.  Total program count is therefore at most
    ``ceil(total_bytes / bucket_bytes)`` plus one per dtype switch."""
    if bucket_bytes is None:
        bucket_bytes = default_bucket_bytes()
    plan, bucket, nbytes, last_dtype = [], [], 0, None
    for i, (size, dtype) in enumerate(sizes_dtypes):
        if bucket and last_dtype != dtype:
            plan.append(bucket)
            bucket, nbytes = [], 0
        last_dtype = dtype
        bucket.append(i)
        nbytes += size
        if nbytes >= bucket_bytes:
            plan.append(bucket)
            bucket, nbytes = [], 0
    if bucket:
        plan.append(bucket)
    return plan


def observe_bucket_fill(bucket_nbytes, op=None, bucket_bytes=None):
    """Feed the ``allreduce_bucket_fill`` histogram from a precomputed
    bucket plan (``[payload bytes per bucket]``).  The per-call bucketed
    path observes fill inline in ``_allreduce_many``; a captured step
    program (mx.step) reduces inside ONE whole-step XLA program where
    that observation point never runs, so it feeds its static plan
    through here each dispatch — keeping the two paths comparable in
    telemetry.  ``bucket_bytes`` is the bucket size the plan was
    ACTUALLY built with (a custom ``plan_buckets(bucket_bytes=...)``);
    normalizing against anything else would lie about fill the moment
    the size varies, so callers with a plan must
    pass theirs — None falls back to the current env default.  ``op``
    additionally accounts the collective itself (one call per bucket,
    PAYLOAD bytes — the same semantics the eager ``_allreduce_many``
    path feeds) under the given label: ``allreduce`` (the eager path's
    series), or ``reduce_scatter`` for a ZeRO-2/3 sharded step.
    Priced WIRE bytes live in the capture report / bench rows, not
    here."""
    if not _tel.ENABLED:
        return
    denom = float(bucket_bytes if bucket_bytes else
                  default_bucket_bytes())
    for nbytes in bucket_nbytes:
        _tel.ALLREDUCE_BUCKET_FILL.observe(nbytes / denom)
    if op is not None:
        _tel.COLLECTIVE_CALLS.labels(op=op).inc(len(bucket_nbytes))
        _tel.COLLECTIVE_BYTES.labels(op=op).inc(
            int(sum(bucket_nbytes)))


def observe_collective(op, nbytes, calls=1):
    """Account one in-program collective (mx.step sharded dispatch:
    the params all-gather of a ZeRO update; ``nbytes`` = payload) in
    the same ``collective_*`` telemetry the eager kvstore path feeds."""
    if not _tel.ENABLED:
        return
    _tel.COLLECTIVE_CALLS.labels(op=op).inc(calls)
    _tel.COLLECTIVE_BYTES.labels(op=op).inc(int(nbytes))


def all_reduce_wire_bytes(payload_bytes, world):
    """Ring all-reduce wire cost: ``2 (N-1)/N * B`` per replica."""
    world = max(1, int(world))
    return 2 * int(payload_bytes) * (world - 1) // world


def reduce_scatter_wire_bytes(payload_bytes, world):
    """Reduce-scatter wire cost: ``(N-1)/N * B`` per replica — half the
    all-reduce price, which is the ZeRO-2/3 collective saving
    (arXiv 2004.13336)."""
    world = max(1, int(world))
    return int(payload_bytes) * (world - 1) // world


def _deadline(fn, site):
    """Run one collective phase under ``MXNET_DIST_COLLECTIVE_TIMEOUT``
    (mx.dist): a dead peer raises a transient-classified
    ``DistTimeout`` instead of hanging this rank forever, and the
    trace watchdog is armed around the wait.  Unarmed (the default, and
    always in a world of one) this is a plain call."""
    if jax.process_count() == 1:
        return fn()
    from ..dist import timeouts as _dt

    timeout = _dt.collective_timeout()
    if not timeout or timeout <= 0:
        with _trace.watchdog.watch(site):
            return fn()
    return _dt.run_with_deadline(fn, site=site, timeout=timeout)


class CollectiveKVStore(KVStoreBase):
    def __init__(self, mode="dist_sync", **kwargs):
        self._mode = mode
        self._store = {}
        self._compression = None
        self._sum_cache = {}
        self._mesh = None

    @property
    def type(self):
        return self._mode

    @property
    def rank(self):
        return jax.process_index()

    @property
    def num_workers(self):
        return jax.process_count()

    def set_gradient_compression(self, compression_params):
        """2-bit gradient compression with error feedback (reference
        gradient_compression.h; kvstore.py set_gradient_compression).
        Targets cross-slice DCN pushes — ICI makes compression
        counterproductive intra-pod."""
        from .gradient_compression import GradientCompression

        params = dict(compression_params or {})
        self._compression = GradientCompression(
            type=params.get("type", "2bit"),
            threshold=float(params.get("threshold", 0.5)))

    def _global_mesh(self):
        if self._mesh is None:
            devs = _np.asarray(jax.devices()).reshape(
                jax.process_count(), -1)
            self._mesh = Mesh(devs, ("proc", "local"))
        return self._mesh

    def _sum_program(self, shape, dtype):
        """Cached jitted cross-process sum: in = (nproc, L) sharded over the
        proc axis, out = (L,) fully replicated.  XLA lowers this to one
        all-reduce over DCN/ICI — no host round-trip, no O(N·size)
        gather."""
        key = (shape, str(dtype))
        fn = self._sum_cache.get(key)
        if fn is None:
            mesh = self._global_mesh()
            fn = jax.jit(
                lambda a: jnp.sum(a, axis=0),
                in_shardings=NamedSharding(mesh, P("proc")),
                out_shardings=NamedSharding(mesh, P()))
            self._sum_cache[key] = fn
        return fn

    def _allreduce_many(self, datas):
        """Sum each jax array across worker processes.

        Keys are fused into ~_BUCKET_BYTES flat buckets (per dtype) and
        each bucket is reduced by ONE compiled collective program.  All
        ranks push the same keys in the same order (same training script),
        so program sequences match across processes."""
        if jax.process_count() == 1:
            return list(datas)
        datas = [jnp.asarray(d) for d in datas]
        out = [None] * len(datas)
        sizes = [(d.size * d.dtype.itemsize, str(d.dtype))
                 for d in datas]
        # the plan's ACTUAL bucket size, read once — threaded through
        # to the fill observation below so fill numbers stay truthful
        # when the environment changes between the two
        bucket_bytes = default_bucket_bytes()
        plan = plan_buckets(sizes, bucket_bytes=bucket_bytes)
        for b, idxs in enumerate(plan):
            bucket = [(i, datas[i]) for i in idxs]
            nbytes = sum(a.size * a.dtype.itemsize for _, a in bucket)
            tel_on = _tel.ENABLED
            t0 = _time.perf_counter() if tel_on else 0.0
            # one flight-recorder span per collective program: bucket
            # index / key count / bytes
            with _trace.span("allreduce_bucket", hist=False,
                             args={"bucket": b, "keys": len(idxs),
                                   "bytes": nbytes}):
                flat = jnp.concatenate(
                    [jnp.ravel(a) for _, a in bucket]) if len(bucket) > 1 \
                    else jnp.ravel(bucket[0][1])
                sharding = NamedSharding(self._global_mesh(), P("proc"))
                # assemble the (nproc, L) global array directly from device
                # buffers — no host round-trip; the per-local-device put is a
                # device-to-device copy (the P('proc') shard is replicated over
                # the local axis).  Buckets are async dispatches, so successive
                # buckets overlap on the interconnect.
                local = flat[None]
                arrs = [jax.device_put(local, d)
                        for d in jax.local_devices()]
                garr = jax.make_array_from_single_device_arrays(
                    (jax.process_count(),) + flat.shape, sharding, arrs)
                summed = self._sum_program(flat.shape, flat.dtype)(garr)
                # detach the replicated global result into this process's
                # local buffer (still on device) — downstream eager ops must
                # not mix multi-process global arrays with single-device
                # arrays
                local_sum = summed.addressable_shards[0].data
                off = 0
                for i, a in bucket:
                    n = a.size
                    out[i] = local_sum[off:off + n].reshape(a.shape)
                    off += n
            if tel_on:
                # dispatch latency only — the psum itself is async (hard
                # syncs would serialize the bucket overlap noted above)
                _tel.COLLECTIVE_CALLS.labels(op="allreduce").inc()
                _tel.COLLECTIVE_BYTES.labels(op="allreduce").inc(nbytes)
                _tel.COLLECTIVE_SECONDS.observe(_time.perf_counter() - t0)
                _tel.ALLREDUCE_BUCKET_FILL.observe(
                    nbytes / float(bucket_bytes))
        return out

    def init(self, key, value):
        keys, values = _pair(key, value)
        for k, v in zip(keys, values):
            self._store[str(k)] = v.copy()

    def broadcast(self, key, value, out):
        keys, values = _pair(key, value)
        for k, v in zip(keys, values):
            # rank-0 value wins (reference: init on servers then pull)
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils

                # host-staged numpy in/out: init-time only, and the result
                # must be a process-local array — eager consumers (copyto
                # etc.) must never see non-addressable global devices
                host = _np.asarray(v._data)
                tel_on = _tel.ENABLED
                t0 = _time.perf_counter() if tel_on else 0.0
                data = _deadline(
                    lambda: multihost_utils.broadcast_one_to_all(host),
                    "broadcast")
                if tel_on:
                    _tel.COLLECTIVE_CALLS.labels(op="broadcast").inc()
                    _tel.COLLECTIVE_BYTES.labels(op="broadcast").inc(
                        host.nbytes)
                    _tel.COLLECTIVE_SECONDS.observe(
                        _time.perf_counter() - t0)
                data = jnp.asarray(data)
            else:
                data = v._data
            self._store[str(k)] = NDArray(data)
        if out is not None:
            self.pull(key, out)

    def push(self, key, value, priority=0):
        keys, values = _pair(key, value)
        if self._compression is not None:
            for k, v in zip(keys, values):
                # compressed path: quantize (+error feedback), exchange
                # packed 2-bit codes, decode-sum — replaces the raw allreduce
                merged = _reduce(v)
                self._store[str(k)] = NDArray(self._compression.allreduce(
                    str(k), merged._data))
            return
        merged = [_reduce(v)._data for v in values]
        for k, data in zip(keys, self._allreduce_many(merged)):
            self._store[str(k)] = NDArray(data)

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        keys, outs = _pair(key, out)
        for k, o in zip(keys, outs):
            src = self._store[str(k)]
            for dst in (o if isinstance(o, (list, tuple)) else [o]):
                src.copyto(dst)

    def pushpull(self, key, value, out=None, priority=0):
        self.push(key, value, priority)
        if out is not None:
            self.pull(key, out, priority)

    def pushpull_all(self, keys, values, out=None, priority=0):
        """The whole gradient list in one call: ``push`` hands every
        merged value to ``_allreduce_many`` at once, so CROSS-parameter
        buckets fill to MXNET_KVSTORE_BUCKET_BYTES — O(total_bytes /
        bucket) collective programs per step instead of one per key."""
        with _trace.span("pushpull_all", hist=False,
                         args={"keys": len(keys)}):
            # mx.resilience drill site: the collective-failure drill
            # fires here, before any bucket program launches
            _inject.fire("collective")
            # mx.dist deadline: the gradient all-reduce is where a dead
            # peer strands this rank — before any optimizer state has
            # mutated, which is why DistTimeout marks the state clean
            _deadline(
                lambda: self.pushpull(list(keys), list(values), out=out,
                                      priority=priority),
                "pushpull_all")

    def set_optimizer(self, optimizer):
        raise MXNetError(
            "collective kvstore runs the optimizer worker-side "
            "(update_on_kvstore=False), like the reference NCCL store")

    @staticmethod
    def is_capable(capability):
        return False
