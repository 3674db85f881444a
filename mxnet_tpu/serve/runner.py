"""ModelRunner — checkpoint-backed, shape-bucketed model execution.

The runner owns everything shape-related on the serving path:

- **load**: builds the block (instance or zero-arg factory), restores
  parameters from an ``mx.checkpoint`` root (restore-with-resharding
  onto the serving ctx via ``Block.load_checkpoint``), hybridizes.
- **bucket table**: the cross product of ``batch_sizes`` and
  ``sample_shapes`` defines every input signature the compiled cache
  will ever see.  ``warm_up()`` pre-compiles all of them through
  ``HybridBlock.warm_up`` so steady-state serving triggers at most one
  compile per bucket — and that compile happens before readiness, not
  on the first live request (TVM-style compile-once/run-many; TPU
  latency is strongly shape-dependent).
- **pad / unpad**: incoming samples are zero-padded up to the smallest
  covering sample bucket, stacked, and the batch is zero-padded up to
  the smallest covering batch size; outputs are sliced back to each
  request's real extent.  Pad waste is metered
  (``serve_pad_elements_total`` / ``serve_pad_fraction``).

Unpadding rule: output axis ``a`` (sample axis ``a-1``) is sliced back
to the request's extent when its size equals the padded size of the
FIRST input's corresponding sample axis.  That is exact for
row/position-independent models (MLPs applied along the last dim,
masked sequence models); models whose outputs do not track input axes
can pass ``unpad=False`` and slice downstream.
"""
from __future__ import annotations

from threading import RLock

import numpy as _np

from .. import autograd, telemetry, trace
from ..gluon.block import Block, HybridBlock
from .batching import NoBucketError

__all__ = ["ModelRunner", "DEFAULT_BATCH_SIZES", "resolve_block",
           "count_nonfinite"]

DEFAULT_BATCH_SIZES = (1, 2, 4, 8)


def resolve_block(block, cls=Block, who="ModelRunner"):
    """Unwrap a zero-arg block factory and type-check the result — the
    shared front door of both serving runners (``ModelRunner`` and
    ``decode.DecodeRunner``)."""
    if not isinstance(block, Block) and callable(block):
        block = block()
    if not isinstance(block, cls):
        raise ValueError("%s needs a %s or a zero-arg factory returning "
                         "one, got %r" % (who, cls.__name__, block))
    return block


def count_nonfinite(arrays):
    """NaN/Inf elements across host float arrays (the mx.monitor serve
    output guard's scan; the decode plane computes the same count
    in-program per logits row)."""
    bad = 0
    for a in arrays:
        if getattr(a.dtype, "kind", "") == "f":
            bad += int(a.size) - int(_np.isfinite(a).sum())
    return bad


def _normalize_sample_shapes(sample_shapes):
    """-> list of per-input shape tuples, sorted by padded volume (the
    bucket chooser scans in order, so the smallest covering bucket
    wins).  Accepts bare shape tuples for single-input models."""
    out = []
    for sig in sample_shapes or ():
        if isinstance(sig, (tuple, list)) and \
                all(isinstance(d, int) for d in sig):
            sig = (tuple(sig),)
        out.append(tuple(tuple(s) for s in sig))
    out.sort(key=lambda sig: sum(int(_np.prod(s)) for s in sig))
    return out


def _bucket_label(batch, sig):
    return "%dx%s" % (batch, "|".join(
        ",".join(str(d) for d in s) for s in sig))


class ModelRunner:
    """Load-once, pad-and-run model executor (swapped atomically by
    ``Server.swap`` — a runner never mutates its model after init).

    Parameters
    ----------
    block : Block or callable — the model, or a zero-arg factory.
    root : str or None — ``mx.checkpoint`` root to restore from.
    step : int or None — checkpoint step (default: latest committed).
    ctx : Context or None — serving device; restore reshards onto it.
    batch_sizes : sorted batch buckets (batch dim padding targets).
    sample_shapes : per-request shape buckets; None disables padding
        (each distinct request shape becomes its own exact bucket and
        compiles on first sight — fine for dev, not for production).
    dtype : input dtype requests are cast to.
    warm : pre-compile the whole bucket table at construction.
    unpad : slice outputs back to each request's real extent.
    """

    def __init__(self, block, root=None, step=None, ctx=None,
                 batch_sizes=DEFAULT_BATCH_SIZES, sample_shapes=None,
                 dtype="float32", warm=True, unpad=True):
        block = resolve_block(block)
        self._block = block
        self._ctx = ctx
        self._dtype = dtype
        self._unpad = bool(unpad)
        self.root = root
        self.step = None
        if root is not None:
            self.step = block.load_checkpoint(root, step=step, ctx=ctx)
        if isinstance(block, HybridBlock) and not block._active:
            block.hybridize(True, clear=False)
        self._batch_sizes = tuple(sorted(set(int(b) for b in batch_sizes)))
        if not self._batch_sizes:
            raise ValueError("batch_sizes must be non-empty")
        self._sample_buckets = _normalize_sample_shapes(sample_shapes)
        self._warmed = False
        self._warm_provenance = {}
        self._run_lock = RLock()  # one compiled program at a time
        if warm:
            self.warm_up()

    # -- introspection ------------------------------------------------------
    @property
    def block(self):
        return self._block

    @property
    def warmed(self):
        return self._warmed

    @property
    def max_batch_size(self):
        return self._batch_sizes[-1]

    def bucket_table(self):
        """[(batch, sample_sig), ...] — every signature warm_up compiles."""
        return [(b, sig) for sig in (self._sample_buckets or [()])
                for b in self._batch_sizes]

    def stats(self):
        return {
            "step": self.step,
            "root": self.root,
            "warmed": self._warmed,
            "dtype": self._dtype,
            "batch_sizes": list(self._batch_sizes),
            "sample_shapes": [[list(s) for s in sig]
                              for sig in self._sample_buckets],
            "buckets": [_bucket_label(b, sig)
                        for b, sig in self.bucket_table()
                        if sig],
            "compiled_signatures": len(getattr(self._block, "_cached_ops",
                                               ())),
            # per-bucket compile provenance from the last warm_up():
            # fresh (XLA compiled here) / warm-start (installed from
            # disk by that warm_up's mx.compile.warm_start preamble) /
            # cache (restored from the persistent cache earlier in this
            # process) / cache-failed (restored but failed at call
            # time; the jit fallback compiled fresh) / warm (compiled
            # earlier in this process) — operators verify a
            # zero-compile restart here (/statz)
            "warm_provenance": dict(self._warm_provenance),
        }

    # -- warm-up ------------------------------------------------------------
    def warm_up(self):
        """Pre-compile every (batch_size x sample_shape) bucket.  Emits
        one ``serve_compile_total{bucket=...}`` per newly built
        signature; re-warming an already-hot runner is a no-op (cache
        hits).  Returns the number of new signatures this process built.

        When the mx.compile persistent cache is enabled, the whole
        bucket table is first ``warm_start``-ed from disk (a restarted
        server reaches readiness with zero fresh XLA compiles), and
        each bucket's provenance — cache / fresh / warm-start /
        cache-failed / warm —
        is recorded for ``stats()`` (surfaced at ``/statz``)."""
        built = 0
        self._warm_provenance = {}
        if not isinstance(self._block, HybridBlock):
            self._warmed = True  # nothing to compile
            return built
        from .. import compile as _compile

        pre_ws = set(self._block._cached_ops)
        ws_installed = set()
        if _compile.is_enabled():
            try:
                # scope the restore to THIS runner's buckets: a shared
                # cache may hold many other deployments' signatures for
                # the same model, and each install pays a pickle +
                # executable device-load
                sigs = [[((b,) + tuple(s), self._dtype) for s in sig]
                        for b, sig in self.bucket_table() if sig]
                # no sample buckets configured means lazy compile —
                # NOT "restore every signature the shared cache holds"
                if sigs:
                    _compile.warm_start(self._block, signatures=sigs)
                    # keys warm_start ACTUALLY installed — a bucket the
                    # live attach path restores later in this loop must
                    # report "cache", not "warm-start"
                    ws_installed = set(self._block._cached_ops) - pre_ws
            except Exception:  # the cache must never block readiness
                pass
        for b, sig in self.bucket_table():
            if not sig:
                continue  # no sample buckets configured: lazy compile
            label = _bucket_label(b, sig)
            n = self._block.warm_up(
                [[((b,) + s, self._dtype) for s in sig]])
            if n:
                # warm_up counts only fresh XLA compiles (disk restores
                # return 0), so n > 0 means this process built it
                built += n
                self._warm_provenance[label] = "fresh"
                if telemetry.ENABLED:
                    telemetry.SERVE_COMPILES.labels(bucket=label).inc(n)
            else:
                # provenance comes from THIS bucket's cache entry (not
                # telemetry deltas or global warm_start counts, which
                # misattribute when telemetry is off or other buckets
                # were the ones installed)
                key, centry = self._bucket_centry(b, sig)
                if centry is not None and \
                        getattr(centry, "provenance", "fresh") == "cache":
                    if centry.cfn is None:
                        # the restored executable failed at call time
                        # during this warm_up's execution pass and the
                        # jit fallback compiled fresh — reporting
                        # "warm-start"/0 compiles would be the exact
                        # false positive /statz exists to catch
                        self._warm_provenance[label] = "cache-failed"
                    else:
                        self._warm_provenance[label] = \
                            "warm-start" if key in ws_installed \
                            else "cache"
                else:
                    self._warm_provenance[label] = "warm"
        self._warmed = True
        return built

    def _bucket_centry(self, b, sig):
        """The hybridize cache (key, entry) serving this warm-up bucket:
        inference mode, flat-input avals matching the bucket's padded
        shapes.  (None, None) when not yet compiled."""
        avals = [((b,) + tuple(s), self._dtype) for s in sig]
        return self._block.find_cached_entry(avals, training=False)

    # -- output guard -------------------------------------------------------
    def _guard_outputs(self, outs_np, B, sig):
        """mx.monitor's serve-side guard: count nonfinite elements in
        the per-request (unpadded) outputs — already on host, the
        asnumpy sync paid for the scan — so a model serving NaN logits
        is visible at /statz (``serve_nonfinite_*`` totals) instead of
        silently poisoning clients.  Armed with the rest of the
        monitor plane (``MXNET_MONITOR=1``); detection only — requests
        still get their outputs (the client contract is the caller's
        call)."""
        from .. import monitor as _monitor

        if not _monitor.core.ENABLED:
            return
        bad = count_nonfinite(outs_np)
        if not bad:
            return
        if telemetry.ENABLED:
            telemetry.SERVE_NONFINITE_OUTPUTS.inc(bad)
            telemetry.SERVE_NONFINITE_BATCHES.inc()
        trace.instant("serve_nonfinite_outputs", cat="serve",
                      args={"elements": bad,
                            "bucket": _bucket_label(B, sig)
                            if sig else str(B)})

    # -- bucketing ----------------------------------------------------------
    def bucket_for(self, sample_shapes):
        """Map a request's per-input sample shapes to its bucket class.

        Returns the index of the smallest covering sample bucket (same
        rank per input, every dim >=).  Without a configured table the
        exact shape tuple is its own class.  Raises ``NoBucketError``
        when nothing covers the request — submit-time validation, so
        oversized inputs are rejected at the front door, not at
        dispatch."""
        sample_shapes = tuple(tuple(s) for s in sample_shapes)
        if not self._sample_buckets:
            return sample_shapes
        for i, sig in enumerate(self._sample_buckets):
            if len(sig) != len(sample_shapes):
                continue
            if all(len(b) == len(s) and
                   all(bd >= sd for bd, sd in zip(b, s))
                   for b, s in zip(sig, sample_shapes)):
                return i
        raise NoBucketError(
            "no shape bucket covers request input shapes %s "
            "(buckets: %s)" % (list(sample_shapes),
                               [list(map(list, s))
                                for s in self._sample_buckets]))

    def _batch_bucket(self, n):
        for b in self._batch_sizes:
            if b >= n:
                return b
        return self._batch_sizes[-1]

    def _target_sig(self, requests):
        cls = requests[0].bucket_class
        if isinstance(cls, int):
            return self._sample_buckets[cls]
        return cls  # exact-shape class: no sample padding

    # -- execution ----------------------------------------------------------
    def run_batch(self, requests):
        """Pad, stack, run, unpad.  ``requests`` are same-class
        ``batching.Request`` objects; returns one result per request
        (a bare array for single-input style requests, else a tuple).
        Batches larger than the biggest batch bucket are chunked."""
        results = []
        cap = self.max_batch_size
        for i in range(0, len(requests), cap):
            results.extend(self._run_chunk(requests[i:i + cap]))
        return results

    def _run_chunk(self, requests):
        from .. import ndarray as nd

        sig = self._target_sig(requests)
        n = len(requests)
        B = self._batch_bucket(n)
        # phase spans nest under the scheduler's serve_dispatch span
        # (the head request's trace context) — or stand alone when
        # run_batch is called directly
        with trace.span("serve_pad", hist=False, cat="serve",
                        args={"batch": B, "requests": n}):
            bufs, real = [], 0
            for j, bucket_shape in enumerate(sig):
                buf = _np.zeros((B,) + bucket_shape, dtype=self._dtype)
                for i, req in enumerate(requests):
                    a = req.inputs[j]
                    real += a.size
                    buf[(i,) + tuple(slice(0, d) for d in a.shape)] = a
                bufs.append(buf)
            total = sum(b.size for b in bufs)
            if telemetry.ENABLED and total:
                telemetry.SERVE_PAD_ELEMENTS.inc(total - real)
                telemetry.SERVE_PAD_FRACTION.observe(
                    (total - real) / total)

        cached = getattr(self._block, "_cached_ops", None)
        before = len(cached) if cached is not None else 0
        with trace.span("serve_execute", hist=False, cat="serve",
                        args={"bucket": _bucket_label(B, sig)
                              if sig else str(B)}):
            with self._run_lock, autograd.pause():
                if self._ctx is not None:
                    with self._ctx:
                        out = self._block(*[nd.array(b, ctx=self._ctx)
                                            for b in bufs])
                else:
                    out = self._block(*[nd.array(b) for b in bufs])
            outs = out if isinstance(out, tuple) else (out,)
            # asnumpy is the hard sync: device time lands in THIS span
            outs_np = [o.asnumpy() for o in outs]
        if cached is not None and len(cached) > before \
                and telemetry.ENABLED:
            # a compile escaped warm-up (unwarmed bucket or lazy mode)
            telemetry.SERVE_COMPILES.labels(
                bucket=_bucket_label(B, sig)).inc(len(cached) - before)

        with trace.span("serve_unpad", hist=False, cat="serve"):
            lead = sig[0] if sig else requests[0].inputs[0].shape
            results = []
            for i, req in enumerate(requests):
                orig = req.inputs[0].shape
                per_req = []
                for o in outs_np:
                    row = o[i]
                    if self._unpad:
                        slices = tuple(
                            slice(0, orig[a]) if a < len(lead)
                            and a < len(orig) and row.shape[a] == lead[a]
                            else slice(None)
                            for a in range(row.ndim))
                        row = row[slices]
                    per_req.append(row)
                results.append(per_req[0] if len(per_req) == 1
                               else tuple(per_req))
        # guard AFTER unpad: only values actually returned to clients
        # count — padding rows/regions may legitimately go nonfinite
        # (log/division on zero-fill) without the model being sick
        self._guard_outputs(
            [a for r in results
             for a in (r if isinstance(r, tuple) else (r,))], B, sig)
        return results
