"""Declarative tuning spaces — one ``TuningSite`` per tunable knob.

A site names a hand-set knob somewhere in the stack, enumerates its
candidate configs for a workload key, and (for measurable sites) builds
the micro-benchmark ``measure.tune`` runs each candidate through.  The
consumer side is a build-time ``autotune.lookup(site, key, default)``
at the code that owns the knob — the registered DEFAULT is always
today's hand-set literal, so ``MXNET_AUTOTUNE=0`` is bit-and-perf
identical to the untuned stack.

Sites (PERF_PLAN hypothesis in parens):

- ``flash_attention``     — Pallas kernel (block_q, block_k) VMEM grid
- ``blockwise_attention`` — lax.scan fallback block_k
- ``allreduce_bucket``    — gradient-fusion bucket_bytes sweep
                            (re-planned via ``plan_buckets``)
- ``conv_layout``         — NHWC vs NCHW conv dimension numbers (H1)
- ``bn_stat_dtype``       — BatchNorm stat-reduction dtype (H2)
- ``decode_bucket``       — serve decode batch-bucket set (structural:
                            measured by the decode runner's idle tuner)
- ``serve_bucket``        — serve bucket latency table (structural:
                            recorded by ModelRunner's idle tuner; cost
                            model / diagnose data, not a lookup knob)
- ``data_prefetch``       — mx.data ring depth + reader workers
                            (structural: order-preserving by
                            construction, measured end-to-end)
- ``adapter_slots``       — mx.tenant LoRA bank slot count
                            (structural: per-slot math is masked out
                            for absent adapters, measured by the
                            tenant bench)
- ``shard_layout``        — mx.shard tensor-parallel layout-rule
                            table (structural: gather mode only moves
                            storage, measured by the committed
                            shard_tp_step bench row)

Measurable sites benchmark with DETERMINISTIC seeded inputs and return
host numpy outputs so the measure harness can enforce the numerics
guard: a candidate whose outputs are not bit-identical to the default's
is rejected outright — a tuned config can never change numerics, only
speed.  Structural sites (``parity="structural"``) choose among
configurations that are output-invariant by construction (the decode
padding design is bit-identity-tested in test_serve_decode) and are
measured by their own idle tuners instead.
"""
from __future__ import annotations

__all__ = ["TuningSite", "register_site", "get_site", "sites"]

_REGISTRY = {}


def register_site(site):
    """Register a ``TuningSite`` (instance, or a class — instantiated
    here so the decorator form reads declaratively)."""
    inst = site() if isinstance(site, type) else site
    _REGISTRY[inst.name] = inst
    return site


def get_site(name):
    if name not in _REGISTRY:
        from ..base import MXNetError

        raise MXNetError("unknown autotune site %r (registered: %s)"
                         % (name, sorted(_REGISTRY)))
    return _REGISTRY[name]


def sites():
    """{name: site} of every registered tuning site."""
    return dict(_REGISTRY)


def _seeded(shape, dtype="float32", seed=0):
    import numpy as _np

    from ..base import _as_np_dtype

    rng = _np.random.default_rng(seed)
    return rng.standard_normal(shape).astype(_as_np_dtype(dtype))


class TuningSite:
    """One tunable site: candidate enumerator + micro-bench builder.

    Subclasses define ``name``, ``doc``, ``parity`` ("bitwise" — the
    measure harness enforces output bit-identity vs the default — or
    "structural"), ``default_config(key)``, ``candidates(key)`` and,
    for measurable sites, ``make_bench(key, config)`` returning a
    zero-arg callable that runs ONE tuned iteration to completion and
    returns a list of host numpy outputs.  ``features(key)`` feeds the
    cost model (numeric workload descriptors)."""

    name = None
    doc = ""
    parity = "bitwise"

    def default_config(self, key):
        raise NotImplementedError

    def candidates(self, key):
        raise NotImplementedError

    def make_bench(self, key, config):
        raise NotImplementedError

    def validate(self, key, config):
        """True when a stored config is shaped right for this site —
        the lookup-side guard against a hand-edited or stale record."""
        return config is not None

    def features(self, key):
        """Numeric workload descriptors for the cost model."""
        return [float(v) for v in key if isinstance(v, (int, float))]

    def describe(self):
        return {"name": self.name, "parity": self.parity, "doc": self.doc}


# ---------------------------------------------------------------------------
# attention kernels
# ---------------------------------------------------------------------------

@register_site
class _FlashAttention(TuningSite):
    """(block_q, block_k) grid of the Pallas flash kernel.

    key = (B, H, Tq, Tk, D, dtype, causal).  block_q candidates are
    bit-identical by construction (each query row's online-softmax
    runs the same k-block sequence regardless of how queries tile);
    block_k candidates change the softmax accumulation partition and
    are expected to be REJECTED by the numerics guard off-TPU — kept
    in the grid so a backend where they measure bit-equal can still
    win with them."""

    name = "flash_attention"
    doc = "Pallas flash-attention (block_q, block_k) VMEM tiling"
    _GRID_Q = (128, 256, 512)
    _GRID_K = (128, 256, 512)

    def default_config(self, key):
        return [512, 512]

    def candidates(self, key):
        _B, _H, tq, tk, _d, _dt, _causal = key
        seen, out = set(), []
        for bq in self._GRID_Q:
            for bk in self._GRID_K:
                eff = (min(bq, tq), min(bk, tk))
                if eff in seen:
                    continue
                seen.add(eff)
                out.append([bq, bk])
        return out

    def validate(self, key, config):
        try:
            bq, bk = config
            return int(bq) > 0 and int(bk) > 0
        except (TypeError, ValueError):
            return False

    def make_bench(self, key, config):
        import functools

        import jax
        import numpy as _np

        from ..ops import pallas_attention as pa

        b, h, tq, tk, d, dtype, causal = key
        bq, bk = int(config[0]), int(config[1])
        q = _seeded((b, h, tq, d), dtype, seed=1)
        k = _seeded((b, h, tk, d), dtype, seed=2)
        v = _seeded((b, h, tk, d), dtype, seed=3)
        fn = jax.jit(functools.partial(
            pa.flash_attention, causal=causal, block_q=bq, block_k=bk))

        def run():
            return [_np.asarray(fn(q, k, v))]

        return run


@register_site
class _BlockwiseAttention(TuningSite):
    """block_k of the pure-JAX lax.scan online-softmax fallback.

    key = (B, H, Tq, Tk, D, dtype, causal).  Changing block_k changes
    the softmax accumulation partition, so off the single-block case
    candidates usually fail the bitwise guard — which is the point:
    the site documents, with a counted rejection, that this knob
    cannot be retuned without changing numerics."""

    name = "blockwise_attention"
    doc = "blockwise_attention lax.scan block_k"
    _GRID = (128, 256, 512, 1024)

    def default_config(self, key):
        return 256

    def candidates(self, key):
        _B, _H, _tq, tk, _d, _dt, _causal = key
        seen, out = set(), []
        for bk in self._GRID:
            eff = min(bk, tk)
            if eff in seen:
                continue
            seen.add(eff)
            out.append(bk)
        return out

    def validate(self, key, config):
        try:
            return int(config) > 0
        except (TypeError, ValueError):
            return False

    def make_bench(self, key, config):
        import functools

        import jax
        import numpy as _np

        from ..ops import pallas_attention as pa

        b, h, tq, tk, d, dtype, causal = key
        q = _seeded((b, h, tq, d), dtype, seed=1)
        k = _seeded((b, h, tk, d), dtype, seed=2)
        v = _seeded((b, h, tk, d), dtype, seed=3)
        fn = jax.jit(functools.partial(
            pa.blockwise_attention, causal=causal, block_k=int(config)))

        def run():
            return [_np.asarray(fn(q, k, v))]

        return run


# ---------------------------------------------------------------------------
# collective bucket size
# ---------------------------------------------------------------------------

@register_site
class _AllreduceBucket(TuningSite):
    """Gradient-fusion bucket_bytes of the collective kvstore / step
    capture bucket planner.

    key = (n_arrays, total_bytes, world).  The bench replays the exact
    per-bucket program structure ``_allreduce_many`` dispatches —
    flatten + concat each ``plan_buckets`` bucket, reduce (a world-of-
    one sum is the identity), split members back out — so the measured
    cost is the launch/concat overhead the bucket size actually
    controls.  Concat/ravel/slice are exact, so every candidate is
    bit-identical to the default and the guard only ever screens real
    failures (nonfinite inputs, broken plans)."""

    name = "allreduce_bucket"
    doc = "collective gradient-fusion bucket_bytes (plan_buckets sweep)"
    _GRID_MB = (1, 2, 4, 8, 16)

    def default_config(self, key):
        from ..kvstore import collective as _coll

        return int(_coll.default_bucket_bytes())

    def candidates(self, key):
        _n, total, _world = key
        out = []
        for mb in self._GRID_MB:
            bb = mb << 20
            out.append(bb)
            if bb >= max(1, int(total)):
                break  # larger buckets plan identically: one bucket
        return out

    def validate(self, key, config):
        try:
            return int(config) > 0
        except (TypeError, ValueError):
            return False

    def features(self, key):
        n, total, world = key
        return [float(n), float(total), float(world)]

    def make_bench(self, key, config):
        import jax
        import jax.numpy as jnp
        import numpy as _np

        from ..kvstore.collective import plan_buckets

        n, total, _world = int(key[0]), int(key[1]), int(key[2])
        itemsize = 4
        per = max(1, total // max(1, n) // itemsize)
        arrays = [_seeded((per + (1 if i == 0 else 0),), "float32",
                          seed=i) for i in range(n)]
        sizes = [(a.size * itemsize, "float32") for a in arrays]
        plan = plan_buckets(sizes, bucket_bytes=int(config))

        def pipeline(arrs):
            out = [None] * len(arrs)
            for idxs in plan:
                flat = jnp.concatenate(
                    [jnp.ravel(arrs[i]) for i in idxs]) \
                    if len(idxs) > 1 else jnp.ravel(arrs[idxs[0]])
                off = 0
                for i in idxs:
                    m = arrs[i].size
                    out[i] = flat[off:off + m].reshape(arrs[i].shape)
                    off += m
            return out

        fn = jax.jit(pipeline)

        def run():
            return [_np.asarray(a) for a in fn(arrays)]

        return run


# ---------------------------------------------------------------------------
# conv layout (PERF_PLAN H1) and BN stat dtype (H2)
# ---------------------------------------------------------------------------

@register_site
class _ConvLayout(TuningSite):
    """Internal conv dimension numbers: NCHW (today's default) vs NHWC
    with transposed operands — PERF_PLAN hypothesis H1.  Models stay
    NCHW externally either way; a tuned NHWC winner makes
    ``ops.convolution`` transpose in/out around an NHWC conv.

    key = (N, C, H, W, O, kh, kw, stride, dtype)."""

    name = "conv_layout"
    doc = "conv internal layout NHWC vs NCHW (PERF_PLAN H1)"

    def default_config(self, key):
        return "NCHW"

    def candidates(self, key):
        return ["NCHW", "NHWC"]

    def validate(self, key, config):
        return config in ("NCHW", "NHWC")

    def make_bench(self, key, config):
        import jax
        import numpy as _np
        from jax import lax

        n, c, h, w, o, kh, kw, stride, dtype = key
        x = _seeded((n, c, h, w), dtype, seed=1)
        wgt = _seeded((o, c, kh, kw), dtype, seed=2)
        strides = (int(stride), int(stride))
        pad = [(kh // 2, kh // 2), (kw // 2, kw // 2)]

        if config == "NCHW":
            dn = lax.conv_dimension_numbers(
                x.shape, wgt.shape, ("NCHW", "OIHW", "NCHW"))

            def conv(xx, ww):
                return lax.conv_general_dilated(
                    xx, ww, window_strides=strides, padding=pad,
                    dimension_numbers=dn)
        else:
            xt = (n, h, w, c)
            wt = (kh, kw, c, o)
            dn = lax.conv_dimension_numbers(
                xt, wt, ("NHWC", "HWIO", "NHWC"))

            def conv(xx, ww):
                y = lax.conv_general_dilated(
                    xx.transpose(0, 2, 3, 1),
                    ww.transpose(2, 3, 1, 0),
                    window_strides=strides, padding=pad,
                    dimension_numbers=dn)
                return y.transpose(0, 3, 1, 2)

        fn = jax.jit(conv)

        def run():
            return [_np.asarray(fn(x, wgt))]

        return run


@register_site
class _BNStatDtype(TuningSite):
    """BatchNorm stat-reduction dtype — PERF_PLAN hypothesis H2.  The
    bf16 candidate changes the mean/var rounding by construction, so
    under the bitwise guard it can only ever win on a backend where
    the reduction happens to round identically; everywhere else the
    counted rejection IS the H2 verdict (killed under the
    no-numerics-change policy).

    key = (N, C, H, W, axis, dtype) — the reduction axis is in the
    key because bit-identity certified for one reduction geometry
    says nothing about another."""

    name = "bn_stat_dtype"
    doc = "BatchNorm stat-reduction dtype f32 vs bf16 (PERF_PLAN H2)"

    def default_config(self, key):
        return "float32"

    def candidates(self, key):
        return ["float32", "bfloat16"]

    def validate(self, key, config):
        return config in ("float32", "bfloat16")

    def make_bench(self, key, config):
        import jax
        import numpy as _np

        from ..ops import nn as _nn

        # .fn = the pure jnp function behind the registered op (the
        # Operator wrapper dispatches through the engine on NDArrays)
        batch_norm = _nn.batch_norm.fn
        n, c, h, w, axis, dtype = key
        shape = (n, c, h, w)
        x = _seeded(shape, dtype, seed=1)
        nchan = shape[int(axis)]
        gamma = _seeded((nchan,), "float32", seed=2)
        beta = _seeded((nchan,), "float32", seed=3)
        mean = _np.zeros((nchan,), "float32")
        var = _np.ones((nchan,), "float32")

        def bn(xx, g, b, m, v):
            return batch_norm(xx, g, b, m, v, training=True,
                              axis=int(axis), stat_dtype=config)

        fn = jax.jit(bn)

        def run():
            return [_np.asarray(a)
                    for a in fn(x, gamma, beta, mean, var)]

        return run


# ---------------------------------------------------------------------------
# serving buckets (structural sites — measured by the idle tuners)
# ---------------------------------------------------------------------------

@register_site
class _DecodeBucket(TuningSite):
    """Serve decode batch-bucket SET.  key = (max_live,).  Candidates
    are subsets of the default power-of-two table (every member is
    compiled during warm-up anyway, so the idle tuner measures each
    bucket's step once and scores sets analytically).  Output-invariant
    by the decode padding design (bit-identity-tested in
    test_serve_decode), so parity is structural; the measured winner
    comes from ``measure.decode_idle_tune`` during warm-up idle time."""

    name = "decode_bucket"
    doc = "serve decode batch-bucket set (idle-time tuned)"
    parity = "structural"

    @staticmethod
    def _pow2(max_live):
        out, b = [], 1
        while b < max_live:
            out.append(b)
            b *= 2
        out.append(int(max_live))
        return sorted(set(out))

    def default_config(self, key):
        return self._pow2(int(key[0]))

    def candidates(self, key):
        max_live = int(key[0])
        full = self._pow2(max_live)
        cands = [full, [max_live]]
        if len(full) > 2:
            cands.append(full[1:])          # drop the B=1 bucket
            cands.append(full[-2:])         # coarse top-of-table pair
        uniq, out = set(), []
        for c in cands:
            t = tuple(c)
            if t not in uniq:
                uniq.add(t)
                out.append(list(c))
        return out

    def validate(self, key, config):
        try:
            buckets = sorted(int(b) for b in config)
        except (TypeError, ValueError):
            return False
        return bool(buckets) and buckets[0] >= 1 and \
            buckets[-1] >= int(key[0])

    def make_bench(self, key, config):
        from ..base import MXNetError

        raise MXNetError(
            "decode_bucket is a structural site: it is measured by the "
            "decode runner's idle tuner (warm_up under "
            "MXNET_AUTOTUNE=search), not by measure.tune()")


@register_site
class _SpecK(TuningSite):
    """Speculative-decoding draft proposal count K.  key =
    (max_live,).  Greedy acceptance makes the emitted stream
    bit-identical to single-step decode for EVERY K (the acceptance
    proof in serve/spec.py), so parity is structural like
    ``decode_bucket`` — K trades draft work against accepted tokens
    per target step and can never change tokens.  Winners are
    committed by the bench sweep / an explicit store put;
    ``SpecPlane`` consumes them whenever ``spec_k`` is left unset."""

    name = "spec_k"
    doc = "speculative draft proposal count per round (structural)"
    parity = "structural"

    def default_config(self, key):
        return 4

    def candidates(self, key):
        return [2, 3, 4, 6, 8]

    def validate(self, key, config):
        try:
            k = int(config)
        except (TypeError, ValueError):
            return False
        return 1 <= k <= 16

    def make_bench(self, key, config):
        from ..base import MXNetError

        raise MXNetError(
            "spec_k is a structural site: it is measured by the serve "
            "bench's acceptance sweep (tools/bench.py --serve), not by "
            "measure.tune()")


@register_site
class _ShardLayout(TuningSite):
    """mx.shard tensor-parallel layout-rule table.  key = (mdl,).
    Candidates are rule tables for ``shard.configure_layout`` — glob
    ``(pattern, kind[, dim])`` tuples choosing which parameters shard
    on the ``mdl`` axis and how (column / row / replicate / auto).
    In the default gather mode the table only moves STORAGE (the
    in-program constraint re-gathers weights, bit-identity-tested in
    test_shard_mp), so parity is structural like ``decode_bucket``:
    a layout can change residency and wire bytes, never tokens or
    weights.  Winners come from committed bench rows (bench.py
    ``shard_tp_step``) — layout changes
    recapture the step program (the table is part of the capture
    signature), which is exactly the cost measure.tune() must not
    pay per candidate."""

    name = "shard_layout"
    doc = "tensor-parallel per-parameter layout table (structural)"
    parity = "structural"

    def default_config(self, key):
        return []                    # the implicit '* -> auto' tail

    def candidates(self, key):
        return [
            [],                                        # auto everywhere
            [("*weight*", "column"), ("*", "replicate")],
            [("*weight*", "row"), ("*", "replicate")],
            # Megatron pairing: column first half, row second half of
            # each Dense pair — glob names are model-specific, so this
            # candidate is a TEMPLATE a campaign rewrites per model
            [("*0*weight*", "column"), ("*1*weight*", "row"),
             ("*", "replicate")],
            [("*", "replicate")],                      # mdl storage off
        ]

    def validate(self, key, config):
        from ..shard.policy import KINDS

        if not isinstance(config, (list, tuple)):
            return False
        for rule in config:
            if not isinstance(rule, (list, tuple)) or \
                    len(rule) not in (2, 3):
                return False
            if not isinstance(rule[0], str) or rule[1] not in KINDS:
                return False
            if len(rule) == 3 and not isinstance(rule[2], int):
                return False
        return True

    def make_bench(self, key, config):
        from ..base import MXNetError

        raise MXNetError(
            "shard_layout is a structural site: a layout change "
            "recaptures the step program, so it is measured by the "
            "committed bench rows (bench.py shard_tp_step) and "
            "drilled by make "
            "shard-smoke, not by measure.tune()")


@register_site
class _DataPrefetch(TuningSite):
    """mx.data prefetch ring depth + reader worker count.
    key = (local_batch, approx_record_bytes).  Order-preserving by
    construction — depth and worker count change WHEN batches are
    read/staged, never WHICH samples ride which batch (the epoch
    order is a pure function of (seed, epoch)) — so the numerics
    guard is trivially satisfied and parity is structural, like
    ``decode_bucket``.  Winners are committed by the bench sweep /
    an explicit store put; ``StreamLoader`` consumes them whenever
    ``num_workers``/``prefetch`` are left unset."""

    name = "data_prefetch"
    doc = "streaming loader ring depth + reader workers (structural)"
    parity = "structural"

    def default_config(self, key):
        # the ONE source of truth for both knobs lives in mx.data
        from ..data.loader import default_workers
        from ..data.ring import default_depth

        return {"depth": default_depth(), "workers": default_workers()}

    def candidates(self, key):
        out = []
        for depth in (2, 3, 4, 8):
            for workers in (1, 2, 4):
                out.append({"depth": depth, "workers": workers})
        return out

    def validate(self, key, config):
        try:
            return int(config["depth"]) >= 1 and \
                int(config["workers"]) >= 1
        except (TypeError, KeyError, ValueError):
            return False

    def features(self, key):
        import math

        return [math.log2(max(1, int(key[0]))),
                math.log2(max(1, int(key[1])))]

    def make_bench(self, key, config):
        from ..base import MXNetError

        raise MXNetError(
            "data_prefetch is a structural site: ring depth/worker "
            "count are measured end-to-end (benchmark/data_bench.py "
            "--train, tools/data_smoke.py), not by measure.tune()")


@register_site
class _AdapterSlots(TuningSite):
    """mx.tenant LoRA adapter-bank slot count.  key = (default_slots,).
    Every slot beyond the resident set is zero weights gathered by an
    out-of-range-clamped index and masked to 0 contribution
    (adapters.AdapterBank), so slot count can never change tokens —
    parity is structural.  It trades per-step gather/einsum width (and
    bank HBM) against how many tenants share ONE compiled decode
    program; winners are committed by the tenant bench sweep and
    consumed by ``TenantConfig`` whenever ``slots=`` is left unset."""

    name = "adapter_slots"
    doc = "tenant LoRA bank slot count (structural)"
    parity = "structural"

    def default_config(self, key):
        try:
            return int(key[0])
        except (TypeError, ValueError, IndexError):
            return 8

    def candidates(self, key):
        return [4, 8, 16, 32]

    def validate(self, key, config):
        try:
            n = int(config)
        except (TypeError, ValueError):
            return False
        return 1 <= n <= 256

    def make_bench(self, key, config):
        from ..base import MXNetError

        raise MXNetError(
            "adapter_slots is a structural site: it is measured by the "
            "tenant mixed-batch bench (tools/tenant_smoke.py --bench), "
            "not by measure.tune()")


@register_site
class _ServeBucket(TuningSite):
    """Per-bucket serve latency table recorded by ModelRunner's
    idle-time tuner — cost-model / diagnose data, not a lookup knob
    (the scheduler's smallest-covering-bucket rule is not configurable).
    key = (block class, dtype, bucket labels)."""

    name = "serve_bucket"
    doc = "serve bucket latency table (idle-time measured)"
    parity = "structural"

    def default_config(self, key):
        return None

    def candidates(self, key):
        return []

    def make_bench(self, key, config):
        from ..base import MXNetError

        raise MXNetError(
            "serve_bucket is a structural record site: ModelRunner."
            "warm_up measures it during idle time under "
            "MXNET_AUTOTUNE=search")
