"""mx.serve.decode — continuous batching over a paged KV-cache.

The PR 3 scheduler coalesces fixed-shape micro-batches: right for
vision, wrong for decoder-LLM traffic, where every request is a
*sequence* that produces one token per model step and lives for
hundreds of steps.  Request-level batching would hold a finished
sequence's slot (and its KV cache) hostage until the slowest
batch-mate finished.  This module implements Orca-style
**iteration-level scheduling** instead: one jitted decode-step program
runs every iteration over whichever sequences are live *right now* —
new sequences are admitted into freed slots mid-flight, finished /
expired / poisoned sequences are evicted and their KV pages reclaimed
the same step.

Layers:

- ``DecodeRunner`` — owns the model (a decoder ``HybridBlock``
  following the contract below), the ``kvcache.PagePool``, and the
  compiled program table: ONE program per decode batch bucket and one
  per prefill length bucket, each built once (``jax.jit`` with pool
  donation), fingerprinted into the ``mx.compile`` persistent cache
  (``attach_lowered``) so a restarted server reaches readiness with
  zero fresh XLA compiles, and metered per bucket
  (``serve_decode_compile_total``: steady state adds nothing).
- ``DecodeScheduler`` — the admission queue + continuous-batching
  loop: bounded waiting queue with deadline expiry, page reservation
  at admission (the whole worst case — never a mid-decode allocation
  failure), prefill through the bucket path, then the decode loop.
  Failure containment mirrors the vision scheduler: a failing step is
  retried **bisected** down to single sequences so a poisoned sequence
  fails ALONE with its pages reclaimed while batch-mates keep
  decoding (``serve_poison_requests_total``; drilled via the
  ``MXNET_FAULTS`` ``serve_poison@<request-id>`` site), and decode
  buckets carry their own circuit breakers.
- ``TinyDecoder`` — a small but real transformer decoder implementing
  the model contract; the reference model for tests, the smoke drill
  and the bench row, and executable documentation of the contract.

**Decoder model contract.**  Any ``HybridBlock`` with integer
attributes ``num_layers`` / ``num_kv_heads`` / ``head_dim`` /
``vocab_size`` (optional ``eos_id``) and the forward signature::

    forward(tokens,        # [B, T]            int32 token ids
            k_ctx, v_ctx,  # [B, L, S, H, D]   gathered paged context
            ctx_lengths,   # [B]               int32 cached positions
            chunk_lengths) # [B]               int32 valid chunk length
        -> (last_logits,   # [B, vocab]        logits at the last
                           #                   valid chunk position
            k_new, v_new)  # [B, T, L, H, D]   cache rows for the chunk

serves through this path.  Prefill is the ``S == 0`` signature
(``T`` = prompt bucket); decode is ``T == 1`` with the full paged
context.  The forward must attend causally within the chunk and mask
context positions ``>= ctx_length``; everything page-shaped (gather,
scatter, argmax sampling, the per-token nonfinite guard) happens in
the jitted wrapper the runner builds around ``export_pure``, so the
model stays paging-agnostic.

Every emitted token passes the PR 7 output guard *in-program* (a
nonfinite logit row costs one int per sequence, not a logits
round-trip): a sequence that goes NaN is evicted alone.  Per-token
``serve_decode_token`` trace spans hang off the request's single
``X-Request-Id`` trace, and token streaming reaches the HTTP
front-end through the ``on_token`` callback (``server.py`` chunked
responses on ``/predict?stream=1``).
"""
from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future, InvalidStateError

import numpy as _np

from .. import telemetry, trace
from ..base import get_env
from ..resilience import inject as _inject
from ..resilience.inject import InjectedFault, InjectedIOError
from .batching import (RequestTimeout, ServeError,
                       ServerClosed, ServerOverloaded, fail_request)
from .kvcache import (PageConfig, PagePool, PagePoolExhausted,
                      gather_pages, scatter_pages)

__all__ = ["DecodeError", "DecodeConfig", "DecodeRequest",
           "DecodeRunner", "DecodeScheduler", "TinyDecoder"]


class DecodeError(ServeError):
    """Decode-path request validation / execution error."""


def _pow2_up_to(lo, hi):
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return tuple(sorted(set(out)))


class DecodeConfig:
    """Knobs of the decode path (README "Autoregressive serving").

    page_size / pool_pages : KV page geometry
        (``MXNET_SERVE_DECODE_PAGE_SIZE`` / ``_POOL_PAGES``).
    max_live : concurrent sequences in the running batch
        (``MXNET_SERVE_DECODE_MAX_LIVE``); also caps the decode batch
        bucket table.
    max_new_tokens : default + hard per-request generation cap
        (``MXNET_SERVE_DECODE_MAX_NEW``).
    max_context : bound on prompt + generated tokens per sequence;
        fixes the paged-attention context extent every decode program
        compiles for.
    prefill_lengths : prompt padding buckets (default: powers of two
        up to ``max_context``).
    batch_sizes : decode batch buckets (default: powers of two up to
        ``max_live``).
    queue_depth : bound on ADMISSION-waiting sequences; beyond it
        submissions are rejected with ``ServerOverloaded``.
    timeout_ms : default per-request deadline (expires a sequence
        mid-generation too).
    stream : whether the HTTP front-end advertises/serves chunked
        token streaming (``MXNET_SERVE_DECODE_STREAM``).
    eos_id : default stop token (None = length-only stopping).
    prefix_cache : enable the radix prefix cache (serve/cache.py;
        ``MXNET_SERVE_PREFIX_CACHE``, default OFF — opt-in so the
        warm-up program table is unchanged for existing deployments).
    spec_k : speculative draft proposal count when a draft model is
        given (``MXNET_SERVE_SPEC_K``; 0 = the built-in default, see
        ``serve.spec.resolve_k``).
    """

    def __init__(self, page_size=None, pool_pages=None, max_live=None,
                 max_new_tokens=None, max_context=128,
                 prefill_lengths=None, batch_sizes=None, queue_depth=64,
                 timeout_ms=None, stream=None, eos_id=None,
                 dtype="float32", prefix_cache=None, spec_k=None):
        self.page_size = get_env("MXNET_SERVE_DECODE_PAGE_SIZE", int, 16) \
            if page_size is None else int(page_size)
        self.pool_pages = get_env("MXNET_SERVE_DECODE_POOL_PAGES", int,
                                  256) \
            if pool_pages is None else int(pool_pages)
        self.max_live = get_env("MXNET_SERVE_DECODE_MAX_LIVE", int, 8) \
            if max_live is None else int(max_live)
        self.max_new_tokens = get_env("MXNET_SERVE_DECODE_MAX_NEW", int,
                                      64) \
            if max_new_tokens is None else int(max_new_tokens)
        self.stream = get_env("MXNET_SERVE_DECODE_STREAM", bool, True) \
            if stream is None else bool(stream)
        self.max_context = int(max_context)
        if prefill_lengths is None:
            prefill_lengths = _pow2_up_to(
                min(8, self.max_context), self.max_context)
        self.prefill_lengths = tuple(sorted(set(
            int(t) for t in prefill_lengths if int(t) <= self.max_context)))
        if not self.prefill_lengths:
            raise ValueError("no prefill bucket <= max_context=%d"
                             % self.max_context)
        if batch_sizes is None:
            batch_sizes = _pow2_up_to(1, max(1, self.max_live))
        self.batch_sizes = tuple(sorted(set(int(b) for b in batch_sizes)))
        if self.batch_sizes[-1] < self.max_live:
            raise ValueError(
                "largest decode batch bucket %d < max_live=%d: live "
                "sequences could never all step"
                % (self.batch_sizes[-1], self.max_live))
        self.queue_depth = int(queue_depth)
        self.timeout_ms = timeout_ms
        self.eos_id = eos_id
        self.dtype = dtype
        self.prefix_cache = get_env("MXNET_SERVE_PREFIX_CACHE", bool,
                                    False) \
            if prefix_cache is None else bool(prefix_cache)
        self.spec_k = get_env("MXNET_SERVE_SPEC_K", int, 0) \
            if spec_k is None else int(spec_k)

    def as_dict(self):
        return {
            "page_size": self.page_size, "pool_pages": self.pool_pages,
            "max_live": self.max_live,
            "max_new_tokens": self.max_new_tokens,
            "max_context": self.max_context,
            "prefill_lengths": list(self.prefill_lengths),
            "batch_sizes": list(self.batch_sizes),
            "queue_depth": self.queue_depth,
            "timeout_ms": self.timeout_ms, "stream": self.stream,
            "eos_id": self.eos_id, "dtype": self.dtype,
            "prefix_cache": self.prefix_cache, "spec_k": self.spec_k,
        }


class DecodeRequest:
    """One autoregressive generation request.

    Carries the same resolution surface as ``batching.Request``
    (``future`` / ``enqueued`` / ``deadline`` / ``request_id`` /
    ``trace``) so the shared failure/telemetry plumbing applies; the
    future resolves to ``{"tokens": [ids...], "finish_reason": ...}``.
    ``on_token(token_id, index)`` — when given — is called once per
    emitted token from the decode loop (it must be cheap and
    non-blocking: enqueue, don't write sockets); the streamed sequence
    is bit-identical to the future's ``tokens``."""

    __slots__ = ("prompt", "max_new_tokens", "eos_id", "on_token",
                 "future", "enqueued", "deadline", "request_id", "trace",
                 "export_only", "handoff", "tenant")

    def __init__(self, prompt, max_new_tokens, eos_id=None, deadline=None,
                 request_id=None, on_token=None, export_only=False,
                 handoff=None, tenant=None):
        # mx.tenant: the registered tenant this request bills to (None
        # = base/anonymous traffic — no WFQ charge, no adapter)
        self.tenant = None if tenant is None else str(tenant)
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.on_token = on_token
        # mx.fleet disaggregation: export_only sequences stop after
        # prefill (future resolves to the handoff state dict); handoff
        # carries an unpacked fleet.handoff state to install instead of
        # prefilling locally
        self.export_only = bool(export_only)
        self.handoff = handoff
        self.future = Future()
        self.enqueued = time.perf_counter()
        self.deadline = deadline
        self.request_id = request_id
        self.trace = trace.new_request(request_id)
        if self.trace is not None:
            trace.instant("serve_decode_enqueue", cat="serve",
                          ctx=self.trace,
                          args={"request_id": request_id,
                                "prompt_tokens": len(self.prompt)})

    def expired(self, now=None):
        return self.deadline is not None and \
            (time.perf_counter() if now is None else now) >= self.deadline


class _Seq:
    """Decode-loop bookkeeping for one live sequence."""

    __slots__ = ("req", "sid", "tokens", "length", "pages", "joined_step",
                 "t_prefill", "first_token_t", "last_token",
                 "cache_class", "prefix_len", "shared",
                 "spec", "dlen", "dpages", "depoch",
                 "tenant", "adapter_slot", "quota_pages")

    def __init__(self, req, sid):
        # mx.tenant: billing identity, the bank slot this sequence
        # decodes with (-1 = base weights), and the pages charged to
        # the tenant's quota ledger (None until admission reserves)
        self.tenant = req.tenant
        self.adapter_slot = -1
        self.quota_pages = None
        self.req = req
        self.sid = sid
        self.tokens = []          # generated token ids
        self.length = 0           # positions resident in the KV pages
        self.pages = None
        self.joined_step = None
        self.t_prefill = None
        self.first_token_t = None
        self.last_token = None    # next decode-step input token
        # serve/cache.py: TTFT class, shared-prefix floor (the scrub
        # guard's write boundary) and the shared pages this sequence
        # holds references on (a prefix of ``pages``)
        self.cache_class = None
        self.prefix_len = 0
        self.shared = []
        # serve/spec.py: None = not yet offered to the plane, True =
        # speculating, False = detached/ineligible; dlen is the draft
        # cache cursor, dpages the draft pool reservation
        self.spec = None
        self.dlen = 0
        self.dpages = None
        self.depoch = None

    @property
    def done_reason(self):
        if self.req.eos_id is not None and self.tokens and \
                self.tokens[-1] == self.req.eos_id:
            return "eos"
        if len(self.tokens) >= self.req.max_new_tokens:
            return "length"
        return None


class _Program:
    __slots__ = ("fn", "label", "provenance", "builds")

    def __init__(self, fn, label, provenance):
        self.fn = fn
        self.label = label
        self.provenance = provenance
        self.builds = 1


class DecodeRunner:
    """Model + paged KV pool + compiled decode/prefill program table.

    ``block`` is a decoder HybridBlock following the module-doc
    contract (or a zero-arg factory); ``root``/``step`` restore from an
    ``mx.checkpoint`` root like ``ModelRunner``.  ``warm_up()`` builds
    every (bucket, page-config) program — consulting the ``mx.compile``
    persistent cache first — and runs each once, so steady-state
    decoding triggers at most ONE compile per bucket and a restarted
    server can reach readiness with zero fresh XLA compiles."""

    def __init__(self, block, root=None, step=None, ctx=None, config=None,
                 warm=True, draft=None, tenant=None, mesh=None):
        from ..gluon.block import HybridBlock
        from .runner import resolve_block

        block = resolve_block(block, HybridBlock, "DecodeRunner")
        for attr in ("num_layers", "num_kv_heads", "head_dim",
                     "vocab_size"):
            if not isinstance(getattr(block, attr, None), int):
                raise ValueError(
                    "decoder contract: block must carry int attribute "
                    "%r (see serve/decode.py module doc)" % attr)
        self._block = block
        self._ctx = ctx
        self.config = config or DecodeConfig()
        # the effective stop token lives on the RUNNER, not the config:
        # a DecodeConfig may be shared across runners/models and must
        # not absorb one model's eos_id
        self.eos_id = self.config.eos_id \
            if self.config.eos_id is not None \
            else getattr(block, "eos_id", None)
        self.root = root
        self.step = None
        if root is not None:
            self.step = block.load_checkpoint(root, step=step, ctx=ctx)
        self._resolve_params()
        self._apply_fn, self._params = block.export_pure(training=False)
        device = None
        if ctx is not None:
            # a runner given a context keeps weights, probe inputs and
            # the KV pool on that device (numpy step inputs follow the
            # committed weights)
            import jax

            device = ctx.jax_device
            self._params = {n: jax.device_put(v, device)
                            for n, v in self._params.items()}
        # mx.shard phase 2: a model sharded over the mesh's mdl axis.
        # Parameters are STORED per the layout table (1/mdl per device)
        # and each program constrains them in-program: gather mode
        # re-materializes replicated weights (the decode math — and
        # therefore the greedy token stream — is byte-identical to the
        # single-chip program), compute mode keeps them sharded and
        # lets GSPMD shard the matmuls.  dp must be 1: replica fan-out
        # is mx.fleet's job, one runner serves one model instance.
        self.mesh = None
        self._fwd_shardings = None
        if mesh is not None:
            from .. import shard as _shard

            gm = _shard.as_global(mesh)
            if gm.dp != 1:
                raise ValueError(
                    "DecodeRunner(mesh=...) needs dp=1 (got dp=%d): "
                    "one runner serves one model instance; use "
                    "mx.fleet for replicas" % gm.dp)
            if gm.mdl > 1:
                import jax

                self.mesh = gm
                policy = _shard.ShardPolicy(0, gm)
                self._params = {
                    n: jax.device_put(v, policy.param_sharding(
                        v.shape, name=n))
                    for n, v in self._params.items()}
                self._fwd_shardings = {
                    n: policy.forward_sharding(v.shape, name=n)
                    for n, v in self._params.items()}
                self._tp_mode = policy.mode
        # mx.tenant: the adapter bank MUST exist before warm_up so
        # every program compiles with the bank inputs in its signature
        # — adapter churn afterwards is slot-content data, never a
        # recompile.  Without a plane the program table (and its
        # mx.compile fingerprints) is byte-identical to pre-tenant.
        self.tenant = tenant
        self.bank = tenant.build_bank(block) if tenant is not None \
            else None
        c = self.config
        self.page_config = PageConfig(
            c.page_size, c.pool_pages, block.num_layers,
            block.num_kv_heads, block.head_dim, c.max_context,
            dtype=c.dtype)
        self.pool = PagePool(self.page_config, mesh=self.mesh,
                             device=device)
        self._programs = {}
        self._run_lock = threading.RLock()
        self._warmed = False
        self.cache = None
        if self.config.prefix_cache:
            from .cache import PrefixCache

            self.cache = PrefixCache(self.pool)
        self.spec = None
        if warm:
            self.warm_up()
        if draft is not None:
            from .spec import SpecPlane

            self.spec = SpecPlane(self, draft,
                                  k=self.config.spec_k or None,
                                  warm=self._warmed)

    # -- setup --------------------------------------------------------------
    def _resolve_params(self):
        """One tiny forward resolves deferred parameter shapes before
        ``export_pure`` (the contract signature with S=0, T=1)."""
        from .. import ndarray as nd

        b, ctx = self._block, self._ctx
        zero_ctx = nd.zeros((1, b.num_layers, 0, b.num_kv_heads,
                             b.head_dim), dtype=self.config.dtype, ctx=ctx)
        ones = nd.array(_np.array([1], dtype="int32"), ctx=ctx)
        self._block(nd.zeros((1, 1), dtype="int32", ctx=ctx), zero_ctx,
                    zero_ctx, nd.zeros((1,), dtype="int32", ctx=ctx), ones)

    @property
    def block(self):
        return self._block

    @property
    def warmed(self):
        return self._warmed

    # -- bucket choice ------------------------------------------------------
    def prefill_bucket(self, n):
        for t in self.config.prefill_lengths:
            if t >= n:
                return t
        raise DecodeError(
            "prompt of %d token(s) exceeds the largest prefill bucket "
            "(%d); buckets: %s" % (n, self.config.prefill_lengths[-1],
                                   list(self.config.prefill_lengths)))

    def decode_bucket(self, n):
        for b in self.config.batch_sizes:
            if b >= n:
                return b
        return self.config.batch_sizes[-1]

    # -- program build ------------------------------------------------------
    @staticmethod
    def bucket_key_label(key):
        kind, n = key
        if kind == "verify":
            return "verify:b%dk%d" % n
        return "%s%d" % ({"decode": "decode:b", "prefill": "prefill:t",
                          "chunk": "chunk:t"}[kind], n)

    def _make_step_fn(self, batch, chunk, with_ctx, with_floors=False):
        """The pure (params, k_pool, v_pool, tokens, tables, ctx_lens,
        chunk_lens) -> (k_pool, v_pool, next_tokens, nonfinite) function
        one (bucket, page-config) jit-compiles.  Sampling (greedy
        argmax) and the per-token output guard run in-program: the host
        reads B ints per step, never a logits tensor."""
        import jax.numpy as jnp

        apply_fn = self._apply_fn
        blk = self._block
        nlayers, nheads, hdim = (blk.num_layers, blk.num_kv_heads,
                                 blk.head_dim)
        dtype = self.page_config.dtype
        bank = self.bank

        def core(params, kp, vp, tokens, tables, ctx_lens, chunk_lens,
                 floors, aidx=None, bankf=None):
            if with_ctx:
                k_ctx = gather_pages(kp, tables)
                v_ctx = gather_pages(vp, tables)
                # scrub positions past each sequence's length: freed
                # pages are reallocated WITHOUT zeroing, so a previous
                # owner's values (possibly NaN — that is how a poisoned
                # sequence died) sit in the tail of the current page.
                # Additive attention masking cannot discard NaN inputs
                # (NaN + -1e9 is NaN, and softmax-0 x NaN is NaN), so
                # the contract guarantees the model NEVER sees
                # unwritten context.
                live = (jnp.arange(k_ctx.shape[2])[None, None, :, None,
                                                   None]
                        < ctx_lens[:, None, None, None, None])
                k_ctx = jnp.where(live, k_ctx, 0)
                v_ctx = jnp.where(live, v_ctx, 0)
            else:
                k_ctx = jnp.zeros((batch, nlayers, 0, nheads, hdim),
                                  dtype=dtype)
                v_ctx = k_ctx
            if bank is not None:
                # mx.tenant: bind the (traced) per-sequence adapter
                # index + bank inputs; the instrumented Dense forwards
                # add gather(A,idx)/gather(B,idx) deltas inline, so the
                # mixed-tenant batch stays ONE program
                with bank.applying(aidx, bankf):
                    outs, _states = apply_fn(params, None, tokens,
                                             k_ctx, v_ctx, ctx_lens,
                                             chunk_lens)
            else:
                outs, _states = apply_fn(params, None, tokens, k_ctx,
                                         v_ctx, ctx_lens, chunk_lens)
            logits, k_new, v_new = outs
            pos = ctx_lens[:, None] + jnp.arange(chunk, dtype=jnp.int32)
            valid = jnp.arange(chunk, dtype=jnp.int32)[None, :] \
                < chunk_lens[:, None]
            if floors is not None:
                # COW scrub guard (serve/cache.py): a shared prefix
                # page is NEVER writable — scatter below the floor is
                # dropped even if a caller miscomputes ctx_lens
                valid = valid & (pos >= floors[:, None])
            kp = scatter_pages(kp, tables, pos, valid, k_new)
            vp = scatter_pages(vp, tables, pos, valid, v_new)
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            bad = jnp.sum(~jnp.isfinite(logits), axis=-1,
                          dtype=jnp.int32)
            return kp, vp, next_tok, bad

        if with_floors and bank is not None:
            def step(params, kp, vp, tokens, tables, ctx_lens,
                     chunk_lens, floors, aidx, bankf):
                return core(params, kp, vp, tokens, tables, ctx_lens,
                            chunk_lens, floors, aidx, bankf)
        elif with_floors:
            def step(params, kp, vp, tokens, tables, ctx_lens,
                     chunk_lens, floors):
                return core(params, kp, vp, tokens, tables, ctx_lens,
                            chunk_lens, floors)
        elif bank is not None:
            def step(params, kp, vp, tokens, tables, ctx_lens,
                     chunk_lens, aidx, bankf):
                return core(params, kp, vp, tokens, tables, ctx_lens,
                            chunk_lens, None, aidx, bankf)
        else:
            def step(params, kp, vp, tokens, tables, ctx_lens,
                     chunk_lens):
                return core(params, kp, vp, tokens, tables, ctx_lens,
                            chunk_lens, None)
        return step

    def _make_verify_fn(self, batch, k):
        """The speculative verify program (serve/spec.py): judge a
        K-token draft chunk with ONE dispatch.  The model contract
        only exposes the LAST valid chunk logit, so each sequence is
        replicated K+1 times with chunk lengths ``1..K+1`` — row j of
        a group yields the target's argmax after the chunk's first
        j+1 tokens.  K/V is scattered once per sequence from the
        full-chunk replica (causal attention makes per-position rows
        identical across replicas); positions past the eventual
        acceptance point hold draft-conditioned garbage that the
        decode-path scrub guard hides until it is overwritten in
        place."""
        import jax.numpy as jnp

        apply_fn = self._apply_fn
        T = k + 1
        bank = self.bank

        def core(params, kp, vp, tokens, tables, ctx_lens, chunk_lens,
                 floors, aidx=None, bankf=None):
            k_ctx = gather_pages(kp, tables)
            v_ctx = gather_pages(vp, tables)
            live = (jnp.arange(k_ctx.shape[2])[None, None, :, None,
                                               None]
                    < ctx_lens[:, None, None, None, None])
            k_ctx = jnp.where(live, k_ctx, 0)
            v_ctx = jnp.where(live, v_ctx, 0)
            rep = lambda a: jnp.repeat(a, T, axis=0)  # noqa: E731
            rj = jnp.tile(jnp.arange(1, T + 1, dtype=jnp.int32), batch)
            # replicas past a sequence's real chunk length would be
            # conditioned on padding garbage; clamp them to the full
            # chunk (their outputs are never read)
            rep_chunk = jnp.minimum(
                rj, jnp.repeat(jnp.maximum(chunk_lens, 1), T))
            if bank is not None:
                # the adapter index replicates with its sequence: every
                # verify replica of a row applies the SAME adapter the
                # decode path would (bit-parity with single-step)
                with bank.applying(rep(aidx), bankf):
                    outs, _states = apply_fn(params, None, rep(tokens),
                                             rep(k_ctx), rep(v_ctx),
                                             rep(ctx_lens), rep_chunk)
            else:
                outs, _states = apply_fn(params, None, rep(tokens),
                                         rep(k_ctx), rep(v_ctx),
                                         rep(ctx_lens), rep_chunk)
            logits, k_new, v_new = outs
            y = jnp.argmax(logits, axis=-1).astype(jnp.int32) \
                .reshape(batch, T)
            mask = (jnp.arange(T, dtype=jnp.int32)[None, :]
                    < chunk_lens[:, None])
            badrow = jnp.sum(~jnp.isfinite(logits), axis=-1,
                             dtype=jnp.int32).reshape(batch, T)
            bad = jnp.sum(jnp.where(mask, badrow, 0), axis=1)
            k_full = k_new.reshape((batch, T) + k_new.shape[1:])[:, T - 1]
            v_full = v_new.reshape((batch, T) + v_new.shape[1:])[:, T - 1]
            pos = ctx_lens[:, None] + jnp.arange(T, dtype=jnp.int32)
            valid = mask & (pos >= floors[:, None])
            kp = scatter_pages(kp, tables, pos, valid, k_full)
            vp = scatter_pages(vp, tables, pos, valid, v_full)
            return kp, vp, y, bad

        if bank is not None:
            def step(params, kp, vp, tokens, tables, ctx_lens,
                     chunk_lens, floors, aidx, bankf):
                return core(params, kp, vp, tokens, tables, ctx_lens,
                            chunk_lens, floors, aidx, bankf)
        else:
            def step(params, kp, vp, tokens, tables, ctx_lens,
                     chunk_lens, floors):
                return core(params, kp, vp, tokens, tables, ctx_lens,
                            chunk_lens, floors)
        return step

    def _mesh_wrap(self, fn):
        """Pin in-program layouts for a ``mdl > 1`` mesh (mx.shard
        phase 2).  Weights are constrained per the ShardPolicy —
        replicated in gather mode, so the decode math and the greedy
        argmax stay byte-identical to single-chip, or their Megatron
        layout in compute mode.  The KV pool is gathered at entry for
        the math and the OUTPUT pool is pinned back onto its
        head-sharded storage layout, so the donated re-bind keeps
        per-device KV residency at 1/mdl between steps."""
        import jax

        fs = self._fwd_shardings
        store = self.pool.sharding
        entry = self.mesh.replicated() if self._tp_mode == "gather" \
            else store

        def wrapped(params, kp, vp, *rest, _fn=fn):
            wsc = jax.lax.with_sharding_constraint
            params = {n: wsc(v, fs[n]) for n, v in params.items()}
            if store is not None:
                kp, vp = wsc(kp, entry), wsc(vp, entry)
            out = _fn(params, kp, vp, *rest)
            if store is not None:
                out = (wsc(out[0], store), wsc(out[1], store)) \
                    + tuple(out[2:])
            return out

        return wrapped

    def _build(self, key):
        """Build (or restore from the mx.compile persistent cache) the
        program for ``key`` = ("decode", B) | ("prefill", T) |
        ("chunk", T) cached-suffix prefill | ("verify", (B, K))
        speculative verify."""
        import jax

        kind, n = key
        if kind == "verify":
            vb, vk = n
            batch, chunk = vb, vk + 1
            with_floors = True
            fn = self._make_verify_fn(vb, vk)
        else:
            batch = n if kind == "decode" else 1
            chunk = 1 if kind == "decode" else n
            with_floors = kind == "chunk"
            fn = self._make_step_fn(
                batch, chunk, with_ctx=kind in ("decode", "chunk"),
                with_floors=with_floors)
        label = self.bucket_key_label(key)
        if self.mesh is not None:
            fn = self._mesh_wrap(fn)
        jitted = jax.jit(fn, donate_argnums=(1, 2))
        # committed layouts (a mesh, or the runner's own device) are
        # part of the program signature: the compiled executable must
        # expect the params/pool where it will be fed them
        placed = self.mesh is not None or self._ctx is not None

        def aval(a):
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=a.sharding if placed else None)

        i32 = _np.dtype("int32")
        avals = [jax.tree_util.tree_map(aval, self._params),
                 aval(self.pool.k), aval(self.pool.v),
                 jax.ShapeDtypeStruct((batch, chunk), i32),
                 jax.ShapeDtypeStruct(
                     (batch, self.page_config.pages_per_seq), i32),
                 jax.ShapeDtypeStruct((batch,), i32),
                 jax.ShapeDtypeStruct((batch,), i32)]
        if with_floors:
            avals.append(jax.ShapeDtypeStruct((batch,), i32))
        if self.bank is not None:
            # adapter index + flat bank tuple (mx.tenant): bank shapes
            # are part of the program fingerprint, so a restored cache
            # entry matches only an identically shaped bank
            avals.append(jax.ShapeDtypeStruct((batch,), i32))
            avals.append(tuple(self.bank.avals()))
        from ..compile.aot import attach_lowered

        # a program that cannot be lowered fails HERE, at warm-up — not
        # later behind a lazy jit (attach_lowered itself degrades every
        # cache failure to a plain compile and returns None only when
        # even that raised)
        compiled, _fp, provenance = attach_lowered(
            jitted.lower(*avals),
            type(self._block).__name__ + ".decode_step", label)
        prog = _Program(compiled if compiled is not None else jitted,
                        label, provenance)
        self._programs[key] = prog
        if telemetry.ENABLED and provenance != "cache":
            telemetry.SERVE_DECODE_COMPILES.labels(bucket=label).inc()
        return prog

    def warm_up(self):
        """Pre-build every decode batch bucket and prefill length
        bucket program and run each once (compiles now, not on the
        first live sequence).  Returns the number of fresh builds
        (cache restores count 0)."""
        fresh = 0
        keys = [("decode", b) for b in self.config.batch_sizes] + \
            [("prefill", t) for t in self.config.prefill_lengths]
        if self.config.prefix_cache:
            # cached-suffix prefill programs (serve/cache.py), one per
            # prefill bucket — opt-in, so deployments without the
            # prefix cache keep an identical program table
            keys += [("chunk", t) for t in self.config.prefill_lengths]
        for key in keys:
            if key in self._programs:
                continue
            with trace.span("serve_decode_warmup", hist=False,
                            cat="serve",
                            args={"bucket": self.bucket_key_label(key)}):
                prog = self._build(key)
                if prog.provenance != "cache":
                    fresh += 1
                # one throw-away execution against all-null page tables
                # (drop-mode scatter: the pool is untouched) proves the
                # program runs — and in the lazy-jit fallback forces
                # the XLA compile to happen before readiness
                kind, n = key
                batch = n if kind == "decode" else 1
                chunk = 1 if kind == "decode" else n
                self._dispatch(prog, self._null_inputs(
                    batch, chunk, floors=(kind == "chunk")))
        self._warmed = True
        spec = getattr(self, "spec", None)
        if spec is not None and not spec.warmed:
            fresh += spec.warm_up()
        return fresh

    def _null_inputs(self, batch, chunk, floors=False):
        c = self.page_config
        inputs = (_np.zeros((batch, chunk), dtype=_np.int32),
                  _np.full((batch, c.pages_per_seq), self.pool.null_page,
                           dtype=_np.int32),
                  _np.zeros((batch,), dtype=_np.int32),
                  _np.ones((batch,), dtype=_np.int32))
        if floors:
            inputs += (_np.zeros((batch,), dtype=_np.int32),)
        if self.bank is not None:
            inputs += (self.bank.null_index(batch),
                       self.bank.flat_arrays())
        return inputs

    def provenance(self):
        return {p.label: p.provenance for p in self._programs.values()}

    # -- dispatch -----------------------------------------------------------
    def _dispatch(self, prog, inputs):
        """Run one program over the CURRENT pool arrays (donated) and
        re-bind the updated pool.  Any failure after the donation point
        can leave the pool consumed — detected and surfaced as a
        ``pool_lost`` DecodeError (the scheduler evicts everything;
        per-sequence containment is impossible without storage)."""
        kp, vp = self.pool.k, self.pool.v
        try:
            out = prog.fn(self._params, kp, vp, *inputs)
            next_tok = _np.asarray(out[2])   # hard sync: errors land here
            bad = _np.asarray(out[3])
            self.pool.k, self.pool.v = out[0], out[1]
            return next_tok, bad
        except (InjectedFault, InjectedIOError):
            raise
        except BaseException as exc:  # noqa: BLE001 - classified below
            if getattr(kp, "is_deleted", lambda: False)():
                self.pool.reset_storage()
                err = DecodeError(
                    "decode step failed AFTER pool donation; KV storage "
                    "lost, all live sequences must restart: %r" % (exc,))
                err.pool_lost = True
                raise err from exc
            raise

    def prefill(self, seq):
        """Run one sequence's prompt through its prefill bucket; writes
        the prompt's K/V into the sequence's reserved pages and returns
        ``(first_token, nonfinite_count)``."""
        c = self.page_config
        prompt = seq.req.prompt
        t_bucket = self.prefill_bucket(len(prompt))
        tokens = _np.zeros((1, t_bucket), dtype=_np.int32)
        tokens[0, :len(prompt)] = prompt
        tables = _np.full((1, c.pages_per_seq), self.pool.null_page,
                          dtype=_np.int32)
        tables[0, :len(seq.pages)] = seq.pages
        ctx_lens = _np.zeros((1,), dtype=_np.int32)
        chunk_lens = _np.array([len(prompt)], dtype=_np.int32)
        inputs = (tokens, tables, ctx_lens, chunk_lens)
        if self.bank is not None:
            inputs += (_np.array([seq.adapter_slot], dtype=_np.int32),
                       self.bank.flat_arrays())
        with self._run_lock:
            prog = self._programs.get(("prefill", t_bucket)) or \
                self._build(("prefill", t_bucket))
            next_tok, bad = self._dispatch(prog, inputs)
        return int(next_tok[0]), int(bad[0])

    def prefill_cached(self, seq, hit_tokens):
        """Cached-suffix prefill (serve/cache.py): the first
        ``hit_tokens`` positions of the prompt are already resident in
        shared pages, so only the suffix runs — through the
        ``("chunk", T)`` program, which attends over the shared
        context and scatters strictly above the ``hit_tokens`` floor
        (a shared page is never writable)."""
        c = self.page_config
        prompt = seq.req.prompt
        suffix = prompt[hit_tokens:]
        t_bucket = self.prefill_bucket(len(suffix))
        tokens = _np.zeros((1, t_bucket), dtype=_np.int32)
        tokens[0, :len(suffix)] = suffix
        tables = _np.full((1, c.pages_per_seq), self.pool.null_page,
                          dtype=_np.int32)
        tables[0, :len(seq.pages)] = seq.pages
        ctx_lens = _np.array([hit_tokens], dtype=_np.int32)
        chunk_lens = _np.array([len(suffix)], dtype=_np.int32)
        floors = _np.array([hit_tokens], dtype=_np.int32)
        inputs = (tokens, tables, ctx_lens, chunk_lens, floors)
        if self.bank is not None:
            inputs += (_np.array([seq.adapter_slot], dtype=_np.int32),
                       self.bank.flat_arrays())
        with self._run_lock:
            prog = self._programs.get(("chunk", t_bucket)) or \
                self._build(("chunk", t_bucket))
            next_tok, bad = self._dispatch(prog, inputs)
        return int(next_tok[0]), int(bad[0])

    def verify_step(self, seqs, chunks, k):
        """One speculative verify dispatch (serve/spec.py): judge each
        sequence's draft chunk (``chunks[i]``, 1..K+1 tokens starting
        at its last committed token) in a single program run.  Returns
        ``(y, bad)`` — ``y[i][j]`` is the target's argmax after
        ``chunks[i][:j+1]``, aligned with ``seqs``."""
        c = self.page_config
        bucket = self.decode_bucket(len(seqs))
        T = k + 1
        tokens = _np.zeros((bucket, T), dtype=_np.int32)
        tables = _np.full((bucket, c.pages_per_seq), self.pool.null_page,
                          dtype=_np.int32)
        ctx_lens = _np.zeros((bucket,), dtype=_np.int32)
        chunk_lens = _np.zeros((bucket,), dtype=_np.int32)
        floors = _np.zeros((bucket,), dtype=_np.int32)
        for i, (seq, ch) in enumerate(zip(seqs, chunks)):
            tokens[i, :len(ch)] = ch
            tables[i, :len(seq.pages)] = seq.pages
            ctx_lens[i] = seq.length
            chunk_lens[i] = len(ch)
            floors[i] = seq.prefix_len
        inputs = (tokens, tables, ctx_lens, chunk_lens, floors)
        if self.bank is not None:
            aidx = _np.full((bucket,), -1, dtype=_np.int32)
            for i, seq in enumerate(seqs):
                aidx[i] = seq.adapter_slot
            inputs += (aidx, self.bank.flat_arrays())
        with self._run_lock:
            key = ("verify", (bucket, k))
            prog = self._programs.get(key) or self._build(key)
            y, bad = self._dispatch(prog, inputs)
        return y[:len(seqs)], bad[:len(seqs)]

    def decode_step(self, seqs):
        """One iteration over ``seqs`` (the live set or a bisected
        subset): each sequence's pending token is written at its next
        position and its next token sampled.  Returns aligned
        ``(next_tokens, nonfinite_counts)`` numpy arrays."""
        c = self.page_config
        bucket = self.decode_bucket(len(seqs))
        tokens = _np.zeros((bucket, 1), dtype=_np.int32)
        tables = _np.full((bucket, c.pages_per_seq), self.pool.null_page,
                          dtype=_np.int32)
        ctx_lens = _np.zeros((bucket,), dtype=_np.int32)
        chunk_lens = _np.ones((bucket,), dtype=_np.int32)
        for i, seq in enumerate(seqs):
            tokens[i, 0] = seq.last_token
            tables[i, :len(seq.pages)] = seq.pages
            ctx_lens[i] = seq.length
        inputs = (tokens, tables, ctx_lens, chunk_lens)
        if self.bank is not None:
            # padding rows stay -1 (base weights, zero delta): a mixed
            # 8-tenant batch is ONE dispatch of the bucket's program
            aidx = _np.full((bucket,), -1, dtype=_np.int32)
            for i, seq in enumerate(seqs):
                aidx[i] = seq.adapter_slot
            inputs += (aidx, self.bank.flat_arrays())
        with self._run_lock:
            prog = self._programs.get(("decode", bucket)) or \
                self._build(("decode", bucket))
            next_tok, bad = self._dispatch(prog, inputs)
        return next_tok[:len(seqs)], bad[:len(seqs)]

    def stats(self):
        return {
            "step": self.step, "root": self.root, "warmed": self._warmed,
            "model": type(self._block).__name__,
            "geometry": {"num_layers": self._block.num_layers,
                         "num_kv_heads": self._block.num_kv_heads,
                         "head_dim": self._block.head_dim,
                         "vocab_size": self._block.vocab_size},
            "pool": self.pool.stats(),
            "buckets": self.provenance(),
            "config": self.config.as_dict(),
            "cache": self.cache.stats() if self.cache is not None
            else {"enabled": False},
            "spec": self.spec.stats() if self.spec is not None
            else {"enabled": False},
            "bank": self.bank.stats() if self.bank is not None
            else {"enabled": False},
        }


class DecodeScheduler:
    """The continuous-batching loop (module doc).

    One daemon thread owns the model, the pool and every live
    sequence; admission (``submit``) only validates, reserves nothing,
    and enqueues — page reservation, prefill, decode, eviction and
    reclamation all happen on the loop so there is exactly one writer
    of serving state.  ``breakers`` (a ``breaker.BreakerBoard``, shared
    with the owning Server) quarantines repeatedly-failing decode /
    prefill buckets: blocked decode buckets are skipped by the bucket
    chooser (a smaller non-blocked bucket chunks the live set), and a
    blocked prefill bucket fast-rejects its admissions."""

    def __init__(self, runner, breakers=None, start=True, tenant=None):
        self._runner = runner
        self.config = runner.config
        self._breakers = breakers
        # mx.tenant plane (registry.TenantPlane): WFQ admission order,
        # per-tenant quota ledger, adapter bank.  Defaults to the
        # runner's plane so Server wiring stays one argument.
        self._tenant = tenant if tenant is not None \
            else getattr(runner, "tenant", None)
        self._cond = threading.Condition()
        self._waiting = deque()
        self._live = {}               # sid -> _Seq, insertion-ordered
        self._next_sid = 0
        self._closed = False
        self._drain = True
        self._pending_runner = None
        self.steps = 0
        self.admitted_total = 0
        self.evictions = {}
        self._recent = deque(maxlen=64)
        self._thread = None
        if start:
            self.start()

    # -- lifecycle ----------------------------------------------------------
    def start(self):
        if self._thread is not None:
            return
        import weakref

        self._thread = threading.Thread(
            target=self._run, args=(weakref.ref(self),), daemon=True,
            name="mx-serve-decode")
        self._thread.start()

    @property
    def alive(self):
        return self._thread is not None and self._thread.is_alive()

    @property
    def runner(self):
        return self._runner

    def stop(self, drain=True, timeout=None):
        """Stop intake; with ``drain`` (default) live sequences finish
        their generation and waiting ones are admitted/served first,
        otherwise everything fails fast with ``ServerClosed``."""
        with self._cond:
            self._closed = True
            self._drain = bool(drain)
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
        return not self.alive

    def swap(self, new_runner):
        """Repoint decoding at a new runner/checkpoint.  Live sequences
        FINISH on the old runner (their KV state is its pool); new
        admissions wait and start on the new one once the old batch
        drains.  Returns immediately."""
        if not isinstance(new_runner, DecodeRunner):
            raise ValueError("swap needs a DecodeRunner")
        with self._cond:
            if self._closed:
                raise ServerClosed("decode scheduler is shut down")
            self._pending_runner = new_runner
            self._cond.notify_all()

    # -- admission ----------------------------------------------------------
    def submit(self, prompt, max_new_tokens=None, eos_id=None,
               timeout_ms=None, request_id=None, on_token=None,
               tenant=None):
        """Enqueue one generation request; returns its
        ``concurrent.futures.Future``.  Validation is all up-front and
        fast: static shape limits raise ``DecodeError``, an impossible
        page reservation raises ``PagePoolExhausted``, a full waiting
        queue rejects with ``ServerOverloaded``, a quarantined prefill
        bucket with ``BucketQuarantined`` — a request that enqueues can
        always be admitted once capacity frees.  ``tenant`` bills the
        request to a registered tenant (mx.tenant): its quota gates
        here (``TenantQuotaExceeded`` -> per-tenant 503), its WFQ
        weight orders admission, its adapter applies in-program."""
        cfg = self.config
        prompt = [int(t) for t in (prompt or ())]
        if not prompt:
            raise DecodeError("decode needs a non-empty prompt "
                              "(list of int token ids)")
        vocab = self._runner.block.vocab_size
        if min(prompt) < 0 or max(prompt) >= vocab:
            raise DecodeError("prompt token ids must be in [0, %d)"
                              % vocab)
        mnt = cfg.max_new_tokens if max_new_tokens is None \
            else int(max_new_tokens)
        if mnt < 1:
            raise DecodeError("max_new_tokens must be >= 1")
        mnt = min(mnt, cfg.max_new_tokens)
        total = len(prompt) + mnt
        if total > cfg.max_context:
            raise DecodeError(
                "prompt (%d) + max_new_tokens (%d) exceeds "
                "max_context=%d" % (len(prompt), mnt, cfg.max_context))
        t_bucket = self._runner.prefill_bucket(len(prompt))
        need = self._runner.page_config.pages_for(total)
        if need > self._runner.pool.capacity:
            raise PagePoolExhausted(
                "request needs %d KV pages but the pool only has %d"
                % (need, self._runner.pool.capacity))
        if self._breakers is not None and \
                self._breakers.blocked(("prefill", t_bucket)):
            if telemetry.ENABLED:
                telemetry.SERVE_REQUESTS.labels(
                    result="quarantined").inc()
            raise self._breakers.quarantine_error(("prefill", t_bucket))
        plane = self._tenant
        if tenant is not None:
            if plane is None:
                raise DecodeError(
                    "request names tenant %r but this server has no "
                    "tenant plane (build with tenant=TenantPlane())"
                    % (tenant,))
            # a quarantined (NaN'ing) adapter fast-rejects ITS tenant's
            # submissions while the half-open probe cools — batch-mates
            # are untouched
            aclass = ("adapter", str(tenant))
            if self._breakers is not None and \
                    self._breakers.blocked(aclass):
                if telemetry.ENABLED:
                    telemetry.SERVE_REQUESTS.labels(
                        result="quarantined").inc()
                    telemetry.TENANT_REQUESTS.labels(
                        tenant=str(tenant), result="quarantined").inc()
                raise self._breakers.quarantine_error(aclass)
            from ..tenant.quota import TenantQuotaExceeded
            from ..tenant.registry import UnknownTenant

            try:
                plane.check_submit(tenant, need)
            except UnknownTenant as exc:
                raise DecodeError(str(exc))
            except TenantQuotaExceeded:
                if telemetry.ENABLED:
                    telemetry.SERVE_REQUESTS.labels(
                        result="rejected").inc()
                    telemetry.TENANT_REQUESTS.labels(
                        tenant=str(tenant), result="rejected").inc()
                raise
        timeout_ms = cfg.timeout_ms if timeout_ms is None else timeout_ms
        deadline = None if timeout_ms is None \
            else time.perf_counter() + float(timeout_ms) / 1e3
        req = DecodeRequest(
            prompt, mnt,
            eos_id=self._runner.eos_id if eos_id is None else eos_id,
            deadline=deadline, request_id=request_id, on_token=on_token,
            tenant=tenant)
        with self._cond:
            if self._closed:
                if tenant is not None:
                    plane.note_dequeue(tenant)
                raise ServerClosed("decode scheduler is shut down")
            if len(self._waiting) >= cfg.queue_depth:
                if tenant is not None:
                    plane.note_dequeue(tenant)
                if telemetry.ENABLED:
                    telemetry.SERVE_REQUESTS.labels(
                        result="rejected").inc()
                raise ServerOverloaded(
                    "decode admission queue full (%d waiting, depth=%d)"
                    % (len(self._waiting), cfg.queue_depth))
            self._waiting.append(req)
            if telemetry.ENABLED:
                telemetry.SERVE_DECODE_WAITING.set(len(self._waiting))
            self._cond.notify_all()
        return req.future

    # -- fleet disaggregation (mxnet_tpu/fleet/handoff.py) -------------------
    def submit_export(self, prompt, max_new_tokens=None, eos_id=None,
                      timeout_ms=None, request_id=None):
        """Prefill-only admission for a disaggregated PREFILL replica:
        the sequence runs its prompt, then its future resolves to the
        ``fleet.handoff`` state dict (pages + cursor + first token)
        instead of decoding — the decode happens on whichever replica
        imports the blob.  Validation mirrors ``submit`` but the page
        reservation is prompt-only (no generation happens here)."""
        cfg = self.config
        prompt = [int(t) for t in (prompt or ())]
        if not prompt:
            raise DecodeError("export needs a non-empty prompt")
        vocab = self._runner.block.vocab_size
        if min(prompt) < 0 or max(prompt) >= vocab:
            raise DecodeError("prompt token ids must be in [0, %d)"
                              % vocab)
        mnt = cfg.max_new_tokens if max_new_tokens is None \
            else int(max_new_tokens)
        if mnt < 1:
            raise DecodeError("max_new_tokens must be >= 1")
        mnt = min(mnt, cfg.max_new_tokens)
        t_bucket = self._runner.prefill_bucket(len(prompt))
        need = self._runner.page_config.pages_for(len(prompt))
        if need > self._runner.pool.capacity:
            raise PagePoolExhausted(
                "export needs %d KV pages but the pool only has %d"
                % (need, self._runner.pool.capacity))
        if self._breakers is not None and \
                self._breakers.blocked(("prefill", t_bucket)):
            if telemetry.ENABLED:
                telemetry.SERVE_REQUESTS.labels(
                    result="quarantined").inc()
            raise self._breakers.quarantine_error(("prefill", t_bucket))
        timeout_ms = cfg.timeout_ms if timeout_ms is None else timeout_ms
        deadline = None if timeout_ms is None \
            else time.perf_counter() + float(timeout_ms) / 1e3
        req = DecodeRequest(
            prompt, mnt,
            eos_id=self._runner.eos_id if eos_id is None else eos_id,
            deadline=deadline, request_id=request_id, export_only=True)
        with self._cond:
            if self._closed:
                raise ServerClosed("decode scheduler is shut down")
            if len(self._waiting) >= cfg.queue_depth:
                if telemetry.ENABLED:
                    telemetry.SERVE_REQUESTS.labels(
                        result="rejected").inc()
                raise ServerOverloaded(
                    "decode admission queue full (%d waiting, depth=%d)"
                    % (len(self._waiting), cfg.queue_depth))
            self._waiting.append(req)
            if telemetry.ENABLED:
                telemetry.SERVE_DECODE_WAITING.set(len(self._waiting))
            self._cond.notify_all()
        return req.future

    def submit_handoff(self, state, timeout_ms=None, request_id=None,
                       on_token=None):
        """Import admission for a disaggregated DECODE replica: the
        PR 12 reservation math re-runs HERE against this pool — full
        worst case (``pages_for(length + max_new_tokens)``) reserved up
        front, geometry cross-checked — so an imported sequence carries
        exactly the admission guarantees of a local one (no mid-decode
        allocation failure, scrub guard over positions >= cursor).
        ``state`` is an unpacked ``fleet.handoff`` blob."""
        from ..fleet import handoff as _handoff

        cfg = self.config
        prompt = [int(t) for t in (state.get("prompt") or ())]
        if not prompt:
            raise DecodeError("handoff carries an empty prompt")
        vocab = self._runner.block.vocab_size
        first = int(state["first_token"])
        if min(prompt) < 0 or max(prompt) >= vocab or \
                not 0 <= first < vocab:
            raise DecodeError(
                "handoff token ids must be in [0, %d)" % vocab)
        mnt = int(state["max_new_tokens"])
        if mnt < 1:
            raise DecodeError("max_new_tokens must be >= 1")
        mnt = min(mnt, cfg.max_new_tokens)
        _handoff.validate_geometry(state, self._runner.page_config)
        total = int(state["length"]) + mnt
        if total > cfg.max_context:
            raise DecodeError(
                "handoff cursor (%d) + max_new_tokens (%d) exceeds "
                "max_context=%d" % (state["length"], mnt,
                                    cfg.max_context))
        need = self._runner.page_config.pages_for(total)
        if need > self._runner.pool.capacity:
            raise PagePoolExhausted(
                "handoff needs %d KV pages but the pool only has %d"
                % (need, self._runner.pool.capacity))
        timeout_ms = cfg.timeout_ms if timeout_ms is None else timeout_ms
        deadline = None if timeout_ms is None \
            else time.perf_counter() + float(timeout_ms) / 1e3
        eos = state.get("eos_id")
        req = DecodeRequest(
            prompt, mnt,
            eos_id=self._runner.eos_id if eos is None else eos,
            deadline=deadline,
            request_id=request_id if request_id is not None
            else state.get("request_id"),
            on_token=on_token, handoff=state)
        with self._cond:
            if self._closed:
                raise ServerClosed("decode scheduler is shut down")
            if len(self._waiting) >= cfg.queue_depth:
                if telemetry.ENABLED:
                    telemetry.SERVE_REQUESTS.labels(
                        result="rejected").inc()
                raise ServerOverloaded(
                    "decode admission queue full (%d waiting, depth=%d)"
                    % (len(self._waiting), cfg.queue_depth))
            self._waiting.append(req)
            if telemetry.ENABLED:
                telemetry.SERVE_DECODE_WAITING.set(len(self._waiting))
            self._cond.notify_all()
        return req.future

    # -- introspection ------------------------------------------------------
    def stats(self):
        with self._cond:
            waiting = len(self._waiting)
            live = [{"request_id": s.req.request_id,
                     "prompt_tokens": len(s.req.prompt),
                     "generated": len(s.tokens),
                     "max_new_tokens": s.req.max_new_tokens,
                     "length": s.length,
                     "pages": len(s.pages or ()),
                     "joined_step": s.joined_step}
                    for s in self._live.values()]
        board = {}
        if self._breakers is not None:
            board = {k: v for k, v in self._breakers.snapshot().items()
                     if k.startswith("('decode'") or
                     k.startswith("('prefill'") or
                     k.startswith("('spec'") or
                     k.startswith("('draft'") or
                     k.startswith("('adapter'")}
        return {
            "alive": self.alive,
            "waiting": waiting,
            "live": live,
            "steps": self.steps,
            "admitted": self.admitted_total,
            "evictions": dict(self.evictions),
            "runner": self._runner.stats(),
            "breakers": board,
            "recent": list(self._recent)[-16:],
        }

    def recent(self):
        return list(self._recent)

    def oldest_waiting_age(self):
        """Seconds the head-of-line waiting request has queued (0.0
        when empty) — the decode-plane half of the fleet router's
        queue-age load signal."""
        with self._cond:
            if not self._waiting:
                return 0.0
            return max(0.0,
                       time.perf_counter() - self._waiting[0].enqueued)

    # -- the loop -----------------------------------------------------------
    @staticmethod
    def _run(ref):
        """Thread body.  Holds the scheduler (and through it the
        runner + device-resident KV pool) only WEAKLY between
        iterations — a Server/scheduler dropped without shutdown()
        must become collectable, not be pinned forever by its own
        daemon thread (same contract as the vision Scheduler's
        weak runner ref)."""
        while True:
            sched = ref()
            if sched is None:
                return            # owner collected: wind down
            try:
                more = sched._loop_once()
            finally:
                del sched         # drop the strong ref before sleeping
            if not more:
                return

    def _loop_once(self):
        """One scheduling iteration; False means the loop must exit."""
        with self._cond:
            if self._closed:
                if not self._drain:
                    self._abort_locked()
                    return False
                if not self._waiting and not self._live:
                    return False
            if not self._waiting and not self._live:
                self._cond.wait(0.25)
                return True
        try:
            self._expire()
            self._maybe_install_runner()
            self._admit()
            if self._live:
                self._step()
            elif self._waiting:
                # waiting but nothing admissible yet (slots/pages held
                # by a draining swap, or breakers cooling): don't spin
                time.sleep(0.005)
        except BaseException:  # noqa: BLE001 - loop must survive
            trace.instant("serve_decode_loop_error", cat="serve")
            time.sleep(0.01)
        return True

    def _abort_locked(self):
        items, self._waiting = list(self._waiting), deque()
        live, self._live = list(self._live.values()), {}
        for req in items:
            if self._tenant is not None:
                self._tenant.note_dequeue(req.tenant)
            fail_request(req, ServerClosed(
                "server shut down before admission"), "cancelled")
            self._bump("cancelled")
        for seq in live:
            self._release(seq)
            fail_request(seq.req, ServerClosed(
                "server shut down mid-generation after %d token(s)"
                % len(seq.tokens)), "cancelled")
            self._bump("cancelled")
            self._record(seq, "cancelled")
        self._gauges()

    def _bump(self, reason):
        self.evictions[reason] = self.evictions.get(reason, 0) + 1
        if telemetry.ENABLED:
            telemetry.SERVE_DECODE_EVICTIONS.labels(reason=reason).inc()

    def _release(self, seq):
        runner = self._runner
        if seq.quota_pages is not None and self._tenant is not None:
            # return the tenant's quota share exactly once
            self._tenant.on_release(seq.tenant, seq.quota_pages)
            seq.quota_pages = None
        if seq.shared:
            # drop this sequence's references on its shared prefix
            # pages BEFORE releasing the private ledger — the pages
            # live in the pool's shared segment, not under the sid
            if runner.cache is not None:
                runner.cache.release(seq.shared)
            else:
                runner.pool.shared_unref(seq.shared)
            seq.shared = []
        if seq.pages is not None:
            runner.pool.release(seq.sid)
            seq.pages = None
        if runner.spec is not None and seq.dpages is not None:
            runner.spec.release(seq)

    def _record(self, seq, reason):
        self._recent.append({
            "request_id": seq.req.request_id,
            "joined_step": seq.joined_step,
            "left_step": self.steps,
            "prompt_tokens": len(seq.req.prompt),
            "generated": len(seq.tokens),
            "reason": reason,
        })

    def _gauges(self):
        if telemetry.ENABLED:
            telemetry.SERVE_DECODE_LIVE.set(len(self._live))
            with self._cond:
                telemetry.SERVE_DECODE_WAITING.set(len(self._waiting))
            pool = self._runner.pool
            telemetry.SERVE_KV_PAGES_IN_USE.set(pool.in_use)
            telemetry.SERVE_KV_PAGES_HIGH_WATER.set(pool.high_water)

    def _expire(self):
        now = time.perf_counter()
        with self._cond:
            keep = deque()
            for req in self._waiting:
                if req.expired(now):
                    if self._tenant is not None:
                        self._tenant.note_dequeue(req.tenant)
                    fail_request(req, RequestTimeout(
                        "deadline expired after %.1f ms waiting for "
                        "admission" % ((now - req.enqueued) * 1e3)),
                        "timeout")
                    self._bump("timeout")
                else:
                    keep.append(req)
            self._waiting = keep
        with self._cond:
            dead = [s for s, q in self._live.items()
                    if q.req.expired(now)]
        for sid in dead:
            with self._cond:
                seq = self._live.pop(sid)
            self._release(seq)
            fail_request(seq.req, RequestTimeout(
                "deadline expired mid-generation after %d token(s)"
                % len(seq.tokens)), "timeout")
            self._bump("timeout")
            self._record(seq, "timeout")
        self._gauges()

    def _maybe_install_runner(self):
        with self._cond:
            if self._pending_runner is None or self._live:
                return
            old, self._runner = self._runner, self._pending_runner
            self._pending_runner = None
            self.config = self._runner.config
        if old.cache is not None:
            old.cache.clear()     # trie refs were the last holders
        old.pool.check()          # every page must have come home
        if telemetry.ENABLED:
            telemetry.SERVE_SWAPS.inc()
        trace.instant("serve_decode_swap", cat="serve",
                      args={"step": self._runner.step})

    def _evict_poisoned(self, seqs):
        """mx.resilience poison drill: sequences whose request id the
        armed ``MXNET_FAULTS`` plan marks (``serve_poison@<rid>``) are
        evicted ALONE — pages reclaimed, batch-mates untouched."""
        out = []
        for seq in seqs:
            if _inject.poisoned(seq.req.request_id):
                _inject.record_firing("serve_poison",
                                      seq.req.request_id, consume=True)
                with self._cond:
                    self._live.pop(seq.sid, None)
                self._release(seq)
                exc = InjectedFault(
                    "injected poison request %s" % seq.req.request_id,
                    site="serve_poison")
                if telemetry.ENABLED:
                    telemetry.SERVE_POISON.inc()
                fail_request(seq.req, exc, "poisoned")
                self._bump("poisoned")
                self._record(seq, "poisoned")
            else:
                out.append(seq)
        return out

    def _pages_needed(self, req):
        """The reservation one request admits with: full worst case
        (prompt + generation) normally; prompt-only for an export
        (generation happens on the importing replica); imported cursor
        + generation for a handoff."""
        if req.export_only:
            total = len(req.prompt)
        elif req.handoff is not None:
            total = int(req.handoff["length"]) + req.max_new_tokens
        else:
            total = len(req.prompt) + req.max_new_tokens
        return self._runner.page_config.pages_for(total)

    def _admit(self):
        """Fill free slots from the waiting queue: reserve the whole
        worst-case page count, prefill through the bucket path (or
        install a handed-off prefill), emit the first token.  Stops at
        the first request the pool cannot hold yet.  Admission order is
        arrival order (FIFO) without a tenant plane; with one, the WFQ
        picker chooses the backlogged tenant with the smallest virtual
        finish time whose quota admits — a tenant at quota is SKIPPED,
        never a head-of-line block."""
        plane = self._tenant
        while len(self._live) < self.config.max_live:
            with self._cond:
                if not self._waiting or self._pending_runner is not None:
                    return
                if plane is not None:
                    req = plane.select(self._waiting, self._pages_needed)
                    if req is None:
                        return    # every backlogged tenant is at quota
                else:
                    req = self._waiting[0]
                pool = self._runner.pool
                cache = self._runner.cache
                need = self._pages_needed(req)
                if cache is not None and not req.export_only and \
                        req.handoff is None:
                    # admission charges only the UNCACHED suffix: the
                    # matched prefix pages are shared, not reserved
                    _, hit_tok = cache.match(req.prompt)
                    need -= hit_tok // self.config.page_size
                if need > pool.capacity:
                    # submit() validated against the runner of its day;
                    # a hot swap may have shrunk the pool since.  Fail
                    # the request rather than head-of-line-block the
                    # queue waiting for pages that can never exist
                    self._waiting.remove(req)
                    if plane is not None:
                        plane.note_dequeue(req.tenant)
                    fail_request(req, PagePoolExhausted(
                        "request needs %d KV pages but the (swapped) "
                        "pool only has %d" % (need, pool.capacity)),
                        "error")
                    self._bump("error")
                    continue
                if not pool.can_alloc(need):
                    # pool pressure: reclaim cold (LRU) cached
                    # prefixes before giving up on this iteration
                    if cache is None or cache.evict(need) == 0 or \
                            not pool.can_alloc(need):
                        return    # wait for evictions to free pages
                self._waiting.remove(req)
                if plane is not None:
                    plane.note_dequeue(req.tenant)
                if telemetry.ENABLED:
                    telemetry.SERVE_DECODE_WAITING.set(len(self._waiting))
                sid = self._next_sid
                self._next_sid += 1
            seq = _Seq(req, sid)
            if _inject.poisoned(req.request_id):
                self._evict_poisoned([seq])
                continue
            if req.handoff is not None:
                self._admit_handoff(seq, need)
                continue
            hit_tok = 0
            if cache is not None and not req.export_only:
                try:
                    _inject.fire("serve_cache", seq=req.request_id)
                except (InjectedFault, InjectedIOError):
                    # corrupt/evict-under-reader drill: the matched
                    # prefix is declared poisoned — drop that subtree
                    # (live readers keep their refs) and prefill cold
                    cache.invalidate(req.prompt)
                shared, hit_tok, cls = cache.acquire(req.prompt)
                seq.cache_class = cls
                seq.prefix_len = hit_tok
                seq.shared = list(shared)
            try:
                t_bucket = self._runner.prefill_bucket(
                    len(req.prompt) - hit_tok)
            except DecodeError as exc:
                # same swap skew: the new runner's bucket table may not
                # cover a prompt the old one admitted — resolve the
                # future, never drop it on the floor
                self._release(seq)
                fail_request(req, exc, "error")
                self._bump("error")
                continue
            bclass = ("prefill", t_bucket)
            if self._breakers is not None and \
                    not self._breakers.allow(bclass):
                self._release(seq)
                fail_request(req, self._breakers.quarantine_error(bclass),
                             "quarantined")
                self._bump("quarantined")
                continue
            if req.tenant is not None and plane is not None:
                # per-adapter breaker gate (half-open probes admit one)
                # + the bank slot the sequence will decode with
                seq.adapter_slot = plane.slot_for(req.tenant)
                aclass = ("adapter", req.tenant)
                if seq.adapter_slot >= 0 and self._breakers is not None \
                        and not self._breakers.allow(aclass):
                    self._release(seq)
                    fail_request(req,
                                 self._breakers.quarantine_error(aclass),
                                 "quarantined")
                    self._bump("quarantined")
                    if telemetry.ENABLED:
                        telemetry.TENANT_REQUESTS.labels(
                            tenant=req.tenant,
                            result="quarantined").inc()
                    continue
            try:
                own = self._pages_needed(req) - len(seq.shared)
                seq.pages = list(seq.shared) + \
                    list(self._runner.pool.alloc(sid, own))
            except PagePoolExhausted as exc:
                # only reachable when the serve_cache drill invalidated
                # a prefix between reservation check and allocation
                self._release(seq)
                fail_request(req, exc, "error")
                self._bump("error")
                continue
            if plane is not None:
                # WFQ charge + quota ledger reservation (mirrors the
                # pool pages this sid really holds)
                plane.admit_granted(
                    req.tenant,
                    plane.cost_of(len(req.prompt), req.max_new_tokens),
                    own)
                if req.tenant is not None:
                    seq.quota_pages = own
            t0 = time.perf_counter()
            blabel = ("chunk:t%d" if hit_tok else "prefill:t%d") \
                % t_bucket
            try:
                with trace.use(req.trace), \
                        trace.span("serve_decode_prefill", hist=False,
                                   cat="serve",
                                   args={"bucket": blabel,
                                         "request_id": req.request_id}):
                    if hit_tok:
                        tok, bad = self._runner.prefill_cached(
                            seq, hit_tok)
                    else:
                        tok, bad = self._runner.prefill(seq)
            except BaseException as exc:  # noqa: BLE001 - per-request
                self._release(seq)
                if self._breakers is not None:
                    self._breakers.failure(bclass)
                if getattr(exc, "pool_lost", False):
                    self._evict_all_live(exc)
                fail_request(req, exc, "error")
                self._bump("error")
                continue
            if self._breakers is not None:
                self._breakers.success(bclass)
            seq.length = len(req.prompt)
            seq.joined_step = self.steps
            seq.t_prefill = time.perf_counter() - t0
            if telemetry.ENABLED:
                telemetry.SERVE_DECODE_PREFILLS.inc()
                telemetry.SERVE_DECODE_PREFILL_TOKENS.inc(
                    len(req.prompt) - hit_tok)
            with self._cond:
                self._live[sid] = seq
            self.admitted_total += 1
            if bad:
                self._evict_nonfinite(seq, bad)
                continue
            if cache is not None and not req.export_only:
                # only a HEALTHY prefill populates the trie; newly
                # adopted full-prompt pages move to the shared segment
                # with refcount 2 (trie + this reader)
                adopted = cache.insert(req.prompt, sid, seq.pages,
                                       hit_tok)
                if adopted:
                    seq.shared = list(
                        seq.pages[:len(seq.shared) + adopted])
            if req.export_only:
                self._finish_export(seq, int(tok))
                self._gauges()
                continue
            self._emit(seq, int(tok), t0)
            self._finish_if_done(seq)
            self._gauges()

    def _admit_handoff(self, seq, need):
        """Admit one imported sequence: reserve the (already
        re-validated) worst case, splice the blob's pages into the
        reservation, and emit the prefill replica's first token so the
        client stream is byte-identical to a colocated run."""
        from ..fleet import handoff as _handoff

        req = seq.req
        state = req.handoff
        seq.pages = self._runner.pool.alloc(seq.sid, need)
        t0 = time.perf_counter()
        try:
            with trace.use(req.trace), \
                    trace.span("serve_decode_handoff_install", hist=False,
                               cat="serve",
                               args={"pages": int(state["pages"]),
                                     "request_id": req.request_id}):
                _handoff.install_seq(self._runner, seq, state)
        except BaseException as exc:  # noqa: BLE001 - per-request
            self._release(seq)
            if getattr(exc, "pool_lost", False):
                self._evict_all_live(exc)
            fail_request(req, exc, "error")
            self._bump("error")
            return
        seq.length = int(state["length"])
        seq.joined_step = self.steps
        seq.t_prefill = time.perf_counter() - t0
        with self._cond:
            self._live[seq.sid] = seq
        self.admitted_total += 1
        self._emit(seq, int(state["first_token"]), t0)
        self._finish_if_done(seq)
        self._gauges()

    def _finish_export(self, seq, first_token):
        """Resolve an export_only sequence: snapshot its pages +
        cursor + first token as the handoff state, reclaim the pages,
        resolve the future with the state dict."""
        from ..fleet import handoff as _handoff

        with self._cond:
            self._live.pop(seq.sid, None)
        try:
            state = _handoff.export_seq(self._runner, seq, first_token)
        except BaseException as exc:  # noqa: BLE001 - per-request
            self._release(seq)
            fail_request(seq.req, exc, "error")
            self._bump("error")
            self._record(seq, "error")
            return
        self._release(seq)
        self._bump("exported")
        self._record(seq, "exported")
        done_t = time.perf_counter()
        try:
            seq.req.future.set_result(state)
        except InvalidStateError:
            return
        if telemetry.ENABLED:
            telemetry.SERVE_REQUESTS.labels(result="ok").inc()
            telemetry.SERVE_REQUEST_SECONDS.observe(
                done_t - seq.req.enqueued)

    def _evict_nonfinite(self, seq, bad):
        """The per-token output guard tripped: this sequence's logits
        went NaN/Inf.  Greedy-sampling a NaN row returns garbage, so
        the sequence fails alone instead of streaming poison."""
        with self._cond:
            self._live.pop(seq.sid, None)
        self._release(seq)
        if seq.tenant is not None and seq.adapter_slot >= 0:
            # attribute the poison to the tenant's ADAPTER: repeated
            # trips open the ("adapter", tenant) breaker and quarantine
            # that slot's traffic alone — batch-mates keep decoding
            if self._breakers is not None:
                self._breakers.failure(("adapter", seq.tenant))
            if telemetry.ENABLED:
                telemetry.TENANT_ADAPTER_POISON.labels(
                    tenant=seq.tenant).inc()
        if telemetry.ENABLED:
            telemetry.SERVE_NONFINITE_OUTPUTS.inc(int(bad))
            telemetry.SERVE_NONFINITE_BATCHES.inc()
            telemetry.SERVE_POISON.inc()
        trace.instant("serve_decode_nonfinite", cat="serve",
                      ctx=seq.req.trace,
                      args={"request_id": seq.req.request_id,
                            "elements": int(bad)})
        fail_request(seq.req, DecodeError(
            "sequence evicted: %d nonfinite logit element(s) at token "
            "%d (output guard)" % (int(bad), len(seq.tokens))),
            "poisoned")
        self._bump("poisoned")
        self._record(seq, "nonfinite")

    def _evict_all_live(self, exc):
        """KV storage was lost (donated pool consumed by a failed
        dispatch): no sequence's context survives."""
        with self._cond:
            doomed, self._live = list(self._live.values()), {}
        for seq in doomed:
            self._release(seq)
            fail_request(seq.req, exc, "error")
            self._bump("error")
            self._record(seq, "pool_lost")
        if self._runner.cache is not None:
            # the replacement pool arrays are zeros: every cached
            # prefix's content is gone with the storage
            self._runner.cache.clear()
        self._gauges()

    def _emit(self, seq, token, t_start):
        """One generated token: bookkeeping, telemetry, the per-token
        trace span on the request's own trace id, and the streaming
        callback."""
        now = time.perf_counter()
        seq.tokens.append(token)
        seq.last_token = token
        if seq.first_token_t is None:
            seq.first_token_t = now
            if telemetry.ENABLED:
                telemetry.SERVE_DECODE_TTFT_SECONDS.labels(
                    cache=seq.cache_class or "miss").observe(
                    now - seq.req.enqueued)
                if seq.tenant is not None:
                    telemetry.TENANT_TTFT_SECONDS.labels(
                        tenant=seq.tenant).observe(
                        now - seq.req.enqueued)
        if telemetry.ENABLED:
            telemetry.SERVE_DECODE_TOKENS.inc()
            if seq.tenant is not None:
                telemetry.TENANT_TOKENS.labels(tenant=seq.tenant).inc()
        if seq.tenant is not None and self._tenant is not None:
            self._tenant.note_tokens(seq.tenant)
        if trace.ENABLED and seq.req.trace is not None:
            trace.record_span(
                "serve_decode_token", t_start, now - t_start,
                ctx=seq.req.trace, cat="serve",
                args={"index": len(seq.tokens) - 1, "token": token,
                      "request_id": seq.req.request_id})
        cb = seq.req.on_token
        if cb is not None:
            try:
                cb(token, len(seq.tokens) - 1)
            except Exception:     # a sick consumer must not stall decode
                seq.req.on_token = None

    def _finish_if_done(self, seq):
        reason = seq.done_reason
        if reason is None:
            return False
        with self._cond:
            self._live.pop(seq.sid, None)
        self._release(seq)
        if seq.tenant is not None and seq.adapter_slot >= 0 and \
                self._breakers is not None:
            # a healthy adapter-applied completion closes the breaker's
            # failure window (and recovers a half-open quarantine)
            self._breakers.success(("adapter", seq.tenant))
        self._bump("finished")
        self._record(seq, reason)
        done_t = time.perf_counter()
        try:
            seq.req.future.set_result(
                {"tokens": list(seq.tokens), "finish_reason": reason})
        except InvalidStateError:
            return True
        if telemetry.ENABLED:
            telemetry.SERVE_REQUESTS.labels(result="ok").inc()
            if seq.tenant is not None:
                telemetry.TENANT_REQUESTS.labels(
                    tenant=seq.tenant, result="ok").inc()
            telemetry.SERVE_REQUEST_SECONDS.observe(
                done_t - seq.req.enqueued)
        if trace.ENABLED and seq.req.trace is not None:
            trace.record_span(
                "serve_request", seq.req.enqueued,
                done_t - seq.req.enqueued, ctx=seq.req.trace, root=True,
                cat="serve",
                args={"result": "ok", "request_id": seq.req.request_id,
                      "tokens": len(seq.tokens),
                      "finish_reason": reason})
        return True

    def _pick_bucket(self, n):
        """Smallest non-quarantined decode bucket covering ``n`` live
        sequences; falls back to the largest non-blocked smaller bucket
        (the live set steps in chunks while a bucket cools down).
        Returns None when every bucket is quarantined."""
        blocked = (lambda b: self._breakers is not None and
                   self._breakers.blocked(("decode", b)))
        for b in self.config.batch_sizes:
            if b >= n and not blocked(b):
                return b
        for b in reversed(self.config.batch_sizes):
            if b <= n and not blocked(b):
                return b
        return None

    def _step(self):
        """One continuous-batching iteration over the live set:
        speculative sequences advance K-at-a-time through the spec
        plane, everything else (and every fallback) through the
        normal one-token decode path."""
        live = self._evict_poisoned(list(self._live.values()))
        if not live:
            self._gauges()
            return
        spec = self._runner.spec
        if spec is not None:
            live = self._spec_round(live, spec)
        if live:
            self._step_normal(live)
        else:
            self._gauges()

    def _spec_round(self, live, spec):
        """Drive one plane round over the speculative slice of the
        live set; emits accepted tokens and returns the slice to step
        normally this iteration."""
        for seq in live:
            if seq.spec is None:
                # first sight of this sequence: offer it to the plane
                # (attach failure just leaves it decoding normally)
                if seq.req.export_only:
                    seq.spec = False
                else:
                    spec.attach(seq)
        normal = [s for s in live if not s.spec]
        cand = [s for s in live if s.spec]
        if not cand:
            return normal
        t0 = time.perf_counter()
        try:
            results, fallen = spec.round(cand, self._breakers)
        except BaseException as exc:  # noqa: BLE001 - classified
            if getattr(exc, "pool_lost", False):
                self._evict_all_live(exc)
                return []
            trace.instant("serve_spec_round_error", cat="serve")
            return normal + cand
        if results:
            self.steps += 1
            if telemetry.ENABLED:
                telemetry.SERVE_DECODE_STEPS.inc()
        for seq, emitted, bad in results:
            if bad:
                self._evict_nonfinite(seq, bad)
                continue
            for tok in emitted:
                seq.length += 1
                self._emit(seq, int(tok), t0)
                if self._finish_if_done(seq):
                    break
        self._gauges()
        return normal + fallen

    def _step_normal(self, live):
        bucket = self._pick_bucket(len(live))
        if bucket is None:
            time.sleep(0.005)     # every decode bucket cooling down
            return
        seqs = live[:bucket]
        bclass = ("decode", bucket)
        if self._breakers is not None and not self._breakers.allow(bclass):
            time.sleep(0.005)
            return
        t0 = time.perf_counter()
        head = seqs[0]
        try:
            _inject.fire("serve_dispatch")
        except (InjectedFault, InjectedIOError):
            # a transient injected dispatch fault: one breaker strike,
            # nobody evicted — sequences retry next iteration
            if self._breakers is not None:
                self._breakers.failure(bclass)
            return
        with trace.use(head.req.trace), \
                trace.span("serve_decode_step", hist=False, cat="serve",
                           args={"bucket": "decode:b%d" % bucket,
                                 "live": len(seqs)}), \
                trace.watchdog.watch("serve_dispatch"):
            pairs = self._step_split(seqs)
        self.steps += 1
        dt = time.perf_counter() - t0
        if telemetry.ENABLED:
            telemetry.SERVE_DECODE_STEPS.inc()
            telemetry.SERVE_DECODE_BATCH.observe(len(seqs))
            telemetry.SERVE_DECODE_TOKEN_SECONDS.observe(dt)
        failed = [p for p in pairs if p[3] is not None]
        if self._breakers is not None:
            (self._breakers.failure if failed
             else self._breakers.success)(bclass)
        any_ok = any(p[3] is None for p in pairs)
        pool_lost = next((p[3] for p in pairs
                          if getattr(p[3], "pool_lost", False)), None)
        if pool_lost is not None:
            self._evict_all_live(pool_lost)
            return
        for seq, tok, bad, exc, isolated in pairs:
            if exc is not None:
                poisoned = isolated and any_ok
                with self._cond:
                    self._live.pop(seq.sid, None)
                self._release(seq)
                if poisoned and telemetry.ENABLED:
                    telemetry.SERVE_POISON.inc()
                fail_request(seq.req, exc,
                             "poisoned" if poisoned else "error")
                self._bump("poisoned" if poisoned else "error")
                self._record(seq, "poisoned" if poisoned else "error")
                continue
            if bad:
                self._evict_nonfinite(seq, bad)
                continue
            seq.length += 1
            self._emit(seq, int(tok), t0)
            self._finish_if_done(seq)
        if len(seqs) < len(self._live):
            # chunked iteration (a larger bucket is cooling down):
            # rotate the just-stepped sequences behind the un-stepped
            # tail so every live sequence keeps making progress —
            # without this, live[:bucket] would starve the tail for
            # the whole breaker cooldown
            with self._cond:
                for seq in seqs:
                    if seq.sid in self._live:
                        self._live[seq.sid] = self._live.pop(seq.sid)
        self._gauges()

    def _step_split(self, seqs, depth=0):
        """Run one decode iteration for ``seqs``; on failure retry
        bisected down to single sequences so a poisoned sequence fails
        alone.  Returns ``[(seq, token, bad, exc, isolated)]``.
        Re-execution of a half is safe: a decode step writes each
        sequence's K/V at the same (page, slot) address it would have
        written the first time (idempotent), and sampling is greedy."""
        try:
            toks, bads = self._runner.decode_step(seqs)
        except BaseException as exc:  # noqa: BLE001 - contained
            if getattr(exc, "pool_lost", False) or len(seqs) == 1:
                isolated = depth > 0 or \
                    getattr(exc, "site", None) == "serve_poison"
                return [(seqs[0], None, None, exc, isolated)]
            if telemetry.ENABLED:
                telemetry.SERVE_BISECT_SPLITS.inc()
            trace.instant("serve_decode_bisect", cat="serve",
                          args={"sequences": len(seqs), "depth": depth,
                                "error": type(exc).__name__})
            mid = len(seqs) // 2
            return self._step_split(seqs[:mid], depth + 1) + \
                self._step_split(seqs[mid:], depth + 1)
        return [(s, int(toks[i]), int(bads[i]), None, False)
                for i, s in enumerate(seqs)]


# ---------------------------------------------------------------------------
# TinyDecoder — the reference decoder model (contract documentation)
# ---------------------------------------------------------------------------

from ..gluon import nn as _nn  # noqa: E402
from ..gluon.block import HybridBlock as _HybridBlock  # noqa: E402


class TinyDecoder(_HybridBlock):
    """A small, real transformer decoder implementing the decode-path
    model contract (module doc): pre-norm-free 2-layer MHA + MLP,
    sinusoidal absolute positions, causal chunk attention over a
    gathered paged context.  Reference model for tests / the smoke
    drill / the bench row — and executable documentation for bringing
    a real decoder onto ``mx.serve.decode``."""

    def __init__(self, vocab_size=64, num_layers=2, num_heads=2,
                 head_dim=8, hidden=None, eos_id=None, **kwargs):
        super().__init__(**kwargs)
        self.vocab_size = int(vocab_size)
        self.num_layers = int(num_layers)
        self.num_kv_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.eos_id = eos_id
        units = self.num_kv_heads * self.head_dim
        self.units = units
        hidden = hidden or units * 2
        self.embed = _nn.Embedding(self.vocab_size, units)
        for layer in range(self.num_layers):
            for name in ("q", "k", "v", "o"):
                setattr(self, "%s%d" % (name, layer),
                        _nn.Dense(units, flatten=False, in_units=units))
            setattr(self, "up%d" % layer,
                    _nn.Dense(hidden, flatten=False, in_units=units))
            setattr(self, "down%d" % layer,
                    _nn.Dense(units, flatten=False, in_units=hidden))
        self.unembed = _nn.Dense(self.vocab_size, flatten=False,
                                 in_units=units)

    def _positional(self, positions):
        """Sinusoidal encoding of absolute positions [B, T] ->
        [B, T, units] (even dims sin, odd dims cos)."""
        from .. import ndarray as nd

        half = self.units // 2
        inv = nd.array(_np.asarray(
            1.0 / (10000.0 ** (_np.arange(half) / max(1, half))),
            dtype="float32"), ctx=positions.context)
        ang = positions.expand_dims(2) * inv.reshape((1, 1, half))
        return nd.concat(nd.sin(ang), nd.cos(ang), dim=2)

    def forward(self, tokens, k_ctx, v_ctx, ctx_lengths, chunk_lengths):
        from .. import ndarray as nd

        B, T = tokens.shape
        S = k_ctx.shape[2]
        H, Dh, C = self.num_kv_heads, self.head_dim, self.units
        ctx_f = ctx_lengths.astype("float32").expand_dims(1)     # [B,1]
        steps = nd.arange(T, dtype="float32",
                          ctx=tokens.context).expand_dims(0)      # [1,T]
        q_pos = ctx_f + steps                                    # [B,T]
        x = self.embed(tokens) + self._positional(q_pos)

        # one [B, T, S+T] additive attention bias shared by all layers:
        # context keys are valid while their position < ctx_length;
        # chunk keys are causal (key j attends-from query i when j <= i
        # — queries past chunk_length produce garbage that is never
        # read: their K/V scatter is dropped and the last-logit
        # selector picks index chunk_length-1)
        key_ctx_pos = nd.arange(S, dtype="float32",
                                ctx=tokens.context).expand_dims(0)
        ctx_valid = (key_ctx_pos < ctx_f).astype("float32")       # [B,S]
        # invalid context keys take position +1e9 so they FAIL the
        # causal test below (key_pos <= q_pos) and are masked out; a
        # negative sentinel would pass it and dilute every softmax
        # with the scrubbed zero-K/V tail
        key_pos = nd.concat(
            ctx_valid * key_ctx_pos + (1.0 - ctx_valid) * 1e9,
            ctx_f + steps, dim=1) if S else (ctx_f + steps)       # [B,S+T]
        causal = (key_pos.expand_dims(1) <=
                  q_pos.expand_dims(2)).astype("float32")    # [B,T,S+T]
        bias = (1.0 - causal) * -1e9

        k_chunks, v_chunks = [], []
        for layer in range(self.num_layers):
            q = getattr(self, "q%d" % layer)(x).reshape((B, T, H, Dh))
            k = getattr(self, "k%d" % layer)(x).reshape((B, T, H, Dh))
            v = getattr(self, "v%d" % layer)(x).reshape((B, T, H, Dh))
            k_chunks.append(k.expand_dims(2))
            v_chunks.append(v.expand_dims(2))
            k_all = nd.concat(k_ctx[:, layer], k, dim=1) if S else k
            v_all = nd.concat(v_ctx[:, layer], v, dim=1) if S else v
            q2 = q.transpose((0, 2, 1, 3)).reshape((B * H, T, Dh))
            k2 = k_all.transpose((0, 2, 1, 3)).reshape((B * H, S + T, Dh))
            v2 = v_all.transpose((0, 2, 1, 3)).reshape((B * H, S + T, Dh))
            scores = nd.batch_dot(q2, k2, transpose_b=True) \
                / float(_np.sqrt(Dh))
            scores = (scores.reshape((B, H, T, S + T)) +
                      bias.expand_dims(1)).reshape((B * H, T, S + T))
            probs = nd.softmax(scores, axis=-1)
            att = nd.batch_dot(probs, v2).reshape((B, H, T, Dh)) \
                .transpose((0, 2, 1, 3)).reshape((B, T, C))
            x = x + getattr(self, "o%d" % layer)(att)
            x = x + getattr(self, "down%d" % layer)(
                nd.relu(getattr(self, "up%d" % layer)(x)))

        logits = self.unembed(x)                          # [B, T, V]
        sel = nd.one_hot((chunk_lengths - 1).astype("int32"), T) \
            .astype("float32")                            # [B, T]
        last = nd.sum(logits * sel.expand_dims(2), axis=1)  # [B, V]
        k_new = nd.concat(*k_chunks, dim=2) if self.num_layers > 1 \
            else k_chunks[0]
        v_new = nd.concat(*v_chunks, dim=2) if self.num_layers > 1 \
            else v_chunks[0]
        return last, k_new, v_new
