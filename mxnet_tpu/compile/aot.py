"""AOT precompile / warm-start over the persistent compile cache.

Two ways compiled artifacts cross process boundaries:

- **Live path** (``attach_from_cache``, called by
  ``HybridBlock._get_cached_op`` on an in-memory miss): the block's
  pure function is LOWERED (traced — cheap), the resulting StableHLO
  text is fingerprinted, and the cache is consulted.  A hit
  deserializes the stored XLA executable (``jax.experimental.
  serialize_executable``) — the expensive ``compile()`` is skipped
  entirely.  A miss compiles eagerly and commits the serialized
  executable for the next process.
- **Warm-start path** (``warm_start(block)``): zero tracing, zero
  compiling.  Every cached entry recorded under this block's signature
  (class + param shapes/dtypes) is deserialized and installed straight
  into ``block._cached_ops`` — its hybridize key, output spec and
  executable all come from the entry's metadata.  A restarted
  ``mx.serve`` server reaches steady state with 0 fresh builds.

Fidelity guard: the live path keys on the StableHLO text itself, so
ANY change to the traced program is a clean miss.  ``warm_start``
trusts the block signature + environment fingerprint instead (it never
traces); a stale artifact can only be installed if the model class,
parameter shapes, jax/framework versions, platform, topology and XLA
flags ALL match while forward()'s code meaningfully changed — pass
``verify=True`` to re-trace and check the StableHLO fingerprint too.

Degradation contract: every function here returns a "nothing happened"
value (None / 0 / False) on ANY failure — a broken cache dir, a
missing serialize API, an unpicklable artifact — and the caller falls
back to the normal in-memory jit compile.  The hot path never raises.

Trust model: artifacts carry pytree defs and are deserialized with
pickle, so loading one executes code from the cache directory.  The
CRC32 manifest detects corruption, NOT tampering — point the cache
only at directories writable solely by principals you already trust
to run code in this process (same stance as jax's own persistent
compilation cache).
"""
from __future__ import annotations

import json
import logging
import pickle
import time

from .. import telemetry

__all__ = ["precompile", "warm_start", "attach_from_cache"]

_LOGGER = logging.getLogger("mxnet_tpu.compile")


def _serialize_api():
    """Capability probe for jax's AOT executable (de)serialization."""
    try:
        from jax.experimental import serialize_executable as se

        se.serialize, se.deserialize_and_load  # noqa: B018 probe
        return se
    except (ImportError, AttributeError):
        return None


def _key_avals(key):
    """The flat-input aval tuple inside a hybridize cache key (via the
    HybridBlock accessor — the tuple layout is private to block.py)."""
    from ..gluon.block import HybridBlock

    return HybridBlock.cachedop_key_avals(key)


def _key_is_portable(key):
    """True when the key can be reconstructed in another process: no
    static (non-NDArray) flat inputs, whose VALUES only live in this
    process's closure (the key carries just their repr)."""
    try:
        pickle.dumps(key)
    except Exception:
        return False
    return all(a[0] != "static" for a in _key_avals(key))


def _spec_json_safe(spec):
    """Specs ride in META.json, and JSON stringifies non-string dict
    keys (``{1: "_"}`` comes back as ``{"1": "_"}``) and rejects tuple
    keys outright — a spec that doesn't survive the round trip must
    mark its entry non-portable, or warm_start would rebuild a
    DIFFERENT container structure than the live compile produced."""
    try:
        return json.loads(json.dumps(spec)) == spec
    except (TypeError, ValueError):
        return False


def _serialize(se, compiled, key):
    """compiled executable -> ARTIFACT.bin bytes.  The ids of the devices
    it runs on, in assignment order, ride along: the loader needs them."""
    exe, in_tree, out_tree = se.serialize(compiled)
    return pickle.dumps({
        "exe": exe, "in_tree": in_tree, "out_tree": out_tree, "key": key,
        "devices": [d.id for d in
                    compiled.runtime_executable().local_devices()]})


def _deserialize(se, raw):
    """raw ARTIFACT.bin bytes -> (loaded executable, key).  The executable
    is loaded onto the devices it was compiled for: left to its default,
    ``deserialize_and_load`` takes EVERY device of the backend as the
    execution devices, and a one-device program then fails at call time
    on a host with several ("expected ... to have 8 shards")."""
    import jax

    payload = pickle.loads(raw)
    by_id = {d.id: d for d in jax.devices()}
    cfn = se.deserialize_and_load(
        payload["exe"], payload["in_tree"], payload["out_tree"],
        execution_devices=[by_id[i] for i in payload["devices"]])
    return cfn, payload["key"]


def attach_lowered(lowered, block_class, block_sig):
    """Compile an already-lowered jax program, consulting / committing
    the persistent cache when enabled.  The shared backend behind the
    non-hybridize program caches — the multi-tensor optimizer groups
    (optimizer/multi_tensor.py) and the whole-step captured programs
    (mx.step) — which re-trace cheaply per process and hit purely by
    StableHLO fingerprint, so their entries are never ``warm_start``
    candidates (``portable: False``).

    Returns ``(compiled_or_None, fingerprint, provenance)``:
    ``provenance`` is ``"cache"`` on a disk hit (zero fresh XLA
    compiles), else ``"fresh"``; ``None`` for the callable means even
    the plain ``lowered.compile()`` failed and the caller should keep
    its lazy-jit path.  Every cache failure degrades to a plain
    compile — the hot path never raises from here."""
    from . import get_cache, is_enabled

    fingerprint = None
    if is_enabled():
        try:
            cache = get_cache()
            se = _serialize_api()
            if cache is not None and se is not None:
                fingerprint = cache.fingerprint(lowered.as_text())
                try:
                    loaded = cache.load(fingerprint)
                except Exception:
                    loaded = None
                if loaded is not None:
                    raw, _meta = loaded
                    try:
                        cfn, _key = _deserialize(se, raw)
                        if telemetry.ENABLED:
                            telemetry.COMPILE_CACHE_HIT.inc()
                        return cfn, fingerprint, "cache"
                    except Exception:
                        cache.quarantine(
                            fingerprint, reason="artifact undeserializable")
                if telemetry.ENABLED:
                    telemetry.COMPILE_CACHE_MISS.inc()
                compiled = lowered.compile()
                try:
                    cache.commit(
                        fingerprint, _serialize(se, compiled, None),
                        {"block_class": block_class,
                         "block_sig": block_sig, "portable": False})
                except Exception:
                    _LOGGER.debug("program cache commit failed",
                                  exc_info=True)
                return compiled, fingerprint, "fresh"
        except Exception:
            _LOGGER.debug("program cache attach failed", exc_info=True)
    try:
        return lowered.compile(), fingerprint, "fresh"
    except Exception:
        return None, fingerprint, "fresh"


# ---------------------------------------------------------------------------
# live path: consult on miss, commit on build
# ---------------------------------------------------------------------------

def attach_from_cache(block, centry, key, flat_inputs, training,
                      call_kwargs):
    """Lower ``centry.jfn``, fingerprint the StableHLO, then either load
    the stored executable (hit) or compile eagerly and commit (miss).
    Sets ``centry.cfn`` either way.  Returns True on a cache hit (no
    fresh XLA compile happened), False on a fresh compile, None when
    the cache could not be used at all (lazy jit path proceeds)."""
    from . import get_cache
    from .cache import block_signature

    cache = get_cache()
    se = _serialize_api()
    if cache is None or se is None:
        return None
    try:
        import jax

        from ..ndarray.ndarray import NDArray

        params = [p._data._data
                  for p in block.collect_params().values()]
        nd_inputs = [x._data for x in flat_inputs
                     if isinstance(x, NDArray)]
        rng0 = jax.random.PRNGKey(0)
        lowered = centry.jfn.lower(params, rng0, *nd_inputs)
        fp = cache.fingerprint(lowered.as_text())
        centry.fingerprint = fp
    except Exception:
        # exotic inputs (or a backend without lowering): lazy jit path
        return None

    try:
        loaded = cache.load(fp)
    except Exception:
        # load() degrades internally; this guards a misbehaving store
        loaded = None
    if loaded is not None:
        raw, _meta = loaded
        try:
            centry.cfn, _stored_key = _deserialize(se, raw)
            if telemetry.ENABLED:
                telemetry.COMPILE_CACHE_HIT.inc()
            return True
        except Exception:
            cache.quarantine(fp, reason="artifact undeserializable")

    if telemetry.ENABLED:
        telemetry.COMPILE_CACHE_MISS.inc()
    try:
        compiled = lowered.compile()
        centry.cfn = compiled
    except Exception:
        return None  # let the lazy jit path surface the real error
    t_io = time.perf_counter()
    try:
        artifact = _serialize(se, compiled, key)
        portable = (_key_is_portable(key)
                    and _spec_json_safe(centry.out_spec)
                    and _spec_json_safe(getattr(centry, "in_spec",
                                                None)))
        meta = {
            "block_class": type(block).__name__,
            "block_sig": block_signature(block),
            "out_spec": centry.out_spec,
            "in_spec": getattr(centry, "in_spec", None),
            "n_flat_inputs": len(_key_avals(key)),
            "training": bool(training),
            "portable": portable,
            # flat-input avals in JSON form, so warm_start can scope to
            # a wanted signature set BEFORE paying the pickle +
            # executable device-load (portable keys have array avals
            # only, so this is always [[shape-list, dtype-str], ...])
            "avals": ([[list(shape), dt]
                       for shape, dt in _key_avals(key)]
                      if portable else None),
        }
        cache.commit(fp, artifact, meta)
    except Exception:
        _LOGGER.debug("compile cache commit failed", exc_info=True)
    # serialize + pickle + durable commit are disk I/O, not build work:
    # the caller subtracts this from the cold-start build histogram
    centry.commit_io_seconds = time.perf_counter() - t_io
    return False


# ---------------------------------------------------------------------------
# AOT export / warm start
# ---------------------------------------------------------------------------

def precompile(block, signatures, dtype="float32", training=False,
               **call_kwargs):
    """Compile ``block`` for every input signature AND persist each
    compiled executable to the cache, so a later process (or a
    restarted server) can ``warm_start`` with zero fresh builds.

    ``signatures`` follows ``HybridBlock.warm_up``: a list of shape
    tuples (single input) or per-input ``(shape, dtype)`` sequences.
    Returns the number of newly built signatures (cache hits from an
    earlier process count as 0 builds but still execute once)."""
    from . import is_enabled

    if not is_enabled():
        raise RuntimeError(
            "mx.compile is disabled — call mxnet_tpu.compile.enable() "
            "or set MXNET_COMPILE_CACHE=1 before precompiling")
    return block.warm_up(signatures, dtype=dtype, training=training,
                         **call_kwargs)


def warm_start(block, verify=False, signatures=None, dtype="float32"):
    """Repopulate ``block``'s hybridize cache from disk — no tracing,
    no compiling.  Returns the number of installed signatures (0 when
    the cache is unusable, the block has no committed entries, or its
    parameters are not initialized yet).

    With ``verify=True`` each candidate entry is re-lowered and its
    StableHLO fingerprint checked before installation (catches a
    forward() whose code changed under an identical block signature, at
    the cost of one trace per entry).

    ``signatures``, when given, scopes the restore: only entries whose
    flat-input avals match one of the listed signatures are installed.
    Signatures follow ``HybridBlock.warm_up``: a bare shape tuple
    (single input, ``dtype`` fills in), or a sequence of per-input
    entries each a shape tuple or ``(shape, dtype-str)`` pair.  A
    shared cache can hold MANY committed signatures for one block
    (other deployments' batch sizes/bucket tables); a server that
    needs 4 buckets should not deserialize and device-load all of
    them — ``serve.ModelRunner`` passes its bucket table here."""
    from . import get_cache, is_enabled
    from .cache import block_signature
    from ..gluon.block import HybridBlock, _CachedOp, normalize_signature

    if not is_enabled() or not isinstance(block, HybridBlock):
        return 0
    cache = get_cache()
    se = _serialize_api()
    if cache is None or se is None:
        return 0
    sig = block_signature(block)
    if sig is None:
        return 0
    try:
        candidates = cache.entries_for_block(sig)
    except Exception:
        return 0

    try:
        env_fp = cache.env_fingerprint()
    except Exception:
        return 0
    wanted = None
    if signatures is not None:
        # normalization errors raise: a malformed filter silently
        # matching nothing would read as "cache empty", not "bad arg"
        wanted = {tuple((tuple(shape), str(dt))
                        for shape, dt in normalize_signature(want_sig,
                                                             dtype))
                  for want_sig in signatures}
    installed = 0
    t0 = time.perf_counter()
    for fp, meta in candidates:
        if not meta.get("portable", False) or meta.get("in_spec") is None:
            continue
        avals = meta.get("avals")
        if wanted is not None:
            # entries committed before avals landed in META can't be
            # scoped cheaply; installing them keeps the old behavior
            if avals is not None and tuple(
                    (tuple(a[0]), a[1]) for a in avals) not in wanted:
                continue
        if avals is not None:
            # dedup BEFORE the expensive load: re-warming an
            # already-warm block must not re-pay disk read + unpickle +
            # executable device-load per entry just to discard it at
            # the key check below (kwargs-carrying entries slip past
            # this cheap pre-filter and are still caught there)
            try:
                _k, existing = block.find_cached_entry(
                    [(tuple(a[0]), a[1]) for a in avals],
                    training=bool(meta.get("training", False)))
            except Exception:
                existing = None
            if existing is not None:
                continue
        if meta.get("env_fingerprint") != env_fp:
            # built under different platform/topology/versions/XLA
            # flags: the executable may deserialize fine here yet
            # compute something else — a clean miss, never a wrong
            # artifact (the live path bakes this into the full
            # fingerprint; warm_start never re-lowers, so it checks
            # the environment half explicitly)
            continue
        try:
            loaded = cache.load(fp)
        except Exception:
            loaded = None
        if loaded is None:
            continue
        raw, _ = loaded
        try:
            cfn, key = _deserialize(se, raw)
        except Exception:
            cache.quarantine(fp, reason="artifact undeserializable")
            continue
        if key in block._cached_ops:
            continue
        try:
            centry = _CachedOp()
            centry.cfn = cfn
            centry.fingerprint = fp
            centry.provenance = "cache"
            centry.out_spec = meta["out_spec"]
            centry.in_spec = meta["in_spec"]
            # rebuild the traceable fallback lazily from the key alone:
            # portable entries have only NDArray flat inputs, so the
            # static-input placeholder list is all-None
            training, kw_items = HybridBlock.cachedop_key_call(key)
            static_inputs = [None] * int(meta["n_flat_inputs"])
            import jax

            centry.jfn = jax.jit(block._make_pure_fn(
                static_inputs, meta["in_spec"], training,
                dict(kw_items), centry))
            if verify and not _verify_entry(block, cache, centry, key,
                                            fp):
                continue
            if not block._active:
                block.hybridize(True, clear=False)
            block._cached_ops[key] = centry
            installed += 1
            if telemetry.ENABLED:
                telemetry.COMPILE_CACHE_HIT.inc()
        except Exception:
            _LOGGER.debug("warm_start skipped entry %s", fp[:12],
                          exc_info=True)
            continue
    if installed:
        _LOGGER.info("warm_start: installed %d cached signature(s) for "
                     "%s in %.3fs", installed, type(block).__name__,
                     time.perf_counter() - t0)
    return installed


def _verify_entry(block, cache, centry, key, fp):
    """Re-lower the rebuilt pure function and compare StableHLO
    fingerprints (the verify=True slow path of warm_start).  Params and
    inputs must be REAL device arrays, exactly as attach_from_cache
    lowered them: committed arrays carry mhlo.sharding annotations in
    the StableHLO text that shape-only avals lack, and a spurious text
    diff here would reject every valid entry — so inputs are lowered
    from zero-filled framework NDArrays (the warm_up discipline)."""
    try:
        import jax

        from .. import ndarray as _nd

        inputs = [_nd.zeros(tuple(shape), dtype=dt)._data
                  for shape, dt in _key_avals(key)]
        params = [p._data._data
                  for p in block.collect_params().values()]
        rng0 = jax.random.PRNGKey(0)
        lowered = centry.jfn.lower(params, rng0, *inputs)
        return cache.fingerprint(lowered.as_text()) == fp
    except Exception:
        return False
