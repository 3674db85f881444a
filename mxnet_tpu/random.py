"""Random state management.

Reference: per-device ``RandGenerator`` (include/mxnet/random_generator.h —
Philox on GPU, per-thread mt19937 on CPU) seeded via ``mx.random.seed``.

TPU-native redesign: XLA's *stateless* threefry PRNG.  A module-level key is
split on every imperative draw (same user-facing contract: global seed,
reproducible streams).  Inside a hybridized trace, draws fold a step counter
into a traced base key, so the compiled computation takes one fresh key per
call — randomness stays inside the fused XLA program instead of a host RNG.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from .base import MXNetError, _as_np_dtype

__all__ = ["seed", "take_key", "uniform", "normal", "randn", "randint",
           "gamma", "exponential", "poisson", "multinomial", "bernoulli",
           "shuffle", "trace_rng", "KeyLog", "logged_keys", "laplace",
           "pareto", "weibull", "rayleigh", "gumbel", "logistic", "choice",
           "categorical"]

# Key is created lazily: jax.random.PRNGKey executes a device computation,
# and module scope here runs during `import mxnet_tpu` — a backend touch at
# import time would claim the chip in every process that imports us.
_state = {"key": None, "seed": 0}
_trace_stack = []


class _TraceRNG:
    __slots__ = ("base_key", "counter")

    def __init__(self, base_key):
        self.base_key = base_key
        self.counter = 0


class KeyLog:
    """Per-recorded-op key journal (ADVICE r3: create_graph replay).

    The first execution of a recorded op's forward (inside invoke's
    jax.vjp) RECORDS every key it draws; any re-execution of the same
    forward — the create_graph backward rebuilds the vjp by replaying the
    stored fn — gets the SAME keys back in draw order, so stochastic ops
    (Dropout, rrelu) use the mask the real forward used.  This is the eager
    counterpart of gluon/block.py pinning ``_rng`` for hybridized blocks.
    """

    __slots__ = ("keys", "finalized", "pos")

    def __init__(self):
        self.keys = []
        self.finalized = False
        self.pos = 0


_keylog_stack = []


@contextlib.contextmanager
def logged_keys(log):
    """Route take_key() through ``log``: record on first entry, replay after."""
    _keylog_stack.append(log)
    log.pos = 0
    try:
        yield
    finally:
        _keylog_stack.pop()
        log.finalized = True


class trace_rng:
    """Context: route key draws through a traced base key (hybridize path)."""

    def __init__(self, base_key):
        self._rng = _TraceRNG(base_key)

    def __enter__(self):
        _trace_stack.append(self._rng)
        return self._rng

    def __exit__(self, *a):
        _trace_stack.pop()


def seed(seed_state, ctx="all"):
    """Set the global seed (reference python/mxnet/random.py)."""
    _state["seed"] = int(seed_state)
    _state["key"] = jax.random.PRNGKey(int(seed_state))


def take_key():
    if _trace_stack:
        # hybridize trace: keys are traced values derived from the program's
        # base-key argument; replay identity is the compiled program's job
        rng = _trace_stack[-1]
        rng.counter += 1
        return jax.random.fold_in(rng.base_key, rng.counter)
    if _keylog_stack:
        log = _keylog_stack[-1]
        if log.finalized:  # replay: hand back the recorded stream
            if log.pos >= len(log.keys):
                raise MXNetError(
                    "RNG replay mismatch: recorded op drew %d key(s) at "
                    "record time but its replayed forward asked for more "
                    "— the op's control flow must not depend on state that "
                    "changed since recording" % len(log.keys))
            key = log.keys[log.pos]
            log.pos += 1
            return key
        key = _fresh_key()
        log.keys.append(key)
        return key
    return _fresh_key()


def _fresh_key():
    if _state["key"] is None:
        _state["key"] = jax.random.PRNGKey(_state["seed"])
    _state["key"], sub = jax.random.split(_state["key"])
    return sub


def _shape(shape):
    if shape is None:
        return ()
    if isinstance(shape, int):
        return (shape,)
    return tuple(shape)


def _wrap(data, ctx=None, out=None):
    from .ndarray.ndarray import NDArray

    if out is not None:
        out._data = data
        return out
    return NDArray(data, ctx=ctx)


def uniform(low=0.0, high=1.0, shape=None, dtype="float32", ctx=None,
            out=None, **kw):
    dt = _as_np_dtype(dtype)
    data = jax.random.uniform(take_key(), _shape(shape), dtype=dt,
                              minval=low, maxval=high)
    return _wrap(data, ctx, out)


def normal(loc=0.0, scale=1.0, shape=None, dtype="float32", ctx=None,
           out=None, **kw):
    dt = _as_np_dtype(dtype)
    data = jax.random.normal(take_key(), _shape(shape), dtype=dt) * scale + loc
    return _wrap(data, ctx, out)


def randn(*shape, loc=0.0, scale=1.0, dtype="float32", ctx=None):
    return normal(loc, scale, shape, dtype=dtype, ctx=ctx)


def randint(low, high=None, shape=(1,), dtype="int32", ctx=None, out=None):
    if high is None:
        low, high = 0, low
    data = jax.random.randint(take_key(), _shape(shape), low, high,
                              dtype=_as_np_dtype(dtype))
    return _wrap(data, ctx, out)


def gamma(alpha=1.0, beta=1.0, shape=None, dtype="float32", ctx=None,
          out=None):
    from .ndarray.ndarray import NDArray

    a = alpha._data if isinstance(alpha, NDArray) else alpha
    b = beta._data if isinstance(beta, NDArray) else beta
    data = jax.random.gamma(take_key(), a, _shape(shape),
                            dtype=_as_np_dtype(dtype)) * b
    return _wrap(data, ctx, out)


def exponential(scale=1.0, shape=None, dtype="float32", ctx=None, out=None):
    data = jax.random.exponential(take_key(), _shape(shape),
                                  dtype=_as_np_dtype(dtype)) * scale
    return _wrap(data, ctx, out)


def poisson(lam=1.0, shape=None, dtype="float32", ctx=None, out=None):
    data = jax.random.poisson(take_key(), lam, _shape(shape)).astype(
        _as_np_dtype(dtype))
    return _wrap(data, ctx, out)


def multinomial(data, shape=None, get_prob=False, dtype="int32", **kw):
    """Sample category indices from (batched) probability rows."""
    from .ndarray.ndarray import NDArray

    p = data._data if isinstance(data, NDArray) else data
    n = 1 if shape is None else shape
    logits = jnp.log(jnp.maximum(p, 1e-37))
    if p.ndim == 1:
        out_shape = _shape(n) if shape is not None else ()
        idx = jax.random.categorical(take_key(), logits, shape=out_shape)
    else:
        out_shape = (p.shape[0],) + (_shape(n) if shape is not None else ())
        idx = jax.random.categorical(take_key(), logits[:, None, :] if shape
                                     is not None else logits, axis=-1,
                                     shape=out_shape)
    return _wrap(idx.astype(_as_np_dtype(dtype)))


def bernoulli(prob=0.5, shape=None, dtype="float32", ctx=None, out=None):
    data = jax.random.bernoulli(take_key(), prob, _shape(shape)).astype(
        _as_np_dtype(dtype))
    return _wrap(data, ctx, out)


def shuffle(data, **kw):
    from .ndarray.ndarray import NDArray

    x = data._data if isinstance(data, NDArray) else data
    return _wrap(jax.random.permutation(take_key(), x, axis=0))


# ---- distribution tail (reference np_random ops: _npi_laplace/_npi_pareto/
# _npi_weibull/_npi_rayleigh/_npi_gumbel/_npi_logistic/_npi_choice) --------
def laplace(loc=0.0, scale=1.0, shape=None, dtype="float32", ctx=None,
            out=None):
    data = jax.random.laplace(take_key(), _shape(shape),
                              dtype=_as_np_dtype(dtype)) * scale + loc
    return _wrap(data, ctx, out)


def pareto(a=1.0, shape=None, dtype="float32", ctx=None, out=None):
    """Lomax-style pareto (np.random.pareto: (1-U)^{-1/a} - 1)."""
    u = jax.random.uniform(take_key(), _shape(shape),
                           dtype=_as_np_dtype(dtype))
    return _wrap(jnp.expm1(-jnp.log1p(-u) / a), ctx, out)


def weibull(a=1.0, shape=None, dtype="float32", ctx=None, out=None):
    u = jax.random.uniform(take_key(), _shape(shape),
                           dtype=_as_np_dtype(dtype))
    return _wrap(jnp.power(-jnp.log1p(-u), 1.0 / a), ctx, out)


def rayleigh(scale=1.0, shape=None, dtype="float32", ctx=None, out=None):
    u = jax.random.uniform(take_key(), _shape(shape),
                           dtype=_as_np_dtype(dtype))
    return _wrap(scale * jnp.sqrt(-2.0 * jnp.log1p(-u)), ctx, out)


def gumbel(loc=0.0, scale=1.0, shape=None, dtype="float32", ctx=None,
           out=None):
    data = jax.random.gumbel(take_key(), _shape(shape),
                             dtype=_as_np_dtype(dtype)) * scale + loc
    return _wrap(data, ctx, out)


def logistic(loc=0.0, scale=1.0, shape=None, dtype="float32", ctx=None,
             out=None):
    data = jax.random.logistic(take_key(), _shape(shape),
                               dtype=_as_np_dtype(dtype)) * scale + loc
    return _wrap(data, ctx, out)


def choice(a, size=None, replace=True, p=None, ctx=None, out=None):
    """np.random.choice (reference _npi_choice)."""
    from .ndarray.ndarray import NDArray

    arr = a._data if isinstance(a, NDArray) else a
    if isinstance(arr, int):
        arr = jnp.arange(arr)
    pv = p._data if isinstance(p, NDArray) else p
    data = jax.random.choice(take_key(), arr, _shape(size),
                             replace=replace, p=pv)
    return _wrap(data, ctx, out)


def categorical(logits, shape=None, ctx=None, out=None):
    """npx.random.categorical (reference _npx__random_categorical)."""
    from .ndarray.ndarray import NDArray

    lg = logits._data if isinstance(logits, NDArray) else logits
    out_shape = None if shape is None else _shape(shape)
    data = jax.random.categorical(take_key(), lg, axis=-1, shape=out_shape)
    return _wrap(data, ctx, out)
