"""NDArray: the imperative tensor handle.

Reference: ``class NDArray`` include/mxnet/ndarray.h:82 — shape/dtype/context
plus a shared Chunk holding a Storage::Handle and an engine var; lazy alloc;
WaitToRead/WaitToWrite; autograd_entry_ linking into the recorded graph.

TPU-native redesign: the storage chunk *is* a ``jax.Array`` (PJRT buffer in
HBM).  The engine var is the buffer's future: JAX dispatch is already async,
so every op returns immediately and ``wait_to_read`` maps to
``block_until_ready`` — the same contract as Engine::WaitForVar
(src/engine/threaded_engine.cc:379) with zero scheduler code.  Exceptions
raised by deferred computations surface at sync points exactly like the
reference's ExceptionRef path (threaded_engine.h:64).
"""
from __future__ import annotations

import numpy as _np

from .. import telemetry as _tel
from ..base import MXNetError, _as_np_dtype, integer_types, numeric_types
from ..context import Context, cpu, current_context
from ..trace.core import span as _span

__all__ = ["NDArray", "waitall", "from_jax", "concatenate"]


def _jnp():
    import jax.numpy as jnp

    return jnp


def _ctx_of(data):
    try:
        dev = list(data.devices())[0]
    except Exception:  # tracer or uncommitted
        return current_context()
    if dev.platform == "cpu":
        return Context("cpu", dev.id)
    return Context("tpu", dev.id)


class NDArray:
    """An n-dimensional array on a device, with async semantics and autograd
    hooks.  Wraps exactly one ``jax.Array`` (or tracer, during hybridize)."""

    __slots__ = ("_data", "_grad", "_grad_req", "_entry", "_marked",
                 "__weakref__")
    # numpy interop priority
    __array_priority__ = 100.0

    def __init__(self, data, ctx=None):
        if isinstance(data, NDArray):
            data = data._data
        if ctx is not None:
            import jax

            data = jax.device_put(data, ctx.jax_device)
        self._data = data
        self._grad = None
        self._grad_req = "null"
        self._entry = None
        self._marked = False

    @classmethod
    def _from_np(cls, arr, ctx=None):
        """Wrap a host numpy array (device transfer deferred to jnp)."""
        import jax.numpy as jnp

        if _tel.ENABLED and isinstance(arr, _np.ndarray):
            _tel.TRANSFER_H2D.inc(arr.nbytes)
        return cls(jnp.asarray(arr), ctx=ctx)

    # ---- basic properties -------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return _np.dtype(self._data.dtype)

    @property
    def size(self):
        sz = 1
        for s in self.shape:
            sz *= s
        return sz

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def context(self):
        return _ctx_of(self._data)

    ctx = context

    @property
    def device(self):
        return self.context

    @property
    def stype(self):
        return "default"

    @property
    def T(self):
        from . import transpose

        return transpose(self)

    @property
    def grad(self):
        return self._grad

    # ---- sync / transfer --------------------------------------------------
    def _waiting(self):
        """The ``mx.wait`` span: the host blocked on the device, in the
        flight record of a hang and in any profiler trace."""
        return _span("mx.wait", hist=False,
                     args={"nbytes": self._data.nbytes})

    def wait_to_read(self):
        """Block until pending computation lands (Engine::WaitForVar)."""
        with self._waiting():
            self._data.block_until_ready()

    wait_to_write = wait_to_read

    def asnumpy(self):
        import jax

        with self._waiting():
            arr = _np.asarray(jax.device_get(self._data))
        if _tel.ENABLED:
            _tel.TRANSFER_D2H.inc(arr.nbytes)
        return arr

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(()).item()

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 0:
            return False
        if self.size == 1:
            return bool(self.asscalar())
        raise MXNetError("ambiguous truth value of multi-element NDArray")

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def tolist(self):
        return self.asnumpy().tolist()

    def as_in_context(self, ctx):
        if ctx == self.context:
            return self
        return NDArray(self._data, ctx=ctx)

    as_in_ctx = as_in_context
    as_nd_ndarray = lambda self: self
    as_np_ndarray = lambda self: self

    def to_device(self, ctx):
        return self.as_in_context(ctx)

    def copyto(self, other):
        if isinstance(other, Context):
            return NDArray(self._data, ctx=other)
        if isinstance(other, NDArray):
            if other.shape != self.shape:
                raise MXNetError("copyto shape mismatch %s vs %s"
                                 % (self.shape, other.shape))
            other._data = _jnp().asarray(self._data, dtype=other.dtype)
            if other.context != self.context:
                import jax

                other._data = jax.device_put(other._data,
                                             other.context.jax_device)
            return other
        raise TypeError("copyto: unsupported target %r" % (other,))

    def copy(self):
        return NDArray(self._data + 0 if self.dtype != _np.bool_
                       else self._data)

    def astype(self, dtype, copy=True):
        np_dtype = _as_np_dtype(dtype)
        if not copy and self.dtype == np_dtype:
            return self
        from ..ops.registry import apply_op

        return apply_op(lambda x: _jnp().asarray(x, dtype=np_dtype), self)

    def detach(self):
        out = NDArray(self._data)
        return out

    # ---- autograd ---------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        """Allocate a gradient buffer (reference ndarray.py attach_grad)."""
        self._grad = NDArray(_jnp().zeros(self.shape, self.dtype))
        self._grad_req = grad_req
        self._marked = grad_req != "null"
        self._entry = None

    def zero_grad(self):
        if self._grad is not None:
            self._grad._data = _jnp().zeros(self.shape, self.dtype)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from .. import autograd

        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    # ---- indexing ---------------------------------------------------------
    def __getitem__(self, key):
        from ..ops.registry import apply_op

        key = _clean_key(key)

        def _slice(x):
            return x[key]

        _slice.__name__ = "getitem"
        return apply_op(_slice, self)

    def __setitem__(self, key, value):
        from ..base import thread_state

        if thread_state.is_recording and (self._marked or self._entry):
            raise MXNetError("in-place write to an array on the autograd tape "
                             "inside record() is not supported; use pause()")
        key = _clean_key(key)
        if isinstance(value, NDArray):
            value = value._data
        self._data = self._data.at[key].set(value)

    def slice(self, begin, end, step=None):
        key = tuple(slice(b, e, s) for b, e, s in
                    zip(begin, end, step or [None] * len(begin)))
        return self[key]

    def take(self, indices, axis=0):
        from . import take

        return take(self, indices, axis=axis)

    # ---- shape manipulation ----------------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        shape = kwargs.get("shape", shape)
        from ..ops.registry import apply_op

        size = self.size
        # reference reshape specials: -1 infer, 0 copy-dim (ndarray.py)
        out_shape = []
        for i, s in enumerate(shape):
            if s == 0:
                out_shape.append(self.shape[i])
            else:
                out_shape.append(int(s))
        def _reshape(x):
            return x.reshape(tuple(out_shape))
        _reshape.__name__ = "reshape"
        return apply_op(_reshape, self)

    def reshape_like(self, other):
        return self.reshape(other.shape)

    def expand_dims(self, axis):
        from . import expand_dims

        return expand_dims(self, axis=axis)

    def squeeze(self, axis=None):
        from . import squeeze

        return squeeze(self, axis=axis)

    def flatten(self):
        return self.reshape((self.shape[0], -1)) if self.ndim > 1 else self

    def transpose(self, *axes):
        from . import transpose

        if len(axes) == 1 and isinstance(axes[0], (list, tuple)):
            axes = tuple(axes[0])
        return transpose(self, axes=axes if axes else None)

    def swapaxes(self, dim1, dim2):
        from . import swapaxes

        return swapaxes(self, dim1, dim2)

    def broadcast_to(self, shape):
        from . import broadcast_to

        return broadcast_to(self, shape=shape)

    def broadcast_like(self, other):
        return self.broadcast_to(other.shape)

    def tile(self, reps):
        from . import tile

        return tile(self, reps=reps)

    def repeat(self, repeats, axis=None):
        from . import repeat

        return repeat(self, repeats=repeats, axis=axis)

    def pad(self, pad_width, mode="constant", constant_value=0):
        from . import pad

        return pad(self, pad_width, mode=mode, constant_value=constant_value)

    def split(self, num_outputs, axis=0):
        from . import split

        return split(self, num_outputs=num_outputs, axis=axis)

    # ---- reductions / math methods ---------------------------------------
    def _reduce(self, name, axis=None, keepdims=False):
        from .. import ndarray as nd

        return getattr(nd, name)(self, axis=axis, keepdims=keepdims)

    def sum(self, axis=None, keepdims=False, **kw):
        return self._reduce("sum", axis, keepdims)

    def mean(self, axis=None, keepdims=False, **kw):
        return self._reduce("mean", axis, keepdims)

    def max(self, axis=None, keepdims=False, **kw):
        return self._reduce("max", axis, keepdims)

    def min(self, axis=None, keepdims=False, **kw):
        return self._reduce("min", axis, keepdims)

    def prod(self, axis=None, keepdims=False, **kw):
        return self._reduce("prod", axis, keepdims)

    def norm(self, ord=2, axis=None, keepdims=False):
        from . import norm

        return norm(self, ord=ord, axis=axis, keepdims=keepdims)

    def argmax(self, axis=None, keepdims=False):
        from . import argmax

        return argmax(self, axis=axis)

    def argmin(self, axis=None, keepdims=False):
        from . import argmin

        return argmin(self, axis=axis)

    def clip(self, a_min=None, a_max=None):
        from . import clip

        return clip(self, a_min, a_max)

    def abs(self):
        from . import abs as _abs

        return _abs(self)

    def sqrt(self):
        from . import sqrt

        return sqrt(self)

    def exp(self):
        from . import exp

        return exp(self)

    def log(self):
        from . import log

        return log(self)

    def sigmoid(self):
        from . import sigmoid

        return sigmoid(self)

    def relu(self):
        from . import relu

        return relu(self)

    def tanh(self):
        from . import tanh

        return tanh(self)

    def softmax(self, axis=-1):
        from . import softmax

        return softmax(self, axis=axis)

    def log_softmax(self, axis=-1):
        from . import log_softmax

        return log_softmax(self, axis=axis)

    def round(self):
        from . import round as _round

        return _round(self)

    def floor(self):
        from . import floor

        return floor(self)

    def ceil(self):
        from . import ceil

        return ceil(self)

    def sign(self):
        from . import sign

        return sign(self)

    def square(self):
        from . import square

        return square(self)

    def expm1(self):
        from . import expm1

        return expm1(self)

    def log1p(self):
        from . import log1p

        return log1p(self)

    def dot(self, other):
        from . import dot

        return dot(self, other)

    def topk(self, k=1, axis=-1, ret_typ="indices", is_ascend=False):
        from . import topk

        return topk(self, k=k, axis=axis, ret_typ=ret_typ,
                    is_ascend=is_ascend)

    def sort(self, axis=-1, is_ascend=True):
        from . import sort

        return sort(self, axis=axis, is_ascend=is_ascend)

    def argsort(self, axis=-1, is_ascend=True):
        from . import argsort

        return argsort(self, axis=axis, is_ascend=is_ascend)

    def one_hot(self, depth, on_value=1.0, off_value=0.0):
        from . import one_hot

        return one_hot(self, depth, on_value=on_value, off_value=off_value)

    def tostype(self, stype):
        if stype != "default":
            raise MXNetError("sparse storage handled by mxnet_tpu.ndarray."
                             "sparse wrappers")
        return self

    # ---- operators --------------------------------------------------------
    def _binop(self, other, name, reverse=False):
        from .. import ndarray as nd

        fn = getattr(nd, name)
        if reverse:
            return fn(other, self)
        return fn(self, other)

    def __add__(self, o):
        return self._binop(o, "add")

    def __radd__(self, o):
        return self._binop(o, "add", True)

    def __iadd__(self, o):
        return self._binop(o, "add")

    def __sub__(self, o):
        return self._binop(o, "subtract")

    def __rsub__(self, o):
        return self._binop(o, "subtract", True)

    def __isub__(self, o):
        return self._binop(o, "subtract")

    def __mul__(self, o):
        return self._binop(o, "multiply")

    def __rmul__(self, o):
        return self._binop(o, "multiply", True)

    def __imul__(self, o):
        return self._binop(o, "multiply")

    def __truediv__(self, o):
        return self._binop(o, "divide")

    def __rtruediv__(self, o):
        return self._binop(o, "divide", True)

    def __itruediv__(self, o):
        return self._binop(o, "divide")

    def __floordiv__(self, o):
        return self._binop(o, "floor_divide")

    def __rfloordiv__(self, o):
        return self._binop(o, "floor_divide", True)

    def __mod__(self, o):
        return self._binop(o, "mod")

    def __rmod__(self, o):
        return self._binop(o, "mod", True)

    def __pow__(self, o):
        return self._binop(o, "power")

    def __rpow__(self, o):
        return self._binop(o, "power", True)

    def __matmul__(self, o):
        from . import dot

        return dot(self, o)

    def __neg__(self):
        return self._binop(-1, "multiply")

    def __abs__(self):
        return self.abs()

    def __eq__(self, o):
        return self._binop(o, "equal")

    def __ne__(self, o):
        return self._binop(o, "not_equal")

    def __lt__(self, o):
        return self._binop(o, "lesser")

    def __le__(self, o):
        return self._binop(o, "lesser_equal")

    def __gt__(self, o):
        return self._binop(o, "greater")

    def __ge__(self, o):
        return self._binop(o, "greater_equal")

    def __hash__(self):
        return id(self)

    def __repr__(self):
        try:
            arr = self.asnumpy()
            return "%s\n<NDArray %s @%s>" % (
                str(arr), "x".join(map(str, self.shape)), self.context)
        except Exception:
            return "<NDArray %s (pending/traced)>" % (
                "x".join(map(str, self.shape)),)

    # numpy interop
    def __array__(self, dtype=None):
        arr = self.asnumpy()
        return arr.astype(dtype) if dtype is not None else arr

    # NEP-18/NEP-13 dispatch (reference numpy/multiarray.py:367 +
    # numpy_dispatch_protocol.py): numpy API calls on NDArray operands
    # route through mx.np — so np.mean(mx_arr) stays on-device and on the
    # autograd tape instead of silently densifying to host numpy
    def __array_function__(self, func, types, args, kwargs):
        from .. import numpy as _mxnp

        target = _mxnp
        mod = getattr(func, "__module__", "") or ""
        for part in mod.split(".")[1:]:  # e.g. numpy.linalg -> .linalg
            target = getattr(target, part, None)
            if target is None:
                return NotImplemented
        f = getattr(target, func.__name__, None)
        if f is None:
            return NotImplemented
        return f(*args, **kwargs)

    def __array_ufunc__(self, ufunc, method, *inputs, **kwargs):
        if method != "__call__" or kwargs.get("out") is not None:
            return NotImplemented
        from .. import numpy as _mxnp

        f = getattr(_mxnp, ufunc.__name__, None)
        if f is None:
            return NotImplemented
        return f(*inputs, **kwargs)

    def __dlpack__(self, stream=None):
        return self._data.__dlpack__(stream=stream)

    def __dlpack_device__(self):
        return self._data.__dlpack_device__()


def _clean_key(key):
    if isinstance(key, NDArray):
        return key._data
    if isinstance(key, tuple):
        return tuple(k._data if isinstance(k, NDArray) else k for k in key)
    return key


def waitall():
    """Block on every pending computation (reference ndarray.py:231 waitall →
    Engine::WaitForAll).

    Guarantee: PJRT executes programs in enqueue order per device, so a
    freshly enqueued trivial program on EACH local device is ready only
    after everything enqueued before it on that device — the same fence
    Engine::WaitForAll provided."""
    import jax

    jax.effects_barrier()
    for d in jax.local_devices():
        # the +0 matters: a bare transfer is not ordered after enqueued
        # programs, but an enqueued trivial PROGRAM is
        (jax.device_put(0, d) + 0).block_until_ready()


def from_jax(x):
    return NDArray(x)


def concatenate(arrays, axis=0):
    from . import concat

    return concat(*arrays, dim=axis)
