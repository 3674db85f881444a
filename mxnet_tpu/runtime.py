"""Runtime feature introspection (reference src/libinfo.cc +
python/mxnet/runtime.py `features.is_enabled`)."""
from __future__ import annotations

__all__ = ["Features", "feature_list", "features"]


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return "%s %s" % ("✔" if self.enabled else "✖", self.name)


def _compile_cache_enabled():
    """mx.compile's persistent compilation cache: built in, but OFF
    unless switched on (env knobs or mxnet_tpu.compile.enable())."""
    try:
        from . import compile as _compile

        return _compile.is_enabled()
    except Exception:
        return False


def _monitor_enabled():
    """mx.monitor training-health numerics: built in, but OFF unless
    armed (MXNET_MONITOR=1 or mxnet_tpu.monitor.enable())."""
    try:
        from . import monitor as _monitor

        return _monitor.is_enabled()
    except Exception:
        return False


def _obs_enabled():
    """mx.obs fleet observability: built in, but OFF unless armed
    (MXNET_OBS=1 or mxnet_tpu.obs.enable())."""
    try:
        from . import obs as _obs

        return _obs.is_enabled()
    except Exception:
        return False


def _serve_cache_enabled():
    """mx.serve.cache radix prefix cache: built in, but OFF unless
    armed (MXNET_SERVE_PREFIX_CACHE=1 or DecodeConfig(
    prefix_cache=True)) — the env default is what this reports."""
    try:
        from .base import get_env

        return bool(get_env("MXNET_SERVE_PREFIX_CACHE", bool, False))
    except Exception:
        return False


def _tenant_enabled():
    """mx.tenant multi-tenant serving: built in, but OFF unless armed
    (MXNET_TENANT=1; the LoRA bank/WFQ plane is opt-in per server)."""
    try:
        from . import tenant as _tenant

        return _tenant.is_enabled()
    except Exception:
        return False


def _step_capture_enabled():
    """mx.step whole-program training-step capture: ON by default,
    killed by MXNET_STEP_CAPTURE=0 (re-read per access — the kill
    switch is checked per call)."""
    try:
        from . import step as _step

        return _step.is_enabled()
    except Exception:
        return False


class _DynamicFeature(Feature):
    """Feature whose enabled state is re-read on every access —
    COMPILE_CACHE toggles at runtime (compile.enable()/disable()), so
    baking it into the one-shot detection map would go stale."""

    def __init__(self, name, probe):
        self.name = name
        self._probe = probe

    @property
    def enabled(self):
        try:
            return bool(self._probe())
        except Exception:
            return False


def _detect():
    import jax

    devs = jax.devices()
    has_tpu = any(d.platform != "cpu" for d in devs)
    feats = {
        "TPU": has_tpu,
        "XLA": True,
        "PALLAS": has_tpu,
        "BF16": True,
        "CUDA": False,
        "CUDNN": False,
        "NCCL": False,
        "MKLDNN": False,
        "BLAS_OPEN": True,
        "DIST_KVSTORE": True,
        "INT64_TENSOR_SIZE": True,
        "SIGNAL_HANDLER": True,
        "PROFILER": True,
        "TELEMETRY": True,
        "TRACE": True,
        "CHECKPOINT": True,
        "SERVE": True,
        "FLEET": True,
        "DATA": True,
        "RESILIENCE": True,
        "OPENMP": True,
        "SSE": False,
        "F16C": False,
        "TENSORRT": False,
        "OPENCV": False,
    }
    out = {k: Feature(k, v) for k, v in feats.items()}
    out["COMPILE_CACHE"] = _DynamicFeature("COMPILE_CACHE",
                                           _compile_cache_enabled)
    out["MONITOR"] = _DynamicFeature("MONITOR", _monitor_enabled)
    out["STEP_CAPTURE"] = _DynamicFeature("STEP_CAPTURE",
                                          _step_capture_enabled)
    out["OBS"] = _DynamicFeature("OBS", _obs_enabled)
    out["SERVE_CACHE"] = _DynamicFeature("SERVE_CACHE",
                                         _serve_cache_enabled)
    out["TENANT"] = _DynamicFeature("TENANT", _tenant_enabled)
    return out


class Features(dict):
    """Fully-populated feature map (a plain dict subclass)."""

    def __init__(self):
        super().__init__(_detect())

    def is_enabled(self, name):
        feat = self.get(name)
        return bool(feat and feat.enabled)


_features = None


def _get_features():
    global _features
    if _features is None:
        _features = Features()
    return _features


def __getattr__(name):
    # PEP 562 single choke point: `runtime.features` triggers detection on
    # FIRST ACCESS, never at import — jax.devices() is a PJRT backend init,
    # and `import mxnet_tpu` must not claim the chip (a worker process
    # imports it too).  Because the attribute itself is materialized
    # lazily, every dict entry point (get/__contains__/iteration/…) sees a
    # fully-detected map; there is no partially-initialized state to leak.
    if name == "features":
        return _get_features()
    raise AttributeError("module %r has no attribute %r" % (__name__, name))


def feature_list():
    return list(_get_features().values())
