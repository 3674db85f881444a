"""mx.monitor — on-device training-health numerics.

The fourth observability layer (README "Training health"): telemetry
says how *fast*, trace says *where the time went*; monitor says whether
the numbers are still *healthy* — per-parameter-group gradient/weight
norms, max|x| and nonfinite counts, computed by ONE fused jitted
reduction program per multi-tensor group (zero hot-path retraces,
stats read the same buffers the update donates), fetched to the host
asynchronously, and acted on:

- **nonfinite sentinel** (``MXNET_MONITOR_SENTINEL=warn|skip_step|
  raise``): a step with NaN/Inf gradients is warned about, skipped
  whole (bit-identical to never calling ``step()`` — Adam bias
  correction never advances), or raised on.
- **divergence detector**: grad-norm spikes vs a trailing window,
  loss plateau/NaN — each fires one rate-limited flight-record +
  chrome-trace dump (reason ``divergence``) naming the offending
  group, through the mx.trace anomaly path.
- **exports**: telemetry gauges/histograms (``monitor_*``), an
  optional per-step JSONL stream (``MXNET_MONITOR_STREAM=<path>``),
  bench-row health columns, ``tools/diagnose.py --monitor``.

Off by default; arm with ``MXNET_MONITOR=1`` (and see the README's
"Training health" section for the recipe).  This is the
MXNet ``mx.monitor.Monitor`` capability rebuilt TPU-native: per-layer
stat inspection without per-layer eager readbacks.
"""
from __future__ import annotations

from . import core, divergence, sentinel, stats
from .core import (disable, enable, flush, group_values, is_enabled,
                   observe_update, reset, stream_path, summary)
from .divergence import DETECTOR, DivergenceDetector, observe_loss

__all__ = [
    "enable", "disable", "is_enabled",
    "observe_update", "observe_loss",
    "flush", "summary", "group_values", "reset", "stream_path",
    "DETECTOR", "DivergenceDetector",
    "core", "divergence", "sentinel", "stats",
]


def __getattr__(name):
    # monitor.ENABLED mirrors core.ENABLED (a mutable module flag —
    # re-exporting the value at import would freeze it)
    if name == "ENABLED":
        return core.ENABLED
    raise AttributeError("module %r has no attribute %r"
                         % (__name__, name))
