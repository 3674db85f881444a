"""Capture a jax.profiler trace of the headline models on the real chip.

Usage (on a machine with a chip):
    python benchmark/profile_tpu.py resnet_bf16 /tmp/trace
    python benchmark/profile_tpu.py bert /tmp/trace

The trace directory is TensorBoard-compatible; the summary printed at the
end (per-step wall time split into dispatch vs device) is the first-order
signal for MFU work (BASELINE.md >=45% target): big host gaps mean the
input/dispatch path is the bottleneck, long device steps mean kernel work.
"""
from __future__ import annotations

import sys
import time


def run(which="resnet_bf16", logdir="/tmp/mxtpu_trace", iters=10):
    sys.path.insert(0, ".")
    from mxnet_tpu.compile import jax_cache_dir

    jax_cache_dir()
    import jax

    import bench

    if which == "resnet_bf16":
        fn = lambda: bench._bench_resnet("bfloat16", 128, iters=iters)
    elif which == "resnet_fp32":
        fn = lambda: bench._bench_resnet("float32", 128, iters=iters)
    elif which == "bert":
        fn = lambda: bench._bench_bert(iters=iters)
    elif which == "lstm":
        fn = lambda: bench._bench_lstm_lm(iters=iters)
    else:
        raise SystemExit("unknown target %r" % which)

    # warm pass outside the trace so compiles don't drown the steps
    row = fn()
    print("warm:", row)
    with jax.profiler.trace(logdir):
        t0 = time.time()
        row = fn()
        wall = time.time() - t0
    print("traced:", row)
    print("trace at %s (load in TensorBoard: Profile plugin)" % logdir)
    print("wall for traced run: %.2fs" % wall)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("which", nargs="?", default="resnet_bf16",
                    choices=["resnet_bf16", "resnet_fp32", "bert", "lstm"])
    ap.add_argument("logdir", nargs="?", default="/tmp/mxtpu_trace")
    ap.add_argument("--iters", type=int, default=10)
    a = ap.parse_args()
    run(a.which, a.logdir, a.iters)
