#!/usr/bin/env python
"""Tune every measurable mx.autotune site at TPU-relevant workload
keys and persist the winners (one on-chip search is ROADMAP D7).

Run with ``MXNET_AUTOTUNE=search`` and ``MXNET_AUTOTUNE_DIR`` pointed
at the capture output dir; afterwards
``MXNET_AUTOTUNE=1 python tools/diagnose.py --autotune`` prints the
winner table.  Every site degrades independently: one failed site
never loses the others' winners.
"""
from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    import jax

    from mxnet_tpu import autotune

    if not autotune.search_enabled():
        autotune.enable("search")
    on_tpu = jax.default_backend() == "tpu"
    dt = "bfloat16" if on_tpu else "float32"
    # BERT-base attention (T=512), ResNet-50 grads/conv/BN stage-2
    capture = [
        ("flash_attention", (1, 12, 512, 512, 64, dt, False)),
        ("flash_attention", (1, 12, 512, 512, 64, dt, True)),
        ("blockwise_attention", (1, 12, 512, 512, 64, dt, False)),
        ("allreduce_bucket", (161, 102 << 20, jax.process_count())),
        ("conv_layout", (128 if on_tpu else 32, 64, 56, 56, 64, 3, 3,
                         1, dt)),
        ("bn_stat_dtype", (128 if on_tpu else 32, 64, 56, 56, 1, dt)),
    ]
    failed = 0
    for site, key in capture:
        try:
            res = autotune.tune(site, key, budget_ms=120000)
            print(json.dumps(res.as_dict()))
        except Exception as exc:  # one dead site must not end the run
            failed += 1
            print(json.dumps({"site": site, "key": list(key),
                              "error": repr(exc)}))
    st = autotune.get_store()
    print("autotune-capture: %d record(s) in %s (%d site(s) failed)"
          % (len(st.records()) if st else 0,
             st.root if st else "(no store)", failed))
    return 1 if failed == len(capture) else 0


if __name__ == "__main__":
    sys.exit(main())
