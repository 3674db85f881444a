"""Device time a step under the scope ``mx.attn.window`` (the sliding layers'
kernels, rotary, gate and head-major layout work), forward, recomputation and
backward, on the busiest chip.  Device clock only."""
import block_readers  # chipbench/block_readers.py


def read(ctx):
    return block_readers.scope_ms("mx.attn.window")
