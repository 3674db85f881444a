"""Device time a step under the scope ``mx.moe.shared`` (the shared expert's
three products on every position), forward, recomputation and backward, on
the busiest chip.  Device clock only."""
import block_readers  # chipbench/block_readers.py


def read(ctx):
    return block_readers.scope_ms("mx.moe.shared")
