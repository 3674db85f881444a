"""Device time a step under the scope ``mx.moe.experts`` (the grouped
products of the held experts and the activation between them), forward,
recomputation and backward, on the busiest chip.  Device clock only."""
import block_readers  # chipbench/block_readers.py


def read(ctx):
    return block_readers.scope_ms("mx.moe.experts")
