"""Device time a step under the scope ``mx.moe.route`` (router product,
softmax, top-k, sort, gathers into and out of expert order), forward,
recomputation and backward, on the busiest chip.  Device clock only."""
import block_readers  # chipbench/block_readers.py


def read(ctx):
    return block_readers.scope_ms("mx.moe.route")
