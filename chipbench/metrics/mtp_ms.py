"""Device time a step under the scope ``mx.mtp`` (the multi-token prediction
module: its two norms, the product with ``W_eh``, its decoder layer, its
final norm and its product with the shared head), forward, recomputation and
backward, on the busiest chip.  Device clock only."""
import block_readers  # chipbench/block_readers.py


def read(ctx):
    return block_readers.scope_ms("mx.mtp")
