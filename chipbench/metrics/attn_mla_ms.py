"""Device time a step under the scope ``mx.attn.mla`` (the latent block of
all six layers between its input and the output projection's: both
down-projections, the latent norms, the up-projections, rotary, the key's
concatenation, the kernels and the layout work around them), forward,
recomputation and backward, on the busiest chip.  Device clock only."""
import block_readers  # chipbench/block_readers.py


def read(ctx):
    return block_readers.scope_ms("mx.attn.mla")
