"""Median over the traced steps of (start of ``mx.step.dispatch`` - start of
``mx.step``): what ``FusedTrainer.step`` does on the host before it calls the
step program (stage, rng, scalars).  Host clock only."""
import spans  # chipbench/spans.py: run.py's own directory is on sys.path


def read(ctx):
    return spans.read_metric("step_pre_dispatch_ms")
