"""Median duration of ``mx.step.dispatch``: the call of the jitted step until
it returns to the host.  Host clock only."""
import spans  # chipbench/spans.py: run.py's own directory is on sys.path


def read(ctx):
    return spans.read_metric("step_dispatch_ms")
