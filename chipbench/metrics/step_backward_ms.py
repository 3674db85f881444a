"""Device time a step of the operations whose ``op_name`` is under
``transpose(jvp(mx.step.forward))``, on the busiest chip.  Device clock
only."""
import spans  # chipbench/spans.py: run.py's own directory is on sys.path


def read(ctx):
    return spans.read_metric("step_backward_ms")
