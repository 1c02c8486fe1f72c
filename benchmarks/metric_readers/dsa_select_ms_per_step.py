"""Device time under the scope ``dsa_select`` (the top-k of each query's
index scores and the list or masks the attention takes) per whole
execution of the serving step, mean over the traced window."""
from benchmarks.harness import dsa_spans


def read(run):
    return dsa_spans.ms_per_step(run, dsa_spans.SELECT)
