"""Device time under the scope ``hc_mix`` (the hyper-connection's three
projections, Sinkhorn and the two mixes of every sub-layer) over the busy
time of the serving step's whole executions in the traced window."""
from benchmarks.harness import xing_spans


def read(run):
    return xing_spans.time_share(run, xing_spans.HC)
