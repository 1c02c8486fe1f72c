"""Device time under the scope ``dsa_index`` (the indexer's projections
and its scores of the step's queries against the rows' pooled index keys)
per whole execution of the serving step, mean over the traced window."""
from benchmarks.harness import dsa_spans


def read(run):
    return dsa_spans.ms_per_step(run, dsa_spans.INDEX)
