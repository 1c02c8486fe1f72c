"""Device time under the scopes ``bsa_compress`` (the step's group sums,
the gather of the rows' compressed keys, their append), ``bsa_score``
(the block scores) and ``bsa_select`` (the top-k) per whole execution of
the serving step, mean over the traced window: what choosing the pages
costs, beside reading them."""
from benchmarks.harness import sala_spans


def read(run):
    return sala_spans.ms_per_step(run, sala_spans.SELECT)
