"""``setup_s`` less the ramp (the traffic's ``ramp_s`` as the run drew
it: ``harness/startup.ramp_of``) less the five classes
(import, runtime, weights, trace and lower, compile): the instants of
set-up that fell to no class.  The books (``notes.setup``) split it by
the span that does cover them (``serve.replica_init``'s self time: in
the benchmark the reference check; ``llm.first_step`` and
``train.first_step`` outside compile stages: the first executions) and
``dark``, under no span of any process."""
from benchmarks.harness import startup


def read(run):
    out = startup.books(run)
    return None if out is None else out["unnamed_s"]
