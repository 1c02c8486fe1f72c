"""Seconds of set-up tracing and lowering: the ``trace`` and ``lower``
stages of every jitted function of every process before the window, as
the program's compile watch saw them (an instant under a nested trace
counted once; stages under 50 ms are not events and stay under the span
they ran in)."""
from benchmarks.harness import startup


def read(run):
    return startup.class_seconds(run, "trace_lower")
