"""Device time in the kernel ``ragged_kv_append`` per whole execution of
the serving step, mean over the traced window."""
from benchmarks.harness import program_spans


def read(run):
    return program_spans.kernel_ms_per_step(
        run, program_spans.SERVE_MODULE, ["ragged_kv_append"])
