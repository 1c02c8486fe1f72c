"""Device time of the operations under the scope ``weight_slice`` (each
layer's slice taken out of the stacked weights) per whole execution of
the serving step, mean over the traced window."""
from benchmarks.harness import program_spans


def read(run):
    return program_spans.kernel_ms_per_step(
        run, program_spans.SERVE_MODULE, ["weight_slice"])
