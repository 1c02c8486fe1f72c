"""Device time of the operations under the scope ``optimizer`` (the
update and its application) per whole execution of the train step, mean
over the traced window."""
from benchmarks.harness import program_spans


def read(run):
    return program_spans.kernel_ms_per_step(
        run, program_spans.TRAIN_MODULE, ["optimizer"])
