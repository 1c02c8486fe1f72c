"""Mean duration of the trainer's own ``train.data_wait`` span (the
iterator's ``next`` and the host-to-device copy of the batch) over the
steps of the traced window."""
from benchmarks.harness import program_spans, stats


def read(run):
    waits = program_spans.named(program_spans.lines_of(run),
                                "train.data_wait")
    return stats.mean(s[2] / 1e9 for s in waits)
