"""Share of the steps' wall time spent in the batch iterator."""
from benchmarks.harness import run_record


def read(run):
    took = run_record.steps_wall_s(run)
    if not took or "data_wait_s" not in run.notes:
        return None
    return 100.0 * run.notes["data_wait_s"] / took
