"""The CPU time the engine loop's thread used in the iterations
(``llm.loop``) that dispatched the traced window's joined steps, a step:
the mean of the spans' ``cpu_us``, ``time.thread_time()`` over the iteration.
A MEAN, where its neighbour ``sched_host_ms_per_step`` (the wall of those
iterations' working phases) is a median: the thread's CPU clock may tick in
steps far longer than an iteration (10 ms on the benchmark's machine, where
an iteration reads 0 or 10 ms), and only a sum over many iterations says
what they used.  Wall less CPU is what the thread spent not running: the
interpreter lock, the machine, a native call that blocks."""
from benchmarks.harness import stats, step_timeline


def read(run):
    got = step_timeline.loop_cpu_ms(run)
    return None if got is None else stats.mean(got)
