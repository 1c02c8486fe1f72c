"""What the way out holds of its threads: self time of every
``serve.stream_item`` span of the traced window (one an item, on the
request's thread of the replica, from the engine's stream handing the token
on to the thread coming back for the next: the item's encode and its
synchronous seal), all threads together, over the joined steps
(``step_timeline``).
Wall time of those threads, so waiting is in it: for the interpreter lock,
for the channel, for the store's reply.  With R rows a step it reads R x the
median item's time, and that grows with R where the seals queue behind one
another."""
from benchmarks.harness import step_timeline


def read(run):
    return step_timeline.stream_send_ms_per_step(run)
