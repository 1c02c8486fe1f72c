"""Seconds of set-up making the weights and the cache: the self time of
``llm.load_weights``, ``llm.init_cache`` and ``train.init_state``,
outside compile stages."""
from benchmarks.harness import startup


def read(run):
    return startup.class_seconds(run, "weights")
