"""Seconds of set-up in the backend compiler or reading the persistent
compilation cache in its place: the ``compile`` stages of every jitted
function of every process before the window."""
from benchmarks.harness import startup


def read(run):
    return startup.class_seconds(run, "compile")
