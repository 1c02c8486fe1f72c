"""Seconds of set-up spent importing: the self time of every
``import{package}`` span of every process before the window (what a
package's import took outside the imports nested in it and outside
compile stages)."""
from benchmarks.harness import startup


def read(run):
    return startup.class_seconds(run, "import")
