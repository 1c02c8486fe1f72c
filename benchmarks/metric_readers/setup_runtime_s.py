"""Seconds of set-up in the runtime's own start: the self time of
``runtime.init``, ``runtime.claim_tpu`` (JAX's import and the TPU
backend coming up in the process that was leased the chip),
``serve.run`` (with ``serve.deploy`` and ``serve.wait_ready``) and
``worker.boot`` less what the replica's own spans cover, and of
``train.build`` outside its two children."""
from benchmarks.harness import startup


def read(run):
    return startup.class_seconds(run, "runtime")
