"""Device time in the flash attention kernels (``flash_fwd``,
``flash_bwd_dq``, ``flash_bwd_dkv``) per whole execution of the train
step, mean over the traced window."""
from benchmarks.harness import program_spans


def read(run):
    return program_spans.kernel_ms_per_step(
        run, program_spans.TRAIN_MODULE,
        ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"])
