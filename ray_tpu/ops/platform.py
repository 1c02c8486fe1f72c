"""Which backend the Pallas kernels are built for.

Every ``pl.pallas_call`` in ``ray_tpu/ops`` asks this one function, by
attribute (``platform.interpret_mode()``), so a test that compiles the
kernels ahead of time for a TPU topology (tests/test_mosaic_aot.py)
patches a single name, and ``chip_smoke.py`` asserts a single name is
False before it trusts a kernel result.
"""

from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True where there is no TPU to compile for — the CPU tests, which
    run the kernels through the Pallas interpreter.  On a TPU backend
    the kernels go through Mosaic."""
    return jax.default_backend() != "tpu"
