"""Pallas TPU kernels of the LGD hot path.

Each kernel family is a package with ``kernel.py`` (the Pallas kernel),
``ref.py`` (the pure-XLA oracle) and ``ops.py`` (the jitted wrapper that
pads, dispatches and unpads).  ``tests/test_tpu_compile.py`` compiles
every main-path kernel for a v5e; the interpret-mode tests pin parity.
"""

import jax


def round_up(a: int, b: int) -> int:
    """Round ``a`` up to the next multiple of ``b`` (block padding)."""
    return (a + b - 1) // b * b


def default_use_pallas() -> bool:
    """Platform dispatch for kernel fast paths.

    True when the active backend compiles Mosaic kernels (TPU); CPU
    hosts take the XLA reference, which beats the Pallas interpreter by
    orders of magnitude and keeps numerics identical to the kernel
    (see the parity tests in tests/test_kernels.py).
    """
    return jax.default_backend() == "tpu"
