"""Pallas TPU kernels (decode attention, flash attention, SSD scan) with
their pure-jnp oracles in ``ref.py``."""
from __future__ import annotations

import jax


def interpret_mode(interpret=None) -> bool:
    """Whether a kernel runs in the Pallas interpreter: as the caller asks,
    else compiled on a TPU and interpreted on the CPU. Any other platform
    raises rather than quietly interpreting on an accelerator."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas TPU kernels cannot run on {backend!r}; "
                       "pass interpret=True to run them in the interpreter")
