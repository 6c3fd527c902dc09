"""Backend selection for the Pallas kernels.

On the TPU every kernel compiles through Mosaic.  On the CPU (the test
suite runs under ``JAX_PLATFORMS=cpu``) the same kernel bodies run in
Pallas interpret mode.  Any other backend is an error: a kernel that
silently ran in Python there would hide that the program is off the
device it was written for.
"""

from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Resolve an ops wrapper's ``interpret`` argument: an explicit value
    wins; None means interpret on the CPU and compile on the TPU."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile only for the TPU (interpret mode is for the "
        f"CPU); the default backend is {backend!r}"
    )
