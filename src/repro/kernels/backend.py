"""The one device rule for the Pallas kernels.

A kernel whose `interpret` argument is left as None asks `interpret_mode()`
while it traces. On a TPU backend it compiles with Mosaic; on the CPU
backend it runs in Pallas interpret mode (tests, CPU rehearsals). Any other
backend is an error, never a silent fallback to the interpreter.
"""

from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True only when JAX's default backend is the CPU."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for 'tpu' or interpret on 'cpu'; JAX's "
        f"default backend is {platform!r}")
