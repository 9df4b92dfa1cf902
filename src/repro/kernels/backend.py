"""How the Pallas kernels execute on the attached JAX backend."""
from __future__ import annotations

import jax


def interpret_default() -> bool:
    """Whether a Pallas call runs in interpret mode when its caller does not
    say: True only on a CPU backend, where Pallas can only interpret a
    kernel (the test suite runs there). Every other backend compiles, so on
    a TPU each kernel goes through Mosaic and a kernel the compiler refuses
    fails the run instead of silently falling back to the interpreter.

    Read from ``jax.default_backend()`` at call time, never at import."""
    return jax.default_backend() == "cpu"
