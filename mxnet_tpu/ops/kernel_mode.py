"""How the Pallas kernels of this package run in the current process.

Every kernel asks :func:`kernel_mode` instead of probing the backend
itself: on a TPU backend Mosaic compiles the kernel body; anywhere else
the op takes its XLA path, unless the caller has asked — explicitly,
with :func:`interpret_kernels` — for the Pallas interpreter, which runs
the same kernel bodies on the CPU (a rehearsal of the control flow and
the math, never a measurement).
"""
from __future__ import annotations

import contextlib

import jax

__all__ = ["kernel_mode", "interpret_kernels"]

_INTERPRET = False


def kernel_mode():
    """``"mosaic"`` on a TPU backend, ``"interpret"`` inside
    :func:`interpret_kernels` on any other, else ``None`` (XLA path)."""
    if jax.default_backend() == "tpu":
        return "mosaic"
    return "interpret" if _INTERPRET else None


@contextlib.contextmanager
def interpret_kernels():
    """Run the package's Pallas kernels in interpret mode where there is
    no TPU.  Process-wide, and read at trace time: enter it before the
    first call of anything jitted that should see it."""
    global _INTERPRET
    prev, _INTERPRET = _INTERPRET, True
    try:
        yield
    finally:
        _INTERPRET = prev
