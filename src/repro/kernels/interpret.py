"""Where the Pallas kernels run: compiled on a TPU, interpreted on CPU.

The interpreter exists for CPU tests only. On a TPU every kernel runs
compiled, and no backend falls back to the interpreter in silence.
"""
from __future__ import annotations

import jax


def default_interpret() -> bool:
    """Compiled (False) on a TPU, the interpreter (True) on CPU; any other
    backend has no Pallas-TPU lowering and raises."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas TPU kernels have no lowering for backend {backend!r}; "
        f"run them on a TPU, or on CPU in interpret mode"
    )


def resolve_interpret(interpret) -> bool:
    """An explicit flag, or the backend default when ``None``. Asking for
    the interpreter on a TPU is an error: the chip runs the compiled
    kernel."""
    if interpret is None:
        return default_interpret()
    if interpret and jax.default_backend() == "tpu":
        raise ValueError(
            "interpret=True on a TPU backend: Pallas kernels run compiled "
            "on the chip"
        )
    return bool(interpret)
