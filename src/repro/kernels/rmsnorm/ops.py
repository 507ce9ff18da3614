"""Public wrapper for fused RMSNorm (leading-dim flattening + dispatch)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.interpret import resolve_interpret
from repro.kernels.rmsnorm.kernel import rmsnorm_pallas
from repro.kernels.rmsnorm.ref import rmsnorm_ref


def rmsnorm(
    x: jax.Array,
    w: jax.Array,
    *,
    eps: float = 1e-6,
    use_pallas: bool = False,
    block_rows: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    if not use_pallas:
        return rmsnorm_ref(x, w, eps)
    interpret = resolve_interpret(interpret)
    shape = x.shape
    d = shape[-1]
    rows = x.size // d
    x2 = x.reshape(rows, d)
    pad = (-rows) % block_rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    y = rmsnorm_pallas(x2, w, eps=eps, block_rows=block_rows, interpret=interpret)
    if pad:
        y = y[:rows]
    return y.reshape(shape)
