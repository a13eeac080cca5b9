"""jit'd public wrapper for the fused RMSNorm kernel."""

from __future__ import annotations

import functools

import jax

from repro.kernels.rmsnorm.kernel import rmsnorm_pallas
from repro.kernels.rmsnorm.ref import rmsnorm_reference


@functools.partial(
    jax.jit, static_argnames=("eps", "zero_centered", "block_rows", "impl")
)
def rmsnorm(
    x, scale, *, eps: float = 1e-6, zero_centered: bool = False,
    block_rows: int = 256, impl: str = "auto",
):
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "reference"
    if impl == "reference":
        return rmsnorm_reference(x, scale, eps, zero_centered)
    return rmsnorm_pallas(
        x, scale, eps, zero_centered, block_rows=block_rows,
        interpret=(impl == "interpret"),
    )
