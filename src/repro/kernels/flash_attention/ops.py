"""jit'd public wrapper for the flash attention kernel.

``flash_attention(...)`` routes to the Pallas kernel on TPU (or in
interpret mode when asked) and to the pure-jnp oracle otherwise. No model
path calls it yet.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_reference


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_kv", "impl"),
)
def flash_attention(
    q, k, v, *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    impl: str = "auto",  # auto | pallas | interpret | reference
):
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "reference"
    if impl == "reference":
        return attention_reference(
            q, k, v, causal=causal, window=window, softcap=softcap
        )
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_kv=block_kv,
        interpret=(impl == "interpret"),
    )
