"""jit'd public wrapper for the paged decode-attention kernel.

``paged_attention(...)`` routes to the Pallas kernel on TPU (or in
interpret mode when asked) and to the pure-jnp gather oracle otherwise —
the same ``impl`` contract as ``kernels.flash_attention``. The serving
stack selects the implementation via ``ModelConfig.paged_attn_impl``; the
reference path is the one that is bitwise identical to the dense cache
layout (the paged-vs-dense token-identity guarantee).
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.paged_attention.kernel import (
    paged_attention_pallas,
    paged_prefill_attention_pallas,
)
from repro.kernels.paged_attention.ref import (
    paged_attention_reference,
    paged_prefill_attention_reference,
)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "impl", "sm_scale")
)
def paged_attention(
    q, k_pages, v_pages, block_tables, *,
    layer, q_position, cache_len,
    window: int | None = None,
    softcap: float | None = None,
    impl: str = "auto",  # auto | pallas | interpret | reference
    sm_scale: float | None = None,
):
    """Single-position attention against a paged KV pool.

    q: (B,1,Hq,D); k_pages/v_pages: the stacked pools
    (R, Hkv, P, page_size, D), passed whole and read at ``layer`` (() int,
    traced: one trace serves every layer); block_tables: (B, n_logical)
    int32, ``-1`` = unallocated; q_position/cache_len: () or (B,);
    ``sm_scale``: the score scale, 1/sqrt(D) when None. Returns
    (B,1,Hq,D) in q.dtype.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "reference"
    if impl == "reference":
        return paged_attention_reference(
            q, k_pages, v_pages, block_tables, layer=layer,
            q_position=q_position, cache_len=cache_len,
            window=window, softcap=softcap, sm_scale=sm_scale,
        )
    return paged_attention_pallas(
        q, k_pages, v_pages, block_tables, layer=layer,
        q_position=q_position, cache_len=cache_len,
        window=window, softcap=softcap, sm_scale=sm_scale,
        interpret=(impl == "interpret"),
    )


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "q_chunk", "kv_chunk",
                     "impl", "sm_scale"),
)
def paged_prefill_attention(
    q, k_pages, v_pages, block_tables, *,
    layer, q_positions, cache_len,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    impl: str = "auto",  # auto | pallas | interpret | reference
    sm_scale: float | None = None,
):
    """Multi-token (S>1) chunked-prefill attention against a paged KV pool.

    q: (B,C,Hq,D) — one prefill chunk per row at positions ``q_positions``
    (B,C) (contiguous: row c sits at ``q_positions[:,0] + c``);
    k_pages/v_pages: the stacked pools (R, Hkv, P, page_size, D), passed
    whole and read at ``layer``; cache_len: () or (B,) written tokens
    including this chunk. ``q_chunk``/``kv_chunk``
    are the reference path's flash chunk sizes — pass the model's so the
    reference stays bitwise identical to the dense-gather prefill (the
    sharing-on/off and paged-vs-dense token-identity guarantees); the
    kernel streams pages and ignores them. ``sm_scale``: the score scale,
    1/sqrt(D) when None. Returns (B,C,Hq,D) in q.dtype.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "reference"
    if impl == "reference":
        return paged_prefill_attention_reference(
            q, k_pages, v_pages, block_tables, layer=layer,
            q_positions=q_positions, cache_len=cache_len,
            causal=causal, window=window, softcap=softcap,
            q_chunk=q_chunk, kv_chunk=kv_chunk, sm_scale=sm_scale,
        )
    return paged_prefill_attention_pallas(
        q, k_pages, v_pages, block_tables, layer=layer,
        q_positions=q_positions, cache_len=cache_len,
        causal=causal, window=window, softcap=softcap, sm_scale=sm_scale,
        interpret=(impl == "interpret"),
    )
