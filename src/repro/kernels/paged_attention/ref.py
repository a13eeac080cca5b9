"""Pure-jnp oracle for the paged decode-attention kernel.

Gathers the pages a slot owns, in one layer of the stacked ``(R, Hkv, P,
page, D)`` pool, into the dense ``(B, Smax, Hkv, D)`` layout through the
block table, then runs the EXACT computation of
``layers.attention.decode_attention`` (same ops, same order, same shapes).
That transcription is load-bearing: the serving acceptance criterion is
*bitwise* token identity between the paged and dense cache layouts, and it
holds because post-mask the two paths are elementwise identical programs —
whatever garbage lives in unallocated/unwritten pages is squashed to an
exact 0 probability by the NEG_INF mask before it can touch the output.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def gather_pages(pages, block_tables, layer):
    """Materialize the per-slot dense view of one layer of a paged pool.

    pages: (R, Hkv, P, page, D) stacked physical pool; block_tables:
    (B, n_logical) int32, ``-1`` = unallocated (clipped to page 0 —
    callers mask by ``cache_len`` so the junk is never visible); layer:
    () int, the layer to read. Returns (B, n_logical*page, Hkv, D).
    """
    _, Hkv, P, page, D = pages.shape
    B, nL = block_tables.shape
    tbl = jnp.clip(block_tables, 0, P - 1)
    rows = pages[layer, :, tbl]  # (B, nL, Hkv, page, D)
    return rows.transpose(0, 1, 3, 2, 4).reshape(B, nL * page, Hkv, D)


def paged_prefill_attention_reference(
    q, k_pages, v_pages, block_tables, *, layer, q_positions, cache_len,
    causal: bool = True, window: int | None = None,
    softcap: float | None = None, q_chunk: int = 512, kv_chunk: int = 1024,
    sm_scale: float | None = None,
):
    """Multi-token (S>1) chunked-prefill attention against a paged cache.

    q: (B,C,Hq,D) — one prefill chunk per row at positions ``q_positions``
    (B,C) (row c sits at ``q_positions[b, 0] + c``); k_pages/v_pages: the
    stacked pools (R, Hkv, P, page, D), read at ``layer``; cache_len: ()
    or (B,) total written tokens (chunk start + chunk length). Gathers the
    rows' pages into the dense ``(B, Smax, Hkv, D)`` layout and calls the
    model's ``flash_attention`` with EXACTLY the arguments the dense-gather
    prefill branch historically used — the reference IS the dense bridge,
    bitwise, by shared code rather than by transcription.
    """
    # lazy: layers.attention lazily imports this module (gather_pages /
    # the ops wrappers), so a module-level import here would be a cycle;
    # function-local keeps the layering acyclic at import time while the
    # bitwise dense bridge stays shared code instead of a copy that drifts
    from repro.layers.attention import flash_attention

    k_cache = gather_pages(k_pages, block_tables, layer)
    v_cache = gather_pages(v_pages, block_tables, layer)
    B, Smax = k_cache.shape[0], k_cache.shape[1]
    k_positions = jnp.broadcast_to(jnp.arange(Smax)[None, :], (B, Smax))
    return flash_attention(
        q, k_cache, v_cache, q_positions=q_positions,
        k_positions=k_positions, causal=causal, window=window,
        softcap=softcap, kv_len=cache_len,
        q_chunk=q_chunk, kv_chunk=kv_chunk, causal_skip=False, sm_scale=sm_scale,
    )


def paged_attention_reference(
    q, k_pages, v_pages, block_tables, *, layer, q_position, cache_len,
    window: int | None = None, softcap: float | None = None,
    sm_scale: float | None = None,
):
    """Single-position attention against a paged cache.

    q: (B,1,Hq,D); k_pages/v_pages: the stacked pools (R, Hkv, P, page, D),
    read at ``layer``; block_tables: (B, n_logical) int32 (logical page j
    of slot b lives in physical page ``block_tables[b, j]``); cache_len: ()
    or (B,) valid token count; q_position: () or (B,) query position
    (window masking); sm_scale: the score scale, 1/sqrt(D) when None.
    Returns (B,1,Hq,D) in q.dtype.
    """
    k_cache = gather_pages(k_pages, block_tables, layer)
    v_cache = gather_pages(v_pages, block_tables, layer)
    # -- from here on: decode_attention verbatim --
    B, _, Hq, D = q.shape
    _, Sk, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D) if sm_scale is None else sm_scale
    qg = q.reshape(B, 1, Hkv, G, D).transpose(0, 3, 2, 1, 4)  # (B,G,Hkv,1,D)
    kg = k_cache.transpose(0, 2, 1, 3)  # (B,Hkv,Sk,D)
    vg = v_cache.transpose(0, 2, 1, 3)
    s = jnp.einsum("bghqd,bhkd->bghqk", qg.astype(jnp.float32), kg.astype(jnp.float32))
    s *= scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    kpos = jnp.arange(Sk)[None, None, None, None, :]
    qpos = jnp.asarray(q_position).reshape(-1, 1, 1, 1, 1)
    mask = kpos < jnp.asarray(cache_len).reshape(-1, 1, 1, 1, 1)
    if window is not None and window > 0:
        mask = mask & (kpos > qpos - window)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bghqk,bhkd->bghqd", p, vg.astype(jnp.float32))
    return o.transpose(0, 3, 2, 1, 4).reshape(B, 1, Hq, D).astype(q.dtype)
