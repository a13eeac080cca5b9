"""Attention: GQA + RoPE + sliding window + softcap; flash-style chunked
computation in pure JAX (bounded memory at 32k+ sequence lengths — also the
oracle for the Pallas flash kernel); KV-cache decode path.

Shapes follow (batch, seq, heads, head_dim) throughout.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.layers.common import LogicalConstraints, NULL_CONSTRAINTS, ParamSpec

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float, rotary_frac: float = 1.0):
    rot = int(head_dim * rotary_frac) // 2 * 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    return inv, rot


def apply_rope(x, positions, theta: float = 10000.0, rotary_frac: float = 1.0):
    """x: (B,S,H,D); positions: (B,S) int32. Interleaved-pair convention."""
    d = x.shape[-1]
    inv, rot = rope_frequencies(d, theta, rotary_frac)
    ang = positions[..., None].astype(jnp.float32) * inv  # (B,S,rot/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    xr = x[..., :rot].astype(jnp.float32)
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x1 * sin + x2 * cos
    y = jnp.stack([y1, y2], axis=-1).reshape(x.shape[:-1] + (rot,))
    if rot < d:
        y = jnp.concatenate([y, x[..., rot:].astype(jnp.float32)], axis=-1)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# masking
# ---------------------------------------------------------------------------


def block_mask(
    q_pos, k_pos, *, causal: bool, window: int | None, kv_len: Any | None = None
):
    """(…,Sq,Sk) boolean visibility. ``kv_len`` masks unwritten cache slots."""
    m = jnp.ones(q_pos.shape[-1:] + k_pos.shape[-1:], dtype=bool)
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    if causal:
        m = m & (kp <= qp)
    if window is not None and window > 0:
        m = m & (kp > qp - window)
    if kv_len is not None:
        m = m & (k_pos[..., None, :] < kv_len)
    return m


# ---------------------------------------------------------------------------
# flash-style chunked attention (pure JAX)
# ---------------------------------------------------------------------------


def _attend_block(q, k, v, mask, scale, softcap):
    """q:(B,G,Hkv,Sq,D) k:(B,Hkv,Sk,D) v:(B,Hkv,Sk,D) mask:(Sq,Sk) or (B,1,1,Sq,Sk).
    Returns partial (o, m, l) in fp32 with m the TRUE masked row max
    (NEG_INF for fully-masked rows). Returning a 0-sentinel here instead
    poisons the cross-block running max whenever real scores are very
    negative: max(m_true<0, 0)=0 underflows the rescale factor exp(m-0)
    to zero, collapsing l and producing silently-wrong outputs + NaN
    gradients (found via the launcher's NaN at seq>q_chunk). The
    0-sentinel is only safe INSIDE this block as the exp stabilizer."""
    s = jnp.einsum("bghqd,bhkd->bghqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s *= scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)  # (B,G,Hkv,Sq) true masked max
    m_safe = jnp.where(m <= NEG_INF / 2, 0.0, m)  # exp stabilizer only
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bghqk,bhkd->bghqd", p, v.astype(jnp.float32))
    return o, m, l


def flash_attention(
    q, k, v,
    *,
    q_positions, k_positions,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    kv_len=None,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    causal_skip: bool = False,
    sm_scale: float | None = None,
):
    """Online-softmax chunked attention.

    q: (B,Sq,Hq,D); k,v: (B,Sk,Hkv,D); GQA via Hq = G*Hkv.
    ``kv_len``: () or (B,) valid key length (keys at positions >= kv_len are
    masked — the chunked-prefill path attends a prompt chunk against the
    partially written KV cache this way).
    ``causal_skip`` bounds the kv scan per q-chunk (skips fully-future
    blocks) — a beyond-paper compute optimization toggled by the perf pass.
    ``sm_scale``: the score scale, 1/sqrt(D) when None.
    Returns (B,Sq,Hq,D) in q.dtype.
    """
    B, Sq, Hq, D = q.shape
    if kv_len is not None:
        # normalize to broadcast against the (B,1,1,qc,kc) block mask
        kv_len = jnp.asarray(kv_len).reshape(-1, 1, 1, 1, 1)
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D) if sm_scale is None else sm_scale
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    nq = -(-Sq // q_chunk)
    nk = -(-Sk // kv_chunk)
    # pad seq dims to chunk multiples
    q = _pad_axis(q, 1, nq * q_chunk)
    k = _pad_axis(k, 1, nk * kv_chunk)
    v = _pad_axis(v, 1, nk * kv_chunk)
    q_positions = _pad_axis(q_positions, 1, nq * q_chunk, value=-(10**9))
    k_positions = _pad_axis(k_positions, 1, nk * kv_chunk, value=10**9)

    qg = q.reshape(B, nq, q_chunk, Hkv, G, D).transpose(1, 0, 4, 3, 2, 5)  # (nq,B,G,Hkv,qc,D)
    kg = k.reshape(B, nk, kv_chunk, Hkv, D).transpose(1, 0, 3, 2, 4)       # (nk,B,Hkv,kc,D)
    vg = v.reshape(B, nk, kv_chunk, Hkv, D).transpose(1, 0, 3, 2, 4)
    qp = q_positions.reshape(B, nq, q_chunk).transpose(1, 0, 2)            # (nq,B,qc)
    kp = k_positions.reshape(B, nk, kv_chunk).transpose(1, 0, 2)

    def q_body(_, qs):
        qi, qblk, qpos = qs

        @functools.partial(jax.checkpoint, policy=None)
        def kv_step(carry, ki):
            o, m, l = carry
            kblk, vblk, kpos = kg[ki], vg[ki], kp[ki]
            # barrier: stop XLA hoisting the (nq x nk x qc x kc) mask out of
            # both chunk loops (a multi-GB loop-invariant tensor otherwise)
            qpos_b, kpos_b = jax.lax.optimization_barrier((qpos, kpos))
            mask = block_mask(
                qpos_b[:, None, None, :], kpos_b[:, None, None, :],
                causal=causal, window=window, kv_len=kv_len,
            )  # (B,1,1,qc,kc)
            ob, mb, lb = _attend_block(qblk, kblk, vblk, mask, scale, softcap)
            m_new = jnp.maximum(m, mb)
            a = jnp.exp(m - m_new)
            b = jnp.exp(mb - m_new)
            o = o * a[..., None] + ob * b[..., None]
            l = l * a + lb * b
            return (o, m_new, l), None

        o0 = jnp.zeros((B, G, Hkv, q_chunk, D), jnp.float32)
        m0 = jnp.full((B, G, Hkv, q_chunk), NEG_INF)
        l0 = jnp.zeros((B, G, Hkv, q_chunk), jnp.float32)
        (o, m, l), _ = jax.lax.scan(kv_step, (o0, m0, l0), jnp.arange(nk))
        o = o / jnp.maximum(l[..., None], 1e-30)
        return None, o

    if causal_skip and causal and q_chunk == kv_chunk and nq == nk:
        # ---- static causal block skipping ----
        # Enumerate only the visible (qi, ki<=qi) block pairs (and within
        # the sliding window when set). The savings are STATIC: the scan
        # trip count shrinks, so both real hardware and the HLO counter
        # analysis see the reduced compute/traffic (a lax.cond skip would
        # hide it from both the roofline and the MXU pipeline).
        wb = None
        if window is not None and window > 0:
            wb = -(-window // kv_chunk) + 1  # visible kv blocks per q block
        pairs_qi, pairs_ki = [], []
        for qi in range(nq):
            lo = 0 if wb is None else max(0, qi - wb + 1)
            for ki in range(lo, qi + 1):
                pairs_qi.append(qi)
                pairs_ki.append(ki)
        # segment boundaries + final pair indices computed on the python
        # lists (constants may be tracers under jax.checkpoint re-tracing)
        final_idx = [i for i, (q_, k_) in enumerate(zip(pairs_qi, pairs_ki))
                     if k_ == q_]
        seg_start_list = []
        prev = -1
        for q_idx in pairs_qi:
            seg_start_list.append(q_idx != prev)
            prev = q_idx
        seg_start = jnp.asarray(seg_start_list)
        pairs_qi = jnp.asarray(pairs_qi, jnp.int32)
        pairs_ki = jnp.asarray(pairs_ki, jnp.int32)

        def pair_step(carry, inp):
            o, m, l = carry
            qi, ki, start = inp
            qblk, qpos = qg[qi], qp[qi]
            kblk, vblk, kpos = kg[ki], vg[ki], kp[ki]
            o = jnp.where(start, 0.0, o)
            m = jnp.where(start, NEG_INF, m)
            l = jnp.where(start, 0.0, l)
            qpos_b, kpos_b = jax.lax.optimization_barrier((qpos, kpos))
            mask = block_mask(
                qpos_b[:, None, None, :], kpos_b[:, None, None, :],
                causal=causal, window=window, kv_len=kv_len,
            )
            ob, mb, lb = _attend_block(qblk, kblk, vblk, mask, scale, softcap)
            m_new = jnp.maximum(m, mb)
            a = jnp.exp(m - m_new)
            bfac = jnp.exp(mb - m_new)
            o = o * a[..., None] + ob * bfac[..., None]
            l = l * a + lb * bfac
            # emit the normalized block every pair; only the last pair of a
            # segment is kept (static gather below). Carrying the full
            # output array instead would be saved per iteration by the
            # scan's VJP — a 5x traffic regression (measured).
            finished = (o / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)
            return (o, m_new, l), finished

        @functools.partial(jax.checkpoint, policy=None)
        def pair_step_ckpt(carry, inp):
            return pair_step(carry, inp)

        o0 = jnp.zeros((B, G, Hkv, q_chunk, D), jnp.float32)
        m0 = jnp.full((B, G, Hkv, q_chunk), NEG_INF)
        l0 = jnp.zeros((B, G, Hkv, q_chunk), jnp.float32)
        _, ys = jax.lax.scan(
            pair_step_ckpt, (o0, m0, l0), (pairs_qi, pairs_ki, seg_start)
        )
        outs = ys[jnp.asarray(final_idx, jnp.int32)]  # (nq, B,G,Hkv,qc,D)
        out = outs.transpose(1, 0, 4, 3, 2, 5).reshape(B, nq * q_chunk, Hq, D)
        return out[:, :Sq].astype(q.dtype)

    _, outs = jax.lax.scan(q_body, None, (jnp.arange(nq), qg, qp))
    # (nq,B,G,Hkv,qc,D) -> (B, nq*qc, Hkv*G, D)
    out = outs.transpose(1, 0, 4, 3, 2, 5).reshape(B, nq * q_chunk, Hq, D)
    return out[:, :Sq].astype(q.dtype)


def _pad_axis(x, axis, size, value=0):
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def decode_attention_paged(
    q, k_pages, v_pages, block_tables, *, layer, q_position, cache_len,
    window: int | None = None, softcap: float | None = None,
    impl: str = "auto", sm_scale: float | None = None,
):
    """Single-position attention against a paged KV pool.

    q: (B,1,Hq,D); k_pages/v_pages: (R, Hkv, P, page_size, D) shared
    stacked pool, read at ``layer``; block_tables: (B, n_logical) int32 —
    logical page j of slot b lives in physical page ``block_tables[b, j]``
    (-1 = unallocated). Routed through
    ``repro.kernels.paged_attention`` (Pallas on TPU, gather oracle
    elsewhere); the reference path is bitwise identical to
    ``decode_attention`` over the equivalent dense cache."""
    from repro.kernels.paged_attention.ops import paged_attention

    return paged_attention(
        q, k_pages, v_pages, block_tables, layer=layer,
        q_position=q_position, cache_len=cache_len,
        window=window, softcap=softcap, impl=impl, sm_scale=sm_scale,
    )


def decode_attention(
    q, k_cache, v_cache, *, q_position, cache_len,
    window: int | None = None, softcap: float | None = None,
    sm_scale: float | None = None,
):
    """Single-position attention against a cache.

    q: (B,1,Hq,D); caches: (B,Smax,Hkv,D); cache_len: () or (B,) valid length
    (positions [0, cache_len) are real; q_position = cache_len typically).
    ``sm_scale``: the score scale, 1/sqrt(D) when None.
    """
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


# ---------------------------------------------------------------------------
# the attention block (projections + rope + attend)
# ---------------------------------------------------------------------------


def attention_params(cfg) -> dict:
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    p = {
        "wq": ParamSpec((d, hq * hd), ("embed", "qkv")),
        "wk": ParamSpec((d, hkv * hd), ("embed", "kv")),
        "wv": ParamSpec((d, hkv * hd), ("embed", "kv")),
        "wo": ParamSpec(
            (hq * hd, d), ("qkv", "embed_out"),
            scale=1.0 / (math.sqrt(hq * hd) * math.sqrt(2 * cfg.n_layers)),
        ),
    }
    if cfg.attn_bias:
        p["bq"] = ParamSpec((hq * hd,), ("qkv",), init="zeros")
        p["bk"] = ParamSpec((hkv * hd,), ("kv",), init="zeros")
        p["bv"] = ParamSpec((hkv * hd,), ("kv",), init="zeros")
    if cfg.qk_norm:
        p["q_norm"] = ParamSpec((hd,), (None,), init="ones")
        p["k_norm"] = ParamSpec((hd,), (None,), init="ones")
    return p


def attention_block(
    params, x, cfg, *,
    positions, lc: LogicalConstraints = NULL_CONSTRAINTS,
    causal=True, window=None, cache=None, cache_len=None,
    seq_mask=None, cache_attend=False, block_tables=None, layer=None,
):
    """Returns (out, new_cache). ``cache``: dict(k=(B,Smax,Hkv,D), v=...),
    dict(k_pages=(R,Hkv,P,page,D), v_pages=...) for the paged layout, or
    None for full-sequence (training / prefill without cache) mode. The
    paged pools come whole, every layer stacked, and this block writes and
    reads layer ``layer`` of them in place; ``block_tables``
    (B, n_logical) maps each row's logical pages to physical pool pages.

    ``positions`` is (B,S) and doubles as the per-slot cache write index —
    each batch row writes its k/v at its own offsets (continuous batching:
    slots sit at different sequence positions). ``seq_mask`` (B,S) bool
    suppresses cache writes for masked entries (padding in a prefill chunk,
    inactive slots in a batched decode step). ``cache_attend`` switches the
    S>1 path from in-chunk attention (full prefill from position 0) to
    attending against the whole written cache (chunked prefill continuing
    at positions[:,0] > 0 — earlier chunks live in the cache)."""
    from repro.layers.norms import rmsnorm

    B, S, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    compute = cfg.compute_dtype

    q = (x @ params["wq"].astype(compute)).reshape(B, S, hq, hd)
    k = (x @ params["wk"].astype(compute)).reshape(B, S, hkv, hd)
    v = (x @ params["wv"].astype(compute)).reshape(B, S, hkv, hd)
    if cfg.attn_bias:
        q = q + params["bq"].reshape(1, 1, hq, hd).astype(compute)
        k = k + params["bk"].reshape(1, 1, hkv, hd).astype(compute)
        v = v + params["bv"].reshape(1, 1, hkv, hd).astype(compute)
    if cfg.qk_norm:
        q = rmsnorm(q, params["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, params["k_norm"], cfg.norm_eps)
    if cfg.rope_theta:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.partial_rotary)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.partial_rotary)
    q = lc(q, "batch", "seq_q", "heads", None)
    k = lc(k, "batch", "seq_kv", "kv_heads", None)
    v = lc(v, "batch", "seq_kv", "kv_heads", None)

    new_cache = None
    if cache is not None and "k_pages" in cache:
        # paged layout: write through the block table into layer ``layer``
        # of the shared pool, in place, then attend through the table.
        # Rows own disjoint physical pages (allocator invariant), so the
        # scatter cannot collide across slots; entries that are masked,
        # unallocated (table -1) or out of logical range push their page
        # index past the pool end and are dropped.
        k_pool, v_pool = cache["k_pages"], cache["v_pages"]
        Pp, psize = k_pool.shape[2], k_pool.shape[3]
        n_logical = block_tables.shape[1]
        pos0 = positions[:, 0] if positions.ndim == 2 else positions
        page_idx = positions // psize
        phys_page = jnp.take_along_axis(
            block_tables, jnp.clip(page_idx, 0, n_logical - 1), axis=1
        )
        valid = (phys_page >= 0) & (page_idx < n_logical)
        if seq_mask is not None:
            valid &= seq_mask
        # index the heads too, one (B,S,Hkv) index a D-row: with the heads
        # left as an update window, the compiler lays the pool out
        # heads-minor and copies it to and from the kernels' layout
        phys_page = jnp.where(valid, phys_page, Pp)[..., None]
        row = (positions % psize)[..., None]
        head = jnp.arange(hkv)
        kc = k_pool.at[layer, head, phys_page, row].set(
            k.astype(k_pool.dtype), mode="drop"
        )
        vc = v_pool.at[layer, head, phys_page, row].set(
            v.astype(v_pool.dtype), mode="drop"
        )
        new_cache = {"k_pages": kc, "v_pages": vc}
        if S == 1:
            o = decode_attention_paged(
                q, kc, vc, block_tables, layer=layer,
                q_position=pos0, cache_len=cache_len,
                window=window, softcap=cfg.attn_softcap,
                impl=cfg.paged_attn_impl, sm_scale=cfg.sm_scale,
            )
        else:
            # chunked prefill: attend the block table directly (multi-token
            # paged read — Pallas streams just the slot's pages on TPU; the
            # reference path gathers and runs the dense cache_attend flash
            # verbatim, keeping paged-vs-dense tokens bitwise identical)
            from repro.kernels.paged_attention.ops import (
                paged_prefill_attention,
            )

            o = paged_prefill_attention(
                q, kc, vc, block_tables, layer=layer,
                q_positions=positions, cache_len=cache_len,
                causal=causal, window=window, softcap=cfg.attn_softcap,
                q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                impl=cfg.paged_attn_impl, sm_scale=cfg.sm_scale,
            )
    elif cache is not None:
        # write current k/v at each row's own positions, then attend against
        # the cache. A masked (B,S) scatter replaces the old scalar
        # dynamic_update_slice: slots at different positions write to
        # different offsets in ONE op, and masked entries (padding /
        # inactive decode slots) are dropped instead of scribbling on live
        # cache lines (write index pushed out of bounds + mode="drop").
        Smax = cache["k"].shape[1]
        pos0 = positions[:, 0] if positions.ndim == 2 else positions
        write_pos = positions
        if seq_mask is not None:
            write_pos = jnp.where(seq_mask, positions, Smax)
        b_idx = jnp.arange(B)[:, None]
        kc = cache["k"].at[b_idx, write_pos].set(
            k.astype(cache["k"].dtype), mode="drop"
        )
        vc = cache["v"].at[b_idx, write_pos].set(
            v.astype(cache["v"].dtype), mode="drop"
        )
        new_cache = {"k": kc, "v": vc}
        if S == 1:
            o = decode_attention(
                q, kc, vc, q_position=pos0, cache_len=cache_len,
                window=window, softcap=cfg.attn_softcap, sm_scale=cfg.sm_scale,
            )
        elif cache_attend:
            # chunked prefill: this chunk's queries see every cache line
            # written so far (earlier chunks + this one), bounded by
            # cache_len, under the usual causal/window visibility
            k_positions = jnp.broadcast_to(jnp.arange(Smax)[None, :], (B, Smax))
            o = flash_attention(
                q, kc, vc, q_positions=positions, k_positions=k_positions,
                causal=causal, window=window, softcap=cfg.attn_softcap,
                kv_len=cache_len,
                q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                causal_skip=False, sm_scale=cfg.sm_scale,
            )
        else:  # full prefill from position 0: in-chunk attention
            o = flash_attention(
                q, k, v, q_positions=positions,
                k_positions=positions, causal=causal, window=window,
                softcap=cfg.attn_softcap,
                q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                causal_skip=cfg.causal_skip, sm_scale=cfg.sm_scale,
            )
    else:
        o = flash_attention(
            q, k, v, q_positions=positions, k_positions=positions,
            causal=causal, window=window, softcap=cfg.attn_softcap,
            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
            causal_skip=cfg.causal_skip, sm_scale=cfg.sm_scale,
        )
    o = lc(o, "batch", "seq_q", "heads", None)
    out = o.reshape(B, S, hq * hd) @ params["wo"].astype(compute)
    return out, new_cache
