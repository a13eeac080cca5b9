"""Paged decode-attention TPU kernel (scalar-prefetch block-table gather).

The block table IS the index map: the grid is ``(batch, kv_heads,
logical_pages)`` and the K/V BlockSpecs fetch ``pool[table[b, p]]`` per
step — the page gather happens inside the pallas_call machinery, so the
kernel streams exactly the pages a slot owns out of the shared HBM pool
(never a dense (B, Smax) view; that materialization is what paging exists
to avoid). Per (b, h) the logical pages arrive in order and fold into the
usual online-softmax recurrence held in VMEM scratch across grid steps;
pages at or past ``cache_len[b]`` are skipped with ``pl.when`` (their
table entries are clipped to page 0 by the wrapper and never read into
the accumulator).

Causal masking is implicit (the cache holds positions < cache_len only);
sliding window and logit softcap match the dense/ref semantics. GQA maps
each kv head's G query heads into one (G, d) q tile per program.

``paged_prefill_attention_pallas`` extends the same page-streaming design
to multi-token (S>1) chunked-prefill reads: the q tile is a whole prefill
chunk per kv head and causality is masked per (row, key) element, so the
prefill path attends the block table directly instead of gathering a
slot's pages into a dense view per chunk.

Validated in interpret mode on CPU against the ref oracles
(tests/test_kernels.py); on real TPUs the same code lowers through Mosaic.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro import compat

pl = compat.pallas()

NEG_INF = -1e30


def _paged_kernel(
    tbl_ref, lens_ref, qpos_ref,  # scalar-prefetch (also feeds the index maps)
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
    page_size: int, n_logical: int, window: int | None,
    softcap: float | None, sm_scale: float,
):
    """One (b, kv_head, logical_page) grid step.

    Refs (VMEM): q_ref (G, d); k_ref/v_ref (page_size, d) — the physical
    page the block table routed here; o_ref (G, d). Scratch: acc (G, d)
    f32, m/l (G, 1) f32 carried across the page loop of one (b, h).
    """
    b, p = pl.program_id(0), pl.program_id(2)
    length = lens_ref[b]

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(p * page_size < length)
    def _page():
        q = q_ref[...].astype(jnp.float32)          # (G, d)
        k = k_ref[...].astype(jnp.float32)          # (page, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale                                 # (G, page)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        kpos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < length
        if window is not None and window > 0:
            mask &= kpos > qpos_ref[b] - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_blk)
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        pmat = jnp.exp(s - m_safe[:, None])
        pmat = jnp.where(mask, pmat, 0.0)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_safe))
        l_new = l_ref[:, 0] * alpha + jnp.sum(pmat, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            pmat, v_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new[:, None]
        l_ref[...] = l_new[:, None]

    @pl.when(p == n_logical - 1)
    def _emit():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def _paged_prefill_kernel(
    tbl_ref, lens_ref, start_ref,  # scalar-prefetch (also feeds the index maps)
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
    page_size: int, n_logical: int, n_chunk: int, causal: bool,
    window: int | None, softcap: float | None, sm_scale: float,
):
    """One (b, kv_head, logical_page) grid step of the S>1 prefill read.

    Same page-streaming recurrence as ``_paged_kernel`` but the q tile is
    a whole prefill chunk per kv head: (G*n_chunk, d), row r = g*n_chunk+c
    at query position ``start[b] + c``. Causality is explicit here (a
    chunk's queries must not see later in-chunk keys, which ARE already
    written to the pool), masked per (row, key) element.
    """
    b, p = pl.program_id(0), pl.program_id(2)
    length = lens_ref[b]

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(p * page_size < length)
    def _page():
        q = q_ref[...].astype(jnp.float32)          # (G*C, d)
        k = k_ref[...].astype(jnp.float32)          # (page, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale                                 # (G*C, page)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        kpos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qpos = start_ref[b] + (
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % n_chunk
        )
        mask = kpos < length
        if causal:
            mask &= kpos <= qpos
        if window is not None and window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_blk)
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        pmat = jnp.exp(s - m_safe[:, None])
        pmat = jnp.where(mask, pmat, 0.0)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_safe))
        l_new = l_ref[:, 0] * alpha + jnp.sum(pmat, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            pmat, v_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new[:, None]
        l_ref[...] = l_new[:, None]

    @pl.when(p == n_logical - 1)
    def _emit():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def paged_prefill_attention_pallas(
    q, k_pages, v_pages, block_tables, *, q_positions, cache_len,
    causal: bool = True, window: int | None = None,
    softcap: float | None = None, interpret: bool = False,
):
    """q: (B,C,Hq,D) one prefill chunk per row; k_pages/v_pages:
    (P, page, Hkv, D); block_tables: (B, n_logical) int32 (``-1`` =
    unallocated); q_positions: (B,C) with row c at ``q_positions[:,0]+c``
    (the chunked-prefill contract: chunks are contiguous); cache_len: ()
    or (B,) written tokens incl. this chunk. Returns (B,C,Hq,D).

    Grid is ``(batch, kv_heads, logical_pages)`` exactly like the decode
    kernel: the chunk's queries stream every owned page once through the
    block-table index map instead of materializing a dense (B, Smax) view.
    """
    pltpu = compat.pallas_tpu()
    B, C, Hq, D = q.shape
    P, page, Hkv, _ = k_pages.shape
    nL = block_tables.shape[-1]
    G = Hq // Hkv
    sm_scale = 1.0 / math.sqrt(D)
    d_pad = -(-D // 128) * 128

    qh = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, d_pad - D)))
    # head h -> (h // G, h % G) as in the dense layout; tile row = g*C + c
    qh = qh.reshape(B, C, Hkv, G, d_pad).transpose(0, 2, 3, 1, 4)
    qh = qh.reshape(B, Hkv, G * C, d_pad)
    kh = jnp.pad(k_pages, ((0, 0), (0, 0), (0, 0), (0, d_pad - D)))
    vh = jnp.pad(v_pages, ((0, 0), (0, 0), (0, 0), (0, d_pad - D)))
    kh = kh.transpose(2, 0, 1, 3)  # (Hkv, P, page, d)
    vh = vh.transpose(2, 0, 1, 3)

    tbl = jnp.clip(block_tables.astype(jnp.int32), 0, P - 1)
    lens = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,)
    )
    start = jnp.asarray(q_positions, jnp.int32).reshape(B, C)[:, 0]

    kernel = functools.partial(
        _paged_prefill_kernel,
        page_size=page, n_logical=nL, n_chunk=C, causal=causal,
        window=window, softcap=softcap, sm_scale=sm_scale,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, nL),
        in_specs=[
            pl.BlockSpec(
                (None, None, G * C, d_pad),
                lambda b, h, p, tbl, lens, start: (b, h, 0, 0),
            ),
            pl.BlockSpec(
                (None, None, page, d_pad),
                lambda b, h, p, tbl, lens, start: (h, tbl[b, p], 0, 0),
            ),
            pl.BlockSpec(
                (None, None, page, d_pad),
                lambda b, h, p, tbl, lens, start: (h, tbl[b, p], 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, None, G * C, d_pad),
            lambda b, h, p, tbl, lens, start: (b, h, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((G * C, d_pad), jnp.float32),
            pltpu.VMEM((G * C, 1), jnp.float32),
            pltpu.VMEM((G * C, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G * C, d_pad), q.dtype),
        interpret=interpret,
    )(tbl, lens, start, qh, kh, vh)
    out = out.reshape(B, Hkv, G, C, d_pad).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, C, Hq, d_pad)[..., :D]


def paged_attention_pallas(
    q, k_pages, v_pages, block_tables, *, q_position, cache_len,
    window: int | None = None, softcap: float | None = None,
    interpret: bool = False,
):
    """q: (B,1,Hq,D); k_pages/v_pages: (P, page, Hkv, D); block_tables:
    (B, n_logical) int32 (``-1`` = unallocated). Returns (B,1,Hq,D).

    Head dim is padded to the 128-lane width; the pool is transposed to
    (Hkv, P, page, d) so one BlockSpec step fetches one head's page.
    """
    pltpu = compat.pallas_tpu()
    B, _, Hq, D = q.shape
    P, page, Hkv, _ = k_pages.shape
    nL = block_tables.shape[-1]
    G = Hq // Hkv
    sm_scale = 1.0 / math.sqrt(D)
    d_pad = -(-D // 128) * 128

    qh = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, d_pad - D)))
    qh = qh.reshape(B, Hkv, G, d_pad)  # head h -> (h // G, h % G), as dense
    kh = jnp.pad(k_pages, ((0, 0), (0, 0), (0, 0), (0, d_pad - D)))
    vh = jnp.pad(v_pages, ((0, 0), (0, 0), (0, 0), (0, d_pad - D)))
    kh = kh.transpose(2, 0, 1, 3)  # (Hkv, P, page, d)
    vh = vh.transpose(2, 0, 1, 3)

    tbl = jnp.clip(block_tables.astype(jnp.int32), 0, P - 1)
    lens = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,)
    )
    qpos = jnp.broadcast_to(
        jnp.asarray(q_position, jnp.int32).reshape(-1), (B,)
    )

    kernel = functools.partial(
        _paged_kernel,
        page_size=page, n_logical=nL, window=window, softcap=softcap,
        sm_scale=sm_scale,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, nL),
        in_specs=[
            pl.BlockSpec(
                (None, None, G, d_pad), lambda b, h, p, tbl, lens, qpos: (b, h, 0, 0)
            ),
            pl.BlockSpec(
                (None, None, page, d_pad),
                lambda b, h, p, tbl, lens, qpos: (h, tbl[b, p], 0, 0),
            ),
            pl.BlockSpec(
                (None, None, page, d_pad),
                lambda b, h, p, tbl, lens, qpos: (h, tbl[b, p], 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, None, G, d_pad), lambda b, h, p, tbl, lens, qpos: (b, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((G, d_pad), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, d_pad), q.dtype),
        interpret=interpret,
    )(tbl, lens, qpos, qh, kh, vh)
    return out.reshape(B, Hq, d_pad)[:, None, :, :D]
