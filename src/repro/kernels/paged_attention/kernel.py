"""Paged attention TPU kernels: decode (one query a slot) and chunked
prefill, both reading K/V pages through the block table.

Both kernels address the stacked pool the model step keeps, ``(R, Hkv,
P, page, D)`` for ``R`` layers, in place: the layer is one more
scalar-prefetch operand, so no layer's pages are sliced out, relaid out or
copied back around a call. Head-major pages make one page of every KV head
a set of contiguous ``(page, D)`` tiles.

The decode kernel's grid is one step a slot. Inside it a loop walks the
slot's live pages only — below ``cache_len[b]``, inside the sliding
window, with an allocated (``>= 0``) table entry — ``pages_per_step`` at
a time. Each page is copied from the shared HBM pool into a VMEM block
with its own async copy (a slot's pages are not contiguous), into two
halves so that the next block's copies run under this block's compute,
and the blocks fold into the usual online-softmax recurrence. Dead pages
cost no copy and no grid step, so the kernel's time follows the live
context, not ``batch x max_len``; a block with no live page is not
computed, and a released slot (table all ``-1``) costs a few scalar
reads. The pool is never gathered into a dense (B, Smax) view; that
materialization is what paging exists to avoid.

Causal masking is implicit (the cache holds positions < cache_len only);
sliding window and logit softcap match the dense/ref semantics. GQA maps
each kv head's G query heads into one (G, d) q tile.

``paged_prefill_attention_pallas`` serves multi-token (S>1)
chunked-prefill reads with the table as the index map: its grid is
``(batch, kv_heads, logical_pages)`` and the K/V BlockSpecs fetch
``pool[layer, h, table[b, p]]`` a step, pages at or past ``cache_len``
skipped with ``pl.when``. The q tile is a whole prefill chunk per kv head and
causality is masked per (row, key) element, so the prefill path attends
the block table directly instead of gathering a slot's pages into a dense
view per chunk.

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
pltpu = compat.pallas_tpu()

NEG_INF = -1e30


# A decode loop step fetches about this many keys of one slot: blocks of
# 128-512 rows keep the MXU passes full without wasting much of a slot's
# last block.
BLOCK_ROWS = 256
# Double-buffered K and V blocks (all KV heads) stay under this much VMEM.
BLOCK_VMEM_BYTES = 4 << 20
# The (G, rows) float32 score tile of one KV head stays under this.
SCORE_TILE_BYTES = 64 << 10


def pages_per_step(page_size: int, n_logical: int, kv_heads: int,
                   head_dim: int, group: int, itemsize: int) -> int:
    """Pages the decode kernel fetches and folds in one loop step, from
    the shapes alone: ``BLOCK_ROWS`` keys, halved while the double-buffered
    K and V blocks or the score tile would be too large, never more pages
    than a table row holds. The last block of a slot may be partial."""
    pps = max(1, BLOCK_ROWS // page_size)
    while pps > 1 and (
        2 * 2 * kv_heads * pps * page_size * head_dim * itemsize
        > BLOCK_VMEM_BYTES
        or group * pps * page_size * 4 > SCORE_TILE_BYTES
    ):
        pps //= 2
    return min(pps, n_logical)


def _paged_kernel(
    tbl_ref, lens_ref, qpos_ref, layer_ref,  # scalar-prefetch (SMEM)
    q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, acc_ref, m_ref, l_ref, *,
    page_size: int, n_logical: int, pps: int, window: int | None,
    softcap: float | None, sm_scale: float,
):
    """One slot: walk its live pages, ``pps`` a loop step.

    Refs: q_ref/o_ref (Hkv, G, d) VMEM; k_hbm/v_hbm (R, Hkv, P, page, d)
    in HBM, read at layer ``layer_ref[0]``; k_buf/v_buf
    (2, Hkv, pps*page, d) VMEM, the two halves of the double buffer; sems
    one DMA semaphore a half. Scratch: acc (Hkv, G, d) f32, m/l (Hkv, G, 1)
    f32. A page is live when it lies below
    ``cache_len``, inside the window, and its table entry is allocated;
    only live pages are fetched, and a block with none is not computed.
    """
    b = pl.program_id(0)
    hkv = q_ref.shape[0]
    rows = pps * page_size
    length, qpos, layer = lens_ref[b], qpos_ref[b], layer_ref[0]
    hi = jnp.minimum((length + page_size - 1) // page_size, n_logical)
    lo = 0
    if window is not None and window > 0:
        lo = jnp.maximum(qpos - window + 1, 0) // page_size
    n_blocks = (jnp.maximum(hi - lo, 0) + pps - 1) // pps

    def page(i, j):
        """Physical page of block i's page j, and whether it is live."""
        p = lo + i * pps + j
        phys = tbl_ref[b, jnp.minimum(p, n_logical - 1)]
        return phys, (p < hi) & (phys >= 0)

    def rows_of(j):
        return pl.ds(pl.multiple_of(j * page_size, page_size), page_size)

    def copies(phys, j, half):
        return [pltpu.make_async_copy(src.at[layer, :, phys],
                                      buf.at[half, :, rows_of(j)],
                                      sems.at[half])
                for src, buf in ((k_hbm, k_buf), (v_hbm, v_buf))]

    def each_page(fn, init):
        """``fn(j, carry)`` over a block's pages, as a loop: unrolled, the
        bodies would multiply the kernel's lowering time, paid in set-up
        on every trace of the step that calls it (about 0.4 s against
        0.1 s on a v5e host), for a kernel 10-20% faster."""
        return jax.lax.fori_loop(0, pps, fn, init)

    def start(i, half):
        """Start the K and V copies of block i's live pages into buffer
        half ``half``: one copy a page, every KV head."""
        def one(j, carry):
            phys, live = page(i, j)

            @pl.when(live)
            def _():
                for c in copies(phys, j, half):
                    c.start()
            return carry

        each_page(one, 0)

    def land(i, half, r):
        """Wait for block i's copies. A page not fetched holds whatever
        the buffer held before: zero its values, so that p = 0 there
        multiplies a finite 0. Returns the number of live pages and a
        (1, rows) int mask of the rows of live pages."""
        def one(j, carry):
            n, row_live = carry
            phys, live = page(i, j)

            @pl.when(live)
            def _():
                for c in copies(phys, j, half):
                    c.wait()

            @pl.when(jnp.logical_not(live))
            def _():
                v_buf[half, :, rows_of(j), :] = jnp.zeros(
                    (hkv, page_size, v_buf.shape[-1]), v_buf.dtype)

            in_page = (r >= j * page_size) & (r < (j + 1) * page_size)
            live = live.astype(jnp.int32)
            return n + live, jnp.where(in_page, live, row_live)

        return each_page(one, (jnp.int32(0), jnp.zeros((1, rows), jnp.int32)))

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(n_blocks > 0)
    def _():
        start(0, 0)

    def block(i, carry):
        half = i % 2

        @pl.when(i + 1 < n_blocks)
        def _():
            start(i + 1, 1 - half)

        r = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        n_live, row_live = land(i, half, r)

        @pl.when(n_live > 0)
        def _():
            kpos = (lo + i * pps) * page_size + r
            mask = (kpos < length) & (row_live > 0)
            if window is not None and window > 0:
                mask &= kpos > qpos - window
            for h in range(hkv):
                q = q_ref[h]                                   # (G, d)
                k = k_buf[half, h]                             # (rows, d)
                dt = jnp.promote_types(q.dtype, k.dtype)
                s = jax.lax.dot_general(
                    q.astype(dt), k.astype(dt), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * sm_scale                                   # (G, rows)
                if softcap:
                    s = jnp.tanh(s / softcap) * softcap
                s = jnp.where(mask, s, NEG_INF)
                m_prev = m_ref[h][:, 0]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
                m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
                pmat = jnp.where(mask, jnp.exp(s - m_safe[:, None]), 0.0)
                alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                                  jnp.exp(m_prev - m_safe))
                l_ref[h] = (l_ref[h][:, 0] * alpha
                            + jnp.sum(pmat, axis=-1))[:, None]
                acc_ref[h] = acc_ref[h] * alpha[:, None] + jax.lax.dot_general(
                    pmat, v_buf[half, h].astype(jnp.float32),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                m_ref[h] = m_new[:, None]

        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_prefill_kernel(
    tbl_ref, lens_ref, start_ref, layer_ref,  # scalar-prefetch (index maps)
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
    page_size: int, n_logical: int, n_chunk: int, causal: bool,
    window: int | None, softcap: float | None, sm_scale: float,
):
    """One (b, kv_head, logical_page) grid step of the S>1 prefill read.

    The online-softmax recurrence of ``_paged_kernel``, one page a step;
    the q tile is a whole prefill chunk per kv head: (G*n_chunk, d), row
    r = g*n_chunk+c at query position ``start[b] + c``. Causality is
    explicit here (a chunk's queries must not see later in-chunk keys,
    which ARE already written to the pool), masked per (row, key) element.
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


def _lane_pools(k_pages, v_pages, layer):
    """The pools as the kernels read them, and the layer to read. Where
    the head dim fills whole 128-lane tiles, the stacked pools themselves,
    addressed in place; else the layer's pages alone, lane-padded, as the
    one layer of a copy made for this call."""
    D = k_pages.shape[-1]
    d_pad = -(-D // 128) * 128
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    if d_pad == D:
        return k_pages, v_pages, layer

    def pad(pages):
        one = jax.lax.dynamic_index_in_dim(pages, layer[0], 0, keepdims=True)
        return jnp.pad(one, ((0, 0),) * 4 + ((0, d_pad - D),))

    return pad(k_pages), pad(v_pages), jnp.zeros((1,), jnp.int32)


def paged_prefill_attention_pallas(
    q, k_pages, v_pages, block_tables, *, layer, q_positions, cache_len,
    causal: bool = True, window: int | None = None,
    softcap: float | None = None, interpret: bool = False,
    sm_scale: float | None = None,
):
    """q: (B,C,Hq,D) one prefill chunk per row; k_pages/v_pages: the
    stacked pools (R, Hkv, P, page, D), read at ``layer`` (() int);
    block_tables: (B, n_logical) int32 (``-1`` = unallocated);
    q_positions: (B,C) with row c at ``q_positions[:,0]+c`` (the
    chunked-prefill contract: chunks are contiguous); cache_len: () or
    (B,) written tokens incl. this chunk; sm_scale: the score scale,
    1/sqrt(D) when None. Returns (B,C,Hq,D).

    Grid is ``(batch, kv_heads, logical_pages)``: the chunk's queries
    stream every owned page once through the block-table index map
    instead of materializing a dense (B, Smax) view.
    """
    B, C, Hq, D = q.shape
    _, Hkv, P, page, _ = k_pages.shape
    nL = block_tables.shape[-1]
    G = Hq // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    d_pad = -(-D // 128) * 128

    qh = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, d_pad - D)))
    # head h -> (h // G, h % G) as in the dense layout; tile row = g*C + c
    qh = qh.reshape(B, C, Hkv, G, d_pad).transpose(0, 2, 3, 1, 4)
    qh = qh.reshape(B, Hkv, G * C, d_pad)
    kh, vh, layer = _lane_pools(k_pages, v_pages, layer)

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
    q_spec = pl.BlockSpec((None, None, G * C, d_pad),
                          lambda b, h, p, *_: (b, h, 0, 0))
    page_spec = pl.BlockSpec(
        (None, None, None, page, d_pad),
        lambda b, h, p, tbl, lens, start, layer: (
            layer[0], h, tbl[b, p], 0, 0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, Hkv, nL),
        in_specs=[q_spec, page_spec, page_spec],
        out_specs=q_spec,
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
    )(tbl, lens, start, layer, qh, kh, vh)
    out = out.reshape(B, Hkv, G, C, d_pad).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, C, Hq, d_pad)[..., :D]


def paged_attention_pallas(
    q, k_pages, v_pages, block_tables, *, layer, q_position, cache_len,
    window: int | None = None, softcap: float | None = None,
    interpret: bool = False, sm_scale: float | None = None,
):
    """q: (B,1,Hq,D); k_pages/v_pages: the stacked pools
    (R, Hkv, P, page, D), read at ``layer`` (() int); block_tables:
    (B, n_logical) int32 (``-1`` = unallocated); sm_scale: the score
    scale, 1/sqrt(D) when None. Returns (B,1,Hq,D); a slot with no live
    key (``cache_len`` 0, or no allocated page below it) gets zeros.

    One copy fetches a page of every KV head straight from the stacked
    pool. The grid is one step a slot; inside it a loop walks the slot's
    live pages ``pages_per_step`` at a time with double-buffered copies
    from HBM. A head dim short of the 128-lane width is padded to it.
    """
    B, _, Hq, D = q.shape
    _, Hkv, _, page, _ = k_pages.shape
    nL = block_tables.shape[-1]
    G = Hq // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    d_pad = -(-D // 128) * 128
    pps = pages_per_step(page, nL, Hkv, d_pad, G, k_pages.dtype.itemsize)

    qh = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, d_pad - D)))
    qh = qh.reshape(B, Hkv, G, d_pad)  # head h -> (h // G, h % G), as dense
    kh, vh, layer = _lane_pools(k_pages, v_pages, layer)

    tbl = block_tables.astype(jnp.int32)
    lens = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,)
    )
    qpos = jnp.broadcast_to(
        jnp.asarray(q_position, jnp.int32).reshape(-1), (B,)
    )

    kernel = functools.partial(
        _paged_kernel,
        page_size=page, n_logical=nL, pps=pps, window=window,
        softcap=softcap, sm_scale=sm_scale,
    )
    qo_spec = pl.BlockSpec((None, Hkv, G, d_pad), lambda b, *_: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[
            qo_spec,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((2, Hkv, pps * page, d_pad), kh.dtype),
            pltpu.VMEM((2, Hkv, pps * page, d_pad), vh.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((Hkv, G, d_pad), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, d_pad), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(tbl, lens, qpos, layer, qh, kh, vh)
    return out.reshape(B, Hq, d_pad)[:, None, :, :D]
