"""Flash attention TPU kernel (pl.pallas_call + explicit BlockSpec tiling).

TPU adaptation of the FlashAttention-2 schedule (DESIGN.md §9):
  * grid = (batch*kv_heads, q_blocks); each program instance owns one
    (B*Hkv, q_block) tile and streams kv blocks through VMEM with the
    online-softmax recurrence — scores never touch HBM (the dominant term
    of the §Roofline memory analysis for train/prefill cells);
  * block shapes are MXU-aligned (q_block x d and kv_block x d tiles,
    d padded to a 128 multiple by the wrapper);
  * the kv loop is a fori_loop with a causal upper bound: fully-future
    blocks are never fetched (compute AND bandwidth saving vs masking);
  * GQA handled by indexing the kv head = q head // group outside the
    kernel (the wrapper reshapes to one kv head per program).

Validated in interpret mode on CPU against ref.attention_reference across
shapes/dtypes (tests/test_kernels.py); on real TPUs the same code lowers
through Mosaic.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro import compat

pl = compat.pallas()

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, *,
    kv_seq_len: int, block_kv: int, causal: bool,
    window: int | None, softcap: float | None, block_q: int, sm_scale: float,
):
    """One (q_block x head_dim) tile vs the full kv stream.

    Refs (VMEM):
      q_ref: (block_q, d)    k_ref/v_ref: (kv_seq_len, d)    o_ref: (block_q, d)
    """
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * sm_scale
    d = q.shape[-1]

    q_start = qi * block_q
    num_kv_blocks = pl.cdiv(kv_seq_len, block_kv)
    if causal:
        # last kv block any row of this q tile can see
        hi = jax.lax.div(q_start + block_q - 1, block_kv) + 1
        hi = jnp.minimum(hi, num_kv_blocks)
    else:
        hi = num_kv_blocks

    def body(ki, carry):
        o, m, l = carry
        k = k_ref[pl.ds(ki * block_kv, block_kv), :]
        v = v_ref[pl.ds(ki * block_kv, block_kv), :]
        s = q @ k.astype(jnp.float32).T  # (block_q, block_kv)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < kv_seq_len
        if causal:
            mask &= kpos <= qpos
        if window is not None and window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - m_safe[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_safe))
        l = l * alpha + jnp.sum(p, axis=-1)
        o = o * alpha[:, None] + p @ v.astype(jnp.float32)
        return o, m_new, l

    o0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, hi, body, (o0, m0, l0))
    o_ref[...] = (o / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q, k, v, *,
    causal: bool = True, window: int | None = None,
    softcap: float | None = None,
    block_q: int = 128, block_kv: int = 128,
    interpret: bool = False,
):
    """q: (B,Sq,Hq,D); k,v: (B,Sk,Hkv,D). Returns (B,Sq,Hq,D).

    The wrapper maps GQA onto a (B*Hq, q_blocks) grid: each q head reads
    its kv head (Hq//G). Head dim is padded to a multiple of 128 (MXU lane
    width); seq dims to their block sizes.
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    sm_scale = 1.0 / math.sqrt(D)

    d_pad = -(-D // 128) * 128
    sq_pad = -(-Sq // block_q) * block_q
    sk_pad = -(-Sk // block_kv) * block_kv

    qp = jnp.pad(q, ((0, 0), (0, sq_pad - Sq), (0, 0), (0, d_pad - D)))
    kp = jnp.pad(k, ((0, 0), (0, sk_pad - Sk), (0, 0), (0, d_pad - D)))
    vp = jnp.pad(v, ((0, 0), (0, sk_pad - Sk), (0, 0), (0, d_pad - D)))

    # (B*Hq, S, d) with q head -> kv head mapping
    qh = qp.transpose(0, 2, 1, 3).reshape(B * Hq, sq_pad, d_pad)
    kh = kp.transpose(0, 2, 1, 3)
    vh = vp.transpose(0, 2, 1, 3)
    head_map = jnp.repeat(jnp.arange(Hkv), G)  # q head -> kv head
    kh = kh[:, head_map].reshape(B * Hq, sk_pad, d_pad)
    vh = vh[:, head_map].reshape(B * Hq, sk_pad, d_pad)

    grid = (B * Hq, sq_pad // block_q)
    kernel = functools.partial(
        _flash_kernel,
        kv_seq_len=Sk, block_kv=block_kv, causal=causal,
        window=window, softcap=softcap, block_q=block_q, sm_scale=sm_scale,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d_pad), lambda h, i: (h, i, 0)),
            pl.BlockSpec((None, sk_pad, d_pad), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((None, sk_pad, d_pad), lambda h, i: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d_pad), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, sq_pad, d_pad), q.dtype),
        interpret=interpret,
    )(qh, kh, vh)

    out = out.reshape(B, Hq, sq_pad, d_pad)[:, :, :Sq, :D].transpose(0, 2, 1, 3)
    return out
