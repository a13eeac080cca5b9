"""Fused RMSNorm TPU kernel (pl.pallas_call + BlockSpec VMEM tiling).

One pass over rows: each program instance normalizes a (block_rows, d)
tile fully inside VMEM (reduction + scale in registers; a single HBM read
and write per element, vs read-reduce-read-write for the unfused lowering).
d is padded to the 128-lane width by the wrapper; the mean uses the true
d so padding does not bias the variance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import compat

pl = compat.pallas()


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float, true_d: int,
                    zero_centered: bool):
    x = x_ref[...].astype(jnp.float32)  # (block_rows, d_pad)
    # padded lanes are zero and do not contribute; divide by true_d
    var = jnp.sum(x * x, axis=-1, keepdims=True) / true_d
    y = x * jax.lax.rsqrt(var + eps)
    s = s_ref[...].astype(jnp.float32)
    if zero_centered:
        s = 1.0 + s
    o_ref[...] = (y * s[None, :]).astype(o_ref.dtype)


def rmsnorm_pallas(
    x, scale, eps: float = 1e-6, zero_centered: bool = False,
    block_rows: int = 256, interpret: bool = False,
):
    """x: (..., d); scale: (d,). Returns same shape/dtype as x."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)

    d_pad = -(-d // 128) * 128
    block_rows = min(block_rows, rows)
    rows_pad = -(-rows // block_rows) * block_rows
    x2 = jnp.pad(x2, ((0, rows_pad - rows), (0, d_pad - d)))
    sp = jnp.pad(scale, (0, d_pad - d))

    kernel = functools.partial(
        _rmsnorm_kernel, eps=eps, true_d=d, zero_centered=zero_centered
    )
    out = pl.pallas_call(
        kernel,
        grid=(rows_pad // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((d_pad,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, d_pad), x.dtype),
        interpret=interpret,
    )(x2, sp)
    return out[:rows, :d].reshape(orig_shape)
