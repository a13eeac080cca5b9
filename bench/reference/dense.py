"""Plain float32 reference of the dense decoder that the GLM-4
configuration serves: pre-norm blocks of grouped-query attention with qkv
bias and partial interleaved rotary embedding, and a SwiGLU MLP.

It imports nothing of the program. It reads the benchmark-made weights by
their names, one layer at a time (each layer upcast to float32 inside its
own call), so that it fits beside the bf16 weights. Every matmul runs at
``highest`` precision. ``quant=True`` gives the control: every matmul's
inputs rounded to float8 (e4m3, scaled per row and per output column).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 512    # query rows per attention block
PAD = 512        # sequences are padded to a multiple of this (causal: harmless)
ROWS = 256       # logits rows per head call


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    if quant:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision="highest")


def _rmsnorm(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * s


def _rope(x, theta, frac):
    """x: (T, H, D); interleaved pairs over the first ``frac`` of D."""
    T, _, D = x.shape
    rot = int(D * frac) // 2 * 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    y = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return jnp.concatenate([y.reshape(T, -1, rot), x[..., rot:]], -1)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _layer(blk, i, x, m, quant):
    f32 = lambda a: a[i].astype(jnp.float32)
    at, mlp = blk["attn"], blk["mlp"]
    T = x.shape[0]
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    h = _rmsnorm(x, f32(blk["norm_in"]["scale"]), m["norm_eps"])
    q = _mm(h, f32(at["wq"]), quant)
    k = _mm(h, f32(at["wk"]), quant)
    v = _mm(h, f32(at["wv"]), quant)
    if m.get("attn_bias"):
        q, k, v = q + f32(at["bq"]), k + f32(at["bk"]), v + f32(at["bv"])
    q = _rope(q.reshape(T, hq, hd), m["rope_theta"], m["partial_rotary"])
    k = _rope(k.reshape(T, hkv, hd), m["rope_theta"], m["partial_rotary"])
    v = v.reshape(T, hkv, hd)
    g = hq // hkv
    kk = jnp.repeat(k, g, axis=1)        # query head j reads kv head j // g
    vv = jnp.repeat(v, g, axis=1)
    keys = jnp.arange(T)

    def block(qs):
        qb, pos = qs
        s = jnp.einsum("qhd,khd->hqk", qb, kk, precision="highest")
        s = s / math.sqrt(hd)
        s = jnp.where(keys[None, None, :] <= pos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vv, precision="highest")

    nb = T // Q_BLOCK
    o = jax.lax.map(block, (q.reshape(nb, Q_BLOCK, hq, hd),
                            keys.reshape(nb, Q_BLOCK)))
    x = x + _mm(o.reshape(T, hq * hd), f32(at["wo"]), quant)
    h = _rmsnorm(x, f32(blk["norm_mlp"]["scale"]), m["norm_eps"])
    a = jax.nn.silu(_mm(h, f32(mlp["wi_gate"]), quant)) * _mm(h, f32(mlp["wi_up"]), quant)
    return x + _mm(a, f32(mlp["wo"]), quant)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _head(params, x, m, quant):
    x = _rmsnorm(x, params["norm_f"]["scale"].astype(jnp.float32), m["norm_eps"])
    return _mm(x, params["head"].astype(jnp.float32), quant)[:, : m["vocab"]]


@jax.jit
def _embed(table, ids):
    return table[ids].astype(jnp.float32)


def logits_at(params, m: dict, ids, rows, quant: bool = False):
    """float32 logits (len(rows), vocab) of sequence ``ids`` at positions
    ``rows``. ``m`` is the configuration's ``model`` block, frozen."""
    T = len(ids)
    Tp = -(-T // PAD) * PAD
    x = _embed(params["embed"], jnp.asarray(list(ids) + [0] * (Tp - T), jnp.int32))
    (blk,) = params["slots"].values()
    for i in range(m["n_layers"]):
        x = _layer(blk, jnp.int32(i), x, m, quant)
    out = []
    rows = list(rows)
    for b in range(0, len(rows), ROWS):
        sel = rows[b : b + ROWS]
        sel = sel + [sel[-1]] * (ROWS - len(sel))
        lg = _head(params, x[jnp.asarray(sel)], m, quant)
        out.append(lg[: len(rows[b : b + ROWS])])
    return jnp.concatenate(out)


def frozen(m: dict):
    """A hashable copy of a model block (a static argument of the calls)."""
    return _Frozen(m)


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))
