"""Plain float32 reference of the xLSTM language model that the xlstm-350m
configuration trains: mLSTM blocks (up-projection, causal depthwise
convolution, gated matrix memory in its parallel stabilized form, output
norm and gate, down-projection) and sLSTM blocks (scalar memory with a
per-head recurrence, then a GeGLU MLP), a final norm and a softmax
cross-entropy with a z-loss over the padded vocabulary, and AdamW with a
float32 master copy, gradient clipping and the warm-up-cosine schedule.

It imports nothing of the program and reads the benchmark-made weights by
their names. Every matmul runs at ``highest`` precision; each block is
recomputed in the backward pass so that it fits on one chip.
``quant=True`` gives the control, the usual float8 training recipe:
every projection's inputs rounded to float8 e4m3 and, in the backward
pass, the gradient flowing into it to float8 e5m2, each tensor with one
scale.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _fp8(x, dtype):
    """``x`` rounded to ``dtype`` under one scale for the whole tensor."""
    s = jnp.max(jnp.abs(x)) / float(jnp.finfo(dtype).max)
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _mm8(x, w):
    return jnp.matmul(_fp8(x, jnp.float8_e4m3fn), _fp8(w, jnp.float8_e4m3fn),
                      precision="highest")


def _mm8_fwd(x, w):
    xq, wq = _fp8(x, jnp.float8_e4m3fn), _fp8(w, jnp.float8_e4m3fn)
    return jnp.matmul(xq, wq, precision="highest"), (xq, wq)


def _mm8_bwd(res, g):
    xq, wq = res
    gq = _fp8(g, jnp.float8_e5m2)
    dx = jnp.matmul(gq, wq.T, precision="highest")
    dw = jnp.einsum("...i,...j->ij", xq, gq, precision="highest")
    return dx, dw


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def _mm(x, w, quant):
    if quant:
        return _mm8(x, w)
    return jnp.matmul(x, w, precision="highest")


def _rmsnorm(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * s


def _mlstm(p, x, m, quant):
    B, S, d = x.shape
    h = m["n_heads"]
    di = int(m["proj_factor"] * d)
    hp = di // h
    up = _mm(x, p["up_proj"], quant)
    xm, z = up[..., :di], up[..., di:]
    K = p["conv_w"].shape[0]
    xp = jnp.pad(xm, ((0, 0), (K - 1, 0), (0, 0)))
    xc = sum(xp[:, i : i + S] * p["conv_w"][i] for i in range(K)) + p["conv_b"]
    xc = jax.nn.silu(xc)
    q = _mm(xc, p["wq"], quant).reshape(B, S, h, hp)
    k = _mm(xc, p["wk"], quant).reshape(B, S, h, hp)
    v = _mm(xm, p["wv"], quant).reshape(B, S, h, hp)
    gates = _mm(xm, p["w_if"], quant)
    log_i = gates[..., :h] + p["b_i"]
    log_f = jax.nn.log_sigmoid(gates[..., h:] + p["b_f"])
    cf = jnp.cumsum(log_f, axis=1).transpose(0, 2, 1)          # (B,h,S)
    logd = cf[..., :, None] - cf[..., None, :] + log_i.transpose(0, 2, 1)[..., None, :]
    causal = jnp.tril(jnp.ones((S, S), bool))
    logd = jnp.where(causal, logd, -jnp.inf)
    mt = jnp.max(logd, axis=-1)                                # (B,h,S)
    w = jnp.exp(logd - mt[..., None])
    s = jnp.einsum("bthp,bjhp->bhtj", q, k, precision="highest") / math.sqrt(hp)
    c = s * w
    num = jnp.einsum("bhtj,bjhp->bthp", c, v, precision="highest")
    den = jnp.maximum(jnp.abs(jnp.sum(c, -1)), jnp.exp(-mt))   # (B,h,S)
    y = (num / den.transpose(0, 2, 1)[..., None]).reshape(B, S, di)
    y = _rmsnorm(y, p["norm"], m["norm_eps"]) * jax.nn.silu(z)
    return _mm(y, p["down_proj"], quant)


def _slstm(p, x, m, quant):
    B, S, d = x.shape
    nh = m["n_heads"]
    dh = d // nh
    wx = _mm(x, p["w_in"], quant) + p["b"]                     # (B,S,4d)

    def step(carry, w_t):
        c, n, hp, mm = carry
        rec = jnp.einsum("bhd,hdk->bhk", hp, p["r"], precision="highest")
        pre = w_t + rec.reshape(B, 4 * d)
        i, f, zz, o = [t.reshape(B, nh, dh) for t in jnp.split(pre, 4, -1)]
        lf = jax.nn.log_sigmoid(f)
        m_new = jnp.maximum(lf + mm, i)
        ip, fp = jnp.exp(i - m_new), jnp.exp(lf + mm - m_new)
        c = fp * c + ip * jnp.tanh(zz)
        n = fp * n + ip
        hn = jax.nn.sigmoid(o) * c / jnp.maximum(n, 1e-6)
        return (c, n, hn, m_new), hn

    zero = jnp.zeros((B, nh, dh), jnp.float32)
    carry0 = (zero, zero, zero, jnp.full((B, nh, dh), -1e30, jnp.float32))
    _, hs = jax.lax.scan(step, carry0, wx.transpose(1, 0, 2))
    return _rmsnorm(hs.transpose(1, 0, 2, 3).reshape(B, S, d), p["norm"], m["norm_eps"])


def _geglu(p, x, quant):
    a = jax.nn.gelu(_mm(x, p["wi_gate"], quant), approximate=True)
    return _mm(a * _mm(x, p["wi_up"], quant), p["wo"], quant)


def loss_sums(params, tokens, labels, m, quant=False):
    """(sum of per-token loss with z-loss, number of labelled tokens)."""
    x = params["embed"][tokens]
    eps = m["norm_eps"]
    names = sorted(params["slots"])

    def apply(blk, x, kind):
        h = _rmsnorm(x, blk["norm_in"]["scale"], eps)
        if kind == "mlstm":
            return x + _mlstm(blk["mlstm"], h, m, quant)
        x = x + _slstm(blk["slstm"], h, m, quant)
        h2 = _rmsnorm(x, blk["norm_mlp"]["scale"], eps)
        return x + _geglu(blk["mlp"], h2, quant)

    def period(x, slots):        # one period of the pattern, block by block
        for name in names:
            kind = name.split("_", 1)[1]
            x = jax.checkpoint(apply, static_argnums=2)(slots[name], x, kind)
        return x, None

    x, _ = jax.lax.scan(period, x, params["slots"])
    x = _rmsnorm(x, params["norm_f"]["scale"], eps)

    @jax.checkpoint
    def ce(x, lab):
        lg = _mm(x, params["head"], quant)
        lse = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, jnp.maximum(lab, 0)[..., None], -1)[..., 0]
        w = (lab >= 0).astype(jnp.float32)
        return jnp.sum(((lse - gold) + m["z_loss"] * lse**2) * w), jnp.sum(w)

    tot, cnt = 0.0, 0.0
    for b in range(x.shape[0]):
        s, c = ce(x[b], labels[b])
        tot, cnt = tot + s, cnt + c
    return tot, cnt


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _grad_block(params, tokens, labels, m, quant):
    f = lambda p: loss_sums(p, tokens, labels, m, quant)
    (s, c), g = jax.value_and_grad(f, has_aux=True)(params)
    return s, c, g


def loss_and_grad(params, tokens, labels, m, quant=False, rows=4):
    """Mean loss over labelled tokens and its gradient, ``rows`` sequences
    at a time."""
    tot, cnt, gsum = 0.0, 0.0, None
    for b in range(0, tokens.shape[0], rows):
        s, c, g = _grad_block(params, tokens[b : b + rows], labels[b : b + rows], m, quant)
        tot, cnt = tot + s, cnt + c
        gsum = g if gsum is None else jax.tree_util.tree_map(jnp.add, gsum, g)
    return tot / cnt, jax.tree_util.tree_map(lambda a: a / cnt, gsum)


def lr_scale(step: int, warmup: int, total: int, floor: float = 0.1) -> float:
    warm = min(step / max(warmup, 1), 1.0)
    prog = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return warm * (floor + (1 - floor) * 0.5 * (1 + math.cos(math.pi * prog)))


@functools.partial(jax.jit, static_argnames=("o",), donate_argnums=(0, 2, 3))
def adamw(params, grads, mom, vel, t, lr, o):
    """One AdamW update of the float32 master ``params``; ``t`` counts from
    1. Takes the place of ``params`` and the moments (donated, so that a model's
    optimizer state fits once on the chip). Returns (params, m, v, norm of
    each leaf of the clipped gradient)."""
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads)))
    clip = jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(gn, 1e-12))
    g = jax.tree_util.tree_map(lambda a: a * clip, grads)
    mom = jax.tree_util.tree_map(lambda m_, a: o["b1"] * m_ + (1 - o["b1"]) * a, mom, g)
    vel = jax.tree_util.tree_map(lambda v_, a: o["b2"] * v_ + (1 - o["b2"]) * a * a, vel, g)
    b1c, b2c = 1 - o["b1"] ** t, 1 - o["b2"] ** t
    params = jax.tree_util.tree_map(
        lambda p, m_, v_: p - lr * ((m_ / b1c) / (jnp.sqrt(v_ / b2c) + o["eps"])
                                     + o["weight_decay"] * p),
        params, mom, vel)
    return params, mom, vel, [jnp.sqrt(jnp.sum(a * a)) for a in jax.tree_util.tree_leaves(g)]
