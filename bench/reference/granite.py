"""Plain float32 reference of the Granite-4.0-H model that the
``granite-4.0-h-small-10l`` configuration serves (hf
ibm-granite/granite-4.0-h-small, ``granitemoehybrid``).

Every layer is a mixer and then a mixture-of-experts FFN, each pre-normed
and entering the residual times ``residual_multiplier``:

- Mamba2 mixer, written as its per-token recurrence (not the chunked
  SSD form): in_proj to [z, xBC, dt]; a causal depthwise convolution with
  bias over xBC, then SiLU; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; per head ``h = exp(dt A) h + dt B x^T`` and
  ``y = C h + D x``; then ``y * silu(z)``, RMSNorm, out_proj;
- attention mixer: grouped-query attention with no position embedding,
  scores scaled by ``attention_multiplier``, no bias;
- MoE: the router's top ``top_k`` logits, softmax over those; each held
  expert (SwiGLU) weighted by its gate, zero where it was not chosen;
  plus one shared SwiGLU expert.

The embedding is multiplied by ``embedding_multiplier``; the head is the
tied embedding, its logits divided by ``logits_scaling``.

Departures from the published model, as the configuration states: the
model is cut in depth to its first ``n_layers`` layers, and each layer
holds only the experts ``experts_held`` of its ``n_experts`` (pairs
routed to the others add nothing), the share one chip of the stated
deployment holds.

It imports nothing of the program. It reads the benchmark-made weights by
their names, one layer at a time (each layer upcast to float32 inside its
own call). Every matmul runs at ``highest`` precision. ``quant=True``
gives the control: every matmul's inputs rounded to float8 (e4m3, scaled
per row and per output column).
"""

from __future__ import annotations

import functools

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


def _swiglu(x, wg, wu, wo, quant):
    return _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wo, quant)


def _mamba(p, x, m, quant):
    """The Mamba2 mixer over a whole sequence x (T, d), token by token."""
    T = x.shape[0]
    h, hp, n, K = m["ssm_heads"], m["ssm_head_dim"], m["d_state"], m["d_conv"]
    di = h * hp
    proj = _mm(x, p["in_proj"], quant)
    z, xbc, dt = proj[:, :di], proj[:, di : 2 * di + 2 * n], proj[:, 2 * di + 2 * n :]
    xp = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    conv = sum(xp[k : k + T] * p["conv_w"][k] for k in range(K)) + p["conv_b"]
    conv = jax.nn.silu(conv)
    xs = conv[:, :di].reshape(T, h, hp)
    B, C = conv[:, di : di + n], conv[:, di + n :]
    dt = jax.nn.softplus(dt + p["dt_bias"])                       # (T, h)
    A = -jnp.exp(p["A_log"])                                      # (h,)

    def step(state, inp):                                         # (h, hp, n)
        x_t, b_t, c_t, dt_t = inp
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + dt_t[:, None, None] * x_t[:, :, None] * b_t[None, None, :])
        y = jnp.einsum("hpn,n->hp", state, c_t, precision="highest")
        return state, y + p["D"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((h, hp, n), jnp.float32), (xs, B, C, dt))
    y = y.reshape(T, di) * jax.nn.silu(z)
    y = _rmsnorm(y, p["norm"], m["norm_eps"])
    return _mm(y, p["out_proj"], quant)


def _attention(p, x, m, quant):
    """Causal GQA over a whole sequence x (T, d): no position embedding."""
    T = x.shape[0]
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    q = _mm(x, p["wq"], quant).reshape(T, hq, hd)
    k = _mm(x, p["wk"], quant).reshape(T, hkv, hd)
    v = _mm(x, p["wv"], quant).reshape(T, hkv, hd)
    g = hq // hkv
    kk = jnp.repeat(k, g, axis=1)        # query head j reads kv head j // g
    vv = jnp.repeat(v, g, axis=1)
    keys = jnp.arange(T)

    def block(qs):
        qb, pos = qs
        s = jnp.einsum("qhd,khd->hqk", qb, kk, precision="highest")
        s = s * m["attention_multiplier"]
        s = jnp.where(keys[None, None, :] <= pos[None, :, None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr, vv, precision="highest")

    nb = T // Q_BLOCK
    o = jax.lax.map(block, (q.reshape(nb, Q_BLOCK, hq, hd), keys.reshape(nb, Q_BLOCK)))
    return _mm(o.reshape(T, hq * hd), p["wo"], quant)


def _moe(p, x, m, quant):
    """The held experts, each weighted by its softmax-of-top-k gate (zero
    where not chosen), and the shared expert."""
    T = x.shape[0]
    first, held = m["experts_held"]
    logits = _mm(x, p["router"], quant)                           # (T, E)
    top, idx = jax.lax.top_k(logits, m["top_k"])
    gate = jnp.zeros_like(logits).at[jnp.arange(T)[:, None], idx].set(
        jax.nn.softmax(top, axis=-1))
    gate = gate[:, first : first + held]                          # (T, held)

    def expert(out, e):
        wg, wu, wo, gj = e
        return out + gj[:, None] * _swiglu(x, wg, wu, wo, quant), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          (p["wi_gate"], p["wi_up"], p["wo"], gate.T))
    sh = p["shared"]
    return out + _swiglu(x, sh["wi_gate"], sh["wi_up"], sh["wo"], quant)


@functools.partial(jax.jit, static_argnames=("m", "mixer", "quant"))
def _layer(blk, i, x, m, mixer, quant):
    f32 = lambda t: jax.tree_util.tree_map(lambda a: a[i].astype(jnp.float32), t)
    r = m["residual_multiplier"]
    h = _rmsnorm(x, f32(blk["norm_in"]["scale"]), m["norm_eps"])
    if mixer == "mamba":
        x = x + r * _mamba(f32(blk["mamba"]), h, m, quant)
    else:
        x = x + r * _attention(f32(blk["attn"]), h, m, quant)
    h = _rmsnorm(x, f32(blk["norm_mlp"]["scale"]), m["norm_eps"])
    return x + r * _moe(f32(blk["moe"]), h, m, quant)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _head(params, x, m, quant):
    x = _rmsnorm(x, params["norm_f"]["scale"].astype(jnp.float32), m["norm_eps"])
    w = params["embed"].astype(jnp.float32).T                    # tied
    return _mm(x, w, quant)[:, : m["vocab"]] / m["logits_scaling"]


@functools.partial(jax.jit, static_argnames=("mult",))
def _embed(table, ids, mult):
    return table[ids].astype(jnp.float32) * mult


def layers(params, m: dict) -> list:
    """(slot's weights, row, mixer) of each layer in order: slot ``i`` of
    the period holds layers ``i, i + period, ...`` stacked by row."""
    slots = sorted(params["slots"].items(),
                   key=lambda kv: int(kv[0].split("_", 1)[0][4:]))
    out = []
    for li in range(m["n_layers"]):
        name, blk = slots[li % len(slots)]
        out.append((blk, li // len(slots), "mamba" if "mamba" in blk else "attn"))
    return out


def logits_at(params, m: dict, ids, rows, quant: bool = False):
    """float32 logits (len(rows), vocab) of sequence ``ids`` at positions
    ``rows``. ``m`` is the configuration's ``model`` block, frozen."""
    T = len(ids)
    Tp = -(-T // PAD) * PAD
    x = _embed(params["embed"], jnp.asarray(list(ids) + [0] * (Tp - T), jnp.int32),
               float(m["embedding_multiplier"]))
    for blk, i, mixer in layers(params, m):
        x = _layer(blk, jnp.int32(i), x, m, mixer, quant)
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
