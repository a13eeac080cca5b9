"""Feed-forward layers: dense (SwiGLU/GeGLU/GELU) and Mixture-of-Experts.

MoE uses sort-based capacity dispatch (MegaBlocks/MaxText-style, adapted to
a dense-shape TPU formulation):
  router top-k -> flatten (token, expert) pairs -> sort by expert ->
  scatter into a per-expert capacity buffer (E, C, d) -> batched expert
  matmuls (einsum over the expert dim, sharded over the "expert" logical
  axis = EP) -> combine with routing weights.

Dropped tokens (beyond capacity) fall through via the residual connection —
the paper-standard "token dropping" behaviour; capacity_factor controls it.
The router also returns per-expert token counts: the monitor's
**expert load-balance** factor (DESIGN.md §3) reads exactly this.

Serving uses ``moe_dropless`` instead: grouped matmuls over exactly the
pairs routed to the experts this chip holds, so a served token never
depends on what else is in the batch.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.layers.common import LogicalConstraints, NULL_CONSTRAINTS, ParamSpec


# ---------------------------------------------------------------------------
# dense FFN
# ---------------------------------------------------------------------------


def mlp_params(cfg, d_ff: int | None = None) -> dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    if cfg.act in ("swiglu", "geglu"):
        return {
            "wi_gate": ParamSpec((d, f), ("embed", "mlp")),
            "wi_up": ParamSpec((d, f), ("embed", "mlp")),
            "wo": ParamSpec((f, d), ("mlp", "embed_out"),
                            scale=1.0 / (math.sqrt(f) * math.sqrt(2 * cfg.n_layers))),
        }
    return {
        "wi": ParamSpec((d, f), ("embed", "mlp")),
        "wo": ParamSpec((f, d), ("mlp", "embed_out"),
                        scale=1.0 / (math.sqrt(f) * math.sqrt(2 * cfg.n_layers))),
    }


def _act(name: str):
    return {
        "swiglu": jax.nn.silu,
        "geglu": lambda x: jax.nn.gelu(x, approximate=True),
        "gelu": lambda x: jax.nn.gelu(x, approximate=True),
        "relu": jax.nn.relu,
    }[name]


def mlp_block(params, x, cfg, lc: LogicalConstraints = NULL_CONSTRAINTS):
    compute = cfg.compute_dtype
    act = _act(cfg.act)
    if "wi_gate" in params:
        g = x @ params["wi_gate"].astype(compute)
        u = x @ params["wi_up"].astype(compute)
        h = act(g) * u
    else:
        h = act(x @ params["wi"].astype(compute))
    h = lc(h, "batch", "seq_mlp", "mlp")
    return h @ params["wo"].astype(compute)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------


def moe_params(cfg) -> dict:
    m = cfg.moe
    d, f, e, h = cfg.d_model, m.d_ff, m.n_experts, m.n_held
    p = {
        "router": ParamSpec((d, e), ("embed", None), scale=0.02),
        "wi_gate": ParamSpec((h, d, f), ("experts", "embed", "expert_mlp")),
        "wi_up": ParamSpec((h, d, f), ("experts", "embed", "expert_mlp")),
        "wo": ParamSpec((h, f, d), ("experts", "expert_mlp", "embed_out"),
                        scale=1.0 / (math.sqrt(f) * math.sqrt(2 * cfg.n_layers))),
    }
    if m.n_shared_experts:
        p["shared"] = mlp_params(cfg, d_ff=m.d_ff * m.n_shared_experts)
    return p


def router_topk(logits, k: int, normalize: bool):
    """logits: (N,E) fp32. Returns (weights (N,k), experts (N,k))."""
    weights, experts = jax.lax.top_k(logits, k)
    if normalize:
        weights = jax.nn.softmax(weights, axis=-1)
    else:
        weights = jax.nn.softmax(logits, axis=-1)
        weights = jnp.take_along_axis(weights, experts, axis=-1)
    return weights, experts


def _dispatch_group(xt, logits, E, K, C, normalize, compute):
    """Dispatch one token group (runs under vmap over groups).

    xt: (n, d); logits: (n, E). Returns (xbuf (E,C,d), st, sw, keep, slot,
    expert_counts) — everything needed to combine after expert compute.
    """
    n = xt.shape[0]
    weights, experts = router_topk(logits, K, normalize)   # (n,K)
    pair_expert = experts.reshape(-1)                      # (n*K,)
    pair_token = jnp.repeat(jnp.arange(n), K)
    pair_weight = weights.reshape(-1)
    order = jnp.argsort(pair_expert)                       # local sort only
    se, st, sw = pair_expert[order], pair_token[order], pair_weight[order]
    # position within expert segment (arange/bincount formulation: cumsum-of-
    # ones and searchsorted lower to giant reduce-windows at scale)
    expert_counts = jnp.zeros((E,), jnp.int32).at[pair_expert].add(1)
    first_idx = jnp.cumsum(expert_counts) - expert_counts
    pos_in_expert = jnp.arange(n * K, dtype=jnp.int32) - first_idx[se]
    keep = pos_in_expert < C
    slot = se * C + jnp.where(keep, pos_in_expert, 0)
    src = xt[st].astype(compute) * keep[:, None].astype(compute)
    xbuf = jnp.zeros((E * C, xt.shape[1]), compute).at[slot].add(src)
    return xbuf.reshape(E, C, -1), st, sw, keep, slot, expert_counts


def moe_block(params, x, cfg, lc: LogicalConstraints = NULL_CONSTRAINTS):
    """x: (B,S,d). Returns (out, aux) with aux["expert_load"]: (E,) counts.

    GShard-style grouped dispatch: tokens are split into G groups aligned
    with the data shards; sort/scatter stay *within* a group (no cross-shard
    sort), and the only cross-device movement is the (G, E, C, d) buffer
    resharding from group-major (data) to expert-major (model) — the MoE
    all-to-all, inserted by GSPMD from the sharding constraints.
    """
    m = cfg.moe
    B, S, d = x.shape
    N = B * S
    E, K = m.n_experts, m.top_k
    compute = cfg.compute_dtype

    G = lc.group_count("batch", B)
    n_loc = N // G
    C = m.capacity(n_loc)

    xt = lc(x, "batch", None, None).reshape(G, n_loc, d)
    xt = lc(xt, "batch", None, None)
    logits = (xt @ params["router"].astype(compute)).astype(jnp.float32)

    xbuf, st, sw, keep, slot, counts = jax.vmap(
        lambda xg, lg: _dispatch_group(xg, lg, E, K, C, m.normalize_topk, compute)
    )(xt, logits)
    # dispatch all-to-all: group-major -> expert-major
    xbuf = lc(xbuf, "batch", "experts", None, None)   # (G,E,C,d)

    act = _act("swiglu" if m.gated else "gelu")
    g = jnp.einsum("gecd,edf->gecf", xbuf, params["wi_gate"].astype(compute))
    if m.gated:
        u = jnp.einsum("gecd,edf->gecf", xbuf, params["wi_up"].astype(compute))
        h = act(g) * u
    else:
        h = act(g)
    h = lc(h, "batch", "experts", None, "expert_mlp")
    ybuf = jnp.einsum("gecf,efd->gecd", h, params["wo"].astype(compute))
    # combine all-to-all: expert-major -> group-major
    ybuf = lc(ybuf, "batch", None, None, None)

    def _combine(yb, st_g, sw_g, keep_g, slot_g):
        y = yb.reshape(E * C, d)[slot_g]
        y = y * (sw_g * keep_g).astype(compute)[:, None]
        return jnp.zeros((n_loc, d), compute).at[st_g].add(y)

    out = jax.vmap(_combine)(ybuf, st, sw, keep, slot)    # (G, n_loc, d)
    out = lc(out, "batch", None, None).reshape(N, d)

    if m.n_shared_experts:
        out = out + mlp_block(
            params["shared"], x.reshape(N, d), cfg, lc=NULL_CONSTRAINTS
        )

    expert_load = jnp.sum(counts, axis=0).astype(jnp.float32)  # (E,)
    me = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=(0, 1))
    ce = expert_load / jnp.maximum(jnp.sum(expert_load), 1.0)
    lb_loss = E * jnp.sum(me * ce)                             # switch-style
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    aux = {"expert_load": expert_load, "moe_lb_loss": lb_loss, "moe_z_loss": z_loss}
    return out.reshape(B, S, d), aux


def moe_dropless(params, x, cfg, seq_mask=None):
    """x: (B,S,d). Dropless MoE over the experts this chip holds.

    Each token is routed over all ``n_experts`` (the router keeps its
    published width); its (token, expert) pairs are sorted by expert, the
    pairs of experts held elsewhere and of positions masked by
    ``seq_mask`` last, and the held experts run as grouped matmuls
    (``jax.lax.ragged_dot``) over exactly the pairs routed to them. No
    capacity: no pair is dropped at any token count. A pair routed to an
    expert not held here adds nothing; the shared expert is added once.

    Returns (out, aux): ``moe_routed``, the pairs on held experts, and
    ``moe_busiest``, the pairs of the busiest held expert (int32 scalars).
    """
    m = cfg.moe
    B, S, d = x.shape
    N, K, H = B * S, m.top_k, m.n_held
    compute = cfg.compute_dtype
    xt = x.reshape(N, d)
    logits = (xt @ params["router"].astype(compute)).astype(jnp.float32)
    weights, experts = router_topk(logits, K, m.normalize_topk)   # (N,K)
    local = experts.reshape(-1) - m.first_held
    held = (local >= 0) & (local < H)
    if seq_mask is not None:
        held &= jnp.repeat(seq_mask.reshape(N), K)
    group = jnp.where(held, local, H)
    order = jnp.argsort(group)
    sizes = jnp.zeros((H + 1,), jnp.int32).at[group].add(1)[:H]
    rows = xt[order // K].astype(compute)          # each sorted pair's token
    act = _act("swiglu" if m.gated else "gelu")
    g = jax.lax.ragged_dot(rows, params["wi_gate"].astype(compute), sizes)
    if m.gated:
        h = act(g) * jax.lax.ragged_dot(rows, params["wi_up"].astype(compute), sizes)
    else:
        h = act(g)
    y = jax.lax.ragged_dot(h, params["wo"].astype(compute), sizes)
    # back to pair order; rows past the held groups are not computed
    y = y[jnp.argsort(order)]
    w = jnp.where(held, weights.reshape(-1), 0.0)
    y = jnp.where(held[:, None], y.astype(jnp.float32), 0.0) * w[:, None]
    out = y.reshape(N, K, d).sum(axis=1)
    if m.n_shared_experts:
        out = out + mlp_block(params["shared"], xt, cfg).astype(jnp.float32)
    aux = {"moe_routed": jnp.sum(sizes), "moe_busiest": jnp.max(sizes)}
    return out.astype(compute).reshape(B, S, d), aux
