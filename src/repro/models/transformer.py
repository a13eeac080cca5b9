"""Model assembly: pattern-scanned transformer covering every architecture
in ``configs/archs.py`` (dense / MoE / hybrid-SSM / hybrid-SSM-MoE / xLSTM /
encoder / VLM backbone).

The layer stack is ``cfg.pattern`` repeated ``cfg.repeats`` times and scanned
over repeats (compact HLO, correct trip-count accounting in the HLO cost
analyzer). Per-slot params/caches are stacked over repeats.

Entry points:
  model_params(cfg)                  ParamSpec tree
  forward(params, batch, cfg, ...)   logits / loss+aux (train)
  init_cache(cfg, batch, max_len)    decode cache pytree
  prefill(params, batch, cfg, ...)   cache fill + last-position logits
  prefill_chunk(params, batch, ...)  incremental prefill at per-slot offsets
  decode_step(params, batch, ...)    one-token step (per-slot positions)
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.layers import attention as A
from repro.layers import ffn as FFN
from repro.layers import recurrent as R
from repro.layers.common import LogicalConstraints, NULL_CONSTRAINTS, ParamSpec
from repro.layers.norms import rmsnorm, rmsnorm_params


# ---------------------------------------------------------------------------
# parameter declaration
# ---------------------------------------------------------------------------


def _slot_params(cfg, kind: str) -> dict:
    p: dict[str, Any] = {"norm_in": rmsnorm_params(cfg.d_model)}
    if kind in ("attn", "local_attn"):
        p["attn"] = A.attention_params(cfg)
        p["norm_mlp"] = rmsnorm_params(cfg.d_model)
        p["mlp"] = FFN.mlp_params(cfg)
    elif kind == "moe":
        p["attn"] = A.attention_params(cfg)
        p["norm_mlp"] = rmsnorm_params(cfg.d_model)
        p["moe"] = FFN.moe_params(cfg)
    elif kind == "mamba2":
        p["mamba"] = R.mamba2_params(cfg)
    elif kind == "mamba2_moe":
        p["mamba"] = R.mamba2_params(cfg)
        p["norm_mlp"] = rmsnorm_params(cfg.d_model)
        p["moe"] = FFN.moe_params(cfg)
    elif kind == "mlstm":
        p["mlstm"] = R.mlstm_params(cfg)
    elif kind == "slstm":
        p["slstm"] = R.slstm_params(cfg)
        p["norm_mlp"] = rmsnorm_params(cfg.d_model)
        p["mlp"] = FFN.mlp_params(
            cfg, d_ff=int(cfg.d_model * cfg.xlstm.slstm_ff_factor) if cfg.xlstm else cfg.d_ff
        )
    else:
        raise ValueError(f"unknown block kind {kind}")
    return p


def _stack(tree, n: int):
    def f(spec: ParamSpec) -> ParamSpec:
        return ParamSpec(
            (n,) + spec.shape, ("layers",) + spec.logical, spec.init, spec.scale,
            spec.dtype,
        )

    return jax.tree_util.tree_map(f, tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def model_params(cfg) -> dict:
    d, v = cfg.d_model, cfg.vocab_padded
    params: dict[str, Any] = {}
    if cfg.frontend != "audio":  # audio stub feeds embeddings directly
        params["embed"] = ParamSpec((v, d), ("vocab", "embed"), scale=0.02)
    params["slots"] = {
        f"slot{i}_{kind}": _stack(_slot_params(cfg, kind), cfg.repeats)
        for i, kind in enumerate(cfg.pattern)
    }
    params["norm_f"] = rmsnorm_params(d)
    if not cfg.tie_embeddings and not cfg.encoder_only:
        params["head"] = ParamSpec((d, v), ("embed", "vocab"), scale=1.0 / math.sqrt(d))
    if cfg.encoder_only:
        params["head"] = ParamSpec((d, v), ("embed", "vocab"), scale=1.0 / math.sqrt(d))
    return params


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------


def _residual(x, o, cfg):
    """A sublayer's output into the residual stream."""
    if cfg.residual_multiplier != 1.0:
        o = o * cfg.residual_multiplier
    return x + o


def _moe(moe_params, h, cfg, lc, *, serving, seq_mask):
    """The MoE FFN. Serving (a cache is threaded through) and a chip that
    holds a share of the experts take the dropless held-expert layer, so
    that a served token never depends on its batch; training keeps the
    grouped capacity dispatch, whose layout follows the data shards."""
    if serving or cfg.moe.held is not None:
        return FFN.moe_dropless(moe_params, h, cfg, seq_mask=seq_mask)
    return FFN.moe_block(moe_params, h, cfg, lc=lc)


def _apply_slot(
    slot_params, kind: str, x, cfg, *, positions, lc, cache=None, cache_len=None,
    seq_mask=None, cache_attend=False, block_tables=None, layer=None,
):
    """One block of the pattern. Returns (x, new_cache, aux).

    ``seq_mask`` (B,S) marks valid positions: masked positions neither write
    the KV cache nor advance recurrent state (continuous batching: chunk
    padding and inactive decode slots). ``cache_attend`` routes S>1 attention
    against the written cache (chunked prefill) instead of in-chunk.
    ``layer`` is the block's row of the stacked paged pools, which come
    whole (see ``_run_stack``)."""
    aux: dict[str, Any] = {}
    h = rmsnorm(
        x, slot_params["norm_in"]["scale"], cfg.norm_eps, cfg.zero_centered_norm
    )
    new_cache = None
    if kind in ("attn", "local_attn", "moe"):
        window = cfg.window if kind == "local_attn" else None
        att_cache = cache.get("attn") if cache else None
        o, att_new = A.attention_block(
            slot_params["attn"], h, cfg, positions=positions, lc=lc,
            causal=not cfg.encoder_only, window=window,
            cache=att_cache, cache_len=cache_len,
            seq_mask=seq_mask, cache_attend=cache_attend,
            block_tables=block_tables, layer=layer,
        )
        # constrain BEFORE the residual add: the TP partial sums then lower
        # to reduce-scatter onto the seq-sharded residual instead of a full
        # f32 all-reduce (16x the bytes, measured on dbrx train_4k)
        o = lc(o, "batch", "seq", None)
        x = _residual(x, o, cfg)
        h2 = rmsnorm(
            x, slot_params["norm_mlp"]["scale"], cfg.norm_eps, cfg.zero_centered_norm
        )
        if kind == "moe":
            o2, moe_aux = _moe(slot_params["moe"], h2, cfg, lc,
                               serving=cache is not None, seq_mask=seq_mask)
            aux.update(moe_aux)
        else:
            o2 = FFN.mlp_block(slot_params["mlp"], h2, cfg, lc=lc)
        o2 = lc(o2, "batch", "seq", None)
        x = _residual(x, o2, cfg)
        if att_new is not None:
            new_cache = {"attn": att_new}
    elif kind in ("mamba2", "mamba2_moe"):
        o, mcache = R.mamba2_block(
            slot_params["mamba"], h, cfg, lc=lc,
            cache=cache.get("mamba") if cache else None, seq_mask=seq_mask,
        )
        o = lc(o, "batch", "seq", None)
        x = _residual(x, o, cfg)
        if kind == "mamba2_moe":
            h2 = rmsnorm(x, slot_params["norm_mlp"]["scale"], cfg.norm_eps,
                         cfg.zero_centered_norm)
            o2, moe_aux = _moe(slot_params["moe"], h2, cfg, lc,
                               serving=cache is not None, seq_mask=seq_mask)
            aux.update(moe_aux)
            x = _residual(x, lc(o2, "batch", "seq", None), cfg)
        if mcache is not None:
            new_cache = {"mamba": mcache}
    elif kind == "mlstm":
        o, mcache = R.mlstm_block(
            slot_params["mlstm"], h, cfg, lc=lc,
            cache=cache.get("mlstm") if cache else None, seq_mask=seq_mask,
        )
        x = _residual(x, o, cfg)
        if mcache is not None:
            new_cache = {"mlstm": mcache}
    elif kind == "slstm":
        o, scache = R.slstm_block(
            slot_params["slstm"], h, cfg, lc=lc,
            cache=cache.get("slstm") if cache else None, seq_mask=seq_mask,
        )
        x = _residual(x, o, cfg)
        h2 = rmsnorm(
            x, slot_params["norm_mlp"]["scale"], cfg.norm_eps, cfg.zero_centered_norm
        )
        x = _residual(x, FFN.mlp_block(slot_params["mlp"], h2, cfg, lc=lc), cfg)
        if scache is not None:
            new_cache = {"slstm": scache}
    else:
        raise ValueError(kind)
    x = lc(x, "batch", "seq", None)
    return x, new_cache, aux


def _remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    return jax.checkpoint(fn)


def _run_stack(params, x, cfg, *, positions, lc, caches=None, cache_len=None,
               seq_mask=None, cache_attend=False, block_tables=None):
    """Scan pattern x repeats. caches: {slot_name: stacked cache} or None.
    ``block_tables`` (B, n_logical) selects the paged attention-cache
    layout (shared across layers — allocation is per token position).
    Returns (x, new_caches, aux_totals).

    Dense and recurrent caches are sliced a layer at a time (the scan's
    ``xs``/``ys``). The paged pools ride whole in the scan's carry with the
    layer index instead: each layer writes and reads its row of them in
    place, so no layer's pool is sliced, written back or copied."""
    slot_names = list(params["slots"].keys())
    caches = caches or {}
    pools = {n: c for n, c in caches.items()
             if "k_pages" in c.get("attn", {})}
    rows = {n: c for n, c in caches.items() if n not in pools}

    def body(carry, layer_inp):
        x, pools = carry
        slot_rows, cache_rows, layer = layer_inp
        pools, new_cache_rows = dict(pools), {}
        aux_tot = None
        for name in slot_names:
            kind = name.split("_", 1)[1]
            x, nc, aux = _apply_slot(
                slot_rows[name], kind, x, cfg, positions=positions, lc=lc,
                cache=pools.get(name, cache_rows.get(name)),
                cache_len=cache_len,
                seq_mask=seq_mask, cache_attend=cache_attend,
                block_tables=block_tables, layer=layer,
            )
            if name in pools:
                pools[name] = nc
            elif nc is not None:
                new_cache_rows[name] = nc
            if aux:
                aux_tot = aux if aux_tot is None else jax.tree_util.tree_map(
                    jnp.add, aux_tot, aux
                )
        if aux_tot is None:
            aux_tot = {}
        return (x, pools), (new_cache_rows, aux_tot)

    body = _remat(body, cfg)

    if cfg.scan_layers and cfg.repeats > 1:
        layers = jnp.arange(cfg.repeats, dtype=jnp.int32) if pools else None
        (x, pools), (new_rows, auxs) = jax.lax.scan(
            body, (x, pools), (params["slots"], rows, layers)
        )
        aux = jax.tree_util.tree_map(jnp.sum, auxs) if auxs else {}
        # expert_load should stay per-expert: re-reduce over layers only
        if auxs and "expert_load" in auxs:
            aux["expert_load"] = jnp.sum(auxs["expert_load"], axis=0)
        return x, ({**new_rows, **pools} if caches else None), aux
    else:
        # unrolled path (small models / remat experiments)
        new_rows_acc = []
        aux_acc: dict[str, Any] = {}
        for r in range(cfg.repeats):
            slot_rows = jax.tree_util.tree_map(lambda p: p[r], params["slots"])
            cache_rows = jax.tree_util.tree_map(lambda c: c[r], rows)
            (x, pools), (ncr, aux) = body((x, pools),
                                          (slot_rows, cache_rows, r))
            new_rows_acc.append(ncr)
            for k, v in aux.items():
                aux_acc[k] = aux_acc.get(k, 0) + v
        new_caches = None
        if caches:
            new_rows = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *new_rows_acc
            )
            new_caches = {**new_rows, **pools}
        return x, new_caches, aux_acc


# ---------------------------------------------------------------------------
# embedding + head
# ---------------------------------------------------------------------------


def _embed_inputs(params, batch, cfg, lc):
    """batch: dict with optional "tokens" (B,S) and "frontend" (B,P,d)."""
    parts = []
    if batch.get("frontend") is not None:
        parts.append(batch["frontend"].astype(cfg.compute_dtype))
    if batch.get("tokens") is not None:
        emb = params["embed"].astype(cfg.compute_dtype)
        parts.append(emb[batch["tokens"]])
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.compute_dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, cfg.compute_dtype)
    return lc(x, "batch", "seq", None)


def _logits(params, x, cfg, lc):
    if cfg.tie_embeddings:
        head = params["embed"].T
    else:
        head = params["head"]
    logits = x @ head.astype(cfg.compute_dtype)
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    if cfg.final_softcap:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    return lc(logits, "batch", None, "vocab")


def cross_entropy(
    params, x, labels, cfg, lc, *, seq_chunk: int = 512, z_loss: float | None = None
):
    """Chunked CE over the sequence — never materializes (B,S,V) logits.
    labels: (B,S) int32; negative labels are masked out.
    Returns (loss_sum, weight_sum, token_count_per_data_shard_proxy)."""
    B, S, _ = x.shape
    V = cfg.vocab_padded
    z_coef = cfg.z_loss if z_loss is None else z_loss
    seq_chunk = min(seq_chunk, S)
    n = -(-S // seq_chunk)
    xpad = A._pad_axis(x, 1, n * seq_chunk)
    lpad = A._pad_axis(labels, 1, n * seq_chunk, value=-1)
    xc = xpad.reshape(B, n, seq_chunk, -1).transpose(1, 0, 2, 3)
    lck = lpad.reshape(B, n, seq_chunk).transpose(1, 0, 2)

    def chunk_loss(carry, inp):
        xi, li = inp
        logits = _logits(params, xi, cfg, lc).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        onehot = jax.nn.one_hot(jnp.maximum(li, 0), V, dtype=jnp.float32)
        gold = jnp.sum(logits * onehot, axis=-1)
        w = (li >= 0).astype(jnp.float32)
        nll = (lse - gold) * w
        zl = z_coef * (lse**2) * w if z_coef else 0.0
        loss_sum, w_sum = carry
        return (loss_sum + jnp.sum(nll + zl), w_sum + jnp.sum(w)), None

    chunk_loss = jax.checkpoint(chunk_loss)
    (loss_sum, w_sum), _ = jax.lax.scan(chunk_loss, (0.0, 0.0), (xc, lck))
    return loss_sum, w_sum


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


def forward(params, batch, cfg, lc: LogicalConstraints = NULL_CONSTRAINTS):
    """Training/eval forward: returns (loss, aux)."""
    x = _embed_inputs(params, batch, cfg, lc)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x, _, aux = _run_stack(params, x, cfg, positions=positions, lc=lc)
    x = rmsnorm(x, params["norm_f"]["scale"], cfg.norm_eps, cfg.zero_centered_norm)
    x = lc(x, "batch", None, None)
    loss_sum, w_sum = cross_entropy(params, x, batch["labels"], cfg, lc)
    loss = loss_sum / jnp.maximum(w_sum, 1.0)
    if "moe_lb_loss" in aux:
        loss = loss + cfg.moe_lb_coef * aux["moe_lb_loss"] / cfg.n_layers
        loss = loss + cfg.moe_z_coef * aux["moe_z_loss"] / cfg.n_layers
    aux["tokens"] = w_sum
    return loss, aux


def apply_logits(params, batch, cfg, lc: LogicalConstraints = NULL_CONSTRAINTS):
    """Full-sequence logits (small-model/eval path; materializes (B,S,V))."""
    x = _embed_inputs(params, batch, cfg, lc)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x, _, aux = _run_stack(params, x, cfg, positions=positions, lc=lc)
    x = rmsnorm(x, params["norm_f"]["scale"], cfg.norm_eps, cfg.zero_centered_norm)
    return _logits(params, x, cfg, lc), aux


def init_cache(cfg, batch: int, max_len: int, dtype=None, *,
               paged: bool = False, page_size: int = 16,
               num_pages: int | None = None) -> dict:
    """Stacked decode caches per slot.

    ``paged=True`` swaps each attention cache's dense per-slot
    ``(R, B, max_len, Hkv, hd)`` buffers for a shared pool of
    ``num_pages`` fixed-size pages ``(R, Hkv, num_pages, page_size, hd)``
    addressed through a per-slot block table (see ``decode_step``) — HBM
    then scales with live tokens, not ``batch x max_len``. The pool is
    head-major, so that one page of every KV head is a set of contiguous
    ``(page_size, hd)`` tiles, and a step passes it whole with the layer
    index to the paged kernels. ``num_pages`` defaults to dense-equivalent
    capacity; serving sizes it to the workload. Recurrent (conv/ssm/xLSTM)
    state stays dense per slot — it is O(batch), not O(batch x seq)."""
    dtype = dtype or cfg.compute_dtype
    if paged and num_pages is None:
        num_pages = -(-batch * max_len // page_size)
    caches: dict[str, Any] = {}
    for i, kind in enumerate(cfg.pattern):
        name = f"slot{i}_{kind}"
        if kind in ("attn", "local_attn", "moe"):
            hkv, hd = cfg.n_kv_heads, cfg.head_dim_
            if paged:
                c = {
                    "attn": {
                        "k_pages": jnp.zeros(
                            (cfg.repeats, hkv, num_pages, page_size, hd), dtype
                        ),
                        "v_pages": jnp.zeros(
                            (cfg.repeats, hkv, num_pages, page_size, hd), dtype
                        ),
                    }
                }
                caches[name] = c
                continue
            c = {
                "attn": {
                    "k": jnp.zeros((cfg.repeats, batch, max_len, hkv, hd), dtype),
                    "v": jnp.zeros((cfg.repeats, batch, max_len, hkv, hd), dtype),
                }
            }
        elif kind in ("mamba2", "mamba2_moe"):
            c = {"mamba": jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (cfg.repeats,) + x.shape),
                R.mamba2_cache(cfg, batch, dtype),
            )}
        elif kind == "mlstm":
            c = {"mlstm": jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (cfg.repeats,) + x.shape),
                R.mlstm_cache(cfg, batch, dtype),
            )}
        elif kind == "slstm":
            c = {"slstm": jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (cfg.repeats,) + x.shape),
                R.slstm_cache(cfg, batch),
            )}
        else:
            raise ValueError(kind)
        caches[name] = c
    return caches


def prefill(params, batch, cfg, caches, lc: LogicalConstraints = NULL_CONSTRAINTS):
    """Run the prompt through the stack filling caches.
    Returns (last_logits (B,V), new_caches)."""
    x = _embed_inputs(params, batch, cfg, lc)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x, new_caches, _ = _run_stack(
        params, x, cfg, positions=positions, lc=lc, caches=caches, cache_len=S
    )
    x = rmsnorm(x, params["norm_f"]["scale"], cfg.norm_eps, cfg.zero_centered_norm)
    logits = _logits(params, x[:, -1:, :], cfg, lc)
    return logits[:, 0], new_caches


def prefill_chunk(
    params, batch, cfg, caches, start, length,
    lc: LogicalConstraints = NULL_CONSTRAINTS, block_tables=None,
    all_logits: bool = False, with_aux: bool = False,
):
    """One chunk of an incremental prefill: run ``batch["tokens"]`` (B,C)
    through the stack as positions ``start .. start+length``, writing the
    caches at each row's own offsets and attending against everything
    written so far (earlier chunks included).

    ``start``: () or (B,) position of the chunk's first token; ``length``:
    () or (B,) valid tokens in the chunk — the rest is padding, which
    neither writes the cache nor advances recurrent state, so a padded
    chunk leaves exactly the state a tight chunk would have.
    Returns (logits (B,V) at each row's LAST VALID position, new_caches) —
    on the final chunk of a prompt those logits sample the first generated
    token. ``block_tables`` (B, n_logical) routes attention-cache writes
    and reads through the paged pool layout (see ``init_cache``) — reads
    go through ``kernels.paged_attention.paged_prefill_attention``, the
    multi-token paged read that attends the block table directly instead
    of gathering a slot's pages into a dense view per chunk.

    ``all_logits=True`` returns logits at EVERY chunk position, (B,C,V) —
    the multi-token scoring path for speculative decoding: each row ``r``
    attends through its own position, so ``logits[:, r]`` is bitwise
    identical to what a sequential ``decode_step`` at ``start + r`` would
    produce after consuming the same tokens. Positions past ``length``
    hold garbage (their cache writes and state advance are masked, their
    logits are not).

    ``with_aux=True`` appends the stack's summed per-layer counters (the
    MoE layers' ``moe_routed`` and ``moe_busiest``) as a third output."""
    x = _embed_inputs(params, batch, cfg, lc)
    B, C, _ = x.shape
    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32).reshape(-1), (B,))
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1), (B,))
    offs = jnp.arange(C, dtype=jnp.int32)[None, :]
    seq_mask = offs < length[:, None]
    positions = start[:, None] + offs
    x, new_caches, aux = _run_stack(
        params, x, cfg, positions=positions, lc=lc, caches=caches,
        cache_len=start + length, seq_mask=seq_mask, cache_attend=True,
        block_tables=block_tables,
    )
    x = rmsnorm(x, params["norm_f"]["scale"], cfg.norm_eps, cfg.zero_centered_norm)
    if all_logits:
        out = (_logits(params, x, cfg, lc), new_caches)
    else:
        x_last = jnp.take_along_axis(
            x, jnp.maximum(length - 1, 0)[:, None, None], axis=1
        )  # (B,1,d)
        out = (_logits(params, x_last, cfg, lc)[:, 0], new_caches)
    return out + (aux,) if with_aux else out


def decode_step(
    params, tokens, pos, cfg, caches, lc: LogicalConstraints = NULL_CONSTRAINTS,
    frontend=None, active=None, block_tables=None, with_aux: bool = False,
):
    """One decode step. tokens: (B,1) int32; pos: () scalar or (B,) vector of
    per-slot positions — continuous batching attaches requests mid-flight, so
    every slot carries its own position (RoPE, cache write offset, visible
    cache length all follow it). ``active``: optional (B,) bool; inactive
    slots neither write the KV cache nor advance recurrent state.
    ``block_tables``: optional (B, n_logical) int32 — paged attention-cache
    layout (``init_cache(..., paged=True)``); the slot's token writes and
    the decode attention both address the shared pool through it.
    Returns (logits (B,V), new_caches), and the stack's summed per-layer
    counters with ``with_aux=True`` (see ``prefill_chunk``)."""
    batch = {"tokens": tokens, "frontend": frontend}
    x = _embed_inputs(params, batch, cfg, lc)
    B = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    positions = jnp.broadcast_to(pos.reshape(-1, 1), (B, 1))
    seq_mask = None if active is None else jnp.asarray(active).reshape(B, 1)
    x, new_caches, aux = _run_stack(
        params, x, cfg, positions=positions, lc=lc, caches=caches,
        cache_len=pos + 1, seq_mask=seq_mask, block_tables=block_tables,
    )
    x = rmsnorm(x, params["norm_f"]["scale"], cfg.norm_eps, cfg.zero_centered_norm)
    logits = _logits(params, x, cfg, lc)
    return (logits[:, 0], new_caches) + ((aux,) if with_aux else ())
