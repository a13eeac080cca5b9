"""Serving: prefill + batched decode with sharded KV caches.

``serve_step`` (one new token against a KV cache of ``seq_len``) is what the
``decode_*`` / ``long_*`` dry-run shapes lower, per the assignment spec.
Caches shard like activations: batch over ("pod","data"), kv-heads over
"model" where divisible (megatron) else replicated; recurrent states shard
over their head/inner dims.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time
import zlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.configs.base import RECURRENT_KINDS
from repro.distributed import sharding as SH
from repro.layers.common import LogicalConstraints
from repro.models import transformer as T
from repro.serve.spec import draft_tokens
from repro.session import span

# the static fields ``_serve_step_fns`` keys its lru cache on — see
# ServeConfig.step_statics() for what belongs here (and what must not)
_StepStatics = collections.namedtuple(
    "_StepStatics",
    ["greedy", "temperature", "top_k",
     "prefix_cache", "prefix_trie_capacity",
     "spec_decode", "spec_k", "spec_min_match"],
)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_len: int
    batch: int
    # sampling: greedy argmax by default (bitwise-stable serving); with
    # greedy=False the decode and prefill-chunk steps sample on device with
    # temperature (and optionally top_k) from a per-request PRNG key the
    # scheduler writes into the slot's key row at attach
    # (fold_in(PRNGKey(sample_seed), request_tag)), folded with the sampled
    # position each step — a request's stream is a pure function of
    # (params, prompt, request_id, sample_seed), never of co-resident
    # traffic, who occupied the slot before, or which slot the request
    # (re)attaches into — which is what makes a preempted request's resumed
    # stream identical in any slot.
    temperature: float = 1.0
    greedy: bool = True
    top_k: int | None = None
    sample_seed: int = 0
    # chunked prefill-on-attach: token budget (= chunk size) the scheduler
    # spends on prefill per tick. Chunks are dispatched asynchronously
    # BETWEEN decode dispatches, so attaching a queued request never stalls
    # the in-flight decode pipeline.
    prefill_chunk: int = 32
    # early stop: retire a request when it emits ``eos_id``. EOS needs token
    # *values* on the host, so pending readbacks are additionally flushed
    # every ``eos_check_every`` ticks (bounded detection latency without
    # paying one transfer per step).
    eos_id: int | None = None
    eos_check_every: int = 8
    # paged KV cache: attention caches live in a shared pool of
    # ``page_size``-token pages addressed through per-slot block tables,
    # allocated as prefill/decode actually write and freed on retire — HBM
    # scales with live tokens instead of batch x max_len. ``max_len`` must
    # be a multiple of ``page_size``. ``num_pages=None`` sizes the pool for
    # every slot at max_len (batch*max_len/page_size); real deployments size
    # it to the expected concurrent-token peak.
    page_size: int = 16
    num_pages: int | None = None
    # cross-request prefix cache (opt-in): a radix trie keyed
    # on prompt-token pages maps previously prefilled prompt prefixes into
    # a new request's block table read-only (refcount bump; that part of
    # chunked prefill is skipped), and the first partially-shared page is
    # copy-on-write. ``prefix_trie_capacity`` caps how many pages the trie
    # may pin, LRU-trimmed on insert; None = unbounded (pool pressure
    # still evicts LRU entries nobody else reads).
    prefix_cache: bool = False
    prefix_trie_capacity: int | None = None
    # preemption under memory pressure: when the page pool
    # exhausts, pick a victim request by policy, release its pages, and park
    # it for recompute-resume — re-prefill is cheap through the paged cache
    # (and the PrefixCache/CoW path when enabled), and the resumed stream is
    # bitwise identical to an uninterrupted run. Policies order victim
    # candidates (never a request older/higher-priority than the one asking):
    #   "priority"  lowest priority first, then most pages, then least
    #               progress (the default — frees the most for the least
    #               wasted work among the least important)
    #   "pages"     most pages first (frees fastest)
    #   "progress"  least generated tokens first (wastes the least recompute)
    #   "never"     pre-preemption behavior: exhaustion unwinds the failed
    #               attach (releasing every page it held — nothing leaks)
    #               and raises
    preempt_policy: str = "priority"
    # resilience (fault detection + recovery). A request whose dispatch is
    # detected bad (NaN/Inf logits via the on-device sentinel, a page
    # checksum mismatch, a watchdog trip) is RETRIED through the existing
    # park/recompute-resume path with capped exponential backoff
    # (min(cap, base << (retries-1)) ticks before it may re-attach) — a
    # retried stream is bitwise identical to an unfaulted run, greedy AND
    # sampled, because resume replays the clean history. After
    # ``max_retries`` failed attempts the request is QUARANTINED: terminal
    # "failed" status on its handle, pages freed, co-residents untouched —
    # the scheduler never crashes on a misbehaving request.
    max_retries: int = 3
    retry_backoff_base: int = 1
    retry_backoff_cap: int = 8
    # watchdog: a decode dispatch whose host-side dispatch call exceeds
    # this many seconds trips the watchdog — the (late) tokens are kept
    # (identity is preserved) and the targeted request retries so a wedged
    # dispatch path cannot stall its stream forever. None = off.
    watchdog_deadline_s: float | None = None
    # per-page content checksums (prefix_cache only): fingerprint
    # each prompt page at trie insert and validate before mapping it into
    # a new request's block table — a silently corrupted shared page
    # (bitflip, not NaN) is evicted and re-prefilled fresh instead of
    # poisoning every future reader.
    checksum_pages: bool = False
    # load shedding: when the admission queue already holds this many
    # requests, a new submit sheds the lowest-priority youngest waiter
    # (possibly the new arrival itself) with a terminal "shed" status —
    # a clear rejection instead of unbounded queueing under sustained
    # pressure or fault rate. None = never shed.
    shed_queue_depth: int | None = None
    # speculative multi-token decoding (opt-in): each tick a
    # deterministic prompt-lookup drafter (repro.serve.spec — a pure
    # function of the request's prompt + emitted tokens, no second model)
    # proposes up to ``spec_k`` draft tokens, and ONE batched verify
    # dispatch scores all K+1 positions against the paged KV cache
    # (the prefill-chunk multi-token path). The longest prefix where
    # draft == model output is accepted — greedy acceptance is provably
    # bitwise-identical to step-by-step decode, and sampled acceptance
    # folds the per-request key at each verified POSITION (the PR 8
    # stream-purity invariant), so spec on/off never changes a token.
    # ``spec_min_match`` is the shortest history n-gram the drafter may
    # match on (shorter = more, lower-confidence drafts).
    spec_decode: bool = False
    spec_k: int = 4
    spec_min_match: int = 2

    def __post_init__(self):
        if self.checksum_pages and not self.prefix_cache:
            raise ValueError(
                "checksum_pages=True requires prefix_cache=True: checksums "
                "guard pages shared across requests through the prefix trie"
            )
        if self.max_retries < 0 or self.retry_backoff_base < 1 \
                or self.retry_backoff_cap < 1:
            raise ValueError(
                "max_retries must be >= 0 and retry backoff base/cap >= 1"
            )
        if self.preempt_policy not in ("priority", "pages", "progress",
                                       "never"):
            raise ValueError(
                f"preempt_policy must be one of priority|pages|progress|never,"
                f" got {self.preempt_policy!r}"
            )
        if self.spec_decode and (self.spec_k < 1 or self.spec_min_match < 1):
            raise ValueError(
                f"spec_k ({self.spec_k}) and spec_min_match "
                f"({self.spec_min_match}) must be >= 1"
            )

    def step_statics(self) -> "_StepStatics":
        """The step-function cache key: every field that changes WHICH
        jitted step functions a scheduler needs or HOW they compute —
        sampling statics, the prefix-cache knobs (the CoW step only
        exists for prefix-cached schedulers), and the speculation knobs
        (the verify step only exists for spec schedulers, and its
        compiled acceptance math depends on them). Shape-only fields
        (max_len, batch, num_pages, ...) stay OUT: jit retraces per
        shape on its own, and excluding them lets A/B benchmark pairs
        (ample vs tight pool, eos on/off) share compiled traces."""
        return _StepStatics(
            self.greedy, self.temperature, self.top_k,
            self.prefix_cache, self.prefix_trie_capacity,
            self.spec_decode, self.spec_k, self.spec_min_match,
        )


def _cache_path_name(path) -> str:
    return "/".join(str(p.key) if hasattr(p, "key") else str(p) for p in path)


def cache_pspec_tree(cfg, mesh, caches):
    """PartitionSpecs for the stacked cache pytree.

    Attention KV caches are the serving-memory wall (command-r decode_32k:
    343 GB). Sharding priority: batch over ("pod","data") when divisible;
    kv-heads over "model" when divisible, else the **sequence** dim over
    "model" (decode attention over a seq-sharded cache = partial softmax +
    tiny all-reduces — the GSPMD-native flash-decode layout)."""
    rules = SH.activation_rules(cfg, mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model = sizes.get("model", 1)

    def batch_ax(b: int):
        return SH.divisible_batch_axes(mesh, b)

    kv_div = cfg.n_kv_heads % model == 0 and model > 1
    inner = rules["inner"]
    ssm_heads = (
        "model"
        if cfg.ssm and cfg.ssm.n_heads(cfg.d_model) % model == 0 and model > 1
        else None
    )

    def f(path_leaf):
        path, leaf = path_leaf
        name = _cache_path_name(path)
        nd = len(leaf.shape)
        if "pages" in name:  # paged pool (R, Hkv, P, page, hd): no batch dim
            # pages are gathered by physical index, so the page axis must
            # stay unsharded; kv-heads shard over "model" like the dense
            # layout (the pool is the same bytes, just re-bucketed)
            return P(None, "model" if kv_div else None, None, None, None)
        b = leaf.shape[1] if nd >= 2 else 1
        batch = batch_ax(b)
        if "attn" in name:  # (R, B, Smax, Hkv, hd)
            if kv_div:
                return P(None, batch, None, "model", None)
            return P(None, batch, "model" if model > 1 else None, None, None)
        if "mamba" in name and nd == 4:  # conv (R, B, K-1, C)
            return P(None, batch, None, inner)
        if "mamba" in name and nd == 5:  # ssm (R, B, h, p, n)
            return P(None, batch, ssm_heads, None, None)
        return P(*([None, batch] + [None] * (nd - 2)))

    paths = jax.tree_util.tree_flatten_with_path(caches)[0]
    specs = [f(pl) for pl in paths]
    treedef = jax.tree_util.tree_structure(caches)
    return jax.tree_util.tree_unflatten(treedef, specs)


def make_prefill_step(cfg, mesh):
    lc = LogicalConstraints(mesh, SH.activation_rules(cfg, mesh))

    def prefill_step(params, batch, caches):
        logits, new_caches = T.prefill(params, batch, cfg, caches, lc)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, new_caches

    return prefill_step


def make_decode_step(cfg, mesh):
    lc = LogicalConstraints(mesh, SH.activation_rules(cfg, mesh))

    def decode_step(params, tokens, pos, caches):
        """tokens: (B,1) int32; pos: () int32 shared position, or (B,) int32
        per-slot positions (continuous batching)."""
        logits, new_caches = T.decode_step(params, tokens, pos, cfg, caches, lc)
        next_tok = jnp.argmax(logits, axis=-1, keepdims=True).astype(jnp.int32)
        return next_tok, new_caches

    return decode_step


def _sample_tokens(logits, rng_keys, positions, *, greedy, temperature,
                   top_k, vocab):
    """On-device next-token selection for a batch of slots.

    logits: (N, V); rng_keys: (N, 2) uint32 base keys (the scheduler
    writes each attached request's own key into its slot's row); positions:
    (N,) int32 — the position whose logits are being sampled. Greedy (the
    default) is a plain argmax, bitwise identical to the historical
    behavior. Otherwise temperature (and optionally top-k) sampling with
    the key ``fold_in(rng_keys[i], positions[i])`` — STATELESS per step,
    so a request's sampled stream is a pure function of (params, prompt,
    request_id, sample_seed): it cannot depend on co-resident requests'
    decode traffic, who occupied the slot before, or which slot it
    (re)attaches into.
    Padded vocab ids are masked out. Returns tokens (N,) int32."""
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    use = jax.vmap(jax.random.fold_in)(rng_keys, positions)
    lg = logits.astype(jnp.float32) / max(float(temperature), 1e-6)
    V = lg.shape[-1]
    if vocab < V:
        lg = jnp.where(jnp.arange(V)[None, :] < vocab, lg, -jnp.inf)
    if top_k:
        kth = jnp.sort(lg, axis=-1)[:, -int(top_k)][:, None]
        lg = jnp.where(lg >= kth, lg, -jnp.inf)
    return jax.vmap(jax.random.categorical)(use, lg).astype(jnp.int32)


def _moe_counters(aux) -> tuple:
    """A MoE model's step counters (the stack's ``aux``, third output of
    ``decode_step`` / ``prefill_chunk`` with ``with_aux=True``) as one more
    output: a (2,) int32 ``[moe_routed, moe_busiest]`` summed over the
    step's MoE layers (``layers.ffn.moe_dropless``)."""
    return (jnp.stack([aux["moe_routed"], aux["moe_busiest"]]).astype(jnp.int32),)


def make_serve_decode_step(cfg, mesh, *, greedy=True, temperature=1.0,
                           top_k=None):
    """Continuous-batching decode: per-slot positions + active mask.

    Inactive slots (empty, or mid-prefill — their pages belong to the
    concurrently dispatched prefill chunks) neither write the KV cache nor
    advance recurrent state; their sampled tokens are garbage and ignored.
    Attention-cache writes and reads go through the shared page pool by
    the ``block_tables`` argument.

    The trailing ``fault_mask`` (B,) bool argument poisons the masked
    slots' logits with NaN *before* the finiteness sentinel — the chaos
    harness's logit-fault injection point. An all-False mask is a bitwise
    no-op (``jnp.where`` selects, never propagates), so the fault-free
    path pays one fused select. The second return value is the on-device
    NaN/Inf sentinel: ``bad[i]`` is True when slot ``i``'s logits contain
    a non-finite value — it rides the deferred token readback for free
    (one extra (B,) bool per flush), and the scheduler retries flagged
    requests instead of streaming garbage. A model with MoE layers returns
    its step's MoE counters last (``_moe_counters``)."""
    lc = LogicalConstraints(mesh, SH.activation_rules(cfg, mesh))
    moe = cfg.moe is not None
    sample = functools.partial(
        _sample_tokens, greedy=greedy, temperature=temperature, top_k=top_k,
        vocab=cfg.vocab,
    )

    def _poison_and_sample(logits, fault_mask, rng_keys, pos):
        logits = jnp.where(fault_mask[:, None],
                           jnp.asarray(jnp.nan, logits.dtype), logits)
        bad = ~jnp.all(jnp.isfinite(logits), axis=-1)
        pos_v = jnp.broadcast_to(jnp.asarray(pos).reshape(-1),
                                 logits.shape[:1])
        return sample(logits, rng_keys, pos_v), bad

    def decode_step(params, tokens, pos, active, caches, block_tables,
                    rng_keys, fault_mask):
        """tokens: (B,1); pos: (B,); active: (B,) bool; block_tables:
        (B, n_logical) int32; rng_keys: (B,2) uint32 (static per slot —
        the sampling key is folded with the position); fault_mask: (B,)
        bool."""
        out = T.decode_step(
            params, tokens, pos, cfg, caches, lc, active=active,
            block_tables=block_tables, with_aux=moe,
        )
        tok, bad = _poison_and_sample(out[0], fault_mask, rng_keys, pos)
        return (tok[:, None], bad, out[1]) + (_moe_counters(out[2]) if moe else ())

    return decode_step


def _is_paged_leaf(path) -> bool:
    return "pages" in _cache_path_name(path)


def make_prefill_chunk_step(cfg, mesh, *, greedy=True, temperature=1.0,
                            top_k=None):
    """One chunk of one request's prompt into ONE slot's pages and state.

    The slot's recurrent-state rows are sliced out of the stacked cache
    pytree, run through ``T.prefill_chunk`` at batch 1, and scattered back —
    the other slots' state passes through untouched. Paged attention pools
    are passed whole: the chunk writes only the pages its block-table row
    owns, so it commutes with in-flight decode dispatches. A model with MoE
    layers returns the chunk's MoE counters last (``_moe_counters``)."""
    lc = LogicalConstraints(mesh, SH.activation_rules(cfg, mesh))
    moe = cfg.moe is not None
    sample = functools.partial(
        _sample_tokens, greedy=greedy, temperature=temperature, top_k=top_k,
        vocab=cfg.vocab,
    )

    def _slot_slice(caches, slot):
        flat = jax.tree_util.tree_flatten_with_path(caches)
        leaves = [
            leaf if _is_paged_leaf(path)
            else jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=1)
            for path, leaf in flat[0]
        ]
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(caches), leaves
        )

    def _scatter_back(caches, new_slot, slot):
        flat_full = jax.tree_util.tree_flatten_with_path(caches)
        flat_new = jax.tree_util.tree_leaves(new_slot)
        leaves = [
            upd if _is_paged_leaf(path)
            else jax.lax.dynamic_update_slice_in_dim(
                full, upd.astype(full.dtype), slot, axis=1
            )
            for (path, full), upd in zip(flat_full[0], flat_new)
        ]
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(caches), leaves
        )

    def chunk_step(params, tokens, start, length, slot, caches, block_tables,
                   rng_keys):
        """tokens: (1,C) int32 (padded); start/length: (1,) int32;
        slot: () int32; caches: full stacked tree; block_tables: the full
        (B, n_logical) table; rng_keys: (B,2) static per-slot base keys.
        Returns (next_tok (1,) sampled at the last valid position, bad
        (1,) — the NaN/Inf finiteness sentinel over the chunk's logits,
        catching a corrupted shared page read during prefill the same way
        the decode sentinel catches it — and new_caches)."""
        slot_caches = _slot_slice(caches, slot)
        tbl_row = jax.lax.dynamic_slice_in_dim(block_tables, slot, 1, axis=0)
        out = T.prefill_chunk(
            params, {"tokens": tokens}, cfg, slot_caches, start, length, lc,
            block_tables=tbl_row, with_aux=moe,
        )
        logits = out[0]
        key_row = jax.lax.dynamic_slice_in_dim(rng_keys, slot, 1, axis=0)
        next_tok = sample(logits, key_row, start + length - 1)
        bad = ~jnp.all(jnp.isfinite(logits), axis=-1)
        new_caches = _scatter_back(caches, out[1], slot)
        return (next_tok, bad, new_caches) + (_moe_counters(out[2]) if moe else ())

    return chunk_step


def make_spec_verify_step(cfg, mesh, *, greedy=True, temperature=1.0,
                          top_k=None, two_pass=False):
    """Batched speculative verify: score K+1 positions per slot in ONE
    dispatch against the paged KV cache.

    The scoring body IS ``T.prefill_chunk`` (``all_logits=True``): each
    slot's row carries ``[last_token, draft_1 .. draft_k]`` at positions
    ``start .. start+length-1``, attends through the block tables with
    per-row causal/window masking (the ``paged_prefill_attention`` S>1
    read), and yields the logits a sequential ``decode_step`` would have
    produced at every one of those positions — so the argmax (greedy) or
    the position-folded sample (sampled mode; the same
    ``fold_in(request_key, position)`` stream as sequential decode) at
    position ``start+i`` is bitwise the token step-by-step decode emits
    there. Acceptance keeps the longest prefix where draft == output,
    computed on device (a cumulative product over the match mask), so the
    host readback is just ``(tokens, accept_len, bad)``.

    Rollback of rejected positions is free under the paged layout: their
    K/V writes are masked scatters that later (correct) writes at the
    same positions overwrite, and every read is clipped to the reader's
    own ``cache_len`` — so the scheduler rolls back by simply not
    advancing ``pos`` past the accepted prefix.

    ``two_pass=True`` (recurrent/hybrid archs — mamba/xLSTM state has no
    positional masking and cannot be clamped back): the scoring pass
    discards its caches, and a second pass over the SAME tokens clamped
    to the accepted length re-commits — recurrent state then advances
    over exactly the accepted tokens, and attention K/V holds no stale
    rejected writes at all. Both passes run inside the one dispatch.

    ``fault_mask`` poisons whole rows ahead of the sentinel exactly like
    the decode step; ``bad`` is the NaN/Inf sentinel over each row's
    VALID positions (a poisoned dispatch, or a corrupted page any of the
    K+1 reads touched)."""
    lc = LogicalConstraints(mesh, SH.activation_rules(cfg, mesh))
    sample = functools.partial(
        _sample_tokens, greedy=greedy, temperature=temperature, top_k=top_k,
        vocab=cfg.vocab,
    )

    def verify_step(params, tokens, start, length, caches, block_tables,
                    rng_keys, fault_mask):
        """tokens: (B,C) int32 — row r is [last_tok, drafts...] padded;
        start: (B,) int32 per-slot positions; length: (B,) int32 valid
        tokens per row (0 = inactive slot: writes masked, state
        untouched); rng_keys: (B,2); fault_mask: (B,) bool.
        Returns (out (B,C) int32 — the verified token at each position,
        accept (B,) int32 — accepted DRAFT count (0..length-1),
        bad (B,) bool, new_caches)."""
        B, C = tokens.shape
        logits, new_caches = T.prefill_chunk(
            params, {"tokens": tokens}, cfg, caches, start, length, lc,
            block_tables=block_tables, all_logits=True,
        )  # (B, C, V)
        logits = jnp.where(
            fault_mask[:, None, None], jnp.asarray(jnp.nan, logits.dtype),
            logits,
        )
        offs = jnp.arange(C, dtype=jnp.int32)[None, :]
        valid = offs < length[:, None]
        bad = jnp.any(~jnp.all(jnp.isfinite(logits), axis=-1) & valid, axis=1)
        positions = start[:, None] + offs
        out = sample(
            logits.reshape(B * C, -1),
            jnp.repeat(rng_keys, C, axis=0),
            positions.reshape(-1),
        ).reshape(B, C)
        # longest accepted draft prefix: draft i (= tokens[:, i+1]) is
        # accepted iff it equals the verified token at position i AND
        # every earlier draft was accepted (cumprod)
        match = (out[:, :-1] == tokens[:, 1:]) & (
            offs[:, : C - 1] < (length - 1)[:, None]
        )
        accept = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
        if two_pass:
            commit = jnp.where(length > 0, jnp.minimum(accept + 1, length), 0)
            _, new_caches = T.prefill_chunk(
                params, {"tokens": tokens}, cfg, caches, start, commit, lc,
                block_tables=block_tables,
            )
        return out, accept, bad, new_caches

    return verify_step


def make_cow_copy_step():
    """Copy one physical page's K/V rows (every layer, both pools) to a
    fresh page, on device — the copy-on-write half of prefix sharing: the
    shared rows of a partially-matched page are duplicated so the new
    request's divergent tokens never touch the donor page. Non-paged
    leaves (recurrent state) pass through untouched."""

    def cow_copy(caches, src, dst):
        """caches: full stacked tree; src/dst: () int32 physical pages."""
        flat = jax.tree_util.tree_flatten_with_path(caches)
        leaves = [
            leaf.at[:, :, dst].set(leaf[:, :, src]) if _is_paged_leaf(path)
            else leaf
            for path, leaf in flat[0]
        ]
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(caches), leaves
        )

    return cow_copy


def make_encoder_step(cfg, mesh):
    """Encoder-only archs have no decode; "prefill" = full forward."""
    lc = LogicalConstraints(mesh, SH.activation_rules(cfg, mesh))

    def encoder_step(params, batch):
        logits, _ = T.apply_logits(params, batch, cfg, lc)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    return encoder_step


# ---------------------------------------------------------------------------
# simple continuous-batching scheduler (example/serving driver)
# ---------------------------------------------------------------------------


# Bounded: each entry pins a tuple of jitted fns with donated-buffer traces
# for the process lifetime, so an unbounded cache grows without limit when
# tests/benchmarks construct many scheduler configurations. The key is the
# FULL static tuple (``ServeConfig.step_statics()``) — every knob that
# changes which step functions exist or how they compute, including the
# speculation knobs, so two distinct configurations can never collide on
# one entry (a collision would hand a spec scheduler a triple with no
# verify step, or a prefix scheduler one with no CoW step). 32 entries
# cover every concurrent pattern in the repo (sampling x prefix x spec x
# arch) without thrashing; an evicted entry merely recompiles on the next
# scheduler construction.
@functools.lru_cache(maxsize=32)
def _serve_step_fns(cfg, mesh, statics: _StepStatics):
    """Shared jitted (decode, prefill-chunk, cow-copy, spec-verify) tuple
    per (cfg, mesh, full serve statics): scheduler instances (restarts,
    A/B benchmark runs) reuse traces instead of paying a fresh compile
    each. ``cow`` is None unless the prefix cache is on; ``verify`` is
    None unless spec decoding is on (its trace depends on the arch —
    recurrent/hybrid patterns verify in two passes so state advances
    over exactly the accepted tokens)."""
    kw = dict(greedy=statics.greedy, temperature=statics.temperature,
              top_k=statics.top_k)
    cow = (
        jax.jit(make_cow_copy_step(), donate_argnums=(0,))
        if statics.prefix_cache else None
    )
    verify = None
    if statics.spec_decode:
        two_pass = any(kind in RECURRENT_KINDS for kind in cfg.pattern)
        verify = jax.jit(
            make_spec_verify_step(
                cfg, mesh, greedy=statics.greedy,
                temperature=statics.temperature, top_k=statics.top_k,
                two_pass=two_pass,
            ),
            donate_argnums=(4,),
        )
    return (
        jax.jit(make_serve_decode_step(cfg, mesh, **kw), donate_argnums=(4,)),
        jax.jit(make_prefill_chunk_step(cfg, mesh, **kw), donate_argnums=(5,)),
        cow,
        verify,
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def _reset_rows(caches, fresh, idx):
    """Recurrent-state rows ``idx`` (slots; an index past the batch writes
    nothing) set to ``fresh`` (one batch-1 leaf a recurrent cache entry,
    None for the attention caches), in place."""
    flat, treedef = jax.tree_util.tree_flatten(caches)
    leaves = [
        leaf if f is None
        else leaf.at[:, idx].set(f.astype(leaf.dtype), mode="drop")
        for leaf, f in zip(flat, fresh)
    ]
    return jax.tree_util.tree_unflatten(treedef, leaves)


class PageAllocator:
    """Refcounted free-list allocator over the shared KV page pool.

    Pages are plain integers into the pool's page axis; the scheduler owns
    the per-slot block tables. ``alloc`` raises a clean error on exhaustion
    *before* any index is handed out — a full pool can never silently remap
    a neighbor's pages. With cross-request prefix sharing a physical page
    may back multiple block-table rows (plus the prefix trie's own pin):
    ``alloc`` hands pages out at refcount 1, ``share`` bumps the count, and
    ``release`` decrements it, returning a page to the free list only when
    its count drops to zero — retiring a request can never free a page a
    neighbor (or the trie) still reads."""

    def __init__(self, num_pages: int):
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, -1, -1))  # pop() -> page 0 first
        self.refs: dict[int, int] = {}  # allocated page -> reference count
        self.peak_used = 0

    @property
    def used(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def shared_pages(self) -> int:
        """Pages currently held by more than one reference."""
        return sum(1 for c in self.refs.values() if c > 1)

    def alloc(self, n: int, *, owner=None) -> list[int]:
        if n > len(self._free):
            raise RuntimeError(
                f"paged KV pool exhausted: request {owner!r} needs {n} more "
                f"page(s) but only {len(self._free)} of {self.num_pages} are "
                f"free; raise ServeConfig.num_pages (--num-pages) or retire "
                f"requests sooner"
            )
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self.refs[p] = 1
        self.peak_used = max(self.peak_used, self.used)
        return pages

    def share(self, pages: list[int]) -> None:
        """Add one reference to each (already allocated) page."""
        for p in pages:
            self.refs[p] += 1

    def release(self, pages: list[int]) -> None:
        for p in pages:
            c = self.refs[p] = self.refs[p] - 1
            if c == 0:
                del self.refs[p]
                self._free.append(p)


class _TrieNode:
    __slots__ = ("tokens", "page", "children", "parent", "last_used",
                 "checksum")

    def __init__(self, tokens, page, parent):
        self.tokens = tokens      # the page_size-token tuple keying this node
        self.page = page          # physical pool page holding their K/V
        self.children: dict[tuple, _TrieNode] = {}
        self.parent = parent
        self.last_used = 0
        self.checksum = None      # uint32 page fingerprint (checksum_pages)


class PrefixCache:
    """Radix trie over prompt-token pages for cross-request KV sharing.

    Nodes are keyed by the exact ``page_size``-token tuple they cover —
    Python's tuple hashing IS the page hash, with exact compare, so a hash
    collision can never alias two different prefixes — and a root-to-node
    path spells out a prompt prefix in whole pages, mapped to resident
    pool pages. The trie holds its OWN reference on every inserted page
    (``PageAllocator.share``), so cached pages survive their inserting
    request's retirement; they are reclaimed by LRU eviction under pool
    pressure (``evict_for`` — only leaves whose page has no reader besides
    the trie, since evicting a still-shared page frees nothing) or by LRU
    trim when ``capacity`` (max pinned pages) would be exceeded on insert.
    """

    def __init__(self, page_size: int, allocator: PageAllocator,
                 capacity: int | None = None):
        self.page_size = page_size
        self.allocator = allocator
        self.capacity = capacity
        self.root = _TrieNode((), -1, None)
        self.size = 0       # nodes == pages currently pinned
        self._clock = 0     # monotonic LRU clock
        self.stats = {
            "hits": 0, "misses": 0, "hit_tokens": 0,
            "prefill_tokens_skipped": 0, "pages_shared": 0, "cow_copies": 0,
            "inserted_pages": 0, "evicted_pages": 0,
        }

    def _touch(self, node: _TrieNode) -> None:
        self._clock += 1
        node.last_used = self._clock

    def match(self, prompt):
        """Longest cached prefix of ``prompt``: the chain of fully-matched
        page nodes, plus the best partially-matching child of the last one
        (the copy-on-write donor) with its matching row count."""
        psize = self.page_size
        node, chain, i = self.root, [], 0
        while len(prompt) - i >= psize:
            child = node.children.get(tuple(int(t) for t in prompt[i:i + psize]))
            if child is None:
                break
            chain.append(child)
            node, i = child, i + psize
        tail = tuple(int(t) for t in prompt[i:i + psize])
        donor, donor_rows = None, 0
        for key, child in node.children.items():
            n = 0
            for a, b in zip(key, tail):
                if a != b:
                    break
                n += 1
            if n > donor_rows:
                donor, donor_rows = child, n
        return chain, donor, donor_rows

    def insert(self, prompt, pages, checksums=None) -> None:
        """Record a prefilled prompt's full pages (called when a request's
        prefill completes). Existing nodes are LRU-touched; new nodes pin
        their page with a trie-owned reference. Pages straddling the
        prompt/generated boundary are never inserted — decode will write
        over their tails. ``checksums`` (one uint fingerprint per full
        page, when ``ServeConfig.checksum_pages`` is on) are stored on
        the nodes and validated before any future attach maps them."""
        psize = self.page_size
        node = self.root
        for j in range(len(prompt) // psize):
            key = tuple(int(t) for t in prompt[j * psize:(j + 1) * psize])
            child = node.children.get(key)
            if child is None:
                if self.capacity is not None and self.size >= self.capacity:
                    # at capacity: trim the LRU leaf off some OTHER path;
                    # if the whole trie is this insertion, stop growing
                    if not self._evict_lru(exclude=self._path_ids(node)):
                        return
                child = _TrieNode(key, pages[j], node)
                node.children[key] = child
                self.allocator.share([pages[j]])
                self.size += 1
                self.stats["inserted_pages"] += 1
            if checksums is not None:
                child.checksum = checksums[j]
            self._touch(child)
            node = child

    # -- eviction --------------------------------------------------------

    def _path_ids(self, node: _TrieNode) -> set:
        out = set()
        while node is not None:
            out.add(id(node))
            node = node.parent
        return out

    def _leaves(self) -> list[_TrieNode]:
        out, stack = [], list(self.root.children.values())
        while stack:
            n = stack.pop()
            if n.children:
                stack.extend(n.children.values())
            else:
                out.append(n)
        return out

    def _evict(self, node: _TrieNode) -> None:
        del node.parent.children[node.tokens]
        self.allocator.release([node.page])
        self.size -= 1
        self.stats["evicted_pages"] += 1

    def _evict_lru(self, *, exclude=frozenset(),
                   only_unreferenced: bool = False) -> bool:
        """Evict the least-recently-used leaf; True if one was evicted."""
        cand = [
            n for n in self._leaves()
            if id(n) not in exclude
            and (not only_unreferenced
                 or self.allocator.refs.get(n.page, 0) == 1)
        ]
        if not cand:
            return False
        self._evict(min(cand, key=lambda n: n.last_used))
        return True

    def evict_subtree(self, node: _TrieNode) -> int:
        """Evict ``node`` and every descendant (post-order): a checksum
        mismatch means the page's content can no longer be trusted, and
        the descendants' pages are unreachable without it. Returns the
        number of nodes evicted."""
        count = 0
        stack, order = [node], []
        while stack:
            n = stack.pop()
            order.append(n)
            stack.extend(n.children.values())
        for n in reversed(order):  # children before parents
            self._evict(n)
            count += 1
        return count

    def evict_for(self, n_pages: int) -> int:
        """Pool pressure: free >= ``n_pages`` by evicting LRU leaves whose
        page has no reader besides the trie. Inner nodes become evictable
        as their children go. Returns the number of pages actually freed
        (may fall short — the caller's alloc then raises cleanly)."""
        freed = 0
        while freed < n_pages:
            before = self.allocator.free_pages
            if not self._evict_lru(only_unreferenced=True):
                break
            freed += self.allocator.free_pages - before
        return freed

    def reclaimable(self) -> int:
        """Pages the trie could free under pressure: nodes whose page has no
        reader besides the trie itself (inner nodes become evictable as
        their children go, so every refcount-1 node counts)."""
        count = 0
        stack = list(self.root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if self.allocator.refs.get(n.page, 0) == 1:
                count += 1
        return count

    def clear(self) -> None:
        """Drop every cached page (teardown / tests)."""
        while self._evict_lru():
            pass


class _PoolPressure(Exception):
    """Internal: an allocation could not be satisfied even after trie
    eviction and victim preemption. ``fatal=False`` means the *requester*
    should be parked (pressure will drop when older/higher-priority work
    retires); ``fatal=True`` means no amount of waiting can help (policy
    "never", or the requester is the only page holder left) — the caller
    unwinds its partial allocation and re-raises as RuntimeError."""

    def __init__(self, fatal: bool, msg: str):
        super().__init__(msg)
        self.fatal = fatal
        self.msg = msg


def _request_tag(request_id) -> int:
    """Stable 31-bit tag for a request id, independent of submission order
    and slot placement — the sampling key seed. Integer ids map to
    themselves; anything else hashes via crc32 (Python's ``hash`` is
    process-seeded for strings, which would break cross-run determinism)."""
    if isinstance(request_id, (int, np.integer)):
        return int(request_id) & 0x7FFFFFFF
    return zlib.crc32(repr(request_id).encode()) & 0x7FFFFFFF


# terminal request statuses: the stream is closed, no more tokens can come
_TERMINAL = ("done", "cancelled", "failed", "shed")


class RequestHandle:
    """Caller-facing view of a submitted request — the async half of the
    admission API. ``submit()`` returns one immediately (arrival time is
    decoupled from slot attach); the handle observes the request's
    lifecycle (``queued -> prefilling -> decoding -> done``, with
    ``preempted``/``retrying`` parking and ``cancelled``/``failed``/
    ``shed`` exits), exposes the tokens generated so far, and can cancel
    mid-stream. ``failed`` is the quarantine exit: the request exhausted
    ``ServeConfig.max_retries`` fault recoveries and was detached with
    its pages freed — co-residents never see it. ``shed`` is the
    load-shedding exit: the admission queue was over
    ``shed_queue_depth`` and this was the lowest-priority youngest
    waiter."""

    __slots__ = ("_sched", "_req")

    def __init__(self, sched: "BatchScheduler", req: dict):
        self._sched = sched
        self._req = req

    @property
    def request_id(self):
        return self._req["id"]

    @property
    def status(self) -> str:
        return self._req["_status"]

    @property
    def tokens(self) -> list[int]:
        """Tokens generated (and flushed to the host) so far."""
        return list(self._req["generated"])

    @property
    def done(self) -> bool:
        return self._req["_status"] in _TERMINAL

    def cancel(self) -> bool:
        return self._sched.cancel(self._req["id"])

    def stream(self, *, timeout: int | None = None):
        """Synchronous token stream (drives the scheduler); see
        ``BatchScheduler.stream``. ``timeout`` bounds the scheduler ticks
        spent waiting for the next token — a stalled scheduler raises
        ``TimeoutError`` instead of spinning forever."""
        return self._sched.stream(self._req["id"], timeout=timeout)

    def result(self, *, timeout: int | None = None) -> list[int]:
        """Drive the scheduler until this request finishes; returns its
        tokens. ``timeout`` (scheduler ticks between tokens) raises
        ``TimeoutError`` on a stall."""
        for _ in self.stream(timeout=timeout):
            pass
        return self.tokens


class BatchScheduler:
    """Slot-based continuous batching with genuine chunked prefill-on-attach
    overlapped with in-flight decode.

    Every slot carries its own position (``pos`` is a (B,) vector): a request
    attached mid-flight decodes at *its* sequence position, not the batch's.
    Attaching runs a real prefill — the prompt is written into the slot's
    pages in fixed ``prefill_chunk``-token chunks, ONE chunk dispatched per
    tick *after* that tick's decode dispatch, so the decode pipeline never
    waits on a prefill. Decode and prefill commute on the cache:
    inactive/prefilling slots are masked out of the decode step's cache
    writes and recurrent-state advance, and a prefill chunk only touches
    its own slot's pages and state rows — so the generated tokens are
    bitwise those of a one-shot prefill followed by sequential decode.
    Reattaching a freed slot restores its recurrent-state carries to their
    initial values (stale attention KV is already out of its block table),
    so a reused slot behaves exactly like a fresh one.

    Attention KV lives in a **paged cache**: a shared pool of
    ``page_size``-token pages plus a per-slot block table. Pages are
    allocated exactly as prefill chunks / decode steps write them and freed
    when the request retires, so KV HBM scales with *live tokens* instead
    of ``batch x max_len``; attention reads K/V through the table
    (``kernels.paged_attention`` — Pallas on TPU, a gather oracle elsewhere
    that is bitwise identical to the model's dense cache). An attention-free
    stack has no pool on the device and is booked the same way. Exhausting
    the pool raises a clean error before any page is handed out —
    neighbors' pages are never remapped.

    With ``scfg.prefix_cache`` (opt-in) a **cross-request
    prefix cache** rides on the pool: completed prefills insert their
    prompts' full pages into a radix trie keyed on page-token tuples, and
    attach walks the trie, maps every fully-matched resident page into the
    new request's block table read-only (refcount bump), skips that part
    of chunked prefill, and copy-on-writes the first partially-shared page
    (fresh page, donor rows copied on device, divergent tokens prefilled
    over the tail). Retire releases references, never pages a neighbor or
    the trie still holds; under pool pressure the trie evicts its LRU
    entries that no live request reads. Generated tokens stay identical
    with sharing on or off — a shared page holds exactly the K/V the
    request would have prefilled itself.

    **Admission and preemption** (the serving-under-pressure layer):
    ``submit`` returns a ``RequestHandle`` immediately — arrival is
    decoupled from slot attach by a priority admission queue (highest
    priority first, FIFO within a class), and a strictly-higher-priority
    arrival may preempt the lowest-priority occupant when every slot is
    busy. When the page pool exhausts, a victim is chosen by
    ``scfg.preempt_policy`` among requests *younger or lower-priority*
    than the one asking (so preemption can never ping-pong), its pages are
    released, and it is **parked for recompute-resume**: on re-attach the
    prompt re-prefills through the normal chunked path (identical chunk
    grid — and the PrefixCache fast-forward when enabled — writes bitwise
    identical K/V), and the tokens it had already generated are *replayed*
    through ordinary decode dispatches at their original positions (inputs
    forced, outputs discarded) so attention KV and recurrent state are
    recomputed by exactly the ops the uninterrupted run executed. A
    resumed stream is therefore **bitwise identical** to an ample-pool
    run, greedy or sampled (``benchmarks/run.py --check`` forces a
    preemption and asserts it). Recurrent/hybrid archs follow the PR 6
    ``done=0`` rule: resume re-runs state over every prompt token. If no
    victim is eligible the requester parks itself; only a request that
    could never fit even alone fails — with its partial allocation fully
    released first (nothing leaks). ``cancel`` frees a request's pages
    mid-stream without touching co-resident slots; ``stream`` /
    ``stream_async`` yield tokens as they flush.

    Sampling: greedy argmax by default (bitwise-stable). With
    ``greedy=False``, temperature/top-k sampling runs inside the decode and
    prefill-chunk steps from per-request base PRNG keys carried on device
    (``fold_in(PRNGKey(sample_seed), request_tag)``, written into the
    slot's key row at attach), folded with the sampled position each step
    (stateless — nothing to reset on slot reuse) — a request's stream
    depends only on (params, prompt, request_id, sample_seed), never on
    the slot it lands in, co-resident traffic, or a preemption/resume
    cycle in the middle of it.

    Token readback is **deferred and batched**: decode steps and prefill
    completions append on-device token arrays to a pending list, and one
    ``jax.device_get`` of the whole pending batch runs when a request is
    about to complete its ``max_new`` budget, every ``eos_check_every``
    ticks when ``eos_id`` is set (EOS needs token values), or on
    ``drain()``. Retirement is budget-based AND EOS-based (generated tokens
    past an EOS are dropped at flush time).

    Monitoring goes through ``repro.session``: pass a ``PerfSession`` and
    every decode dispatch is a visit of its ``decode`` region and every
    prefill chunk a visit of its ``prefill`` region, each with its own
    derived StepProfile — the report shows prefill and decode factor
    regressions separately. With no session (or a null backend) the
    scheduler runs fully uninstrumented at zero cost.

    Resilience: the scheduler is **self-healing** under injected or real
    faults. Detection is layered — an on-device NaN/Inf sentinel rides
    every decode/prefill readback (one (B,) bool per flush), optional
    per-page checksums (``checksum_pages``) validate shared pages at
    prefix attach, and an optional per-dispatch watchdog
    (``watchdog_deadline_s``) catches wedged dispatch paths. Recovery is
    unified: a faulted request RETRIES through the park/recompute-resume
    path with capped exponential backoff (its stream stays bitwise
    identical to an unfaulted run), exhausting ``max_retries``
    QUARANTINES it (terminal "failed", pages freed and scrubbed,
    co-residents untouched), and ``shed_queue_depth`` sheds the
    lowest-priority waiter at admission under sustained pressure.
    Every recovery action is a visit of the session's ``recovery``
    region and is counted in ``kv_cache_stats()["recovery"]``. A seeded
    ``repro.serve.faults.FaultInjector`` (``fault_injector=``) drives
    chaos schedules through these exact paths.
    """

    def __init__(self, cfg, mesh, scfg: ServeConfig, params, session=None,
                 fault_injector=None):
        from repro.session import PerfSession, SessionConfig

        self.cfg, self.mesh, self.scfg = cfg, mesh, scfg
        self.params = params
        # chunked recurrences re-chunk internally at ssm/xlstm chunk: a
        # prefill chunk larger than that must tile it exactly
        for inner in (cfg.ssm.chunk if cfg.ssm else None,
                      cfg.xlstm.chunk if cfg.xlstm else None):
            if inner and scfg.prefill_chunk > inner and scfg.prefill_chunk % inner:
                raise ValueError(
                    f"prefill_chunk={scfg.prefill_chunk} must be <= the "
                    f"recurrent chunk {inner} or a multiple of it"
                )
        if scfg.max_len % scfg.page_size:
            raise ValueError(
                f"max_len ({scfg.max_len}) must be divisible by page_size "
                f"({scfg.page_size}): each slot's block table holds "
                f"max_len / page_size pages"
            )
        # default: off, but env-activatable (TALP_ENABLE=1) like every other
        # entry point; the caller owns finalize() (also via self.session)
        self.session = session if session is not None else PerfSession(
            SessionConfig(app_name="serve", backend="null")
        )
        if scfg.spec_decode:
            # the verify chunk is spec_k draft tokens + the committed input
            # token, scored in one multi-token dispatch — it tiles the
            # recurrent inner chunk under the same rule as prefill chunks
            for inner in (cfg.ssm.chunk if cfg.ssm else None,
                          cfg.xlstm.chunk if cfg.xlstm else None):
                verify_c = scfg.spec_k + 1
                if inner and verify_c > inner and verify_c % inner:
                    raise ValueError(
                        f"spec_k+1={verify_c} (the verify chunk) must be <= "
                        f"the recurrent chunk {inner} or a multiple of it"
                    )
        decode_fn, prefill_fn, self._cow_copy, verify_fn = _serve_step_fns(
            cfg, mesh, scfg.step_statics(),
        )
        self.decode = self.session.wrap_step(
            decode_fn,
            region="decode",
            derive=True,
            num_devices=mesh.devices.size,
            # observe the sampled tokens only: blocking on the donated cache
            # tuple would serialize the decode pipeline
            observe=lambda out: {"outputs": out[0]},
        )
        self.prefill = self.session.wrap_step(
            prefill_fn,
            region="prefill",
            derive=True,
            num_devices=mesh.devices.size,
            observe=lambda out: {"outputs": out[0]},
        )
        # batched speculative verify shares the decode session region: a
        # spec tick IS the decode tick, just K+1 tokens wide
        self.verify = None
        if verify_fn is not None:
            self.verify = self.session.wrap_step(
                verify_fn,
                region="decode",
                derive=True,
                num_devices=mesh.devices.size,
                observe=lambda out: {"outputs": out[0]},
            )
        # paged KV: shared pool + per-slot block tables + free-list
        # allocator. Tables are host-authored (numpy, -1 = unallocated) and
        # mirrored to device lazily — one small upload per tick at most,
        # only when an allocation or a free actually changed them.
        self._max_pages = scfg.max_len // scfg.page_size
        n_pages = scfg.num_pages
        if n_pages is None:
            n_pages = scfg.batch * self._max_pages
        self._alloc = PageAllocator(n_pages)
        self._tables = np.full((scfg.batch, self._max_pages), -1, np.int32)
        self._slot_pages: list[list[int]] = [[] for _ in range(scfg.batch)]
        self._tables_dirty = True
        self._tables_dev = None
        self.caches = T.init_cache(
            cfg, scfg.batch, scfg.max_len, paged=True,
            page_size=scfg.page_size, num_pages=n_pages,
        )
        self._prefix: PrefixCache | None = (
            PrefixCache(scfg.page_size, self._alloc,
                        capacity=scfg.prefix_trie_capacity)
            if scfg.prefix_cache else None
        )
        # per-slot sampling key rows, carried on device. In sampled mode the
        # attach overwrites the slot's row with the REQUEST's key
        # (fold_in(base_key, request_tag)), and each sampling step folds that
        # with the sampled position — so a request's stream is a pure
        # function of (params, prompt, request_id, sample_seed), independent
        # of slot placement (a preempted request may resume elsewhere) and
        # co-resident traffic (greedy never reads the keys)
        self._base_key = jax.random.PRNGKey(scfg.sample_seed)
        self.rng_keys = jax.random.split(self._base_key, scfg.batch)
        # fresh-state template for slot reuse: unlike attention KV (stale
        # lines are masked by cache_len/kv_len), recurrent state has no
        # positional masking, so a reattached slot must have its carries
        # restored to their INITIAL values — which are not all zero (sLSTM's
        # stabilizer m starts at -1e30). One batch-1 leaf per recurrent
        # cache entry, broadcast into the reused slots' rows at attach.
        self._fresh_state = [
            None if "attn" in _cache_path_name(path) else leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                T.init_cache(cfg, 1, 1)
            )[0]
        ]
        self._has_recurrent = any(l is not None for l in self._fresh_state)
        if self._has_recurrent:
            # compiled here, in set-up, with no slot index in range: once
            # for the caches as made, once for them as placed on the mesh,
            # as every step hands them back, so a reuse compiles nothing
            self._reset_slots([])
            self._reset_slots([])
        self._dirty: set[int] = set()  # slots whose state may be non-fresh
        self.tokens = jnp.zeros((scfg.batch, 1), jnp.int32)
        self.queue: list[dict] = []    # admission queue: priority, FIFO within
        self.active: list[dict | None] = [None] * scfg.batch   # decoding slots
        self.pos = np.zeros(scfg.batch, np.int32)              # per-slot position
        self.completed: list[dict] = []
        self.cancelled: list[dict] = []   # cancelled mid-stream
        self.failed: list[dict] = []      # fatal pool pressure (unwound clean)
        self._parked: list[dict] = []     # preempted, awaiting recompute-resume
        self._by_id: dict = {}            # request_id -> req (handles, cancel)
        self._seq = 0                     # admission order: FIFO within a class
        # recompute-resume replay: slot -> generated tokens still to re-feed
        # through decode at their original positions (outputs discarded)
        self._replay: dict[int, list[int]] = {}
        # in-flight prefills: FIFO of {"req","slot","prompt","done"}
        self._prefills: list[dict] = []
        self._prefilling: list[dict | None] = [None] * scfg.batch
        # next-token seeds {slot: device scalar} applied in ONE scatter/tick;
        # keyed by slot so a retired request's still-queued seed can never
        # race the reattached request's seed in the scatter
        self._seeds: dict[int, Any] = {}
        # pending readbacks: (device tokens (n,1), device bad-sentinel (n,),
        # row->request map); flushed in a single device_get
        self._pending: list[tuple[Any, Any, list[dict | None]]] = []
        # (dispatch kind, tokens, device counters) kept while a capture
        # runs, for the next flush's readback (``_keep_moe_counters``)
        self._moe_counts: list[tuple[str, int, Any]] = []
        # speculative decode: per-slot next input token, host-side. Spec
        # ticks trade the deferred-readback pipeline for width — the accept
        # count must reach the host before the next tick can plan drafts,
        # so each verify dispatch reads back immediately (a few scalars)
        # and amortizes the sync over up to spec_k+1 tokens.
        self._last_tok = np.zeros(scfg.batch, np.int32)
        # -- fault injection + recovery state --------------------------
        # ``faults`` is a repro.serve.faults.FaultInjector (or None); the
        # scheduler polls it once per tick and applies due events through
        # the same paths real faults would corrupt
        self.faults = fault_injector
        self.shed: list[dict] = []        # load-shed at admission
        self._fault_nan_slots: set[int] = set()   # poison next decode dispatch
        # request ids with a poisoned row dispatched but not yet flushed:
        # ineligible for further nan/hang targeting (a second poison in
        # that window would be swallowed by the retry already in flight)
        self._fault_nan_inflight: set = set()
        self._fault_mask_zero = jnp.zeros((scfg.batch,), bool)
        # transient allocator spikes: (release_tick, pages) — released in
        # _apply_faults even after the injector drains, and force-released
        # by drain() so chaos runs can never leak pool pages
        self._spike_holds: list[tuple[int, list[int]]] = []
        self._hang_pending: float = 0.0   # injected dispatch delay (s)
        self._hang_slot: int | None = None
        # physical pages the injector corrupted: scrubbed (zeroed on
        # device) when their holder retries/quarantines, so a recycled
        # page can never leak NaNs into its next owner's masked tail
        self._corrupted_pages: set[int] = set()
        self._page_edit = None            # lazily-built jitted page edits
        self._fingerprint = None
        if scfg.checksum_pages:
            from repro.serve import faults as _F
            self._fingerprint = _F.page_fingerprint_step()
        self.stats = {
            "ticks": 0, "decode_steps": 0, "prefill_chunks": 0,
            # overlap accounting: "overlap_ticks" counts ticks where a
            # prefill was in flight alongside >=1 decoding slot (proof the
            # two phases actually co-existed); "decode_after_prefill_ticks"
            # counts ticks whose decode dispatch only happened AFTER prefill
            # work ran in the same tick — i.e. the decode pipeline waited on
            # a prefill. The no-decode-gap guarantee is overlap_ticks > 0
            # under mixed traffic and decode_after_prefill_ticks == 0.
            "overlap_ticks": 0, "decode_after_prefill_ticks": 0,
            # pressure accounting (the serving-under-load counters surfaced
            # by kv_cache_stats()["pressure"] and launch/serve.py)
            "preemptions": 0, "resumes": 0, "cancellations": 0,
            "pages_freed_by_preempt": 0, "evictions_for_preempt": 0,
            "peak_queue_depth": 0,
            # recovery accounting (kv_cache_stats()["recovery"]): what the
            # self-healing layer actually did — retries taken, backoff
            # ticks served, quarantines, load-sheds, checksum mismatches
            # caught at prefix attach, watchdog trips
            "retries": 0, "backoff_total_ticks": 0, "quarantined": 0,
            "shed": 0, "checksum_failures": 0, "watchdog_trips": 0,
            # speculation accounting (kv_cache_stats()["speculation"]):
            # drafted/accepted/rejected count DRAFT tokens only (the
            # committed input token of each verify chunk is not a draft);
            # spec_emitted counts newly-emitted tokens (excludes resume/
            # retry replay tokens re-verified through the same dispatches)
            "spec_dispatches": 0, "spec_drafted": 0, "spec_accepted": 0,
            "spec_rejected": 0, "spec_emitted": 0,
        }

    def submit(self, prompt_tokens, request_id, max_new: int = 32,
               priority: int = 0) -> RequestHandle:
        """Admit a request; returns a ``RequestHandle`` immediately (arrival
        is decoupled from slot attach by the admission queue). ``priority``
        orders admission — higher first, FIFO within a class — and bounds
        preemption: a request can only ever evict strictly-lower-priority
        or strictly-younger work."""
        prompt = list(prompt_tokens)
        if max_new < 1:
            # the first generated token falls out of the prefill logits
            # unconditionally, so a zero budget is unsatisfiable
            raise ValueError(f"request {request_id!r}: max_new must be >= 1")
        # cache writes past max_len would be silently dropped by the masked
        # scatter (mode="drop") — garbage tokens with no error — so reject
        # oversized requests at the door. The last decode writes position
        # prompt_len + max_new - 2 (the final sampled token is never fed
        # back), hence the -1 slack; an empty prompt gets no prefill token,
        # so all max_new tokens come from decode writes at 0..max_new-1.
        need = len(prompt) + max(max_new - 1, 0) if prompt else max_new
        if need > self.scfg.max_len:
            raise ValueError(
                f"request {request_id!r} needs {need} cache positions "
                f"(prompt {len(prompt)}, max_new {max_new}) but "
                f"max_len={self.scfg.max_len}"
            )
        # a request that cannot fit even with the pool to itself would
        # otherwise park forever under the preemption policy (and the
        # admission queue hides the old immediate RuntimeError) — reject it
        # at the door like the max_len check above
        pages = -(-need // self.scfg.page_size)
        if pages > self._alloc.num_pages:
            raise ValueError(
                f"request {request_id!r} needs {pages} page(s) "
                f"(prompt {len(prompt)}, max_new {max_new}, page_size "
                f"{self.scfg.page_size}) but the pool only holds "
                f"{self._alloc.num_pages}; raise ServeConfig.num_pages "
                f"(--num-pages)"
            )
        req = {
            "id": request_id, "prompt": prompt, "max_new": max_new,
            "generated": [], "_pending": 0, "priority": int(priority),
            "_seq": self._seq, "_tag": _request_tag(request_id),
            "_status": "queued", "_cancelled": False,
            "_retries": 0, "_not_before": 0,
        }
        self._seq += 1
        self.queue.append(req)
        self._by_id[request_id] = req
        if (self.scfg.shed_queue_depth is not None
                and len(self.queue) > self.scfg.shed_queue_depth):
            # sustained pressure: shed the lowest-priority youngest waiter
            # (possibly this arrival) with a clear terminal status rather
            # than queueing without bound — the shed handle reports "shed"
            # immediately, it never raises
            victim = min(self.queue, key=lambda r: (r["priority"], -r["_seq"]))
            self.queue.remove(victim)
            victim["_status"] = "shed"
            self.shed.append(victim)
            self.stats["shed"] += 1
            self.session.event("recovery")
        self.stats["peak_queue_depth"] = max(
            self.stats["peak_queue_depth"],
            len(self.queue) + len(self._parked),
        )
        return RequestHandle(self, req)

    def cancel(self, request_id) -> bool:
        """Cancel mid-stream: remove the request from whichever pool holds
        it (admission queue, parked set, in-flight prefill, or a decoding
        slot), release its pages, and close its stream. Prefix-trie pins
        and co-resident slots are untouched — their token streams are
        bitwise unaffected. Tokens already flushed stay on the handle;
        dispatched-but-unflushed rows are dropped at the next flush.
        Returns True if the request was still live."""
        req = self._by_id.get(request_id)
        if req is None or req["_status"] in _TERMINAL:
            return False
        req["_cancelled"] = True
        if req in self.queue:
            self.queue.remove(req)
        elif req in self._parked:
            self._parked.remove(req)
        else:
            slot = self._slot_of(req)
            if slot is not None:
                self._detach(slot)
        req["_status"] = "cancelled"
        self.cancelled.append(req)
        self.stats["cancellations"] += 1
        return True

    def flush(self) -> None:
        """Materialize pending tokens now (streaming callers; batch callers
        can keep relying on the automatic flush boundaries)."""
        self._flush()

    def stream(self, request_id, *, timeout: int | None = None):
        """Generator of ``request_id``'s tokens, driving the scheduler:
        each iteration steps and flushes until new tokens land. Ends when
        the request completes (or is cancelled / fails / is shed).
        Co-resident requests advance as a side effect, exactly as in a
        plain step loop — several interleaved ``stream`` consumers are
        fine. ``timeout`` bounds the scheduler ticks spent waiting
        BETWEEN tokens: when the request makes no progress for that many
        ticks (a stalled scheduler, a wedged dispatch with the watchdog
        off), ``TimeoutError`` is raised instead of spinning forever."""
        req = self._by_id.get(request_id)
        if req is None:
            raise KeyError(f"unknown request {request_id!r}")
        limit = timeout if timeout is not None else 100_000
        sent, idle = 0, 0
        while True:
            while sent < len(req["generated"]):
                idle = 0
                yield req["generated"][sent]
                sent += 1
            if req["_status"] in _TERMINAL:
                return
            if idle >= limit:
                if timeout is not None:
                    raise TimeoutError(
                        f"request {request_id!r} made no progress in "
                        f"{idle} scheduler ticks (status "
                        f"{req['_status']!r})"
                    )
                # insurance against a scheduling livelock
                raise RuntimeError(
                    f"request {request_id!r} stalled in stream() "
                    f"(status {req['_status']!r})"
                )
            self.step()
            self._flush()
            idle += 1

    async def stream_async(self, request_id):
        """Async variant of ``stream``: yields control to the event loop
        between ticks, so several ``stream_async`` consumers (one per
        request) interleave over one scheduler — whichever consumer runs
        next drives the shared tick, and every slot advances."""
        import asyncio

        req = self._by_id.get(request_id)
        if req is None:
            raise KeyError(f"unknown request {request_id!r}")
        sent = 0
        while True:
            while sent < len(req["generated"]):
                yield req["generated"][sent]
                sent += 1
            if req["_status"] in _TERMINAL:
                return
            self.step()
            self._flush()
            await asyncio.sleep(0)

    # -- attach / prefill ------------------------------------------------

    def _free(self, slot: int) -> bool:
        return self.active[slot] is None and self._prefilling[slot] is None

    def _attach(self) -> None:
        if self.scfg.preempt_policy != "never":
            self._preempt_for_priority()
        if not (self.queue or self._parked):
            return
        order = lambda r: (-r["priority"], r["_seq"])
        self.queue.sort(key=order)    # stable: FIFO within a priority class
        self._parked.sort(key=order)
        reused: list[int] = []
        for slot in range(self.scfg.batch):
            if not self._free(slot):
                continue
            req = self._next_admittable()
            if req is None:
                break
            if not self._attach_one(slot, req, reused):
                break  # attach-time pool pressure: try again next tick
        if reused:
            self._reset_slots(reused)

    def _next_admittable(self) -> dict | None:
        """Best waiter across the admission queue and the parked set, on
        (priority desc, arrival seq asc) — a parked request keeps its
        original seq, so at equal priority it naturally outranks younger
        queued arrivals. Parked candidates must also pass the resume gate
        (enough free or trie-reclaimable pages for prompt + history), so a
        resume cannot immediately thrash back out — and the retry
        backoff gate (``_ready``), so a retrying request serves its
        backoff before it may re-attach."""
        order = lambda r: (-r["priority"], r["_seq"])
        parked = next(
            (r for r in self._parked
             if self._ready(r) and self._resume_fits(r)),
            None,
        )
        queued = self.queue[0] if self.queue else None
        if parked is not None and (
            queued is None or order(parked) <= order(queued)
        ):
            self._parked.remove(parked)
            return parked
        if queued is not None:
            return self.queue.pop(0)
        return None

    def _ready(self, req: dict) -> bool:
        """Retry backoff gate: a retrying request stays parked until its
        ``_not_before`` tick passes (capped exponential backoff set by
        ``_fault_retry``)."""
        return req["_not_before"] <= self.stats["ticks"]

    def _resume_fits(self, req) -> bool:
        need = len(req["prompt"]) + max(len(req["generated"]), 1)
        need = -(-need // self.scfg.page_size)
        avail = self._alloc.free_pages
        if self._prefix is not None:
            avail += self._prefix.reclaimable()
        return avail >= need

    def _attach_one(self, slot: int, req: dict, reused: list[int]) -> bool:
        """Attach ``req`` to the free ``slot``; False on attach-time pool
        pressure (the request is put back where it came from, fully
        unwound). A request with generated history is a recompute-resume:
        the prompt re-prefills on the normal chunk grid and the history is
        scheduled for decode replay. A retrying request (fault recovery)
        rides the identical path: the clean history replays, the faulted
        suffix recomputes — which is why a retried stream is bitwise
        identical to an unfaulted run."""
        resume = req["_status"] in ("preempted", "retrying")
        self.pos[slot] = 0
        if slot in self._dirty:
            reused.append(slot)
        self._dirty.add(slot)
        if not self.scfg.greedy:
            # the slot's sampling key row becomes the REQUEST's key, so a
            # resumed request keeps its exact stream in any slot
            self.rng_keys = self.rng_keys.at[slot].set(
                jax.random.fold_in(self._base_key, req["_tag"])
            )
        if not req["prompt"]:
            # nothing to prefill: decode from an empty cache off a constant
            # BOS-like seed; on resume, replay the WHOLE history (the seed
            # token regenerates generated[0], which is discarded)
            if self.scfg.spec_decode:
                self._last_tok[slot] = 0
            else:
                self._seeds[slot] = 0
            if req["generated"]:
                self._replay[slot] = list(req["generated"])
            self.active[slot] = req
            req["_status"] = "decoding"
            if resume:
                self.stats["resumes"] += 1
            return True
        # drop any stale seed a just-retired request left queued
        self._seeds.pop(slot, None)
        task = {"req": req, "slot": slot, "done": 0,
                "prompt": np.asarray(req["prompt"], np.int32)}
        if self._prefix is not None:
            try:
                task["done"] = self._attach_prefix(slot, req)
            except _PoolPressure as e:
                # unwind the partial page mapping — a failed attach leaks
                # nothing — and put the request back
                self._release_slot_pages(slot)
                if e.fatal:
                    req["_status"] = "failed"
                    self.failed.append(req)
                    raise RuntimeError(
                        f"{e.msg} [kv_cache_stats: {self.kv_cache_stats()}]"
                    ) from None
                if resume:
                    self._parked.append(req)
                else:
                    req["_status"] = "queued"
                    self.queue.append(req)
                return False
        req["_status"] = "prefilling"
        if resume:
            self.stats["resumes"] += 1
        self._prefilling[slot] = task
        self._prefills.append(task)
        return True

    # -- preemption (serving under memory pressure) ----------------------

    def _occupant(self, slot: int) -> dict | None:
        task = self._prefilling[slot]
        return self.active[slot] or (task["req"] if task else None)

    def _slot_of(self, req: dict) -> int | None:
        for slot in range(self.scfg.batch):
            if self._occupant(slot) is req:
                return slot
        return None

    def _detach(self, slot: int) -> None:
        """Pull whatever occupies ``slot`` off the batch: drop its
        in-flight prefill task, clear the slot, release its pages and its
        per-slot decode state. The request dict itself is untouched —
        callers decide where it goes next (parked, cancelled,
        quarantined)."""
        task = self._prefilling[slot]
        if task is not None:
            self._prefills.remove(task)
            self._prefilling[slot] = None
        self.active[slot] = None
        self._release_slot_pages(slot)
        self._seeds.pop(slot, None)
        self._replay.pop(slot, None)

    def _preempt_for_priority(self) -> None:
        """A strictly-higher-priority waiter stuck behind a fully-busy
        batch evicts the lowest-priority occupant — one per tick (attach
        runs every tick), so a burst of high-priority arrivals drains the
        batch incrementally instead of thrashing it in one go."""
        waiters = [r["priority"] for r in self.queue]
        waiters += [r["priority"] for r in self._parked
                    if self._ready(r) and self._resume_fits(r)]
        if not waiters or any(
            self._free(s) for s in range(self.scfg.batch)
        ):
            return
        top = max(waiters)
        occ = [
            (r["priority"], -r["_seq"], slot)
            for slot in range(self.scfg.batch)
            if (r := self._occupant(slot)) is not None and r["priority"] < top
        ]
        if occ:
            self._preempt(min(occ)[2])  # lowest priority, youngest tiebreak

    def _pick_victim(self, requester: dict) -> int | None:
        """Pool-pressure victim for ``requester``, by ``preempt_policy``.
        Only strictly lower-priority — or equal-priority strictly younger —
        occupants are eligible, so preemption is a strict order and can
        never ping-pong (the oldest highest-priority request always makes
        progress). Slots holding no pages are skipped: evicting them frees
        nothing."""
        rp, rs = requester["priority"], requester["_seq"]
        cand = []
        for slot in range(self.scfg.batch):
            occ = self._occupant(slot)
            if occ is None or occ is requester or not self._slot_pages[slot]:
                continue
            if occ["priority"] < rp or (
                occ["priority"] == rp and occ["_seq"] > rs
            ):
                cand.append((slot, occ))
        if not cand:
            return None
        policy = self.scfg.preempt_policy
        if policy == "pages":        # free the most memory per eviction
            key = lambda c: -len(self._slot_pages[c[0]])
        elif policy == "progress":   # least work lost to recompute
            key = lambda c: len(c[1]["generated"]) + c[1]["_pending"]
        else:                        # "priority": cheapest class first, then
            key = lambda c: (        # most pages, then least progress
                c[1]["priority"],
                -len(self._slot_pages[c[0]]),
                len(c[1]["generated"]) + c[1]["_pending"],
            )
        return min(cand, key=key)[0]

    def _preempt(self, slot: int) -> None:
        """Evict ``slot``'s request for recompute-resume: flush first (its
        generated history must be complete on the host — replay re-feeds
        it), release every page it holds, and park it. The prefix trie
        keeps its own pins, so a preempted request's shared prompt pages
        stay cached for its resume (and for everyone else)."""
        self._flush()
        req = self._occupant(slot)
        if req is None:
            return  # the flush retired it — pressure already relieved
        with self.session.region("preempt"):
            freed = len(self._slot_pages[slot])
            self._detach(slot)
            req["_status"] = "preempted"
            self._parked.append(req)
            self.stats["preemptions"] += 1
            self.stats["pages_freed_by_preempt"] += freed

    def _handle_pressure(self, slot: int, e: _PoolPressure) -> None:
        """An allocation for ``slot``'s own request failed even after trie
        eviction and victim preemption. Non-fatal: park the requester
        itself (pressure relieves as older/higher-priority work retires).
        Fatal: unwind everything the request holds — nothing leaks — and
        surface the exhaustion."""
        if not e.fatal:
            self._preempt(slot)
            return
        req = self._occupant(slot)
        self._detach(slot)
        if req is not None:
            req["_status"] = "failed"
            self.failed.append(req)
        raise RuntimeError(
            f"{e.msg} [kv_cache_stats: {self.kv_cache_stats()}]"
        ) from None

    def _reset_slots(self, slots: list[int]) -> None:
        """Restore reused slots' recurrent-state cache rows (SSM/conv/xLSTM
        carries) to their initial values before the new request runs.
        Attention KV needs no reset — freed pages leave the block table,
        so stale lines never enter the visible window — and the sampling
        keys are stateless (folded with the position per step), but
        recurrent state carries unconditionally, so without this the first
        prefill chunk (or decode step) of a reattached slot would continue
        from the retired request's final state."""
        if not self._has_recurrent:
            return
        # one shape whatever the number of slots (the rest of the index
        # row is out of range and dropped): one program, compiled once
        idx = np.full(self.scfg.batch, self.scfg.batch, np.int32)
        idx[: len(slots)] = slots
        with compat.use_mesh(self.mesh):
            self.caches = _reset_rows(self.caches, self._fresh_state,
                                      jnp.asarray(idx))

    # -- fault recovery (retry / quarantine / injection) -----------------

    def _fault_retry(self, req: dict) -> None:
        """Send ``req`` through the retry path: flush (its generated
        history must be complete on the host — resume replays it), detach
        it from its slot (pages freed, injector-corrupted pages scrubbed
        on the way out), and park it with a capped-exponential-backoff
        ready tick. The re-attach rides the same recompute-resume path as
        preemption — prompt re-prefill on the original chunk grid, decode
        replay with forced inputs — so the retried stream is bitwise
        identical to an unfaulted run, greedy and sampled. A request that
        exhausts ``max_retries`` is quarantined instead."""
        self._flush()
        if req["_status"] in _TERMINAL or (
            req["_status"] == "retrying" and req in self._parked
        ):
            return  # already resolved (or already parked for retry)
        if req["_retries"] >= self.scfg.max_retries:
            self._quarantine(req)
            return
        slot = self._slot_of(req)
        if slot is not None:
            self._detach(slot)
        elif req in self.queue:
            self.queue.remove(req)
        elif req in self._parked:
            self._parked.remove(req)
        req["_retries"] += 1
        backoff = min(
            self.scfg.retry_backoff_cap,
            self.scfg.retry_backoff_base << (req["_retries"] - 1),
        )
        req["_not_before"] = self.stats["ticks"] + backoff
        req["_status"] = "retrying"
        self._parked.append(req)
        self.stats["retries"] += 1
        self.stats["backoff_total_ticks"] += backoff
        self.session.event("recovery")

    def _quarantine(self, req: dict) -> None:
        """Retries exhausted: the request ends in terminal ``failed``
        status (surfaced on its handle exactly like a fatal pool
        exhaustion), its pages are freed, and every co-resident stream is
        untouched — a request the hardware keeps poisoning is a cheap
        rejection, never a scheduler crash."""
        slot = self._slot_of(req)
        if slot is not None:
            self._detach(slot)
        elif req in self.queue:
            self.queue.remove(req)
        elif req in self._parked:
            self._parked.remove(req)
        req["_status"] = "failed"
        self.failed.append(req)
        self.stats["quarantined"] += 1
        self.session.event("recovery")

    def _scrub_slot(self, slot: int) -> None:
        """Zero (on device) any injector-corrupted page ``slot`` still
        maps, just before its pages return to the free list. The free
        list recycles pages verbatim and attention's additive masking
        propagates NaN even from masked rows — a NaN page handed to the
        next request would poison it. Scrubbed through the same jitted
        page-edit step the injector corrupts with."""
        dirty = [p for p in self._slot_pages[slot]
                 if p in self._corrupted_pages]
        if not dirty:
            return
        if self._page_edit is None:
            from repro.serve import faults as _F
            self._page_edit = _F.page_edit_step("zero")
        with compat.use_mesh(self.mesh):
            for p in dirty:
                self.caches = self._page_edit(
                    self.caches, jnp.asarray(p, jnp.int32)
                )
        self._corrupted_pages.difference_update(dirty)

    def _apply_faults(self) -> None:
        """Release expired allocator spikes and apply every due injector
        event (chaos runs only — ``self.faults`` is None otherwise). An
        event with no applicable target this tick is deferred, so every
        scheduled fault eventually lands while work is live; targets are
        chosen by the event's seeded picks, so a rerun of the same
        schedule hits the same victims."""
        tick = self.stats["ticks"]
        if self._spike_holds:
            expired = [h for h in self._spike_holds if h[0] <= tick]
            if expired:
                self._spike_holds = [
                    h for h in self._spike_holds if h[0] > tick
                ]
                for _, pages in expired:
                    self._alloc.release(pages)
        if self.faults is None:
            return
        for e in self.faults.due(tick):
            if e.kind == "nan":
                cand = self._fault_decode_slots(e.request_id)
                if not cand:
                    self.faults.defer(e, tick)
                    continue
                victim = cand[e.pick % len(cand)]
                if victim in self._fault_nan_slots:
                    # already poisoned this tick: two NaNs in one dispatch
                    # are indistinguishable from one — defer so every
                    # scheduled injection poisons a distinct dispatch
                    self.faults.defer(e, tick)
                    continue
                self._fault_nan_slots.add(victim)
                self.faults.record(e.kind)
            elif e.kind == "hang":
                cand = self._fault_decode_slots(e.request_id)
                if not cand:
                    self.faults.defer(e, tick)
                    continue
                self._hang_slot = cand[e.pick % len(cand)]
                self._hang_pending = self.faults.fcfg.hang_s
                self.faults.record(e.kind)
            elif e.kind == "alloc_spike":
                if self._alloc.free_pages == 0:
                    self.faults.defer(e, tick)
                    continue
                n = min(self.faults.fcfg.spike_pages,
                        self._alloc.free_pages)
                pages = self._alloc.alloc(n, owner="fault-injector")
                self._spike_holds.append(
                    (tick + self.faults.fcfg.spike_ticks, pages)
                )
                self.faults.record(e.kind)
            elif e.kind == "page_corrupt":
                mode = self.faults.fcfg.corrupt_mode
                cand = self._fault_page_candidates(mode, e.request_id)
                if not cand:
                    self.faults.defer(e, tick)
                    continue
                page = cand[e.pick2 % len(cand)]
                from repro.serve import faults as _F
                with compat.use_mesh(self.mesh):
                    self.caches = _F.page_edit_step(mode)(
                        self.caches, jnp.asarray(page, jnp.int32)
                    )
                if mode == "nan":
                    # a NaN page must be scrubbed before recycling; flipped
                    # bits stay finite and are fully overwritten/masked for
                    # the next owner, so they need no scrub
                    self._corrupted_pages.add(page)
                self.faults.record(e.kind)

    def _fault_decode_slots(self, request_id) -> list[int]:
        """Slots a decode-dispatch fault (nan/hang) can target: actively
        decoding, not replaying history (a replay row's output is
        discarded — poisoning it would be invisible), optionally pinned
        to one request id (quarantine tests)."""
        return [
            s for s in range(self.scfg.batch)
            if (r := self.active[s]) is not None
            and r["_status"] == "decoding"
            and s not in self._replay
            and r["id"] not in self._fault_nan_inflight
            and (request_id is None or r["id"] == request_id)
        ]

    def _fault_page_candidates(self, mode: str, request_id) -> list[int]:
        """Physical pages a corruption can hit. ``nan`` mode targets an
        UNSHARED page of a decoding slot — the victim's own sentinel
        catches it on its next attention read, nobody else maps the page.
        ``bitflip`` mode targets a trie-cached page no slot currently
        maps (trie pin only) — finite garbage that only the checksum
        validation at the next prefix share can catch."""
        if mode == "nan":
            return [
                p
                for s in self._fault_decode_slots(request_id)
                for p in self._slot_pages[s]
                if self._alloc.refs.get(p) == 1
            ]
        if self._prefix is None:
            return []
        mapped = {p for pages in self._slot_pages for p in pages}
        cand = []
        stack = list(self._prefix.root.children.values())
        while stack:
            node = stack.pop()
            if node.page not in mapped and self._alloc.refs.get(
                node.page
            ) == 1:
                cand.append(node.page)
            stack.extend(node.children.values())
        return sorted(cand)

    # -- paged-pool bookkeeping ------------------------------------------

    def _alloc_pages(self, n: int, req: dict) -> list[int]:
        """Allocate for ``req``, escalating under pool pressure: (1) evict
        LRU prefix-trie entries no live request reads, (2) preempt a victim
        chosen by ``scfg.preempt_policy`` (strictly younger or
        lower-priority than the requester), repeat. If the pool is still
        short, raise ``_PoolPressure`` — non-fatal parks the requester for
        recompute-resume; fatal (policy "never", or nobody else holds
        anything reclaimable) unwinds and surfaces as RuntimeError with the
        full kv/sharing accounting."""
        while True:
            if self._prefix is not None and n > self._alloc.free_pages:
                freed = self._prefix.evict_for(n - self._alloc.free_pages)
                self.stats["evictions_for_preempt"] += freed
            if n <= self._alloc.free_pages:
                return self._alloc.alloc(n, owner=req["id"])
            victim = (
                self._pick_victim(req)
                if self.scfg.preempt_policy != "never" else None
            )
            if victim is not None:
                self._preempt(victim)
                continue
            others_hold = any(
                self._slot_pages[s]
                for s in range(self.scfg.batch)
                if (occ := self._occupant(s)) is not None and occ is not req
            )
            reclaim = (
                self._prefix.reclaimable() if self._prefix is not None else 0
            )
            # an injected allocator spike holds pages that WILL come back
            # in a few ticks: never a fatal exhaustion — park and wait it
            # out (same transient-pressure semantics as a co-tenant burst)
            fatal = not self._spike_holds and (
                self.scfg.preempt_policy == "never"
                or (not others_hold and reclaim == 0)
            )
            raise _PoolPressure(
                fatal,
                f"paged KV pool exhausted: request {req['id']!r} needs {n} "
                f"more page(s) but only {self._alloc.free_pages} of "
                f"{self._alloc.num_pages} are free and no victim is "
                f"eligible (preempt_policy={self.scfg.preempt_policy!r}); "
                f"raise ServeConfig.num_pages (--num-pages) or retire "
                f"requests sooner",
            )

    def _ensure_pages(self, slot: int, last_pos: int, req: dict) -> None:
        """Grow ``slot``'s block table so position ``last_pos`` (inclusive)
        is backed by a physical page; no-op when already covered.

        Pages are acquired ONE AT A TIME so each gets the full
        ``_alloc_pages`` escalation (trie eviction, victim preemption)
        before the next is requested; when the pool runs dry mid-grow —
        a multi-page speculative accept is the common trigger — the pages
        already taken are unwound page-by-page (freed, table row restored
        to -1) before the pressure propagates, so a failed grow can never
        leak a partial allocation."""
        need = last_pos // self.scfg.page_size + 1
        have = len(self._slot_pages[slot])
        if need <= have:
            return
        added: list[int] = []
        try:
            for j in range(have, need):
                page = self._alloc_pages(1, req)[0]
                self._tables[slot, j] = page
                self._slot_pages[slot].append(page)
                added.append(page)
                self._tables_dirty = True
        except _PoolPressure:
            for page in reversed(added):
                self._slot_pages[slot].pop()
                self._tables[slot, len(self._slot_pages[slot])] = -1
                self._alloc.release([page])
                self._tables_dirty = True
            raise

    def _attach_prefix(self, slot: int, req) -> int:
        """Map the trie's longest cached prefix of ``req``'s prompt into
        ``slot``'s block table at attach. Fully-matched pages are mapped
        read-only (refcount bump — their prefill is skipped entirely); a
        partially-matched page is copy-on-write: a fresh page is
        allocated, the donor's rows are copied on device, and the
        divergent tokens are prefilled over its tail. At least one prompt
        token is always left to prefill — the final chunk's logits sample
        the request's first generated token. Returns the prefill
        fast-forward (prompt tokens already backed by mapped pages).

        Hybrid/recurrent archs still share matched pages (the memory win)
        but skip no compute: recurrent state has no positional masking,
        so the full prompt must run through the stack regardless.
        Re-prefilling a shared page writes bitwise-identical K/V (same
        tokens, same positions, same chunk grid as the original), so
        concurrent readers of the shared page are unharmed."""
        prompt = req["prompt"]
        psize = self.scfg.page_size
        chain, donor, donor_rows = self._prefix.match(prompt)
        if self._fingerprint is not None:
            chain, donor, donor_rows = self._verify_chain(
                chain, donor, donor_rows
            )
        st = self._prefix.stats
        if self._has_recurrent:
            for j, node in enumerate(chain):
                self._alloc.share([node.page])
                self._tables[slot, j] = node.page
                self._slot_pages[slot].append(node.page)
                self._prefix._touch(node)
            if chain:
                self._tables_dirty = True
                st["hits"] += 1
                st["hit_tokens"] += len(chain) * psize
                st["pages_shared"] += len(chain)
            else:
                st["misses"] += 1
            return 0
        use = len(chain) * psize + donor_rows
        use = min(use, len(prompt) - 1)
        if use <= 0:
            st["misses"] += 1
            return 0
        n_full, cow_rows = divmod(use, psize)
        # the leave-one-token clamp can demote the last fully-matched page
        # to the copy-on-write donor (prompt ends exactly on its boundary)
        cow_donor = None
        if cow_rows:
            cow_donor = chain[n_full] if n_full < len(chain) else donor
        for node in chain[:n_full]:
            self._alloc.share([node.page])
            self._tables[slot, len(self._slot_pages[slot])] = node.page
            self._slot_pages[slot].append(node.page)
            self._prefix._touch(node)
        if cow_donor is not None:
            new = self._alloc_pages(1, req)[0]
            self._tables[slot, len(self._slot_pages[slot])] = new
            self._slot_pages[slot].append(new)
            self._prefix._touch(cow_donor)
            with compat.use_mesh(self.mesh):
                self.caches = self._cow_copy(
                    self.caches,
                    jnp.asarray(cow_donor.page, jnp.int32),
                    jnp.asarray(new, jnp.int32),
                )
            st["cow_copies"] += 1
        self._tables_dirty = True
        st["hits"] += 1
        st["hit_tokens"] += use
        st["prefill_tokens_skipped"] += use
        st["pages_shared"] += n_full
        return use

    def _verify_chain(self, chain, donor, donor_rows):
        """Per-page checksum validation at sharing time
        (``ServeConfig.checksum_pages``): recompute each matched page's
        content fingerprint and compare against the value recorded at
        trie insert. A mismatch (bit rot, a fault-injector bit flip —
        values can stay finite, so the NaN sentinel alone cannot catch
        it) evicts the damaged node's whole subtree and truncates the
        match just before it: the request re-prefills those tokens fresh
        instead of reading corrupt K/V, and no future request can match
        the poisoned entry again."""
        with compat.use_mesh(self.mesh):
            for j, node in enumerate(chain):
                if node.checksum is None:
                    continue
                now = int(self._fingerprint(
                    self.caches, jnp.asarray(node.page, jnp.int32)
                ))
                if now != node.checksum:
                    self.stats["checksum_failures"] += 1
                    self._prefix.evict_subtree(node)
                    self.session.event("recovery")
                    return chain[:j], None, 0
            if donor is not None and donor.checksum is not None:
                now = int(self._fingerprint(
                    self.caches, jnp.asarray(donor.page, jnp.int32)
                ))
                if now != donor.checksum:
                    self.stats["checksum_failures"] += 1
                    self._prefix.evict_subtree(donor)
                    self.session.event("recovery")
                    donor, donor_rows = None, 0
        return chain, donor, donor_rows

    def _release_slot_pages(self, slot: int) -> None:
        if not self._slot_pages[slot]:
            return
        if self._corrupted_pages:
            self._scrub_slot(slot)
        self._alloc.release(self._slot_pages[slot])
        self._slot_pages[slot] = []
        self._tables[slot, :] = -1
        self._tables_dirty = True

    def _tables_device(self):
        """Device mirror of the block tables. ``-1`` sentinels are uploaded
        intact: every read path clips them to page 0 (and masks by
        cache_len), while the write path's ``phys_page >= 0`` guard drops
        any write to an unallocated page — a scheduler bug can then never
        scribble on whoever owns physical page 0. The ``.copy()`` matters:
        a zero-copy upload would alias the host table the allocator
        mutates under in-flight dispatches."""
        if self._tables_dirty:
            self._tables_dev = jnp.asarray(self._tables.copy())
            self._tables_dirty = False
        return self._tables_dev

    def kv_cache_stats(self) -> dict:
        """KV-memory accounting for benchmarks and reports.

        ``kv_bytes`` is the attention-cache HBM footprint as allocated (the
        whole pool), beside live-page peaks and pool utilization."""
        attn_bytes = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(self.caches)[0]:
            name = _cache_path_name(path)
            if "attn" in name:
                attn_bytes += leaf.size * leaf.dtype.itemsize
        per_page = attn_bytes / max(self._alloc.num_pages, 1)
        out = {
            "kv_bytes": int(attn_bytes),
            "page_size": self.scfg.page_size,
            "num_pages": self._alloc.num_pages,
            "pages_in_use": self._alloc.used,
            "peak_used_pages": self._alloc.peak_used,
            "peak_live_kv_bytes": int(self._alloc.peak_used * per_page),
            "pool_utilization": round(
                self._alloc.peak_used / max(self._alloc.num_pages, 1), 4
            ),
            "refcounted_pages": len(self._alloc.refs),
            "shared_pages": self._alloc.shared_pages,
        }
        if self._prefix is not None:
            st = self._prefix.stats
            lookups = st["hits"] + st["misses"]
            out["prefix_cache"] = {
                "trie_pages": self._prefix.size,
                "hits": st["hits"],
                "misses": st["misses"],
                "hit_rate": round(st["hits"] / lookups, 4) if lookups else 0.0,
                "hit_tokens": st["hit_tokens"],
                "prefill_tokens_skipped": st["prefill_tokens_skipped"],
                "pages_saved_by_sharing": st["pages_shared"],
                "cow_copies": st["cow_copies"],
                "inserted_pages": st["inserted_pages"],
                "evicted_pages": st["evicted_pages"],
            }
        out["pressure"] = {
            k: self.stats[k]
            for k in ("preemptions", "resumes", "cancellations",
                      "pages_freed_by_preempt", "evictions_for_preempt",
                      "peak_queue_depth")
        }
        out["pressure"]["queued"] = len(self.queue)
        out["pressure"]["parked"] = len(self._parked)
        # recovery accounting: what the self-healing layer did (all zeros
        # outside chaos/fault conditions — the block is always present so
        # bench artifacts and dashboards have a stable shape)
        out["recovery"] = {
            k: self.stats[k]
            for k in ("retries", "backoff_total_ticks", "quarantined",
                      "shed", "checksum_failures", "watchdog_trips")
        }
        if self.faults is not None:
            out["recovery"]["injected"] = dict(self.faults.counters)
        # speculation accounting (always present, like "recovery": stable
        # artifact shape whether or not spec decoding ran). drafted/
        # accepted/rejected count drafter proposals only; acceptance_rate
        # is the fraction of proposals verification kept, and
        # tokens_per_dispatch is the end-to-end win (1.0 = plain decode)
        drafted = self.stats["spec_drafted"]
        dispatches = self.stats["spec_dispatches"]
        out["speculation"] = {
            "enabled": self.scfg.spec_decode,
            "drafted": drafted,
            "accepted": self.stats["spec_accepted"],
            "rejected": self.stats["spec_rejected"],
            "acceptance_rate": (
                round(self.stats["spec_accepted"] / drafted, 4)
                if drafted else 0.0
            ),
            "mean_accepted_len": (
                round(self.stats["spec_accepted"] / dispatches, 4)
                if dispatches else 0.0
            ),
            "verify_dispatches": dispatches,
            "tokens_per_dispatch": (
                round(self.stats["spec_emitted"] / dispatches, 4)
                if dispatches else 0.0
            ),
        }
        return out

    def _dispatch_prefill_chunk(self) -> None:
        """Dispatch one ``prefill_chunk``-token chunk for the oldest
        in-flight prefill (asynchronous: no host sync here), in the span
        ``repro.serve.prefill`` with the chunk's ``start`` and ``tokens``."""
        task = self._prefills[0]
        start = task["done"]
        L = min(self.scfg.prefill_chunk, len(task["prompt"]) - start)
        with span("serve.prefill", start=start, tokens=L):
            self._prefill_chunk(task, start, L)

    def _prefill_chunk(self, task: dict, start: int, L: int) -> None:
        C = self.scfg.prefill_chunk
        prompt = task["prompt"]
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :L] = prompt[start : start + L]
        # back the chunk's positions [start, start+L) with pool pages before
        # anything writes them; pool pressure here may preempt a victim,
        # park this request, or (fatal) unwind and raise — either way this
        # chunk does not dispatch
        try:
            self._ensure_pages(task["slot"], start + L - 1, task["req"])
        except _PoolPressure as e:
            self._handle_pressure(task["slot"], e)
            return
        next_tok, bad, self.caches, *moe = self.prefill(
            self.params, jnp.asarray(chunk),
            jnp.asarray([start], jnp.int32), jnp.asarray([L], jnp.int32),
            jnp.asarray(task["slot"], jnp.int32), self.caches,
            self._tables_device(), self.rng_keys,
        )
        self._keep_moe_counters("prefill", moe, L)
        task["done"] = start + L
        self.stats["prefill_chunks"] += 1
        if task["done"] >= len(prompt):
            # prefill complete: next_tok is the request's FIRST generated
            # token — it joins the deferred readback like any decode output,
            # and seeds the slot's decode input (device-side, next tick)
            slot, req = task["slot"], task["req"]
            if self._prefix is not None:
                # cache the prompt's full pages for future requests: shared
                # pages re-touch their nodes, fresh/CoW pages insert new
                # ones (each pinned with a trie-owned reference); with
                # checksum_pages on, fingerprint each full prompt page now
                # — its content is final (decode writes land past the
                # prompt) and every future share validates against it
                checks = None
                if self._fingerprint is not None:
                    n_full = len(req["prompt"]) // self.scfg.page_size
                    checks = [
                        int(self._fingerprint(
                            self.caches, jnp.asarray(p, jnp.int32)
                        ))
                        for p in self._slot_pages[slot][:n_full]
                    ]
                self._prefix.insert(req["prompt"], self._slot_pages[slot],
                                    checksums=checks)
            self._prefills.remove(task)
            self._prefilling[slot] = None
            self.active[slot] = req
            req["_status"] = "decoding"
            self.pos[slot] = len(prompt)
            if req["generated"]:
                # recompute-resume: the chunk grid above rebuilt the prompt
                # KV bitwise; the re-sampled first token is generated[0]
                # again, already on the host — discard it and schedule the
                # rest of the history for decode replay (inputs forced,
                # outputs discarded)
                if self.scfg.spec_decode:
                    self._last_tok[slot] = req["generated"][0]
                else:
                    self._seeds[slot] = req["generated"][0]
                if len(req["generated"]) > 1:
                    self._replay[slot] = list(req["generated"][1:])
            elif self.scfg.spec_decode:
                # spec mode has no deferred-readback pipeline (the accept
                # count syncs every tick anyway): materialize the first
                # token here — this is the TTFT point regardless
                with span("serve.readback"):
                    tok_h, bad_h = jax.device_get([next_tok, bad])
                if bool(bad_h[0]):
                    # poisoned prefill: nothing was emitted — retry from
                    # the (empty) clean history via the standard path
                    self._fault_nan_inflight.discard(req["id"])
                    self._fault_retry(req)
                    return
                req["generated"].append(int(tok_h[0]))
                self._last_tok[slot] = int(tok_h[0])
                eos = self.scfg.eos_id
                if req["max_new"] <= 1 or (
                        eos is not None and int(tok_h[0]) == eos):
                    req["_status"] = "done"
                    self.completed.append(req)
                    self.active[slot] = None
                    self._release_slot_pages(slot)
            else:
                req["_pending"] += 1
                self._pending.append(
                    (next_tok.reshape(1, 1), bad.reshape(1), [req])
                )
                self._seeds[slot] = next_tok[0]

    def _apply_seeds(self) -> None:
        """All newly seeded slots in ONE vectorized device-side scatter —
        no per-slot host round-trips. ``_seeds`` is keyed by slot (newest
        seed wins), so the scatter indices are unique by construction."""
        if not self._seeds:
            return
        seeds, self._seeds = self._seeds, {}
        slots = jnp.asarray(list(seeds), jnp.int32)
        toks = jnp.stack(
            [jnp.asarray(t, jnp.int32).reshape(()) for t in seeds.values()]
        )
        self.tokens = self.tokens.at[slots, 0].set(toks)

    # -- readback --------------------------------------------------------

    def _flush(self) -> None:
        """Materialize all pending tokens in ONE host transfer; retire
        requests that hit their budget or emitted EOS. The NaN/Inf
        sentinel rides the same transfer: a flagged row's token is
        garbage (sampled from poisoned logits) — it is dropped, and so is
        every LATER row of the same request in this flush (tokens decoded
        downstream of the poison are finite but wrong), then the request
        goes through ``_fault_retry`` instead of streaming poison."""
        if not self._pending:
            return
        with span("serve.flush"):
            self._flush_pending()

    def _keep_moe_counters(self, dispatch: str, moe: list, tokens: int) -> None:
        """While a capture runs, keep a dispatch's MoE counters (still on
        the device) and its token count for the next flush's readback."""
        if moe and jax.profiler.TraceAnnotation.is_enabled():
            self._moe_counts.append((dispatch, tokens, moe[0]))

    def _flush_pending(self) -> None:
        pending, self._pending = self._pending, []
        counts, self._moe_counts = self._moe_counts, []
        with span("serve.readback"):
            host, moe = jax.device_get(
                [[[toks, bad] for toks, bad, _ in pending],
                 [c for _, _, c in counts]]
            )  # single transfer
            # the MoE counters are known once the device has run: each
            # dispatch's land here, one span each
            for (dispatch, tokens, _), (routed, busiest) in zip(counts, moe):
                with span("serve.moe", dispatch=dispatch, tokens=tokens,
                          moe_routed=int(routed), moe_busiest=int(busiest)):
                    pass
        poisoned: list[dict] = []
        poisoned_ids: set = set()
        for (toks, bad), (_, _, reqmap) in zip(host, pending):
            for row, req in enumerate(reqmap):
                if req is None:
                    continue
                req["_pending"] -= 1
                if bool(bad[row]):
                    # the poison landed on the host: the request is
                    # targetable again once its retry resolves
                    self._fault_nan_inflight.discard(req["id"])
                if req["_cancelled"]:
                    continue  # cancelled mid-stream: drop the dispatched row
                if bool(bad[row]) or req["id"] in poisoned_ids:
                    if req["id"] not in poisoned_ids:
                        poisoned_ids.add(req["id"])
                        poisoned.append(req)
                    continue
                req["generated"].append(int(toks[row, 0]))
        eos = self.scfg.eos_id
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            done = len(req["generated"]) >= req["max_new"]
            if eos is not None and eos in req["generated"]:
                # early stop: drop anything decoded past the EOS between
                # flush boundaries
                req["generated"] = req["generated"][: req["generated"].index(eos) + 1]
                done = True
            if done:
                req["_status"] = "done"
                self.completed.append(req)
                self.active[slot] = None
                self._release_slot_pages(slot)
                self._replay.pop(slot, None)
        # after retirement (a poisoned request cannot be done — its bad
        # rows never appended): all pending rows are drained above, and a
        # parked request dispatches nothing, so no stale poisoned row can
        # surface in a later flush
        for req in poisoned:
            if req["_status"] not in _TERMINAL:
                self._fault_retry(req)

    def drain(self) -> None:
        """Run the scheduler to quiescence: every queued, parked,
        prefilling and decoding request completes (the admission queue and
        parked set are serviced through ordinary ``step`` ticks — drain is
        exactly "keep serving until the work is gone"), then flush the last
        readbacks. Cancelled requests' dispatched-but-unflushed rows are
        materialized and dropped on the way."""
        live = (
            self.queue + self._parked
            + [r for r in self.active if r is not None]
            + [t["req"] for t in self._prefills]
        )
        # generous tick budget: prefill chunks + decode budget per request,
        # with headroom for preemption/replay rounds (bounded — the oldest
        # highest-priority request always makes progress) plus fault-
        # recovery slack: each request may burn its full retry budget
        # (each retry is one more recompute round plus its backoff), and
        # injected allocator spikes stall everyone for spike_ticks
        rounds = len(live) + 2 + self.scfg.max_retries
        budget = 64 + rounds * sum(
            r["max_new"] + len(r["prompt"]) // max(self.scfg.prefill_chunk, 1)
            + len(r["prompt"]) + 1
            for r in live
        )
        budget += len(live) * self.scfg.max_retries * (
            self.scfg.retry_backoff_cap + 1
        )
        if self.faults is not None:
            budget += 64 + self.faults.fcfg.spike_ticks * (
                self.faults.fcfg.n_alloc_spike + 1
            )
        ticks = 0
        while (self.queue or self._parked or self._prefills
               or any(r is not None for r in self.active)):
            self.step()
            ticks += 1
            if ticks > budget:
                raise RuntimeError(
                    f"drain() reached no quiescence after {ticks} ticks: "
                    f"queued={len(self.queue)} parked={len(self._parked)} "
                    f"active={sum(r is not None for r in self.active)} "
                    f"prefilling={len(self._prefills)} "
                    f"[kv_cache_stats: {self.kv_cache_stats()}]"
                )
        self._flush()
        if self._spike_holds:
            # the workload finished while an injected spike still held
            # pool pages: give them back — a chaos run must end with the
            # same zero-leak guarantee as any other drain
            for _, pages in self._spike_holds:
                self._alloc.release(pages)
            self._spike_holds = []

    # -- speculative decode (draft + batched verify) ---------------------

    def _plan_drafts(self) -> dict[int, dict]:
        """Per decoding slot, the draft window for this tick's verify
        dispatch. Recompute-resume/retry replay tokens come FIRST — they
        are true history, so verification accepts them bitwise and replay
        rides the speculative path at up to ``spec_k+1`` tokens per
        dispatch instead of one. Fresh proposals from the n-gram drafter
        are only appended once the replay queue fits entirely in the
        window (the drafter's input is the full history, which ends at
        the replay queue's end). The drafter budget is clamped so
        accepted-and-emitted tokens can never exceed the request's
        ``max_new`` (at most ``n_draft + 1`` new emissions per dispatch)
        and the deepest K/V write stays at ``max_len - 1`` (a deeper
        write would be silently dropped by the masked scatter)."""
        K = self.scfg.spec_k
        plans: dict[int, dict] = {}
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            replay = self._replay.get(slot, [])
            drafts = [int(t) for t in replay[:K]]
            n_replay = len(drafts)
            n_draft = 0
            if n_replay == len(replay):
                remaining = req["max_new"] - len(req["generated"])
                budget = min(
                    K - n_replay,
                    max(remaining - 1, 0),
                    max(self.scfg.max_len - 1 - int(self.pos[slot])
                        - n_replay, 0),
                )
                if budget > 0:
                    drafts += draft_tokens(
                        req["prompt"] + req["generated"], budget,
                        min_match=self.scfg.spec_min_match,
                    )
                    n_draft = len(drafts) - n_replay
            plans[slot] = {
                "drafts": drafts, "n_replay": n_replay, "n_draft": n_draft,
            }
        return plans

    def _spec_tick(self, chunks_at_tick_start: int) -> None:
        """The speculative replacement for the one-token decode dispatch:
        plan per-slot draft windows, back every window with physical pages
        (multi-page accepts cross page boundaries — ``_ensure_pages``
        unwinds page-by-page on pool pressure), then score all windows in
        ONE batched verify dispatch and commit each slot's longest
        accepted prefix. Rejected positions need no KV rollback: their
        writes are masked scatters that the next dispatch overwrites at
        the same positions before any read can see them — rollback is
        simply not advancing ``pos``. The accept counts must reach the
        host before the next tick can draft, so the dispatch reads back
        immediately (a few small arrays), amortized over up to
        ``spec_k+1`` tokens."""
        plans = self._plan_drafts()
        with span("serve.pages"):
            for slot, plan in plans.items():
                req = self.active[slot]
                if req is None:
                    continue  # a pressure round below evicted this slot
                try:
                    self._ensure_pages(
                        slot, int(self.pos[slot]) + len(plan["drafts"]), req,
                    )
                except _PoolPressure as e:
                    self._handle_pressure(slot, e)
            if any(r is not None for r in self.active):
                self._tables_device()  # the upload, for the verify
        decoding = list(self.active)
        plans = {s: p for s, p in plans.items() if decoding[s] is not None}
        if bool(self._prefills) and plans:
            self.stats["overlap_ticks"] += 1
        if not plans:
            return
        B, C = self.scfg.batch, self.scfg.spec_k + 1
        chunk = np.zeros((B, C), np.int32)
        length = np.zeros(B, np.int32)
        for slot, plan in plans.items():
            drafts = plan["drafts"]
            chunk[slot, 0] = self._last_tok[slot]
            chunk[slot, 1:1 + len(drafts)] = drafts
            length[slot] = 1 + len(drafts)
        pos_now = jnp.asarray(self.pos.copy())
        fault_mask = self._fault_mask_zero
        if self._fault_nan_slots:
            m = np.zeros(self.scfg.batch, bool)
            m[list(self._fault_nan_slots)] = True
            for s in self._fault_nan_slots:
                if decoding[s] is not None:
                    self._fault_nan_inflight.add(decoding[s]["id"])
            self._fault_nan_slots.clear()
            fault_mask = jnp.asarray(m)
        t0 = time.perf_counter()
        if self._hang_pending:
            time.sleep(self._hang_pending)
            self._hang_pending = 0.0
        with span("serve.decode"):
            out_dev, acc_dev, bad_dev, self.caches = self.verify(
                self.params, jnp.asarray(chunk), pos_now,
                jnp.asarray(length), self.caches, self._tables_device(),
                self.rng_keys, fault_mask,
            )
        dispatch_s = time.perf_counter() - t0
        self.stats["decode_steps"] += 1
        self.stats["spec_dispatches"] += 1
        if self.stats["prefill_chunks"] > chunks_at_tick_start:
            self.stats["decode_after_prefill_ticks"] += 1
        with span("serve.readback"):
            out, acc, bad = jax.device_get([out_dev, acc_dev, bad_dev])
        poisoned: list[dict] = []
        eos = self.scfg.eos_id
        for slot, plan in plans.items():
            req = decoding[slot]
            if bool(bad[slot]):
                # poisoned verify: nothing committed for this slot (pos
                # untouched, replay queue untouched) — the whole window
                # recomputes after the retry
                self._fault_nan_inflight.discard(req["id"])
                if not req["_cancelled"]:
                    poisoned.append(req)
                continue
            n_acc = int(acc[slot])
            emitted = [int(t) for t in out[slot, : n_acc + 1]]
            self.pos[slot] += n_acc + 1
            self._last_tok[slot] = emitted[-1]
            acc_draft = max(0, n_acc - plan["n_replay"])
            self.stats["spec_drafted"] += plan["n_draft"]
            self.stats["spec_accepted"] += acc_draft
            self.stats["spec_rejected"] += plan["n_draft"] - acc_draft
            # replay outputs are tokens already in ``generated`` — pop
            # them off the queue instead of double-counting
            new_toks = emitted
            if slot in self._replay:
                hist = self._replay[slot]
                n_hist = min(len(hist), len(emitted))
                del hist[:n_hist]
                if not hist:
                    del self._replay[slot]
                new_toks = emitted[n_hist:]
            if req["_cancelled"]:
                continue
            req["generated"].extend(new_toks)
            self.stats["spec_emitted"] += len(new_toks)
            done = len(req["generated"]) >= req["max_new"]
            if eos is not None and eos in req["generated"]:
                # EOS inside the accepted window: truncate right after it
                req["generated"] = (
                    req["generated"][: req["generated"].index(eos) + 1]
                )
                done = True
            if done:
                req["_status"] = "done"
                self.completed.append(req)
                self.active[slot] = None
                self._release_slot_pages(slot)
                self._replay.pop(slot, None)
        if (self.scfg.watchdog_deadline_s is not None
                and dispatch_s > self.scfg.watchdog_deadline_s):
            self.stats["watchdog_trips"] += 1
            self.session.event("recovery")
            victim, self._hang_slot = self._hang_slot, None
            req = self.active[victim] if victim is not None else None
            if req is not None and req["_status"] not in _TERMINAL:
                self._fault_retry(req)
        for req in poisoned:
            if req["_status"] not in _TERMINAL:
                self._fault_retry(req)

    # -- the tick --------------------------------------------------------

    def step(self) -> int:
        """One scheduler tick: decode dispatch for all decoding slots, then
        at most one prefill chunk dispatch. Returns #busy slots.

        The tick is the profiler span ``repro.serve.tick``, its arguments
        the counters at its start (``_tick_counters``); its phases are the
        spans ``repro.serve.{attach,pages,decode,prefill,flush,readback}``.
        """
        # counted only while a capture records them: the backlog is a sum
        # over every waiting request
        counters = (self._tick_counters()
                    if jax.profiler.TraceAnnotation.is_enabled() else {})
        with span("serve.tick", **counters):
            return self._tick()

    def _tick_counters(self) -> dict:
        """Waiting requests (queued and parked), in-flight prefills, prompt
        tokens not yet prefilled over all of them, decoding slots and free
        pages."""
        waiting = self.queue + self._parked
        out = {
            "queued": len(waiting),
            "prefilling": len(self._prefills),
            "backlog_tok": sum(len(r["prompt"]) for r in waiting) + sum(
                len(t["prompt"]) - t["done"] for t in self._prefills),
            "decoding": sum(r is not None for r in self.active),
            "free_pages": self._alloc.free_pages,
        }
        return out

    def _decode_pages(self) -> dict:
        """Pages the paged decode kernel walks in this tick's dispatch:
        each slot's allocated pages below its ``cache_len`` (``pos + 1``),
        summed (``live_pages``; a sliding window walks fewer); and
        ``grid_pages``, every table entry of every slot."""
        n_slots, n_logical = self._tables.shape
        psize = self.scfg.page_size
        below = np.arange(n_logical)[None, :] < (
            (self.pos[:, None] + psize) // psize)
        live = below & (self._tables >= 0)
        return {"live_pages": int(live.sum()),
                "grid_pages": n_slots * n_logical}

    def _tick(self) -> int:
        self.stats["ticks"] += 1
        with span("serve.attach"):
            self._attach()
        if self.faults is not None or self._spike_holds:
            self._apply_faults()
        chunks_at_tick_start = self.stats["prefill_chunks"]
        with compat.use_mesh(self.mesh):
            self._apply_seeds()  # seeds collected since last tick
            if self.scfg.spec_decode:
                # speculative tick: draft windows + ONE batched verify
                # replace the one-token decode dispatch entirely (page
                # ensuring moves inside — the window's extent is per-plan)
                self._spec_tick(chunks_at_tick_start)
                decoding: list[dict | None] = [None] * self.scfg.batch
            else:
                # this step writes each active slot's K/V at pos[slot]: back
                # any page boundary being crossed BEFORE snapshotting the
                # active set — pool pressure here can preempt (remove) a
                # victim slot mid-loop, or park the requesting slot itself
                with span("serve.pages"):
                    for slot in range(self.scfg.batch):
                        req = self.active[slot]
                        if req is not None:
                            try:
                                self._ensure_pages(slot, int(self.pos[slot]), req)
                            except _PoolPressure as e:
                                self._handle_pressure(slot, e)
                    if any(r is not None for r in self.active):
                        self._tables_device()  # the upload, for the decode
                decoding = list(self.active)
            if bool(self._prefills) and any(r is not None for r in decoding):
                self.stats["overlap_ticks"] += 1
            if any(r is not None for r in decoding):
                active = np.asarray([r is not None for r in decoding])
                args = (jnp.asarray(active), self.caches,
                        self._tables_device())
                # snapshot pos: jnp.asarray can zero-copy alias an aligned
                # numpy buffer on CPU, and the async decode would then read
                # the ``self.pos`` mutations below (and next tick's attach
                # resets) instead of this tick's values
                pos_now = jnp.asarray(self.pos.copy())
                fault_mask = self._fault_mask_zero
                if self._fault_nan_slots:
                    # injected logit poison for this dispatch only: the
                    # masked slots' logits become NaN ahead of the
                    # sentinel (the all-False mask every normal tick is a
                    # bitwise no-op select)
                    m = np.zeros(self.scfg.batch, bool)
                    m[list(self._fault_nan_slots)] = True
                    for s in self._fault_nan_slots:
                        if decoding[s] is not None:
                            self._fault_nan_inflight.add(decoding[s]["id"])
                    self._fault_nan_slots.clear()
                    fault_mask = jnp.asarray(m)
                t0 = time.perf_counter()
                if self._hang_pending:
                    # injected dispatch hang (a wedged host thread): burn
                    # wall time where the watchdog measures it
                    time.sleep(self._hang_pending)
                    self._hang_pending = 0.0
                # counted only while a capture records them, as the tick's
                pages = (self._decode_pages()
                         if jax.profiler.TraceAnnotation.is_enabled() else {})
                with span("serve.decode", **pages):
                    self.tokens, bad_dev, self.caches, *moe = self.decode(
                        self.params, self.tokens, pos_now,
                        *args, self.rng_keys, fault_mask,
                    )
                dispatch_s = time.perf_counter() - t0
                self._keep_moe_counters("decode", moe, int(active.sum()))
                self.stats["decode_steps"] += 1
                if self.stats["prefill_chunks"] > chunks_at_tick_start:
                    # prefill work ran before this tick's decode dispatch:
                    # the decode pipeline waited on it
                    self.stats["decode_after_prefill_ticks"] += 1
                self.pos[active] += 1
                # recompute-resume replay: a replaying slot's output is a
                # token already in its ``generated`` history — discard it
                # (None row, no _pending) instead of double-counting it
                reqmap = [
                    None if (r is not None and s in self._replay) else r
                    for s, r in enumerate(decoding)
                ]
                self._pending.append((self.tokens, bad_dev, reqmap))
                for req in reqmap:
                    if req is not None:
                        req["_pending"] += 1
                if (self.scfg.watchdog_deadline_s is not None
                        and dispatch_s > self.scfg.watchdog_deadline_s):
                    # the dispatch call itself blew its deadline (a wedged
                    # dispatch path; in chaos runs, the injected hang).
                    # The late tokens are kept — identity is preserved —
                    # and the hung slot's request retries so a recurring
                    # wedge cannot stall its stream forever
                    self.stats["watchdog_trips"] += 1
                    self.session.event("recovery")
                    victim, self._hang_slot = self._hang_slot, None
                    req = (
                        self.active[victim] if victim is not None else None
                    )
                    if req is not None and req["_status"] not in _TERMINAL:
                        self._fault_retry(req)
                # advance the forced-input schedule: the popped history
                # token overrides the sampled output as next tick's input
                # for its slot; when the list empties, the NEXT output is
                # the first genuinely new token and is kept
                for slot in list(self._replay):
                    if decoding[slot] is not None:
                        hist = self._replay[slot]
                        self._seeds[slot] = hist.pop(0)
                        if not hist:
                            del self._replay[slot]
            if self._prefills:
                self._dispatch_prefill_chunk()
        flush_due = any(
            req is not None
            and len(req["generated"]) + req["_pending"] >= req["max_new"]
            for req in self.active
        )
        if (self.scfg.eos_id is not None and self._pending
                and self.stats["ticks"] % self.scfg.eos_check_every == 0):
            flush_due = True
        if flush_due:
            self._flush()
        return sum(
            1 for slot in range(self.scfg.batch) if not self._free(slot)
        )
