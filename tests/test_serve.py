"""Serving correctness: decode path must agree with the full forward pass."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.layers.common import init_params
from repro.models import transformer as T
from repro.launch.mesh import make_host_mesh
from repro.serve.serve import BatchScheduler, ServeConfig, make_decode_step, make_prefill_step


@pytest.fixture(autouse=True)
def _release_compiled_programs():
    """Drop the compiled programs after a test once the process maps many
    regions. The reference generator runs eagerly, one program per
    operation and shape, and the CPU backend maps memory for each program
    it keeps: kept for the whole file, they pass the kernel's limit on a
    process's mappings (vm.max_map_count, 65530 by default) and the process
    dies in the compiler. One test adds fewer than 10,000."""
    yield
    try:
        with open("/proc/self/maps") as f:
            mapped = sum(1 for _ in f)
    except OSError:  # no such file off Linux, and no such limit
        return
    if mapped > 30_000:
        jax.clear_caches()


@pytest.mark.parametrize("arch", [
    "tinyllama-1.1b", "gemma2-2b", "qwen3-moe-30b-a3b", "zamba2-2.7b",
    "xlstm-350m",
])
def test_decode_matches_forward_logits(arch):
    """Prefill+decode must reproduce the teacher-forced forward logits —
    the strongest end-to-end consistency check for every cache type
    (KV, conv, ssm, mLSTM, sLSTM)."""
    cfg = smoke_config(arch)
    if arch in ("zamba2-2.7b", "xlstm-350m"):
        # chunked-prefill vs stepwise-decode recurrences are mathematically
        # identical but round differently; the recurrent denominators
        # (mLSTM max(|q.n|, exp(-m))) amplify reassociation noise roughly
        # exponentially with depth. Run the cache-logic consistency check
        # in f32 at one pattern repeat — deeper stacks diverge numerically,
        # not logically (see DESIGN.md numerics notes).
        cfg = cfg.replace(compute_dtype_name="float32",
                          param_dtype_name="float32")
    if arch == "xlstm-350m":
        cfg = cfg.replace(repeats=1)
    mesh = make_host_mesh()
    params = init_params(T.model_params(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    Bs, prompt_len, total = 2, 16, 24
    toks = jax.random.randint(jax.random.PRNGKey(1), (Bs, total), 4, cfg.vocab)

    # xlstm: jit-vs-eager op fusion perturbs the mLSTM state slightly and
    # its denominator amplifies that; keep both sides in the same
    # compilation mode so the check isolates cache logic.
    jit_ = (lambda f: f) if arch == "xlstm-350m" else jax.jit
    with mesh:
        full_logits, _ = jit_(lambda p, b: T.apply_logits(p, b, cfg))(
            params, {"tokens": toks}
        )
        caches = T.init_cache(cfg, Bs, total + 8)
        _, caches = jit_(make_prefill_step(cfg, mesh))(
            params, {"tokens": toks[:, :prompt_len]}, caches
        )
        decode = jax.jit(make_decode_step(cfg, mesh))
        errs = []
        for i in range(prompt_len, total):
            logits, caches = T.decode_step(
                params, toks[:, i : i + 1], jnp.asarray(i, jnp.int32), cfg, caches
            )
            err = np.max(np.abs(
                np.asarray(logits, np.float32)
                - np.asarray(full_logits[:, i], np.float32)
            ))
            errs.append(err)
    assert max(errs) < 0.1, f"{arch}: decode/forward divergence {max(errs)}"


def test_prefill_last_logits_match_forward():
    cfg = smoke_config("tinyllama-1.1b")
    mesh = make_host_mesh()
    params = init_params(T.model_params(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 4, cfg.vocab)
    with mesh:
        full_logits, _ = T.apply_logits(params, {"tokens": toks}, cfg)
        caches = T.init_cache(cfg, 2, 32)
        next_tok, _ = make_prefill_step(cfg, mesh)(params, {"tokens": toks}, caches)
    expected = np.argmax(np.asarray(full_logits[:, -1], np.float32), axis=-1)
    np.testing.assert_array_equal(np.asarray(next_tok), expected)


# ---------------------------------------------------------------------------
# BatchScheduler: chunked prefill-on-attach overlapped with in-flight decode
# ---------------------------------------------------------------------------
# f32 so the chunked-prefill-vs-reference and A/B token-identity checks
# isolate scheduler logic from bf16 argmax near-ties. One shared config =
# one shared (decode, prefill) jit pair across every scheduler instance.


@functools.cache
def _serve_fixtures():
    cfg = smoke_config("tinyllama-1.1b").replace(
        compute_dtype_name="float32", param_dtype_name="float32"
    )
    mesh = make_host_mesh()
    params = init_params(T.model_params(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    return cfg, mesh, params


def _run(sched, n_requests, max_ticks=200):
    ticks = 0
    while len(sched.completed) < n_requests and ticks < max_ticks:
        sched.step()
        ticks += 1
    sched.drain()
    return ticks


def _reference_generate(cfg, mesh, params, prompt, max_new, max_len=64):
    """Stop-the-world reference: full one-shot prefill + sequential decode."""
    with mesh:
        caches = T.init_cache(cfg, 1, max_len)
        toks = jnp.asarray([prompt], jnp.int32)
        next_tok, caches = make_prefill_step(cfg, mesh)(
            params, {"tokens": toks}, caches
        )
        out = [int(next_tok[0])]
        pos = len(prompt)
        tok = next_tok.reshape(1, 1)
        while len(out) < max_new:
            logits, caches = T.decode_step(
                params, tok, jnp.asarray(pos, jnp.int32), cfg, caches
            )
            tok = jnp.argmax(logits, axis=-1, keepdims=True).astype(jnp.int32)
            out.append(int(tok[0, 0]))
            pos += 1
    return out


def test_batch_scheduler_completes_requests():
    cfg, mesh, params = _serve_fixtures()
    with mesh:
        sched = BatchScheduler(cfg, mesh, ServeConfig(max_len=64, batch=2), params)
        for rid in range(4):
            sched.submit([1, 2, 3], request_id=rid, max_new=5)
        _run(sched, 4)
    assert len(sched.completed) == 4
    for req in sched.completed:
        assert len(req["generated"]) == 5
        assert all(0 <= t < cfg.vocab_padded for t in req["generated"])


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "gemma2-2b"])
def test_scheduler_chunked_prefill_matches_reference(arch):
    """Chunked prefill at per-slot offsets + continuous-batching decode must
    reproduce the stop-the-world reference (one-shot prefill + sequential
    decode) token for token — the end-to-end correctness gate for the
    per-slot position vector and the cache-attend prefill path. gemma2 runs
    with a sliding window SMALLER than the prompts so the window actually
    cuts into the cache_attend path at test lengths."""
    if arch == "tinyllama-1.1b":
        cfg, mesh, params = _serve_fixtures()
    else:
        cfg = smoke_config(arch).replace(
            compute_dtype_name="float32", param_dtype_name="float32", window=5
        )
        mesh = make_host_mesh()
        params = init_params(
            T.model_params(cfg), jax.random.PRNGKey(0), cfg.param_dtype
        )
    rng = np.random.default_rng(7)
    prompts = [rng.integers(4, cfg.vocab, size=n).tolist() for n in (3, 9, 14, 6)]
    with mesh:
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=64, batch=2, prefill_chunk=4), params,
        )
        for rid, p in enumerate(prompts):
            sched.submit(p, request_id=rid, max_new=6)
        _run(sched, len(prompts))
    assert len(sched.completed) == len(prompts)
    for req in sched.completed:
        ref = _reference_generate(cfg, mesh, params, prompts[req["id"]], 6)
        assert req["generated"] == ref, (req["id"], req["generated"], ref)


def test_submit_rejects_nonpositive_max_new():
    """The prefill-completion token is unconditionally the first generated
    token, so a zero (or negative) budget is unsatisfiable — reject it."""
    cfg, mesh, params = _serve_fixtures()
    with mesh:
        sched = BatchScheduler(cfg, mesh, ServeConfig(max_len=64, batch=2), params)
    with pytest.raises(ValueError, match="max_new"):
        sched.submit([1, 2], request_id=0, max_new=0)


def test_attach_during_decode_does_not_change_inflight_outputs():
    """Attaching (and prefilling) request B mid-flight must not perturb
    request A's token stream: the prefill only touches B's cache lines and
    the masked decode write leaves B's lines alone."""
    cfg, mesh, params = _serve_fixtures()
    prompt_a = [5, 6, 7, 8]
    prompt_b = list(range(4, 16))

    def run(with_b):
        with mesh:
            sched = BatchScheduler(
                cfg, mesh,
                ServeConfig(max_len=64, batch=2, prefill_chunk=4), params,
            )
            sched.submit(prompt_a, request_id="a", max_new=10)
            sched.step()
            sched.step()  # A is prefilled and decoding
            if with_b:
                sched.submit(prompt_b, request_id="b", max_new=4)
            _run(sched, 2 if with_b else 1)
        return {req["id"]: req["generated"] for req in sched.completed}

    alone = run(with_b=False)
    together = run(with_b=True)
    assert together["a"] == alone["a"]
    assert together["b"] == _reference_generate(cfg, mesh, params, prompt_b, 4)


def test_per_slot_positions_after_staggered_attach():
    """Slots attached at different times decode at their own positions."""
    cfg, mesh, params = _serve_fixtures()
    prompt_a, prompt_b = list(range(4, 12)), [30, 31, 32]
    with mesh:
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=64, batch=2, prefill_chunk=8), params,
        )
        sched.submit(prompt_a, request_id="a", max_new=32)
        sched.step()   # tick 1: prefill A dispatched (1 chunk = whole prompt)
        sched.step()   # tick 2: A decodes its first step
        slot_a = next(i for i, r in enumerate(sched.active)
                      if r is not None and r["id"] == "a")
        assert sched.pos[slot_a] == len(prompt_a) + 1
        sched.submit(prompt_b, request_id="b", max_new=32)
        sched.step()   # tick 3: A decodes; B prefills
        sched.step()   # tick 4: A and B decode together
        slot_b = next(i for i, r in enumerate(sched.active)
                      if r is not None and r["id"] == "b")
        assert slot_b != slot_a
        assert sched.pos[slot_a] == len(prompt_a) + 3
        assert sched.pos[slot_b] == len(prompt_b) + 1
        sched.drain()


def test_eos_retirement_before_max_new():
    """EOS-based early stop: the deferred readback detects the EOS at a
    flush boundary, truncates anything decoded past it, and frees the slot
    before the count budget is reached."""
    cfg, mesh, params = _serve_fixtures()
    prompt = [9, 10, 11, 12, 13]
    free_run = _reference_generate(cfg, mesh, params, prompt, 8)
    eos = free_run[2]
    assert eos not in free_run[:2]  # make the truncation point unambiguous
    with mesh:
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=64, batch=2, eos_id=eos, eos_check_every=3),
            params,
        )
        sched.submit(prompt, request_id=0, max_new=8)
        ticks = _run(sched, 1)
    (req,) = sched.completed
    assert req["generated"] == free_run[:3]          # ends at the EOS
    assert len(req["generated"]) < 8                 # retired early
    assert ticks < 12  # the slot was freed well before the budget


def test_drain_runs_to_quiescence():
    """drain() completes EVERYTHING still in the system — a mid-flight
    (partial) prefill AND requests still waiting in the admission queue
    that never attached — so a stopped serve loop never strands work."""
    cfg, mesh, params = _serve_fixtures()
    prompt = list(range(4, 16))  # 12 tokens -> 3 chunks of 4
    with mesh:
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=64, batch=1, prefill_chunk=4), params,
        )
        sched.submit(prompt, request_id=0, max_new=8)
        sched.step()  # one tick: exactly one chunk in
        assert sched._prefills and sched._prefills[0]["done"] == 4
        # a second request arrives and (batch=1) stays in the admission
        # queue — the old drain would have silently dropped it
        sched.submit([20, 21, 22], request_id=1, max_new=4)
        assert sched.queue
        sched.drain()
        assert not sched._prefills and not sched.queue
        assert all(r is None for r in sched.active)
    got = {r["id"]: r["generated"] for r in sched.completed}
    assert got[0] == _reference_generate(cfg, mesh, params, prompt, 8)
    assert got[1] == _reference_generate(cfg, mesh, params, [20, 21, 22], 4)


def test_overlap_on_off_identical_tokens_and_no_decode_gap():
    """The acceptance check: chunked prefill overlapped with decode produces
    bitwise the tokens of a one-shot prefill followed by sequential decode,
    and while a prefill is in flight every tick still dispatches a decode
    step (no gap > one tick)."""
    cfg, mesh, params = _serve_fixtures()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(4, cfg.vocab, size=n).tolist() for n in (10, 14, 5)]
    with mesh:
        sched = BatchScheduler(
            cfg, mesh, ServeConfig(max_len=64, batch=2, prefill_chunk=4),
            params,
        )
        sched.submit(prompts[0], request_id=0, max_new=8)
        sched.step()
        sched.step()
        for rid in (1, 2):
            sched.submit(prompts[rid], request_id=rid, max_new=8)
        _run(sched, 3)
    got = {r["id"]: r["generated"] for r in sched.completed}
    assert got == {rid: _reference_generate(cfg, mesh, params, p, 8)
                   for rid, p in enumerate(prompts)}
    # requests 1/2 prefilled while request 0 was decoding: those ticks exist
    # and no decode dispatch ever ran after prefill work in its tick
    assert sched.stats["overlap_ticks"] > 0
    assert sched.stats["decode_after_prefill_ticks"] == 0


def test_scheduler_chunked_prefill_recurrent_hybrid():
    """The masked state advance (dt-zeroing, conv-state gather, frozen SSM
    state for padding and inactive decode slots) must hold on a hybrid
    mamba+attention stack too: chunked prefill with a ragged final chunk,
    overlapped with the other request's decode, matches the one-shot
    reference."""
    cfg = smoke_config("zamba2-2.7b").replace(
        compute_dtype_name="float32", param_dtype_name="float32"
    )
    mesh = make_host_mesh()
    params = init_params(T.model_params(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    prompts = [list(range(4, 4 + n)) for n in (7, 10)]  # ragged vs chunk=4

    with mesh:
        sched = BatchScheduler(
            cfg, mesh, ServeConfig(max_len=64, batch=2, prefill_chunk=4),
            params,
        )
        sched.submit(prompts[0], request_id=0, max_new=5)
        sched.step()  # request 0 mid-prefill / decoding...
        sched.submit(prompts[1], request_id=1, max_new=5)
        _run(sched, 2)
    overlapped = {r["id"]: r["generated"] for r in sched.completed}
    for rid, p in enumerate(prompts):
        ref = _reference_generate(cfg, mesh, params, p, 5)
        assert overlapped[rid] == ref, (rid, overlapped[rid], ref)


def test_recurrent_hybrid_slot_reuse_matches_reference():
    """More requests than slots on a hybrid mamba+attention arch: a freed
    slot's recurrent state (SSM/conv) must be restored to fresh before the
    next request prefills into it. Attention KV is masked by cache_len, but
    recurrent carries are not — without the reset the reused slots' tokens
    continue from the retired request's final state."""
    cfg = smoke_config("zamba2-2.7b").replace(
        compute_dtype_name="float32", param_dtype_name="float32"
    )
    mesh = make_host_mesh()
    params = init_params(T.model_params(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    prompts = [list(range(4, 4 + n)) for n in (7, 10, 5, 8)]  # 4 reqs, 2 slots
    with mesh:
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=64, batch=2, prefill_chunk=4), params,
        )
        for rid, p in enumerate(prompts):
            sched.submit(p, request_id=rid, max_new=5)
        _run(sched, len(prompts))
    assert len(sched.completed) == len(prompts)
    for req in sched.completed:
        ref = _reference_generate(cfg, mesh, params, prompts[req["id"]], 5)
        assert req["generated"] == ref, (req["id"], req["generated"], ref)


def test_slot_reuse_matches_fresh_scheduler_xlstm():
    """Slot reuse on an xLSTM stack: the reset must restore INITIAL carry
    values, not zeros (sLSTM's stabilizer m starts at -1e30). Identity
    check against a fresh scheduler (same jitted steps, so any stale or
    mis-reset state shows up as a token difference)."""
    cfg = smoke_config("xlstm-350m").replace(
        compute_dtype_name="float32", param_dtype_name="float32", repeats=1
    )
    mesh = make_host_mesh()
    params = init_params(T.model_params(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    prompt_a, prompt_b = [5, 6, 7, 8, 9], [20, 21, 22]

    def run(submit_a):
        with mesh:
            sched = BatchScheduler(
                cfg, mesh,
                ServeConfig(max_len=64, batch=1, prefill_chunk=4), params,
            )
            if submit_a:
                sched.submit(prompt_a, request_id="a", max_new=4)
            sched.submit(prompt_b, request_id="b", max_new=6)
            _run(sched, 2 if submit_a else 1)
        return {r["id"]: r["generated"] for r in sched.completed}

    reused = run(submit_a=True)       # "b" runs in the slot "a" retired from
    fresh = run(submit_a=False)       # "b" runs in a never-used slot
    assert reused["b"] == fresh["b"], (reused["b"], fresh["b"])


def test_masked_decode_freezes_inactive_slots_mlstm():
    """Batched masked decode on an mLSTM/sLSTM stack with batch != n_heads:
    the per-slot freeze masks must broadcast over the head axis (a (B,) mask
    against (B,h) carries), inactive slots' state stays bitwise frozen, and
    active slots match the unmasked step exactly."""
    cfg = smoke_config("xlstm-350m").replace(
        compute_dtype_name="float32", param_dtype_name="float32", repeats=1
    )
    mesh = make_host_mesh()
    params = init_params(T.model_params(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    Bs, plen = 3, 6  # 3 slots vs n_heads=4: a wrong-axis broadcast cannot hide
    toks = jax.random.randint(jax.random.PRNGKey(2), (Bs, plen), 4, cfg.vocab)
    with mesh:
        caches = T.init_cache(cfg, Bs, 16)
        _, caches = make_prefill_step(cfg, mesh)(params, {"tokens": toks}, caches)
        step_tok = jax.random.randint(jax.random.PRNGKey(3), (Bs, 1), 4, cfg.vocab)
        pos = jnp.full((Bs,), plen, jnp.int32)
        logits_m, caches_m = T.decode_step(
            params, step_tok, pos, cfg, caches,
            active=jnp.asarray([True, False, True]),
        )
        logits_u, caches_u = T.decode_step(params, step_tok, pos, cfg, caches)
    for before, masked, unmasked in zip(
        jax.tree_util.tree_leaves(caches),
        jax.tree_util.tree_leaves(caches_m),
        jax.tree_util.tree_leaves(caches_u),
    ):
        before, masked, unmasked = map(np.asarray, (before, masked, unmasked))
        np.testing.assert_array_equal(  # inactive slot: no state advance
            masked[:, 1], before[:, 1]
        )
        np.testing.assert_array_equal(  # active slots: same as unmasked
            masked[:, [0, 2]], unmasked[:, [0, 2]]
        )
    np.testing.assert_array_equal(
        np.asarray(logits_m)[[0, 2]], np.asarray(logits_u)[[0, 2]]
    )


def test_stale_seed_dropped_on_reattach():
    """A request retiring in the same tick its prefill completes leaves its
    next-token seed queued; if the freed slot is immediately reattached, the
    stale seed must not race the new request's seed in the scatter."""
    cfg, mesh, params = _serve_fixtures()
    with mesh:
        sched = BatchScheduler(cfg, mesh, ServeConfig(max_len=64, batch=1), params)
        sched.submit([5, 6, 7], request_id="a", max_new=1)
        _run(sched, 1)  # retires at its prefill-completion flush
        # empty prompt: the reattached slot seeds directly (no prefill), the
        # exact duplicate-scatter window the stale seed could race
        sched.submit([], request_id="b", max_new=4)
        _run(sched, 2)
        got = {r["id"]: r["generated"] for r in sched.completed}

        fresh = BatchScheduler(cfg, mesh, ServeConfig(max_len=64, batch=1), params)
        fresh.submit([], request_id="b", max_new=4)
        _run(fresh, 1)
    (ref,) = [r["generated"] for r in fresh.completed]
    assert got["b"] == ref, (got["b"], ref)


# ---------------------------------------------------------------------------
# paged KV cache: shared page pool + per-slot block tables
# ---------------------------------------------------------------------------
# NOTE: every scheduler test above already runs the paged layout — the
# scheduler's only one. The tests below pin the paged-specific guarantees:
# identity with the model's dense cache, allocator lifecycle, exhaustion
# behavior.


def test_paged_matches_dense_tokens_overlap_on_off():
    """The paged KV cache, with prefill chunks overlapped with decode,
    produces bitwise the tokens of the reference generator (one-shot
    prefill + sequential decode through the model's dense cache) on a
    staggered multi-request trace with slot reuse."""
    cfg, mesh, params = _serve_fixtures()
    rng = np.random.default_rng(11)
    prompts = [rng.integers(4, cfg.vocab, size=n).tolist()
               for n in (10, 17, 5, 8)]  # 4 requests > 2 slots
    with mesh:
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=64, batch=2, prefill_chunk=4, page_size=16),
            params,
        )
        sched.submit(prompts[0], request_id=0, max_new=7)
        sched.step()  # request 0 mid-prefill when the rest arrive
        for rid in (1, 2, 3):
            sched.submit(prompts[rid], request_id=rid, max_new=7)
        _run(sched, len(prompts))
    got = {r["id"]: r["generated"] for r in sched.completed}
    assert got == {rid: _reference_generate(cfg, mesh, params, p, 7)
                   for rid, p in enumerate(prompts)}


@pytest.mark.parametrize("arch", ["gemma2-2b", "zamba2-2.7b", "xlstm-350m"])
def test_paged_scheduler_matches_reference_small_pages(arch):
    """Paged-vs-reference token identity with page_size SMALLER than the
    attention span: gemma2 runs a sliding window (5) that crosses every
    page boundary (page_size 4), zamba2 covers the hybrid mamba+attention
    stack (recurrent state stays dense per slot while attention pages),
    and xlstm an attention-free stack served with an empty pool. More
    requests than slots also exercises block free/realloc on reuse."""
    cfg = smoke_config(arch).replace(
        compute_dtype_name="float32", param_dtype_name="float32",
        **({"window": 5} if arch == "gemma2-2b" else {}),
    )
    mesh = make_host_mesh()
    params = init_params(T.model_params(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(4, cfg.vocab, size=n).tolist() for n in (3, 9, 14, 6)]
    with mesh:
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=64, batch=2, prefill_chunk=4,
                        page_size=4),
            params,
        )
        for rid, p in enumerate(prompts):
            sched.submit(p, request_id=rid, max_new=6)
        _run(sched, len(prompts))
    assert len(sched.completed) == len(prompts)
    for req in sched.completed:
        ref = _reference_generate(cfg, mesh, params, prompts[req["id"]], 6)
        assert req["generated"] == ref, (req["id"], req["generated"], ref)


def test_paged_allocator_frees_and_reallocates_on_slot_reuse():
    """Block lifecycle with more requests than slots: pages are allocated
    as prefill/decode write, freed when a request retires, and the freed
    pages back the next request — the pool never leaks and the block
    tables of retired slots are fully cleared."""
    cfg, mesh, params = _serve_fixtures()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(4, cfg.vocab, size=n).tolist()
               for n in (20, 9, 18, 5)]  # 4 requests, 2 slots
    with mesh:
        sched = BatchScheduler(
            cfg, mesh,
            # pool sized so 4 requests can only complete if retirement
            # actually recycles pages: 2 slots x ceil((20+6)/8) = 8 pages
            ServeConfig(max_len=64, batch=2, prefill_chunk=4,
                        page_size=8, num_pages=8),
            params,
        )
        for rid, p in enumerate(prompts):
            sched.submit(p, request_id=rid, max_new=6)
        _run(sched, len(prompts))
    assert len(sched.completed) == len(prompts)
    alloc = sched._alloc
    assert alloc.used == 0, "pages leaked past request retirement"
    assert alloc.peak_used > 0
    assert alloc.peak_used <= alloc.num_pages
    assert (sched._tables == -1).all()
    stats = sched.kv_cache_stats()
    assert stats["num_pages"] == 8 and stats["pages_in_use"] == 0
    assert stats["peak_used_pages"] == alloc.peak_used
    # and the recycled pool still produced reference tokens
    for req in sched.completed:
        ref = _reference_generate(cfg, mesh, params, prompts[req["id"]], 6)
        assert req["generated"] == ref, (req["id"], req["generated"], ref)


def test_paged_pool_exhaustion_raises_clean_error():
    """With preempt_policy="never" a dry pool must fail the requester
    loudly BEFORE handing out any page — never remap a neighbor's pages —
    and the failed request must be fully unwound (every page it already
    held released, no leak). The neighbor keeps running correctly
    afterwards. Pool math: 3 pages of 8; "a" (prompt 4, max_new 12) holds
    page 0 and asks for its second page at decode position 8 on the tick
    after "b"'s prefill (20 tokens) has taken the other two — "a" fails,
    "b" completes against the reference."""
    cfg, mesh, params = _serve_fixtures()
    prompt_a, prompt_b = [5, 6, 7, 8], list(range(4, 24))
    with mesh:
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=64, batch=2, prefill_chunk=4,
                        page_size=8, num_pages=3,
                        preempt_policy="never"),
            params,
        )
        sched.submit(prompt_a, request_id="a", max_new=12)
        sched.step()  # "a" owns page 0 (prompt) — 2 pages left
        sched.submit(prompt_b, request_id="b", max_new=4)
        with pytest.raises(RuntimeError, match="exhausted"):
            _run(sched, 2)
        # "a" failed mid-decode and was unwound: its page is back in the
        # free list (the no-leak guarantee) and only "b"'s prefill pages
        # remain live
        (req_a,) = sched.failed
        assert req_a["id"] == "a" and req_a["_status"] == "failed"
        assert sched._alloc.used == 2
        _run(sched, 1)
    got = {r["id"]: r["generated"] for r in sched.completed}
    assert got["b"] == _reference_generate(cfg, mesh, params, prompt_b, 4)
    assert sched._alloc.used == 0, "pages leaked past retirement"
    # whatever "a" produced before failing is a clean prefix of its
    # reference stream — the unwind never corrupted its (or b's) pages
    ref = _reference_generate(cfg, mesh, params, prompt_a, 12)
    assert req_a["generated"] == ref[: len(req_a["generated"])]


def test_paged_rejects_indivisible_max_len():
    cfg, mesh, params = _serve_fixtures()
    with pytest.raises(ValueError, match="divisible"):
        BatchScheduler(
            cfg, mesh, ServeConfig(max_len=60, batch=2, page_size=16), params
        )


# ---------------------------------------------------------------------------
# sampling: temperature/top-k with per-request on-device PRNG keys
# ---------------------------------------------------------------------------


def test_sampling_deterministic_and_reset_on_slot_reuse():
    """With greedy=False the decode/prefill-chunk steps sample on device
    from ``fold_in(request_key, position)`` — stateless, so a request's
    stream depends only on (params, prompt, request_id, seed): running it after
    a predecessor retired from the slot must reproduce the fresh-scheduler
    stream exactly."""
    cfg, mesh, params = _serve_fixtures()
    scfg = ServeConfig(max_len=64, batch=1, prefill_chunk=4,
                       greedy=False, temperature=0.8, top_k=20, sample_seed=3)
    prompt_a, prompt_b = [5, 6, 7, 8, 9], [20, 21, 22]

    def run(submit_a):
        with mesh:
            sched = BatchScheduler(cfg, mesh, scfg, params)
            if submit_a:
                sched.submit(prompt_a, request_id="a", max_new=5)
            sched.submit(prompt_b, request_id="b", max_new=8)
            _run(sched, 2 if submit_a else 1)
        return {r["id"]: r["generated"] for r in sched.completed}

    reused = run(submit_a=True)    # "b" samples in the slot "a" retired from
    fresh = run(submit_a=False)    # "b" samples in a never-used slot
    assert reused["b"] == fresh["b"], (reused["b"], fresh["b"])
    # determinism: the same scheduler run twice is bitwise repeatable
    assert run(submit_a=True) == reused
    # sampled ids stay inside the real vocab (padded ids are masked out)
    for toks in reused.values():
        assert all(0 <= t < cfg.vocab for t in toks)


def test_sampling_independent_of_coresident_traffic():
    """A sampled request's stream must not depend on what the OTHER slots
    are doing: attaching it late (after another request decoded for a few
    ticks) must reproduce the solo stream bit for bit.
    The stateless fold_in(request_key, position) keying guarantees it — a
    carried-and-split key would advance with every batched decode and
    fail this."""
    cfg, mesh, params = _serve_fixtures()
    prompt_x, prompt_b = list(range(4, 14)), [20, 21, 22]

    def scfg():
        return ServeConfig(max_len=64, batch=2, prefill_chunk=4,
                           greedy=False, temperature=0.8, top_k=20,
                           sample_seed=3)

    def stream_of_b(sched, late):
        sched.submit(prompt_x, request_id="x", max_new=10)
        if late:
            sched.step()
            sched.step()  # x decodes alone for a while
        sched.submit(prompt_b, request_id="b", max_new=6)
        _run(sched, 2)
        return {r["id"]: r["generated"] for r in sched.completed}["b"]

    with mesh:
        # solo-ish baseline: b attaches immediately alongside x
        base = stream_of_b(BatchScheduler(cfg, mesh, scfg(), params), late=False)
        late = stream_of_b(BatchScheduler(cfg, mesh, scfg(), params), late=True)
    assert base == late, (base, late)


def test_sampling_greedy_flag_matches_historical_argmax():
    """greedy=True (the default) must stay bitwise identical to the
    pre-sampling scheduler — the reference generator IS the historical
    argmax path."""
    cfg, mesh, params = _serve_fixtures()
    prompt = [9, 10, 11, 12]
    with mesh:
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=64, batch=2, temperature=0.7, top_k=5),
            params,
        )  # temperature/top_k are inert while greedy=True
        sched.submit(prompt, request_id=0, max_new=6)
        _run(sched, 1)
    (req,) = sched.completed
    assert req["generated"] == _reference_generate(cfg, mesh, params, prompt, 6)


# ---------------------------------------------------------------------------
# cross-request prefix cache: radix trie + copy-on-write pages
# ---------------------------------------------------------------------------
# The guarantee under test everywhere below: prefix sharing is a pure
# memory/compute optimization — generated tokens are bitwise identical with
# the cache on or off, because a shared page holds exactly the K/V the
# request would have prefilled itself.


def _run_shared_prefix(cfg, mesh, params, prompts, *, prefix_cache,
                       page_size=8, num_pages=None, prefill_chunk=4,
                       max_new=6, batch=2, trie_capacity=None):
    """Warm-first schedule: request 0 completes (inserting its prompt pages
    into the trie when sharing is on), then the rest attach against it."""
    with mesh:
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=64, batch=batch, prefill_chunk=prefill_chunk,
                        page_size=page_size, num_pages=num_pages,
                        prefix_cache=prefix_cache,
                        prefix_trie_capacity=trie_capacity),
            params,
        )
        sched.submit(prompts[0], request_id=0, max_new=max_new)
        _run(sched, 1)
        for rid, p in enumerate(prompts[1:], start=1):
            sched.submit(p, request_id=rid, max_new=max_new)
        _run(sched, len(prompts))
    return sched


def _tokens(sched):
    return {r["id"]: r["generated"] for r in sched.completed}


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "gemma2-2b", "zamba2-2.7b"])
def test_prefix_sharing_identical_tokens(arch):
    """Sharing on/off bitwise token identity on a shared-system-prompt
    workload — across a plain KV stack, a sliding window SMALLER than the
    shared prefix (gemma2: the window crosses shared-page boundaries), and
    a hybrid mamba+attention stack (zamba2: attention pages are shared for
    the memory win but no prefill compute is skipped, because the recurrent
    state must still advance over every prompt token)."""
    if arch == "tinyllama-1.1b":
        cfg, mesh, params = _serve_fixtures()
    else:
        cfg = smoke_config(arch).replace(
            compute_dtype_name="float32", param_dtype_name="float32",
            **({"window": 5} if arch == "gemma2-2b" else {}),
        )
        mesh = make_host_mesh()
        params = init_params(
            T.model_params(cfg), jax.random.PRNGKey(0), cfg.param_dtype
        )
    rng = np.random.default_rng(13)
    system = rng.integers(4, cfg.vocab, size=24).tolist()  # 3 pages of 8
    prompts = [system + rng.integers(4, cfg.vocab, size=int(n)).tolist()
               for n in rng.integers(3, 8, size=5)]

    on = _run_shared_prefix(cfg, mesh, params, prompts, prefix_cache=True)
    off = _run_shared_prefix(cfg, mesh, params, prompts, prefix_cache=False)
    assert _tokens(on) == _tokens(off)
    pc = on.kv_cache_stats()["prefix_cache"]
    assert pc["hits"] == len(prompts) - 1  # everyone after the warmup hits
    assert pc["pages_saved_by_sharing"] > 0
    if arch == "zamba2-2.7b":
        # hybrid: pages shared (memory), no compute skipped (the recurrent
        # state has no positional mask to fast-forward through)
        assert pc["prefill_tokens_skipped"] == 0
    else:
        assert pc["prefill_tokens_skipped"] > 0
        assert on.stats["prefill_chunks"] < off.stats["prefill_chunks"]
    # strictly fewer live pages at peak, trie pins included
    assert (on.kv_cache_stats()["peak_used_pages"]
            < off.kv_cache_stats()["peak_used_pages"])


def test_prefix_cow_mid_page_divergence():
    """Prompts diverging MID-page: the fully-matched pages are shared
    read-only, the partially-matched page is copy-on-write (fresh page,
    device copy of the donor's rows, divergent tokens prefilled over the
    tail) — and the tokens still match the no-sharing run exactly."""
    cfg, mesh, params = _serve_fixtures()
    rng = np.random.default_rng(17)
    common = rng.integers(4, cfg.vocab, size=20).tolist()  # 2.5 pages of 8
    prompts = [common + rng.integers(4, cfg.vocab, size=4).tolist()
               for _ in range(3)]  # diverge at token 20, mid-page 2

    on = _run_shared_prefix(cfg, mesh, params, prompts, prefix_cache=True)
    off = _run_shared_prefix(cfg, mesh, params, prompts, prefix_cache=False)
    assert _tokens(on) == _tokens(off)
    pc = on.kv_cache_stats()["prefix_cache"]
    assert pc["cow_copies"] >= 1
    assert pc["hit_tokens"] >= 20  # 2 full pages + 4 donor rows per hit


def test_prefix_refcounts_no_leak_under_churn():
    """Slot-reuse churn with sharing on: after every request retires, the
    only pages still allocated are the trie's own pins (one reference
    each); clear() then returns the pool to empty and the block tables of
    all slots are fully cleared — no leaked references either way."""
    cfg, mesh, params = _serve_fixtures()
    rng = np.random.default_rng(19)
    system = rng.integers(4, cfg.vocab, size=16).tolist()
    prompts = [system + rng.integers(4, cfg.vocab, size=int(n)).tolist()
               for n in rng.integers(3, 8, size=8)]  # 8 requests, 2 slots

    sched = _run_shared_prefix(cfg, mesh, params, prompts, prefix_cache=True)
    assert len(sched.completed) == len(prompts)
    alloc, trie = sched._alloc, sched._prefix
    assert alloc.used == trie.size, "pages leaked past request retirement"
    assert all(c == 1 for c in alloc.refs.values()), (
        "dangling non-trie references after all requests retired"
    )
    assert (sched._tables == -1).all()
    trie.clear()
    assert alloc.used == 0 and trie.size == 0
    assert not alloc.refs


def test_prefix_trie_eviction_under_pool_pressure():
    """A pool too small to hold every retired prompt's pages forces LRU
    trie eviction on attach; the evicted entries' neighbors (still-cached
    prefixes AND in-flight requests) are unharmed — every request still
    matches the no-sharing tokens, and eviction provably happened."""
    cfg, mesh, params = _serve_fixtures()
    rng = np.random.default_rng(23)
    # 4 DISTINCT 16-token prompts (2 pages each) + decode growth vs an
    # 8-page pool: the trie cannot keep them all pinned
    prompts = [rng.integers(4, cfg.vocab, size=16).tolist() for _ in range(4)]

    on = _run_shared_prefix(cfg, mesh, params, prompts, prefix_cache=True,
                            num_pages=8, batch=2)
    off = _run_shared_prefix(cfg, mesh, params, prompts, prefix_cache=False,
                             num_pages=8, batch=2)
    assert _tokens(on) == _tokens(off)
    pc = on.kv_cache_stats()["prefix_cache"]
    assert pc["evicted_pages"] >= 1
    assert on._alloc.used == on._prefix.size  # pins accounted, nothing leaked


def test_prefix_trie_capacity_lru_trim():
    """prefix_trie_capacity bounds the trie's pinned pages: inserts past
    the cap LRU-trim other paths, size never exceeds the cap, and sharing
    still works for the prefixes that stay resident."""
    cfg, mesh, params = _serve_fixtures()
    rng = np.random.default_rng(29)
    system = rng.integers(4, cfg.vocab, size=16).tolist()
    prompts = [system + rng.integers(4, cfg.vocab, size=int(n)).tolist()
               for n in rng.integers(3, 8, size=5)]

    sched = _run_shared_prefix(cfg, mesh, params, prompts, prefix_cache=True,
                               trie_capacity=2)
    off = _run_shared_prefix(cfg, mesh, params, prompts, prefix_cache=False)
    assert _tokens(sched) == _tokens(off)
    assert sched._prefix.size <= 2
    assert sched.kv_cache_stats()["prefix_cache"]["hits"] > 0


def test_prefix_sharing_sampled_streams_identical():
    """Sampling composes with sharing: streams are keyed on
    fold_in(request_key, position) — a function of the request and the
    position it samples, not of how the KV for earlier positions got
    there — so sampled tokens are bitwise identical with sharing on or
    off."""
    cfg, mesh, params = _serve_fixtures()
    rng = np.random.default_rng(31)
    system = rng.integers(4, cfg.vocab, size=16).tolist()
    prompts = [system + rng.integers(4, cfg.vocab, size=int(n)).tolist()
               for n in rng.integers(3, 8, size=4)]

    def run(prefix_cache):
        with mesh:
            sched = BatchScheduler(
                cfg, mesh,
                ServeConfig(max_len=64, batch=2, prefill_chunk=4,
                            page_size=8,
                            prefix_cache=prefix_cache,
                            greedy=False, temperature=0.8, top_k=20,
                            sample_seed=3),
                params,
            )
            sched.submit(prompts[0], request_id=0, max_new=6)
            _run(sched, 1)
            for rid, p in enumerate(prompts[1:], start=1):
                sched.submit(p, request_id=rid, max_new=6)
            _run(sched, len(prompts))
        return _tokens(sched)

    assert run(True) == run(False)


def test_batch_scheduler_batches_token_readback(monkeypatch):
    """Decode steps must NOT pay one host round-trip each: readbacks are
    deferred and flushed in a single device_get at completion boundaries."""
    cfg, mesh, params = _serve_fixtures()
    calls = {"n": 0}
    real_get = jax.device_get

    def counting_get(x):
        calls["n"] += 1
        return real_get(x)

    with mesh:
        sched = BatchScheduler(cfg, mesh, ServeConfig(max_len=64, batch=2), params)
        for rid in range(4):
            sched.submit([1, 2, 3], request_id=rid, max_new=6)
        monkeypatch.setattr("repro.serve.serve.jax.device_get", counting_get)
        steps = 0
        while len(sched.completed) < 4 and steps < 64:
            sched.step()
            steps += 1
        sched.drain()
    assert len(sched.completed) == 4
    # 4 requests x 6 tokens: per-step readback would pay >= 20 transfers;
    # deferred flushing pays at most one per request-completion boundary
    # (completions stagger by one tick because prefills serialize at one
    # chunk per tick) + the drain
    assert steps >= 12
    assert calls["n"] <= 5, f"{calls['n']} readbacks in {steps} steps"
    for req in sched.completed:
        assert len(req["generated"]) == 6


# ---------------------------------------------------------------------------
# admission queue, preemption under memory pressure, recompute-resume
# ---------------------------------------------------------------------------
# The guarantee under test: preemption is a pure scheduling decision — a
# preempted request's resumed stream is bitwise identical to an ample-pool
# run (recompute rebuilds the prompt KV on the same chunk grid and replays
# the generated history through ordinary decode steps), and neighbors never
# see a difference.


def _run_under_pressure(cfg, mesh, params, prompts, *, num_pages,
                        max_new=8, greedy=True, page_size=8,
                        prefill_chunk=4, batch=2, policy="priority"):
    kw = {} if greedy else dict(greedy=False, temperature=0.8, top_k=20,
                                sample_seed=3)
    with mesh:
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=64, batch=batch, prefill_chunk=prefill_chunk,
                        page_size=page_size, num_pages=num_pages,
                        preempt_policy=policy, **kw),
            params,
        )
        for rid, p in enumerate(prompts):
            sched.submit(p, request_id=rid, max_new=max_new)
        sched.drain()
    return sched


@pytest.mark.parametrize("greedy", [True, False])
def test_preempt_resume_identity(greedy):
    """Forced preemption: a 3-page pool cannot hold two 2-page requests, so
    the younger parks itself mid-decode and resumes after the older
    retires — and every token stream is bitwise identical to an ample-pool
    run, greedy AND sampled (per-request sampling keys make the stream
    independent of the slot it resumes into)."""
    cfg, mesh, params = _serve_fixtures()
    prompts = [list(range(4, 12)), list(range(20, 28))]  # 1 page each, grow to 2

    ample = _run_under_pressure(cfg, mesh, params, prompts, num_pages=16,
                                greedy=greedy)
    tight = _run_under_pressure(cfg, mesh, params, prompts, num_pages=3,
                                greedy=greedy)
    assert tight.stats["preemptions"] > 0, "pressure never materialized"
    assert tight.stats["resumes"] > 0
    assert _tokens(tight) == _tokens(ample)
    assert tight._alloc.used == 0, "pages leaked across preempt/resume"
    press = tight.kv_cache_stats()["pressure"]
    assert press["preemptions"] == tight.stats["preemptions"]
    assert press["pages_freed_by_preempt"] > 0


@pytest.mark.parametrize("arch", ["zamba2-2.7b", "xlstm-350m"])
def test_preempt_resume_identity_recurrent(arch):
    """Recompute-resume on recurrent/hybrid stacks: state has no positional
    masking, so resume must re-run it over EVERY token — the full prompt
    through the chunked prefill (the PR 6 done=0 rule) and the generated
    history through replayed decode steps. Tokens must match the
    ample-pool run exactly."""
    cfg = smoke_config(arch).replace(
        compute_dtype_name="float32", param_dtype_name="float32",
        **({"repeats": 1} if arch == "xlstm-350m" else {}),
    )
    mesh = make_host_mesh()
    params = init_params(T.model_params(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    prompts = [list(range(4, 12)), list(range(20, 28))]

    ample = _run_under_pressure(cfg, mesh, params, prompts, num_pages=16,
                                max_new=6)
    tight = _run_under_pressure(cfg, mesh, params, prompts, num_pages=3,
                                max_new=6)
    assert tight.stats["preemptions"] > 0, "pressure never materialized"
    assert _tokens(tight) == _tokens(ample)
    for rid, p in enumerate(prompts):
        ref = _reference_generate(cfg, mesh, params, p, 6)
        assert _tokens(tight)[rid] == ref, (rid, _tokens(tight)[rid], ref)


def test_victim_selection_policies():
    """_pick_victim unit semantics: only strictly-younger (or strictly
    lower-priority) occupants are eligible — the oldest request can never
    be evicted by a newcomer — and each policy orders the eligible set as
    documented."""
    cfg, mesh, params = _serve_fixtures()

    def scheduler(policy):
        with mesh:
            s = BatchScheduler(
                cfg, mesh,
                ServeConfig(max_len=64, batch=4, page_size=8,
                            num_pages=32, preempt_policy=policy),
                params,
            )
        # hand-place occupants; submit() assigns _seq in call order
        prios = {"w": 0, "x": 0, "y": 0, "z": 1}
        for rid in ("w", "x", "y", "z"):
            s.submit([1, 2, 3], request_id=rid, max_new=4,
                     priority=prios[rid])
        reqs = {r["id"]: r for r in s.queue}
        s.queue.clear()
        for slot, rid in enumerate(("w", "x", "y", "z")):
            s.active[slot] = reqs[rid]
        s._slot_pages[0] = [0]              # w: oldest
        s._slot_pages[1] = [1, 2, 3]        # x: most pages
        s._slot_pages[2] = [4, 5]           # y
        s._slot_pages[3] = [6, 7]           # z: higher priority class
        reqs["x"]["generated"] = [9]        # x: some progress
        reqs["y"]["generated"] = []         # y: least progress
        return s, reqs

    s, reqs = scheduler("priority")
    # requester w (oldest, prio 0): z is NOT eligible (higher priority);
    # among x/y the cheapest class ties and most-pages wins -> x (slot 1)
    assert s._pick_victim(reqs["w"]) == 1
    s, reqs = scheduler("pages")
    assert s._pick_victim(reqs["w"]) == 1   # most pages outright
    s, reqs = scheduler("progress")
    assert s._pick_victim(reqs["w"]) == 2   # y lost the least work
    # anti-livelock: the NEWEST same-priority request sees no eligible
    # victim at all (everyone is older) — it must park itself instead
    s, reqs = scheduler("priority")
    assert s._pick_victim(reqs["y"]) is None
    # ...but a high-priority newcomer may evict older lower-priority work
    assert s._pick_victim(reqs["z"]) == 1


def test_mid_stream_cancel_frees_pages_neighbors_unaffected():
    """cancel() mid-decode frees the victim's pages immediately, leaves
    the prefix trie's own pins resident, and does not perturb the
    co-resident request's stream by a single bit."""
    cfg, mesh, params = _serve_fixtures()
    prompt_a, prompt_b = list(range(4, 14)), list(range(30, 38))

    def run(with_cancel):
        with mesh:
            sched = BatchScheduler(
                cfg, mesh,
                ServeConfig(max_len=64, batch=2, prefill_chunk=4,
                            page_size=8, prefix_cache=True),
                params,
            )
            sched.submit(prompt_a, request_id="a", max_new=10)
            handle_b = sched.submit(prompt_b, request_id="b", max_new=10)
            for _ in range(7):  # both prefilled; b decoding mid-stream
                sched.step()
            if with_cancel:
                used_before = sched._alloc.used
                trie_before = sched._prefix.size
                assert handle_b.cancel()
                assert not handle_b.cancel()  # idempotent: already closed
                assert handle_b.status == "cancelled" and handle_b.done
                assert sched._alloc.used < used_before  # pages freed NOW
                assert sched._prefix.size == trie_before  # pins unharmed
            sched.drain()
        return sched

    full = run(False)
    cut = run(True)
    a_full = {r["id"]: r["generated"] for r in full.completed}["a"]
    a_cut = {r["id"]: r["generated"] for r in cut.completed}["a"]
    assert a_cut == a_full, "cancel perturbed the co-resident stream"
    assert [r["id"] for r in cut.cancelled] == ["b"]
    assert all(r["id"] != "b" for r in cut.completed)
    assert cut.stats["cancellations"] == 1
    # nothing leaked: only the trie's pins remain after drain
    assert cut._alloc.used == cut._prefix.size
    # cancelling a request still waiting in the admission queue works too
    with mesh:
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=64, batch=1, prefill_chunk=4), params,
        )
        sched.submit(prompt_a, request_id="a", max_new=4)
        hq = sched.submit(prompt_b, request_id="q", max_new=4)  # queued
        sched.step()
        assert hq.cancel() and not sched.queue
        sched.drain()
    assert {r["id"] for r in sched.completed} == {"a"}


def test_priority_preempts_lower_and_both_match_reference():
    """A strictly-higher-priority arrival behind a full batch evicts the
    lowest-priority occupant; the evicted request resumes afterwards and
    BOTH streams match the stop-the-world reference exactly."""
    cfg, mesh, params = _serve_fixtures()
    prompt_lo, prompt_hi = list(range(4, 12)), list(range(20, 26))
    with mesh:
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=64, batch=1, prefill_chunk=4,
                        page_size=8),
            params,
        )
        h_lo = sched.submit(prompt_lo, request_id="lo", max_new=8, priority=0)
        for _ in range(4):
            sched.step()  # lo prefilled and decoding
        h_hi = sched.submit(prompt_hi, request_id="hi", max_new=6, priority=5)
        assert h_hi.result() == _reference_generate(
            cfg, mesh, params, prompt_hi, 6
        )
        sched.drain()
    assert sched.stats["preemptions"] >= 1
    assert sched.stats["resumes"] >= 1
    assert sched.kv_cache_stats()["pressure"]["peak_queue_depth"] >= 1
    assert h_lo.status == "done"
    assert h_lo.tokens == _reference_generate(cfg, mesh, params, prompt_lo, 8)
    # "hi" finished before "lo" despite arriving later: priority worked
    order = [r["id"] for r in sched.completed]
    assert order.index("hi") < order.index("lo")


def test_stream_async_interleaves_two_requests():
    """stream_async: two concurrent consumers over one scheduler, each
    driving shared ticks — both streams complete and match the greedy
    reference."""
    import asyncio

    cfg, mesh, params = _serve_fixtures()
    prompts = {"a": list(range(4, 12)), "b": list(range(20, 27))}
    with mesh:
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=64, batch=2, prefill_chunk=4), params,
        )
        for rid, p in prompts.items():
            sched.submit(p, request_id=rid, max_new=5)

        async def collect(rid):
            return [t async for t in sched.stream_async(rid)]

        async def main():
            return await asyncio.gather(collect("a"), collect("b"))

        got_a, got_b = asyncio.run(main())
        sched.drain()
    assert got_a == _reference_generate(cfg, mesh, params, prompts["a"], 5)
    assert got_b == _reference_generate(cfg, mesh, params, prompts["b"], 5)


def _stall_request(sched, request_id):
    """Wedge a request: parked with a ready tick the scheduler will never
    reach — the shape of a stalled retry backoff or a lost resume."""
    req = sched._by_id[request_id]
    sched.queue.remove(req)
    req["_status"] = "retrying"
    req["_not_before"] = 10**9
    sched._parked.append(req)


def test_result_and_stream_timeout_raise():
    """``result(timeout=)``/``stream(timeout=)`` bound the scheduler ticks
    spent waiting between tokens: a wedged request raises ``TimeoutError``
    instead of spinning, and the default (no timeout) still raises the
    livelock ``RuntimeError`` eventually rather than hanging."""
    cfg, mesh, params = _serve_fixtures()
    with mesh:
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=64, batch=2, prefill_chunk=4,
                        page_size=8), params,
        )
        handle = sched.submit(list(range(4, 12)), request_id=0, max_new=4)
        _stall_request(sched, 0)
        with pytest.raises(TimeoutError, match="no progress"):
            handle.result(timeout=5)
        with pytest.raises(TimeoutError, match="no progress"):
            next(iter(handle.stream(timeout=3)))
    assert handle.status == "retrying", "timeout must not kill the request"


def test_drain_nonquiescence_raises_with_stats():
    """drain() on a scheduler that cannot reach quiescence raises a
    descriptive ``RuntimeError`` carrying the kv_cache_stats snapshot —
    the bug report IS the error message."""
    cfg, mesh, params = _serve_fixtures()
    with mesh:
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=64, batch=2, prefill_chunk=4,
                        page_size=8), params,
        )
        sched.submit(list(range(4, 12)), request_id=0, max_new=4)
        _stall_request(sched, 0)
        with pytest.raises(RuntimeError) as exc:
            sched.drain()
    msg = str(exc.value)
    assert "no quiescence" in msg and "parked=1" in msg
    assert "kv_cache_stats" in msg and "'recovery'" in msg
