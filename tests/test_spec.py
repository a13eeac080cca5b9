"""Speculative decoding: drafter purity, batched-verify identity, composition.

The contract under test: speculation is an OPTIMIZATION, never a behavior
change. A draft token is only kept when verification proves it is the token
sequential decode would have produced, so spec on/off must be bitwise
identical — greedy and sampled, across attention/window/recurrent archs,
and composed with preemption-resume and fault-retry — while the page pool
stays leak-free under rejections and mid-accept exhaustion.
"""

import functools

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.layers.common import init_params
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.serve.faults import FaultEvent, FaultInjector
from repro.serve.serve import (
    BatchScheduler,
    ServeConfig,
    _PoolPressure,
    _serve_step_fns,
)
from repro.serve.spec import draft_tokens


# ---------------------------------------------------------------------------
# drafter: pure function of the history, deterministic, bounded
# ---------------------------------------------------------------------------


def test_draft_tokens_deterministic_and_pure():
    h = [1, 2, 3, 1, 2, 3, 1, 2]
    first = draft_tokens(h, 4)
    assert first == draft_tokens(h, 4)  # same history -> same proposal
    assert h == [1, 2, 3, 1, 2, 3, 1, 2]  # input untouched
    first.append(99)  # returned list is a copy, not a view into state
    assert draft_tokens(h, 4) == first[:-1]
    # numpy token histories (what the scheduler holds) work and yield ints
    out = draft_tokens(np.asarray(h, np.int32), 4)
    assert out == first[:-1] and all(type(t) is int for t in out)


def test_draft_tokens_matches_most_recent_occurrence():
    # suffix [1, 2] occurs earlier at index 2 and 5; the most recent
    # earlier occurrence (5) wins, so the proposal is what followed THERE
    h = [7, 8, 1, 2, 9, 1, 2, 3, 1, 2]
    assert draft_tokens(h, 3) == [3, 1, 2]
    assert draft_tokens(h, 1) == [3]  # k caps the proposal


def test_draft_tokens_prefers_longer_suffix():
    # both [2, 3] and the longer [1, 2, 3] recur; the 3-gram match wins
    # even though a 2-gram occurrence is nearer the end
    h = [1, 2, 3, 4, 2, 3, 9, 1, 2, 3]
    assert draft_tokens(h, 2) == [4, 2]


def test_draft_tokens_min_match_and_degenerate_cases():
    h = [1, 2, 3, 4, 2]
    assert draft_tokens(h, 4) == []  # only a 1-gram recurs; min_match=2
    assert draft_tokens(h, 4, min_match=1) == [3, 4, 2]
    assert draft_tokens(h, 0) == []
    assert draft_tokens([5], 4) == []
    assert draft_tokens([], 4) == []


# ---------------------------------------------------------------------------
# shared fixtures (f32: identity checks must isolate scheduler logic from
# bf16 argmax near-ties, same rationale as tests/test_serve.py)
# ---------------------------------------------------------------------------


@functools.cache
def _fixtures(arch="tinyllama-1.1b"):
    over = {"compute_dtype_name": "float32", "param_dtype_name": "float32"}
    if arch == "xlstm-350m":
        over["repeats"] = 1
    if arch == "gemma2-2b":
        # sliding window smaller than the prompt AND the verify chunk so
        # windowed attention genuinely crosses the speculated positions
        over["window"] = 5
    cfg = smoke_config(arch).replace(**over)
    mesh = make_host_mesh()
    params = init_params(T.model_params(cfg), jax.random.PRNGKey(0),
                         cfg.param_dtype)
    return cfg, mesh, params


def _copy_regime(params):
    """Residual-zeroed weights: logits become a pure function of the last
    token, so greedy decode must cycle (pigeonhole) — the deterministic way
    to force real multi-token accepts out of the n-gram drafter."""
    return dict(params, slots=jax.tree_util.tree_map(
        lambda x: x * 0.0, params["slots"]))


def _run_sched(cfg, mesh, params, prompts, *, spec, greedy=True, max_new=6,
               num_pages=32, spec_k=4, injector=None, **over):
    kw = dict(max_len=64, batch=2, prefill_chunk=4, page_size=8,
              num_pages=num_pages)
    if not greedy:
        kw.update(greedy=False, temperature=0.8, top_k=20, sample_seed=3)
    if spec:
        kw.update(spec_decode=True, spec_k=spec_k)
    kw.update(over)
    with mesh:
        sched = BatchScheduler(cfg, mesh, ServeConfig(**kw), params,
                               fault_injector=injector)
        for rid, p in enumerate(prompts):
            sched.submit(p, request_id=rid, max_new=max_new)
        sched.drain()
    return sched


def _tokens(sched):
    return {r["id"]: r["generated"] for r in sched.completed}


# a prompt with a repeated 4-gram (the drafter locks on immediately) plus a
# non-repetitive one (the drafter proposes little) — both paths every run
_PROMPTS = [[5, 9, 13, 7] * 3, list(range(20, 28))]


# ---------------------------------------------------------------------------
# _serve_step_fns cache: spec knobs are part of the key, no collisions
# ---------------------------------------------------------------------------


def test_serve_step_fns_keys_on_full_statics_no_collision():
    cfg, mesh, _ = _fixtures()
    kw = dict(max_len=64, batch=2, prefill_chunk=4, page_size=8,
              num_pages=8)
    plain = _serve_step_fns(cfg, mesh, ServeConfig(**kw).step_statics())
    assert plain[3] is None  # no verify step without spec_decode
    # an equal config (fresh instance) must hit the same cache entry
    assert _serve_step_fns(cfg, mesh,
                           ServeConfig(**kw).step_statics()) is plain
    spec = _serve_step_fns(
        cfg, mesh, ServeConfig(spec_decode=True, **kw).step_statics())
    assert spec is not plain and spec[3] is not None
    # every spec knob is a distinct key: a collision would hand a spec_k=6
    # scheduler a verify trace shaped for spec_k=4
    for knob in ({"spec_k": 6}, {"spec_min_match": 3}):
        other = _serve_step_fns(
            cfg, mesh,
            ServeConfig(spec_decode=True, **knob, **kw).step_statics())
        assert other is not spec
    # sampling knobs key the verify trace too (greedy argmax vs folded keys)
    sampled = _serve_step_fns(
        cfg, mesh,
        ServeConfig(spec_decode=True, greedy=False, temperature=0.8,
                    top_k=20, **kw).step_statics())
    assert sampled is not spec
    info = _serve_step_fns.cache_info()
    assert info.maxsize >= 32  # room for the repo's A/B patterns


# ---------------------------------------------------------------------------
# spec on/off bitwise identity — the tentpole guarantee
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("greedy", [True, False])
@pytest.mark.parametrize("arch", [
    "tinyllama-1.1b", "gemma2-2b", "zamba2-2.7b", "xlstm-350m",
])
def test_spec_matches_plain_decode(arch, greedy):
    """Speculation on vs off must be bitwise identical per request —
    full attention, windowed attention crossing the verify chunk, and
    recurrent/hybrid stacks (whose verify runs two passes so state
    advances over exactly the accepted tokens) — greedy AND sampled."""
    cfg, mesh, params = _fixtures(arch)
    plain = _run_sched(cfg, mesh, params, _PROMPTS, spec=False, greedy=greedy)
    spec = _run_sched(cfg, mesh, params, _PROMPTS, spec=True, greedy=greedy)
    assert _tokens(spec) == _tokens(plain)
    assert spec.stats["spec_dispatches"] > 0
    assert spec._alloc.used == 0, "pages leaked after drain"


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "zamba2-2.7b"])
def test_spec_multi_token_accept_copy_regime(arch):
    """With residual-zeroed weights greedy decode cycles, the drafter
    locks on, and verification must accept multi-token windows — while
    staying bitwise identical to sequential decode (on recurrent archs
    this is the state-advances-over-every-accepted-token check).

    ``max_new=48``: the cycle (a walk in the last-token map's functional
    graph) is entered around token ~20 on both fixtures, so the drafter
    has real accepting room only past that point."""
    cfg, mesh, params = _fixtures(arch)
    params0 = _copy_regime(params)
    prompt = [[5, 9, 13, 7] * 4]
    plain = _run_sched(cfg, mesh, params0, prompt, spec=False, max_new=48,
                       max_len=128)
    spec = _run_sched(cfg, mesh, params0, prompt, spec=True, max_new=48,
                      max_len=128)
    assert _tokens(spec) == _tokens(plain)
    sp = spec.kv_cache_stats()["speculation"]
    assert sp["accepted"] > 0 and sp["acceptance_rate"] > 0.5, sp
    # multi-token accepts amortize dispatches (> 1 token/dispatch) and
    # cross page boundaries (page_size=8 < the accept windows' span)
    assert sp["tokens_per_dispatch"] > 1.0, sp
    assert spec.stats["decode_steps"] < plain.stats["decode_steps"]
    assert spec._alloc.used == 0


def test_spec_rejections_no_leak_and_identity():
    """Guaranteed rejection, deterministically: probe the copy-regime
    last-token map for the orbit of token 7, then plant a decoy after an
    earlier occurrence of the orbit's first token. The 1-gram drafter
    must propose the decoy and verification must reject it (the model's
    continuation is known and differs) — with tokens still bitwise equal
    to plain decode and the rolled-back pages returned at drain."""
    cfg, mesh, params = _fixtures()
    params0 = _copy_regime(params)
    orbit = _tokens(_run_sched(cfg, mesh, params0, [[4, 7]], spec=False,
                               max_new=4))[0]  # [f(7), f(f(7)), ...]
    decoy = (orbit[1] + 1) % cfg.vocab  # never what the model emits next
    # last prompt token 7 -> first generated token is f(7) = orbit[0];
    # its planted earlier occurrence is followed by the decoy
    prompt = [orbit[0], decoy, 11, 3, 7]
    plain = _run_sched(cfg, mesh, params0, [prompt], spec=False, max_new=8)
    spec = _run_sched(cfg, mesh, params0, [prompt], spec=True, max_new=8,
                      spec_min_match=1)
    assert _tokens(spec) == _tokens(plain)
    assert spec.stats["spec_rejected"] > 0, spec.stats
    assert spec._alloc.used == 0, "rejected speculation leaked pages"


# ---------------------------------------------------------------------------
# composition: preemption-resume and fault-retry stay bitwise-correct
# ---------------------------------------------------------------------------


def test_spec_preempt_resume_identity():
    """A preempted spec request resumes with its token history, so the
    drafter re-derives the same proposals and the replayed generated
    tokens ride the verify path — the tight-pool run must match both the
    ample-pool spec run and plain decode, with nothing leaked."""
    cfg, mesh, params = _fixtures()
    prompts = [list(range(4, 12)), list(range(20, 28))]
    plain = _run_sched(cfg, mesh, params, prompts, spec=False, max_new=8,
                       num_pages=16)
    ample = _run_sched(cfg, mesh, params, prompts, spec=True, max_new=8,
                       num_pages=16)
    tight = _run_sched(cfg, mesh, params, prompts, spec=True, max_new=8,
                       num_pages=3)
    assert tight.stats["preemptions"] > 0, "pressure never materialized"
    assert _tokens(tight) == _tokens(ample) == _tokens(plain)
    assert tight._alloc.used == 0, "pages leaked across preempt/resume"


@pytest.mark.parametrize("greedy", [True, False])
def test_spec_fault_retry_identity(greedy):
    """NaN-poisoned verify dispatches must be invisible in the output:
    the victim retries through recompute-resume (replaying its clean
    history as auto-accepting drafts) and every stream matches the
    fault-free spec run bitwise."""
    cfg, mesh, params = _fixtures()
    events = [FaultEvent(kind="nan", tick=4), FaultEvent(kind="nan", tick=9)]
    base = _run_sched(cfg, mesh, params, _PROMPTS, spec=True, greedy=greedy,
                      max_new=8)
    chaos = _run_sched(cfg, mesh, params, _PROMPTS, spec=True, greedy=greedy,
                       max_new=8, injector=FaultInjector(events=events))
    assert chaos.stats["retries"] >= 1, chaos.kv_cache_stats()["recovery"]
    assert _tokens(chaos) == _tokens(base)
    assert chaos._alloc.used == 0, "pages leaked across fault retry"


# ---------------------------------------------------------------------------
# allocator exhaustion mid-accept: the partial grow must unwind page-by-page
# ---------------------------------------------------------------------------


def test_ensure_pages_unwinds_partial_alloc_on_exhaustion():
    """A multi-page grow (the multi-token-accept shape) that runs the pool
    dry partway must free the pages it already took and restore the block
    table before the pressure propagates — no partial allocation may leak."""
    cfg, mesh, params = _fixtures()
    with mesh:
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=64, batch=1, prefill_chunk=4,
                        page_size=8, num_pages=8, preempt_policy="never",
                        spec_decode=True, spec_k=4),
            params,
        )
        sched.submit(list(range(4, 10)), request_id=0, max_new=4)
        for _ in range(10):
            if sched.active[0] is not None:
                break
            sched.step()
        req = sched.active[0]
        assert req is not None
        # hold all but ONE free page: the 2-page grow below succeeds on its
        # first page, then hits exhaustion on the second
        held = sched._alloc.alloc(sched._alloc.free_pages - 1, owner="hold")
        used_before = sched._alloc.used
        pages_before = list(sched._slot_pages[0])
        tables_before = sched._tables.copy()
        grow_to = (len(pages_before) + 2) * sched.scfg.page_size - 1
        with pytest.raises(_PoolPressure):
            sched._ensure_pages(0, grow_to, req)
        assert sched._alloc.used == used_before, "partial grow leaked pages"
        assert sched._slot_pages[0] == pages_before
        np.testing.assert_array_equal(sched._tables, tables_before)
        # the unwound scheduler is still healthy: release the hold and the
        # request must run to completion with the pool fully returned
        sched._alloc.release(held)
        sched.drain()
    assert len(sched.completed) == 1
    assert sched._alloc.used == 0
