"""Fault injection + self-healing: seeded chaos schedules, and recovery
that is bitwise invisible to every surviving stream."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.layers.common import init_params
from repro.models import transformer as T
from repro.launch.mesh import make_host_mesh
from repro.serve.faults import (
    FaultConfig,
    FaultEvent,
    FaultInjector,
    generate_faults,
    page_edit_step,
    page_fingerprint_step,
)
from repro.serve.serve import BatchScheduler, ServeConfig


# ---------------------------------------------------------------------------
# schedule + injector units (no model)
# ---------------------------------------------------------------------------


def test_fault_schedule_pure_function_of_config():
    fcfg = FaultConfig(seed=7, n_nan=3, n_page_corrupt=2, n_alloc_spike=2,
                       n_hang=1)
    a, b = generate_faults(fcfg), generate_faults(fcfg)
    assert a == b, "same config must generate the same schedule bit-for-bit"
    assert len(a) == 8
    assert a != generate_faults(dataclasses.replace(fcfg, seed=8))
    kinds = {e.kind for e in a}
    assert kinds == {"nan", "page_corrupt", "alloc_spike", "hang"}
    assert all(1 <= e.tick <= fcfg.horizon_ticks for e in a)


def test_invalid_fault_configs_rejected():
    with pytest.raises(ValueError, match="corrupt_mode"):
        FaultConfig(corrupt_mode="flip")
    with pytest.raises(ValueError, match="horizon"):
        FaultConfig(horizon_ticks=0)


def test_injector_due_and_defer():
    events = [FaultEvent(kind="nan", tick=2), FaultEvent(kind="hang", tick=5)]
    inj = FaultInjector(events=events)
    assert inj.due(1) == []
    ready = inj.due(3)
    assert [e.kind for e in ready] == ["nan"]
    # no applicable target: the event comes due again next tick, counted
    inj.defer(ready[0], 3)
    assert inj.counters["deferrals"] == 1
    assert [e.kind for e in inj.due(4)] == ["nan"]
    assert not inj.exhausted
    assert [e.kind for e in inj.due(10)] == ["hang"]
    assert inj.exhausted
    inj.record("alloc_spike")
    assert inj.counters["alloc_spikes"] == 1


# ---------------------------------------------------------------------------
# device-side page edits + fingerprints (tiny synthetic pool)
# ---------------------------------------------------------------------------


def _tiny_caches():
    # mimics the paged-pool pytree shape, (R, Hkv, P, page, hd): the paged
    # leaves carry "pages" in their path, others must pass through edits
    # untouched
    k = jnp.arange(2 * 2 * 4 * 8 * 4, dtype=jnp.float32).reshape(2, 2, 4, 8, 4)
    return {"pages_k": k, "pages_v": k + 1.0, "state": jnp.ones((3, 3))}


def test_page_edit_nan_zero_and_bitflip_roundtrip():
    caches = _tiny_caches()
    ref = jax.tree_util.tree_map(lambda x: np.asarray(x), caches)
    nan_ed = page_edit_step("nan")(jax.tree_util.tree_map(jnp.copy, caches), 2)
    assert np.all(np.isnan(np.asarray(nan_ed["pages_k"])[:, :, 2]))
    np.testing.assert_array_equal(np.asarray(nan_ed["pages_k"])[:, :, 1],
                                  ref["pages_k"][:, :, 1])
    np.testing.assert_array_equal(np.asarray(nan_ed["state"]), ref["state"])
    zeroed = page_edit_step("zero")(nan_ed, 2)
    assert np.all(np.asarray(zeroed["pages_k"])[:, :, 2] == 0)
    # bitflip is an XOR: applying it twice restores the page exactly
    once = page_edit_step("bitflip")(
        jax.tree_util.tree_map(jnp.copy, caches), 1
    )
    assert not np.array_equal(np.asarray(once["pages_v"])[:, :, 1],
                              ref["pages_v"][:, :, 1])
    twice = page_edit_step("bitflip")(once, 1)
    np.testing.assert_array_equal(np.asarray(twice["pages_k"]),
                                  ref["pages_k"])


def test_page_fingerprint_moves_on_any_edit():
    caches = _tiny_caches()
    fp = page_fingerprint_step()
    base = int(fp(caches, 1))
    assert int(fp(caches, 1)) == base, "fingerprint must be deterministic"
    assert int(fp(caches, 2)) != base
    flipped = page_edit_step("bitflip")(
        jax.tree_util.tree_map(jnp.copy, caches), 1
    )
    assert int(fp(flipped, 1)) != base, "a bit flip must move the checksum"


@pytest.mark.parametrize("edit", ["cow", "nan", "zero", "bitflip"])
def test_page_edit_reaches_every_layer_and_head(edit):
    """A copy-on-write copy and a fault edit of physical page p rewrite
    page p in every layer and every KV head of each paged pool, and
    nothing else: not another page, not a non-paged leaf."""
    from repro.serve.serve import make_cow_copy_step

    caches = _tiny_caches()
    ref = jax.tree_util.tree_map(np.asarray, caches)
    fresh = jax.tree_util.tree_map(jnp.copy, caches)
    if edit == "cow":
        out = jax.jit(make_cow_copy_step())(fresh, 1, 3)
    else:
        out = page_edit_step(edit)(fresh, 3)
    np.testing.assert_array_equal(np.asarray(out["state"]), ref["state"])
    for name in ("pages_k", "pages_v"):
        got, was = np.asarray(out[name]), ref[name]
        others = np.arange(got.shape[2]) != 3
        np.testing.assert_array_equal(got[:, :, others], was[:, :, others])
        page = got[:, :, 3]
        if edit == "cow":
            np.testing.assert_array_equal(page, was[:, :, 1])
        elif edit == "nan":
            assert np.isnan(page).all()
        elif edit == "zero":
            assert (page == 0).all()
        else:  # the XOR pattern moves every value of the page
            assert (page != was[:, :, 3]).all()


# ---------------------------------------------------------------------------
# scheduler-level recovery: identity, quarantine, watchdog, spike, shed,
# checksum validation (tinyllama smoke in f32 — scheduler logic, not argmax
# near-ties, must decide every comparison)
# ---------------------------------------------------------------------------


@functools.cache
def _fixtures(arch="tinyllama-1.1b"):
    cfg = smoke_config(arch).replace(
        compute_dtype_name="float32", param_dtype_name="float32"
    )
    mesh = make_host_mesh()
    params = init_params(T.model_params(cfg), jax.random.PRNGKey(0),
                         cfg.param_dtype)
    return cfg, mesh, params


def _chaos_run(cfg, mesh, params, *, events=None, fcfg=None, greedy=True,
               prompts=None, max_new=6, **scfg_kw):
    injector = None
    if events is not None or fcfg is not None:
        injector = FaultInjector(fcfg, events=events)
    kw = dict(max_len=64, batch=2, prefill_chunk=4, page_size=8,
              num_pages=16, watchdog_deadline_s=0.05)
    if not greedy:
        kw.update(greedy=False, temperature=0.8, top_k=20, sample_seed=3)
    kw.update(scfg_kw)
    prompts = prompts or [list(range(4, 14)), list(range(30, 38))]
    with mesh:
        sched = BatchScheduler(cfg, mesh, ServeConfig(**kw), params,
                               fault_injector=injector)
        for rid, p in enumerate(prompts):
            sched.submit(p, request_id=rid, max_new=max_new)
        sched.drain()
    return sched, injector


def _tokens(sched):
    return {r["id"]: r["generated"] for r in sched.completed}


@pytest.mark.parametrize("greedy", [True, False])
def test_nan_retry_stream_identity(greedy):
    """A poisoned decode dispatch must be invisible in the output: the
    victim retries through recompute-resume and every stream — victim and
    neighbor — is bitwise identical to the unfaulted run, greedy AND
    sampled."""
    cfg, mesh, params = _fixtures()
    base, _ = _chaos_run(cfg, mesh, params, greedy=greedy)
    events = [FaultEvent(kind="nan", tick=4), FaultEvent(kind="nan", tick=9)]
    chaos, inj = _chaos_run(cfg, mesh, params, events=events, greedy=greedy)
    assert inj.counters["nan_injected"] == 2
    assert chaos.stats["retries"] >= 1
    assert chaos.stats["backoff_total_ticks"] >= chaos.stats["retries"]
    assert _tokens(chaos) == _tokens(base)
    assert chaos._alloc.used == 0, "pages leaked across fault retries"


def test_quarantine_frees_pages_neighbors_untouched():
    """Retries exhausted: exactly the pinned victim ends terminal
    ``failed`` with its pages freed; its co-resident's stream is bitwise
    unchanged and nothing leaks."""
    cfg, mesh, params = _fixtures()
    base, _ = _chaos_run(cfg, mesh, params, max_retries=2)
    events = [FaultEvent(kind="nan", tick=3 + 3 * i, request_id=0)
              for i in range(3)]
    quar, inj = _chaos_run(cfg, mesh, params, events=events, max_retries=2)
    assert inj.counters["nan_injected"] == 3
    assert [r["id"] for r in quar.failed] == [0]
    assert quar.failed[0]["_status"] == "failed"
    assert quar.stats["quarantined"] == 1
    assert _tokens(quar) == {k: v for k, v in _tokens(base).items() if k != 0}
    assert quar._alloc.used == 0, "quarantine leaked pages"


def test_watchdog_trip_and_alloc_spike_recover():
    """A hung dispatch trips the watchdog and the victim retries; a
    transient allocator spike parks work through the normal pressure path
    — both recover to the exact unfaulted streams."""
    cfg, mesh, params = _fixtures()
    base, _ = _chaos_run(cfg, mesh, params, num_pages=6)
    fcfg = FaultConfig(hang_s=0.2, spike_pages=2, spike_ticks=3)
    events = [FaultEvent(kind="hang", tick=4),
              FaultEvent(kind="alloc_spike", tick=6)]
    chaos, inj = _chaos_run(cfg, mesh, params, events=events, fcfg=fcfg,
                            num_pages=6)
    assert inj.counters["hangs"] == 1 and inj.counters["alloc_spikes"] == 1
    assert chaos.stats["watchdog_trips"] >= 1
    assert not chaos._spike_holds, "spike pages not released"
    assert _tokens(chaos) == _tokens(base)
    assert chaos._alloc.used == 0


def test_shed_queue_depth_drops_lowest_priority_youngest():
    """Admission past ``shed_queue_depth`` sheds the lowest-priority
    youngest waiter with a terminal ``shed`` status — the handle reports
    it, nothing raises, and survivors complete normally."""
    cfg, mesh, params = _fixtures()
    with mesh:
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=64, batch=1, prefill_chunk=4,
                        page_size=8, num_pages=16, shed_queue_depth=2),
            params,
        )
        handles = [
            sched.submit(list(range(4 + 3 * i, 10 + 3 * i)), request_id=i,
                         max_new=3, priority=(1 if i == 2 else 0))
            for i in range(4)
        ]
        sched.drain()
    shed_ids = [r["id"] for r in sched.shed]
    assert sched.stats["shed"] == len(shed_ids) > 0
    # the priority-1 arrival must never be the one shed
    assert 2 not in shed_ids
    for h in handles:
        assert h.done
        if h.request_id in shed_ids:
            assert h.status == "shed" and h.tokens == []
        else:
            assert h.status == "done" and len(h.tokens) == 3
    assert sched._alloc.used == 0


def test_checksum_catches_bitflip_and_evicts_subtree():
    """A silent bit flip in a trie-cached page stays finite — only the
    per-page checksum at prefix-share time can catch it. The corrupted
    subtree is evicted, the request re-prefills from scratch, and its
    stream matches the donor's bit-for-bit."""
    cfg, mesh, params = _fixtures()
    prompt = list(range(4, 22))  # 2 full pages land in the trie
    events = [FaultEvent(kind="page_corrupt", tick=40)]
    injector = FaultInjector(FaultConfig(corrupt_mode="bitflip"),
                             events=events)
    with mesh:
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=64, batch=2, prefill_chunk=4,
                        page_size=8, num_pages=16, prefix_cache=True,
                        checksum_pages=True),
            params, fault_injector=injector,
        )
        first = sched.submit(prompt, request_id="a", max_new=4).result()
        # idle past the event tick: the corruption lands on a page only the
        # trie still pins (finite garbage, invisible to the NaN sentinel)
        while not injector.exhausted:
            sched.step()
        assert injector.counters["pages_corrupted"] == 1
        second = sched.submit(prompt, request_id="b", max_new=4).result()
        sched.drain()
    assert sched.stats["checksum_failures"] >= 1
    assert second == first, "post-eviction re-prefill changed the stream"
    assert sched._alloc.used - sched._prefix.size == 0


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "zamba2-2.7b"])
def test_fault_isolation_coresident(arch):
    """Satellite isolation on attention-only AND hybrid stacks: NaN poison
    plus a NaN page corruption pinned to one request of a full batch leave
    the neighbor's stream bitwise unchanged, with zero leaks."""
    cfg, mesh, params = _fixtures(arch)
    base, _ = _chaos_run(cfg, mesh, params, max_new=5)
    events = [FaultEvent(kind="nan", tick=5, request_id=0),
              FaultEvent(kind="page_corrupt", tick=7, request_id=0)]
    chaos, inj = _chaos_run(cfg, mesh, params, events=events, max_new=5)
    assert inj.counters["nan_injected"] == 1
    # a pinned page corruption needs an unshared page of request 0's slot;
    # it may defer off the run's end on some grids, but must never touch
    # the neighbor when it lands
    assert _tokens(chaos) == _tokens(base)
    assert chaos._alloc.used == 0, "pages leaked under co-resident faults"
