"""Serving throughput of ``serve.BatchScheduler`` on a request trace.

Runs a staggered request trace (half the requests arrive while the first
half decodes) through the scheduler's one path — paged KV pool, prefill
chunks overlapped with decode — and reports:

  tokens/sec            end-to-end generated-token throughput
  ttft                  time from submit to the first-token dispatch
                        (prefill completion), per request
  decode max gap        longest wall-clock gap between consecutive decode
                        dispatches while a prefill was in flight
  KV bytes              the paged pool's footprint (sized to the workload's
                        concurrent-token peak), its live-page peak and
                        utilization
  overlap guarantee     scheduler-level invariant: every tick with an
                        in-flight prefill and >=1 decoding slot also
                        dispatched a decode (no gap > one tick)

A second section runs the shared-prefix workload (one long system prompt,
divergent tails) with the cross-request prefix cache on vs off on the SAME
warm-first schedule, reporting TTFT, tokens/sec, peak live pages and the
prefill chunks the trie hits skipped — plus bitwise token identity between
the two sides.

Emits ``BENCH_serve.json`` (default ``results/BENCH_serve.json``) so the
repo carries a serve-path trajectory next to the TALP records; the
``--check`` shape in ``benchmarks/run.py`` runs the tiny variant and
asserts the overlap guarantee, and that prefix sharing keeps tokens
bitwise identical (greedy AND sampled) while strictly lowering peak live
pages and skipping prefill chunks.

A third section (``"traffic"``) replays the open-loop harness from
``repro.serve.traffic`` — the same seeded workload under Poisson and
Markov-modulated bursty arrivals against a deliberately tight page pool —
and reports goodput, p50/p95/p99 TTFT, queue depth and the scheduler's
preemption/resume/cancellation counters; ``--check`` additionally forces
a preemption (tiny pool vs ample pool) and asserts the recompute-resume
token streams are bitwise identical, greedy AND sampled, with zero pages
leaked after drain.

A fourth section (``--chaos`` / ``run_chaos``) is the fault-injection
soak: the same seeded burst workload replayed fault-free and under a
seeded ``repro.serve.faults`` schedule (NaN logits, page corruption,
allocator spikes, dispatch hangs), reporting the recovery counters,
goodput retention and completed-token identity between the two runs;
``--check`` gates fault-recovery token identity (greedy AND sampled,
every fault kind injected at least once), quarantine-works (a request
whose faults exhaust ``max_retries`` ends terminal ``failed`` while its
neighbors stay bitwise intact) and zero pages leaked after drain.

A fifth section (``"speculation"`` / ``run_spec`` / ``--spec``) is the
speculative-decode A/B: the same trace with ``spec_decode`` on vs off, on
a repetitive trace (residual-zeroed "copy regime" weights whose greedy
decode provably cycles — the prompt-lookup drafter's home turf) and a
non-repetitive trace (random weights and prompts, where the drafter
proposes little and speculation must degrade gracefully to sequential
decode). Reports bitwise token identity, tokens/sec speedup, TTFT,
acceptance rate, dispatches saved, and pages leaked after drain;
``--check`` gates identity on both traces in greedy AND sampled modes, a
STRICT tokens/sec speedup plus acceptance_rate > 0 on the repetitive
trace, and zero leaked pages.

    PYTHONPATH=src:. python benchmarks/serve_throughput.py [arch ...]
    PYTHONPATH=src:. python benchmarks/serve_throughput.py --traffic [arch ...]
    PYTHONPATH=src:. python benchmarks/serve_throughput.py --chaos [arch ...]
    PYTHONPATH=src:. python benchmarks/serve_throughput.py --spec [arch ...]

With archs given (the nightly sweep), the first writes BENCH_serve.json
and each additional arch writes BENCH_serve_<arch>.json; ``--traffic``
writes ``BENCH_serve_traffic_<arch>.json`` per arch, ``--chaos`` writes
``BENCH_serve_chaos_<arch>.json`` per arch and ``--spec`` writes
``BENCH_serve_spec_<arch>.json`` per arch.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

from benchmarks.common import RESULTS_DIR, csv_line


import functools


@functools.lru_cache(maxsize=4)
def _build(cfg_name: str = "tinyllama-1.1b"):
    import jax

    from repro.configs import smoke_config
    from repro.launch.mesh import make_host_mesh
    from repro.layers.common import init_params
    from repro.models import transformer as T

    cfg = smoke_config(cfg_name)
    mesh = make_host_mesh(devices=jax.devices()[:1])
    params = init_params(T.model_params(cfg), jax.random.PRNGKey(0),
                         cfg.param_dtype)
    return cfg, mesh, params


def _request_trace(cfg, n_requests: int, seed: int = 0):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(4, cfg.vocab, size=int(n)).tolist()
            for n in rng.integers(8, 24, size=n_requests)]


def run_mode(cfg, mesh, params, prompts, *, max_new: int,
             batch: int, prefill_chunk: int, max_len: int = 128,
             page_size: int = 16,
             num_pages: int | None = None, prefix_cache: bool = False,
             greedy: bool = True, temperature: float = 1.0,
             top_k: int | None = None, sample_seed: int = 0,
             spec_decode: bool = False, spec_k: int = 4,
             spec_min_match: int = 2, warm_first: bool = False) -> dict:
    """One scheduler pass; returns the measured dict for BENCH_serve.json.

    ``warm_first`` runs ``prompts[0]`` to completion before the rest are
    submitted — the shared-prefix A/B schedule: the first request warms
    the prefix trie, then the wave attaches against it (the no-sharing
    pass runs the SAME schedule so the comparison is honest)."""
    from repro import compat
    from repro.serve.serve import BatchScheduler, ServeConfig

    with compat.use_mesh(mesh):
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=max_len, batch=batch,
                        prefill_chunk=prefill_chunk, page_size=page_size,
                        num_pages=num_pages, prefix_cache=prefix_cache,
                        greedy=greedy, temperature=temperature, top_k=top_k,
                        sample_seed=sample_seed, spec_decode=spec_decode,
                        spec_k=spec_k, spec_min_match=spec_min_match),
            params,
        )
        if warm_first:
            first, late = prompts[:1], prompts[1:]
        else:
            # stagger: half the requests arrive while the first half decodes,
            # so prefill-on-attach genuinely competes with in-flight decode
            half = max(1, len(prompts) // 2)
            first, late = prompts[:half], prompts[half:]
        t0 = time.perf_counter()
        submit_t: dict = {}
        for rid, p in enumerate(first):
            sched.submit(p, request_id=rid, max_new=max_new)
            submit_t[rid] = time.perf_counter()
        decode_times: list[float] = []
        gaps_during_prefill: list[float] = []
        ttft: dict = {}
        ticks = 0
        injected = False
        while len(sched.completed) < len(prompts) and ticks < 50 * max_new:
            inject_due = (len(sched.completed) >= len(first)) if warm_first \
                else (ticks >= 2)
            if not injected and inject_due:
                for rid, p in enumerate(late, start=len(first)):
                    sched.submit(p, request_id=rid, max_new=max_new)
                    submit_t[rid] = time.perf_counter()
                injected = True
            prefill_inflight = bool(sched._prefills)
            decodes_before = sched.stats["decode_steps"]
            sched.step()
            now = time.perf_counter()
            if sched.stats["decode_steps"] > decodes_before:
                if decode_times and prefill_inflight:
                    gaps_during_prefill.append(now - decode_times[-1])
                decode_times.append(now)
            for slot, req in enumerate(sched.active):
                if req is not None and req["id"] not in ttft:
                    # first-token dispatch: the request just finished prefill
                    ttft[req["id"]] = now - submit_t[req["id"]]
            ticks += 1
        sched.drain()
        wall = time.perf_counter() - t0
    tokens = sum(len(r["generated"]) for r in sched.completed)
    return {
        "requests": len(prompts),
        "completed": len(sched.completed),
        "ticks": ticks,
        "wall_s": round(wall, 4),
        "tokens": tokens,
        "tokens_per_sec": round(tokens / max(wall, 1e-9), 2),
        "ttft_mean_s": round(sum(ttft.values()) / max(len(ttft), 1), 4),
        "ttft_max_s": round(max(ttft.values(), default=0.0), 4),
        "decode_max_gap_during_prefill_s": round(
            max(gaps_during_prefill, default=0.0), 4
        ),
        # overall stall, whether or not a prefill was in flight
        "decode_max_gap_s": round(
            max((b - a for a, b in zip(decode_times, decode_times[1:])),
                default=0.0), 4
        ),
        "kv": sched.kv_cache_stats(),
        "stats": dict(sched.stats),
        "generated": {str(r["id"]): r["generated"] for r in sched.completed},
    }


def _shared_prefix_trace(cfg, n_requests: int, prefix_len: int,
                         seed: int = 0):
    """N requests sharing a long system prompt, divergent short tails.

    ``prefix_len`` should be a page multiple: the shared pages then skip
    whole prefill chunks on the same chunk grid the cold path uses, which
    keeps the sharing-on/off token identity bitwise even in bf16 (mid-page
    divergence — the copy-on-write path — is exercised at f32 in
    tests/test_serve.py)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    system = rng.integers(4, cfg.vocab, size=prefix_len).tolist()
    return [system + rng.integers(4, cfg.vocab, size=int(n)).tolist()
            for n in rng.integers(3, 11, size=n_requests)]


def run_prefix(cfg, mesh, params, *, n_requests: int, prefix_len: int,
               max_new: int, batch: int, prefill_chunk: int, max_len: int,
               page_size: int, greedy: bool = True, temperature: float = 1.0,
               top_k: int | None = None) -> dict:
    """Shared-prefix workload, sharing on vs off (same warm-first schedule:
    request 0 completes — and, with sharing on, warms the trie — before the
    wave attaches). Returns the A/B with TTFT, tokens/sec and peak live
    pages per side."""
    prompts = _shared_prefix_trace(cfg, n_requests, prefix_len)
    num_pages = _workload_pages(prompts, max_new, batch, page_size)
    kw = dict(max_new=max_new, batch=batch,
              prefill_chunk=prefill_chunk, max_len=max_len,
              page_size=page_size, num_pages=num_pages, warm_first=True,
              greedy=greedy, temperature=temperature, top_k=top_k)
    # warmup: compile both sides' step functions (the prefix_cache=True pair
    # is a distinct jit key — without this the sharing-on pass would pay
    # compilation inside its timed region and the TTFT columns would lie)
    for pc in (True, False):
        run_mode(cfg, mesh, params, prompts[:2], prefix_cache=pc,
                 **{**kw, "max_new": 2})
    on = run_mode(cfg, mesh, params, prompts, prefix_cache=True, **kw)
    off = run_mode(cfg, mesh, params, prompts, prefix_cache=False, **kw)
    gen_on, gen_off = on.pop("generated"), off.pop("generated")
    return {
        "config": {"requests": n_requests, "prefix_len": prefix_len,
                   "max_new": max_new, "batch": batch,
                   "prefill_chunk": prefill_chunk, "max_len": max_len,
                   "page_size": page_size, "num_pages": num_pages,
                   "greedy": greedy},
        # sharing on/off bitwise token identity (a shared page holds exactly
        # the K/V the request would have prefilled itself)
        "identical_tokens": gen_on == gen_off,
        # the memory win: strictly fewer live pages at peak, trie pins and
        # all, because the wave's prefix pages exist once instead of B times
        "peak_pages_below_no_sharing": (
            on["kv"]["peak_used_pages"] < off["kv"]["peak_used_pages"]
        ),
        # the compute win, deterministically (no wall-clock jitter): shared
        # prefix pages skip their prefill chunks outright
        "prefill_chunks_saved": (
            off["stats"]["prefill_chunks"] - on["stats"]["prefill_chunks"]
        ),
        "ttft_mean_speedup": round(
            off["ttft_mean_s"] / max(on["ttft_mean_s"], 1e-9), 3
        ),
        "sharing_on": on,
        "sharing_off": off,
    }


def run_traffic(cfg, mesh, params, *, arrival: str, n_requests: int = 10,
                rate: float = 0.8, batch: int = 2, max_len: int = 64,
                page_size: int = 8, num_pages: int = 6,
                prefill_chunk: int = 4, cancel_frac: float = 0.2,
                preempt_policy: str = "priority", seed: int = 0,
                keep_generated: bool = False) -> dict:
    """One open-loop traffic pass: a seeded workload (Poisson or bursty
    arrivals, mixed lengths, priority classes, scheduled cancellations)
    against a deliberately tight page pool, measured by ``traffic.replay``
    — goodput, TTFT percentiles, queue depth and preemption counts."""
    from repro import compat
    from repro.serve.serve import BatchScheduler, ServeConfig
    from repro.serve.traffic import TrafficConfig, generate_workload, replay

    tcfg = TrafficConfig(
        n_requests=n_requests, seed=seed, arrival=arrival, rate=rate,
        prompt_short=(4, 10), prompt_long=(12, 20), max_new_short=(3, 6),
        max_new_long=(8, 12), cancel_frac=cancel_frac, vocab_hi=cfg.vocab,
    )
    workload = generate_workload(tcfg)
    with compat.use_mesh(mesh):
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=max_len, batch=batch,
                        prefill_chunk=prefill_chunk,
                        page_size=page_size, num_pages=num_pages,
                        preempt_policy=preempt_policy),
            params,
        )
        metrics = replay(sched, workload)
    if not keep_generated:
        metrics.pop("generated", None)
    metrics["arrival"] = arrival
    metrics["config"] = {
        "n_requests": n_requests, "rate": rate, "batch": batch,
        "page_size": page_size, "num_pages": num_pages,
        "cancel_frac": cancel_frac, "preempt_policy": preempt_policy,
        "seed": seed,
    }
    return metrics


def _forced_preempt(cfg, mesh, params, *, num_pages: int,
                    greedy: bool) -> "object":
    """Two 2-page requests through a pool of ``num_pages``: at 3 the
    younger parks itself mid-decode and resumes after the older retires;
    at 16 nothing ever waits. Returns the drained scheduler."""
    from repro import compat
    from repro.serve.serve import BatchScheduler, ServeConfig

    kw = {} if greedy else dict(greedy=False, temperature=0.8, top_k=20,
                                sample_seed=3)
    with compat.use_mesh(mesh):
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=64, batch=2, prefill_chunk=4,
                        page_size=8, num_pages=num_pages, **kw),
            params,
        )
        for rid, p in enumerate([list(range(4, 12)), list(range(20, 28))]):
            sched.submit(p, request_id=rid, max_new=8)
        sched.drain()
    return sched


def _check_preemption(cfg, mesh, params) -> None:
    """The forced-preemption identity gate: preemption + recompute-resume
    must be a pure scheduling decision — tokens bitwise identical to the
    ample-pool run, greedy AND sampled, with real pressure (preemptions
    > 0) and nothing leaked after drain."""
    for greedy in (True, False):
        mode = "greedy" if greedy else "sampled"
        ample = _forced_preempt(cfg, mesh, params, num_pages=16,
                                greedy=greedy)
        tight = _forced_preempt(cfg, mesh, params, num_pages=3,
                                greedy=greedy)
        if tight.stats["preemptions"] <= 0:
            raise AssertionError(
                f"forced-preemption run ({mode}) saw no preemption: "
                f"{tight.kv_cache_stats()['pressure']}"
            )
        toks = lambda s: {r["id"]: r["generated"] for r in s.completed}
        if toks(tight) != toks(ample):
            raise AssertionError(
                f"preempt-resume changed tokens vs ample pool ({mode}): "
                f"{toks(tight)} vs {toks(ample)}"
            )
        if tight._alloc.used != 0:
            raise AssertionError(
                f"allocator leaked {tight._alloc.used} pages across "
                f"preempt/resume ({mode})"
            )


def _leaked_pages(sched) -> int:
    """Pages still allocated after drain beyond the prefix trie's own pins
    (with ``prefix_cache`` on, trie-pinned pages legitimately survive their
    inserting request — anything else is a leak)."""
    kv = sched.kv_cache_stats()
    pinned = kv.get("prefix_cache", {}).get("trie_pages", 0)
    return sched._alloc.used - pinned


def run_chaos(cfg, mesh, params, *, arrival: str = "burst",
              n_requests: int = 12, seed: int = 0,
              fault_seed: int = 0) -> dict:
    """Goodput-under-faults: the seeded burst workload replayed twice on
    the same scheduler config — fault-free baseline, then under the
    seeded chaos schedule — reporting the recovery counters, the goodput
    retention ratio, and completed-request token identity between the
    two runs (the recovery-correctness signal the nightly soak records).
    No scheduled cancellations: every request must complete in both runs
    so the identity comparison covers the full workload."""
    from repro import compat
    from repro.serve.faults import FaultConfig, FaultInjector
    from repro.serve.serve import BatchScheduler, ServeConfig
    from repro.serve.traffic import TrafficConfig, generate_workload, replay

    tcfg = TrafficConfig(
        n_requests=n_requests, seed=seed, arrival=arrival, rate=0.8,
        prompt_short=(4, 10), prompt_long=(12, 20), max_new_short=(3, 6),
        max_new_long=(8, 12), cancel_frac=0.0, vocab_hi=cfg.vocab,
    )
    workload = generate_workload(tcfg)
    fcfg = FaultConfig(seed=fault_seed, horizon_ticks=24, n_nan=2,
                       n_page_corrupt=1, n_alloc_spike=1, n_hang=1,
                       hang_s=0.2)

    def one(injector):
        with compat.use_mesh(mesh):
            sched = BatchScheduler(
                cfg, mesh,
                ServeConfig(max_len=64, batch=2, prefill_chunk=4,
                            page_size=8, num_pages=10,
                            prefix_cache=True, watchdog_deadline_s=0.05),
                params,
            )
            metrics = replay(sched, workload, faults=injector)
        return metrics, sched

    base_m, base_s = one(None)
    injector = FaultInjector(fcfg)
    chaos_m, chaos_s = one(injector)
    gen_b, gen_c = base_m.pop("generated"), chaos_m.pop("generated")
    common = set(gen_b) & set(gen_c)
    return {
        "arrival": arrival,
        "fault_config": dataclasses.asdict(fcfg),
        "identical_completed_tokens": (
            set(gen_b) == set(gen_c)
            and all(gen_b[k] == gen_c[k] for k in common)
        ),
        "completed_both": len(common),
        "injected": dict(injector.counters),
        "goodput_retention": round(
            chaos_m["goodput_tokens_per_sec"]
            / max(base_m["goodput_tokens_per_sec"], 1e-9), 3
        ),
        "zero_leak": _leaked_pages(base_s) == 0 and _leaked_pages(chaos_s) == 0,
        "baseline": base_m,
        "chaos": chaos_m,
    }


def _chaos_batch(cfg, mesh, params, *, greedy: bool, fault_cfg=None,
                 fault_events=None, max_new: int = 8):
    """One drained scheduler pass over a fixed 6-request trace, with an
    optional fault schedule; the ``_check_chaos`` building block (small
    direct submits — faster and more controllable than the traffic
    composition, which ``run_chaos`` covers)."""
    from repro import compat
    from repro.serve.faults import FaultInjector
    from repro.serve.serve import BatchScheduler, ServeConfig

    kw = {} if greedy else dict(greedy=False, temperature=0.8, top_k=20,
                                sample_seed=3)
    injector = None
    if fault_cfg is not None or fault_events is not None:
        injector = FaultInjector(fault_cfg, events=fault_events)
    prompts = _request_trace(cfg, 6, seed=5)
    with compat.use_mesh(mesh):
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=64, batch=4, prefill_chunk=4,
                        page_size=8, num_pages=24, prefix_cache=True,
                        watchdog_deadline_s=0.05, **kw),
            params, fault_injector=injector,
        )
        for rid, p in enumerate(prompts):
            sched.submit(p, request_id=rid, max_new=max_new)
        sched.drain()
    return sched, injector


def _check_chaos(cfg, mesh, params) -> None:
    """The fault-recovery identity gate (tiny shape): under a seeded
    schedule injecting every fault kind at least once, each request's
    tokens must be bitwise identical to the fault-free run — greedy AND
    sampled — with zero pages leaked; then a targeted schedule that
    exhausts one request's retries must quarantine exactly that request
    (terminal ``failed``, pages freed) while its co-residents stay
    bitwise intact."""
    from repro.serve.faults import FaultConfig, FaultEvent

    fcfg = FaultConfig(seed=3, horizon_ticks=20, n_nan=2, n_page_corrupt=1,
                       n_alloc_spike=1, n_hang=1, hang_s=0.2)
    toks = lambda s: {r["id"]: r["generated"] for r in s.completed}
    for greedy in (True, False):
        mode = "greedy" if greedy else "sampled"
        base, _ = _chaos_batch(cfg, mesh, params, greedy=greedy)
        chaos, inj = _chaos_batch(cfg, mesh, params, greedy=greedy,
                                  fault_cfg=fcfg)
        for kind in ("nan_injected", "pages_corrupted", "alloc_spikes",
                     "hangs"):
            if inj.counters[kind] < 1:
                raise AssertionError(
                    f"chaos schedule injected no {kind} ({mode}): "
                    f"{inj.counters}"
                )
        rec = chaos.kv_cache_stats()["recovery"]
        if rec["retries"] < 1 or rec["watchdog_trips"] < 1:
            raise AssertionError(
                f"chaos run recovered nothing ({mode}): {rec}"
            )
        if toks(chaos) != toks(base):
            raise AssertionError(
                f"fault recovery changed tokens vs fault-free run "
                f"({mode}): {toks(chaos)} vs {toks(base)}"
            )
        if _leaked_pages(chaos) != 0:
            raise AssertionError(
                f"chaos run leaked {_leaked_pages(chaos)} pages ({mode})"
            )
    # quarantine: more NaN faults pinned to request 0 than max_retries
    # allows -> terminal failed, pages freed, neighbors bitwise intact
    base, _ = _chaos_batch(cfg, mesh, params, greedy=True)
    n_faults = base.scfg.max_retries + 1
    events = [FaultEvent(kind="nan", tick=4 + 3 * i, request_id=0)
              for i in range(n_faults)]
    quar, _ = _chaos_batch(cfg, mesh, params, greedy=True,
                           fault_events=events)
    victims = [r for r in quar.failed if r["id"] == 0]
    if not victims or victims[0]["_status"] != "failed":
        raise AssertionError(
            f"request 0 was not quarantined: failed={quar.failed} "
            f"stats={quar.kv_cache_stats()['recovery']}"
        )
    if quar.stats["quarantined"] != 1:
        raise AssertionError(
            f"expected exactly 1 quarantine: {quar.stats['quarantined']}"
        )
    expect = {k: v for k, v in toks(base).items() if k != 0}
    if toks(quar) != expect:
        raise AssertionError(
            f"quarantine disturbed co-resident streams: {toks(quar)} "
            f"vs {expect}"
        )
    if _leaked_pages(quar) != 0:
        raise AssertionError(
            f"quarantine leaked {_leaked_pages(quar)} pages"
        )


def _workload_pages(prompts, max_new: int, batch: int, page_size: int) -> int:
    """Pool size for the trace: every concurrently-resident request (at most
    ``batch``) fully extended — the honest paged footprint, well below the
    dense ``batch x max_len`` equivalent for mixed-length request sets."""
    need = max(len(p) for p in prompts) + max_new
    return batch * (-(-need // page_size))


def _copy_regime(params):
    """Zero the residual blocks so the logits become a pure function of the
    LAST token (embed -> final norm -> unembed: a near-Markov map over the
    vocab). Greedy decode on such a model must fall into a cycle
    (pigeonhole), which is exactly the workload a prompt-lookup drafter can
    predict — random init weights generate aperiodic continuations no
    n-gram lookup ever matches, and the spec A/B would measure pure
    overhead. The zeroed model runs the exact same jitted step functions
    at the exact same shapes, so the dispatch-count and wall-clock win it
    measures is the real one."""
    import jax

    return dict(params, slots=jax.tree_util.tree_map(
        lambda x: x * 0.0, params["slots"]))


def _spec_repetitive_trace():
    """Prompts built from a repeated 4-gram: the drafter locks on from the
    prompt itself, and the copy-regime model keeps the repetition going."""
    pat = [5, 9, 13, 7]
    return [pat * 4, pat * 6, [2, 3] + pat * 5]


def run_spec(cfg_name: str = "tinyllama-1.1b", *, spec_k: int = 4,
             greedy: bool = True, max_new: int = 160,
             max_new_nonrep: int = 12) -> dict:
    """Speculative-decode A/B: the same trace with ``spec_decode`` on vs
    off, on a repetitive trace (copy-regime weights — the drafter's home
    turf) and a non-repetitive one (random weights + random prompts — the
    drafter proposes little and speculation must degrade gracefully to
    the sequential path). Reports bitwise token identity, tokens/sec
    speedup, TTFT, acceptance rate and pages leaked after drain."""
    cfg, mesh, params = _build(cfg_name)
    kw = dict(batch=4, prefill_chunk=16, max_len=256,
              page_size=16, spec_k=spec_k)
    if not greedy:
        kw.update(greedy=False, temperature=0.8, top_k=20, sample_seed=3)
    traces = {
        "repetitive": (_copy_regime(params), _spec_repetitive_trace(),
                       max_new),
        "non_repetitive": (params, _request_trace(cfg, 3, seed=7),
                           max_new_nonrep),
    }
    out: dict = {"arch": cfg_name, "spec_k": spec_k, "greedy": greedy}
    for name, (ps, prompts, new) in traces.items():
        mkw = dict(kw, max_new=new,
                   num_pages=_workload_pages(prompts, new, kw["batch"],
                                             kw["page_size"]))
        # warmup: spec on/off are distinct jit keys (the verify step only
        # exists on the spec side) — compile both outside the timed passes
        for spec in (False, True):
            run_mode(cfg, mesh, ps, prompts[:2], spec_decode=spec,
                     **{**mkw, "max_new": 2})
        off = run_mode(cfg, mesh, ps, prompts, spec_decode=False, **mkw)
        on = run_mode(cfg, mesh, ps, prompts, spec_decode=True, **mkw)
        gen_on, gen_off = on.pop("generated"), off.pop("generated")
        spec = on["kv"]["speculation"]
        out[name] = {
            # the tentpole guarantee: accepted draft tokens are exactly the
            # tokens sequential decode would have produced
            "identical_tokens": gen_on == gen_off,
            "tokens_per_sec_speedup": round(
                on["tokens_per_sec"] / max(off["tokens_per_sec"], 1e-9), 3),
            # deterministic win (no wall-clock jitter): decode dispatches
            # the accepted drafts made unnecessary
            "dispatches_saved": (off["stats"]["decode_steps"]
                                 - on["stats"]["decode_steps"]),
            "acceptance_rate": spec["acceptance_rate"],
            "mean_accepted_len": spec["mean_accepted_len"],
            "tokens_per_dispatch": spec["tokens_per_dispatch"],
            "leaked_pages_on": on["kv"]["pages_in_use"],
            "leaked_pages_off": off["kv"]["pages_in_use"],
            "spec_on": on,
            "spec_off": off,
        }
    return out


def run(n_requests: int = 6, max_new: int = 16, batch: int = 4,
        prefill_chunk: int = 8, cfg_name: str = "tinyllama-1.1b",
        page_size: int = 16, max_len: int = 128) -> dict:
    cfg, mesh, params = _build(cfg_name)
    prompts = _request_trace(cfg, n_requests)
    num_pages = _workload_pages(prompts, max_new, batch, page_size)
    kw = dict(max_new=max_new, batch=batch, prefill_chunk=prefill_chunk,
              max_len=max_len, page_size=page_size)
    # warmup: compile the decode + prefill traces outside the measured pass
    run_mode(cfg, mesh, params, prompts[:2], num_pages=num_pages,
             **{**kw, "max_new": 2})
    serve = run_mode(cfg, mesh, params, prompts, num_pages=num_pages, **kw)
    # shared-prefix A/B: longest page-aligned system prompt that still leaves
    # room for the divergent tail + generation inside max_len
    prefix_len = max(page_size,
                     ((max_len // 2 - max_new) // page_size) * page_size)
    prefix = run_prefix(cfg, mesh, params, n_requests=n_requests,
                        prefix_len=prefix_len, max_new=max_new, batch=batch,
                        prefill_chunk=prefill_chunk, max_len=max_len,
                        page_size=page_size)
    serve.pop("generated")
    # open-loop traffic: the same seeded workload under memoryless and
    # bursty arrivals, against a pool tight enough that bursts queue and
    # preempt — goodput and TTFT tails are the load-dependent numbers a
    # fixed FIFO trace can never produce
    traffic = {
        arrival: run_traffic(cfg, mesh, params, arrival=arrival)
        for arrival in ("poisson", "burst")
    }
    # speculative decode A/B at its own tuned shape (the strict-speedup
    # comparison needs enough decode steps that dispatch savings dominate)
    speculation = run_spec(cfg_name)
    ostats = serve["stats"]
    return {
        "arch": cfg_name,
        "config": {"requests": n_requests, "max_new": max_new, "batch": batch,
                   "prefill_chunk": prefill_chunk, "max_len": max_len,
                   "page_size": page_size, "num_pages": num_pages},
        # prefill and decode genuinely co-existed (overlap_ticks > 0) and no
        # tick's decode dispatch ever waited behind prefill work — "no
        # decode gap > one tick while a prefill is in progress"
        "overlap_no_decode_gap": (
            ostats["overlap_ticks"] > 0
            and ostats["decode_after_prefill_ticks"] == 0
        ),
        "serve": serve,
        "prefix": prefix,
        "traffic": traffic,
        "speculation": speculation,
    }


def check(out_path: str | None = None) -> str:
    """The cheap CI shape: tiny trace, asserts the acceptance criteria."""
    result = run(n_requests=3, max_new=6, batch=2, prefill_chunk=4,
                 max_len=64)
    if not result["overlap_no_decode_gap"]:
        raise AssertionError(
            "decode gap while a prefill was in flight: "
            f"{result['serve']['stats']}"
        )
    ov = result["serve"]
    prefix = result["prefix"]
    if not prefix["identical_tokens"]:
        raise AssertionError(
            "prefix sharing changed generated tokens vs the cold path (greedy)"
        )
    if not prefix["peak_pages_below_no_sharing"]:
        raise AssertionError(
            "prefix sharing did not reduce peak live pages: "
            f"on={prefix['sharing_on']['kv']['peak_used_pages']} vs "
            f"off={prefix['sharing_off']['kv']['peak_used_pages']}"
        )
    if prefix["prefill_chunks_saved"] <= 0:
        raise AssertionError(
            "prefix sharing skipped no prefill chunks: "
            f"{prefix['prefill_chunks_saved']}"
        )
    # sampling must be sharing-invariant too (per-slot streams are keyed on
    # absolute position, not on how the KV for earlier positions got there)
    cfg, mesh, params = _build()
    sampled = run_prefix(cfg, mesh, params, n_requests=3, prefix_len=16,
                         max_new=6, batch=2, prefill_chunk=4, max_len=64,
                         page_size=16, greedy=False, temperature=0.8, top_k=5)
    if not sampled["identical_tokens"]:
        raise AssertionError(
            "prefix sharing changed sampled tokens (temperature=0.8, top_k=5)"
        )
    # forced-preemption identity (greedy AND sampled) + no-leak gate
    _check_preemption(cfg, mesh, params)
    # fault-recovery identity (greedy AND sampled), every fault kind
    # injected, quarantine-works + no-leak gate
    _check_chaos(cfg, mesh, params)
    # goodput sanity under both arrival processes: the tight pool must
    # degrade gracefully (preempt/queue), never drop or fail a request
    for arrival, m in result["traffic"].items():
        if m["completed"] + m["cancelled"] != m["requests"] or m["failed"]:
            raise AssertionError(
                f"traffic[{arrival}] lost requests: {m['completed']} done + "
                f"{m['cancelled']} cancelled + {m['failed']} failed "
                f"of {m['requests']}"
            )
        if m["good_tokens"] <= 0 or m["goodput_tokens_per_sec"] <= 0:
            raise AssertionError(
                f"traffic[{arrival}] produced no goodput: {m}"
            )
        if not (m["ttft_p50_s"] <= m["ttft_p95_s"] <= m["ttft_p99_s"]):
            raise AssertionError(
                f"traffic[{arrival}] TTFT percentiles inverted: {m}"
            )
    # speculative decode: bitwise identity on BOTH traces, a strict
    # tokens/sec win + real acceptance on the repetitive one, and zero
    # pages leaked after drain with rejections in play
    spec = result["speculation"]
    for name in ("repetitive", "non_repetitive"):
        s = spec[name]
        if not s["identical_tokens"]:
            raise AssertionError(
                f"speculative decode changed tokens on the {name} trace "
                "(greedy)"
            )
        if s["leaked_pages_on"] or s["leaked_pages_off"]:
            raise AssertionError(
                f"speculative {name} run leaked pages: "
                f"on={s['leaked_pages_on']} off={s['leaked_pages_off']}"
            )
    rep = spec["repetitive"]
    if rep["acceptance_rate"] <= 0:
        raise AssertionError(
            "drafter accepted nothing on the repetitive trace: "
            f"{rep['acceptance_rate']}"
        )
    if rep["tokens_per_sec_speedup"] <= 1.0:
        raise AssertionError(
            "speculation did not beat plain decode on the repetitive "
            f"trace: {rep['tokens_per_sec_speedup']}x "
            f"(dispatches_saved={rep['dispatches_saved']})"
        )
    # sampled mode must stay bitwise-invariant too (per-request keys folded
    # at the accepted position == the keys sequential decode would fold);
    # smaller max_new — identity is the gate here, not throughput
    sspec = run_spec(greedy=False, max_new=24, max_new_nonrep=8)
    for name in ("repetitive", "non_repetitive"):
        if not sspec[name]["identical_tokens"]:
            raise AssertionError(
                f"speculative decode changed sampled tokens on the {name} "
                "trace (temperature=0.8, top_k=20)"
            )
    _save(result, out_path)
    return csv_line(
        "check_serve_paged",
        ov["wall_s"] * 1e6 / max(ov["ticks"], 1),
        f"tok/s={ov['tokens_per_sec']};"
        f"pool_util={ov['kv']['pool_utilization']};"
        f"prefix_chunks_saved={prefix['prefill_chunks_saved']};"
        f"traffic_goodput={result['traffic']['burst']['goodput_tokens_per_sec']};"
        f"spec_speedup={rep['tokens_per_sec_speedup']}x;"
        f"spec_accept={rep['acceptance_rate']}",
    )


def _save(result: dict, out_path: str | None = None) -> str:
    path = out_path or os.environ.get(
        "BENCH_SERVE_OUT",
        os.path.join(os.path.dirname(RESULTS_DIR) or "results",
                     "BENCH_serve.json"),
    )
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    return path


def _lines(result: dict, path: str) -> list[str]:
    po = result["serve"]
    pf = result["prefix"]
    pon, poff = pf["sharing_on"], pf["sharing_off"]
    tag = result["arch"]
    return [
        csv_line(f"serve[{tag}]",
                 po["wall_s"] * 1e6 / max(po["ticks"], 1),
                 f"tok/s={po['tokens_per_sec']};ttft={po['ttft_mean_s']}s;"
                 f"kv_bytes={po['kv']['kv_bytes']};"
                 f"pool_util={po['kv']['pool_utilization']};"
                 f"no_decode_gap={result['overlap_no_decode_gap']};"
                 f"json={path}"),
        csv_line(f"serve_prefix_sharing_on[{tag}]",
                 pon["wall_s"] * 1e6 / max(pon["ticks"], 1),
                 f"tok/s={pon['tokens_per_sec']};ttft={pon['ttft_mean_s']}s;"
                 f"peak_pages={pon['kv']['peak_used_pages']}"),
        csv_line(f"serve_prefix_sharing_off[{tag}]",
                 poff["wall_s"] * 1e6 / max(poff["ticks"], 1),
                 f"tok/s={poff['tokens_per_sec']};ttft={poff['ttft_mean_s']}s;"
                 f"peak_pages={poff['kv']['peak_used_pages']}"),
        csv_line(f"serve_prefix_identity[{tag}]", 0.0,
                 f"identical={pf['identical_tokens']};"
                 f"peak_pages_below={pf['peak_pages_below_no_sharing']};"
                 f"prefill_chunks_saved={pf['prefill_chunks_saved']};"
                 f"ttft_speedup={pf['ttft_mean_speedup']}x"),
    ] + _spec_lines(result["speculation"], tag) + [
        csv_line(f"serve_traffic_{arrival}[{tag}]",
                 tr["wall_s"] * 1e6 / max(tr["ticks"], 1),
                 f"goodput={tr['goodput_tokens_per_sec']}tok/s;"
                 f"ttft_p50={tr['ttft_p50_s']}s;ttft_p99={tr['ttft_p99_s']}s;"
                 f"queue_peak={tr['queue_depth_peak']};"
                 f"preempt={tr['preemptions']};resume={tr['resumes']};"
                 f"cancel={tr['cancellations']}")
        for arrival, tr in result["traffic"].items()
    ]


def _spec_lines(spec: dict, tag: str) -> list[str]:
    lines = []
    for name in ("repetitive", "non_repetitive"):
        s = spec[name]
        on = s["spec_on"]
        lines.append(csv_line(
            f"serve_spec_{name}[{tag}]",
            on["wall_s"] * 1e6 / max(on["ticks"], 1),
            f"speedup={s['tokens_per_sec_speedup']}x;"
            f"accept_rate={s['acceptance_rate']};"
            f"tok_per_dispatch={s['tokens_per_dispatch']};"
            f"dispatches_saved={s['dispatches_saved']};"
            f"identical={s['identical_tokens']};"
            f"ttft={on['ttft_mean_s']}s",
        ))
    return lines


def main_spec(archs: list[str] | None = None) -> list[str]:
    """The nightly speculation sweep: per arch, the spec on/off A/B on the
    repetitive and non-repetitive traces, written to
    ``BENCH_serve_spec_<arch>.json`` next to the serve artifacts (the
    Pages assembly globs ``BENCH_serve*.json``, so the speculation
    trajectory rides the existing pipeline)."""
    archs = archs or ["tinyllama-1.1b"]
    lines: list[str] = []
    for arch in archs:
        result = {"arch": arch, "speculation": run_spec(arch)}
        path = _save(result, os.path.join(
            os.path.dirname(RESULTS_DIR) or "results",
            f"BENCH_serve_spec_{arch}.json",
        ))
        lines += _spec_lines(result["speculation"], arch)
        lines.append(csv_line(
            f"serve_spec_json[{arch}]", 0.0, f"json={path}"))
    return lines


def main_traffic(archs: list[str] | None = None) -> list[str]:
    """The nightly traffic sweep: per arch, the open-loop harness under
    Poisson and bursty arrivals (moderate scale, tight pool), written to
    ``BENCH_serve_traffic_<arch>.json`` next to the serve artifacts."""
    archs = archs or ["tinyllama-1.1b"]
    lines: list[str] = []
    for arch in archs:
        cfg, mesh, params = _build(arch)
        result = {
            "arch": arch,
            "traffic": {
                arrival: run_traffic(cfg, mesh, params, arrival=arrival,
                                     n_requests=16, num_pages=8)
                for arrival in ("poisson", "burst")
            },
        }
        path = _save(result, os.path.join(
            os.path.dirname(RESULTS_DIR) or "results",
            f"BENCH_serve_traffic_{arch}.json",
        ))
        lines += [
            csv_line(f"serve_traffic_{arrival}[{arch}]",
                     tr["wall_s"] * 1e6 / max(tr["ticks"], 1),
                     f"goodput={tr['goodput_tokens_per_sec']}tok/s;"
                     f"ttft_p99={tr['ttft_p99_s']}s;"
                     f"preempt={tr['preemptions']};json={path}")
            for arrival, tr in result["traffic"].items()
        ]
    return lines


def main_chaos(archs: list[str] | None = None) -> list[str]:
    """The nightly chaos soak: per arch, the seeded burst workload under
    the seeded fault schedule vs fault-free, written to
    ``BENCH_serve_chaos_<arch>.json`` next to the serve artifacts (the
    Pages assembly globs ``BENCH_serve*.json``, so the robustness
    trajectory rides the existing pipeline)."""
    archs = archs or ["tinyllama-1.1b"]
    lines: list[str] = []
    for arch in archs:
        cfg, mesh, params = _build(arch)
        result = {"arch": arch,
                  "chaos": run_chaos(cfg, mesh, params, arrival="burst")}
        path = _save(result, os.path.join(
            os.path.dirname(RESULTS_DIR) or "results",
            f"BENCH_serve_chaos_{arch}.json",
        ))
        ch = result["chaos"]
        rec = ch["chaos"].get("recovery", {})
        lines.append(csv_line(
            f"serve_chaos_{ch['arrival']}[{arch}]",
            ch["chaos"]["wall_s"] * 1e6 / max(ch["chaos"]["ticks"], 1),
            f"goodput_retention={ch['goodput_retention']};"
            f"identical={ch['identical_completed_tokens']};"
            f"retries={rec.get('retries', 0)};"
            f"quarantined={rec.get('quarantined', 0)};"
            f"watchdog={rec.get('watchdog_trips', 0)};"
            f"zero_leak={ch['zero_leak']};json={path}",
        ))
    return lines


def main(archs: list[str] | None = None) -> list[str]:
    archs = archs or ["tinyllama-1.1b"]
    lines: list[str] = []
    for i, arch in enumerate(archs):
        result = run(cfg_name=arch)
        path = _save(result) if i == 0 else _save(
            result,
            os.path.join(os.path.dirname(RESULTS_DIR) or "results",
                         f"BENCH_serve_{arch}.json"),
        )
        lines += _lines(result, path)
    return lines


if __name__ == "__main__":
    argv = sys.argv[1:]
    print("name,us_per_call,derived")
    if argv and argv[0] == "--traffic":
        for line in main_traffic(argv[1:] or None):
            print(line)
    elif argv and argv[0] == "--chaos":
        for line in main_chaos(argv[1:] or None):
            print(line)
    elif argv and argv[0] == "--spec":
        for line in main_spec(argv[1:] or None):
            print(line)
    else:
        for line in main(argv or None):
            print(line)
