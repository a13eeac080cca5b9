"""Batched serving demo: continuous batching over a paged KV cache with
chunked prefill-on-attach overlapped with in-flight decode + TALP
monitoring of the serving loop through ``repro.session``, emitting a run
record suitable for the same CI report as training runs.

    PYTHONPATH=src python examples/serve_batch.py
    PYTHONPATH=src python examples/serve_batch.py --shared-prefix
        # cross-request prefix cache: requests share a system prompt whose
        # KV pages are prefilled once and mapped into every later request's
        # block table (copy-on-write at the divergence point); the demo
        # prints pages saved and prefill tokens skipped
    PYTHONPATH=src python examples/serve_batch.py --traffic
        # open-loop bursty traffic against a deliberately tight page pool:
        # arrivals queue, the pool exhausts, victims preempt and
        # recompute-resume (bitwise identically), some clients hang up
        # mid-stream — the demo prints goodput, TTFT percentiles and the
        # scheduler's pressure counters
    PYTHONPATH=src python examples/serve_batch.py --chaos
        # the traffic run under a seeded fault schedule: NaN logits, a
        # corrupted KV page, an allocator spike and a hung dispatch land
        # mid-run; victims retry through recompute-resume (their streams
        # stay bitwise identical), the watchdog trips on the hang, and
        # the demo prints the recovery counters next to goodput
    PYTHONPATH=src python examples/serve_batch.py --spec
        # speculative decoding A/B on a repetitive workload: an n-gram
        # drafter proposes up to spec_k tokens from each request's own
        # history and one batched verify dispatch scores them all — the
        # demo runs the same trace spec on and off and prints the
        # acceptance rate, dispatches saved, and bitwise token identity

The scheduler keeps attention KV in a shared pool of ``page_size``-token
pages addressed through per-slot block tables — ``num_pages`` below sizes
the pool to this workload's concurrent-token peak, well under the
``batch x max_len`` a slot-major cache would hold, and the demo prints the
pool accounting to show it.

The scheduler takes the session directly — every decode dispatch is a visit
of its ``decode`` region and every prefill chunk a visit of its ``prefill``
region, each with its own StepProfile derived from the compiled step by
``session.wrap_step``, so the report tracks prefill and decode factors
separately. No code edits needed to re-plug it: ``TALP_ENABLE=1
TALP_BACKEND=tracer`` swaps the collector, ``TALP_ENABLE=0`` turns the
whole thing off.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

import repro
from repro import compat
from repro.configs import smoke_config
from repro.core import ResourceConfig
from repro.launch.mesh import make_host_mesh
from repro.layers.common import init_params
from repro.models import transformer as T
from repro.serve.serve import BatchScheduler, ServeConfig


def main():
    shared_prefix = "--shared-prefix" in sys.argv[1:]
    traffic = "--traffic" in sys.argv[1:]
    chaos = "--chaos" in sys.argv[1:]
    spec = "--spec" in sys.argv[1:]
    if traffic or chaos:
        return main_traffic(chaos=chaos)
    if spec:
        return main_spec()
    cfg = smoke_config("tinyllama-1.1b")
    mesh = make_host_mesh()
    params = init_params(T.model_params(cfg), jax.random.PRNGKey(0),
                         cfg.param_dtype)
    session = repro.start(
        "serve", backend="monitor", lb_sample_every=1,
        resources=ResourceConfig(num_hosts=1,
                                 devices_per_host=len(jax.devices())),
    )

    rng = np.random.default_rng(0)
    with compat.use_mesh(mesh), session:
        sched = BatchScheduler(
            cfg, mesh,
            # pool sized to the workload: 4 slots x ceil((10+8)/16) pages,
            # vs 4 x 128/16 = 32 pages for every slot at max_len (the
            # shared-prefix run carries 48 extra prompt tokens per request,
            # shared after the first — plus the trie's pinned copy)
            ServeConfig(max_len=128, batch=4, prefill_chunk=16,
                        page_size=16, num_pages=16 if shared_prefix else 8,
                        prefix_cache=shared_prefix),
            params, session=session,
        )
        # --shared-prefix: one 48-token system prompt, divergent user tails
        system = (rng.integers(4, cfg.vocab, size=48).tolist()
                  if shared_prefix else [])
        for rid in range(10):
            prompt = system + rng.integers(4, cfg.vocab,
                                           size=rng.integers(3, 10)).tolist()
            sched.submit(prompt, request_id=rid, max_new=8)
        steps = 0
        while len(sched.completed) < 10 and steps < 200:
            sched.step()
            steps += 1
        sched.drain()  # finish partial prefills + flush deferred readbacks

    run = session.finalize("results/serve_batch")
    print(f"completed {len(sched.completed)} requests in {steps} ticks "
          f"({sched.stats['decode_steps']} decode steps, "
          f"{sched.stats['prefill_chunks']} prefill chunks)")
    kv = sched.kv_cache_stats()
    print(f"paged KV pool: {kv['kv_bytes']} bytes "
          f"({kv['num_pages']} pages x {kv['page_size']} tokens), "
          f"peak {kv['peak_used_pages']} pages live, "
          f"utilization {kv['pool_utilization']}")
    if "prefix_cache" in kv:
        pc = kv["prefix_cache"]
        print(f"prefix cache: {pc['pages_saved_by_sharing']} pages saved "
              f"by sharing, {pc['prefill_tokens_skipped']} prefill tokens "
              f"skipped, hit rate {pc['hit_rate']} "
              f"({pc['cow_copies']} copy-on-write pages)")
    for req in sched.completed[:3]:
        print(f"  request {req['id']}: generated {req['generated']}")
    if run is None:
        print("monitoring disabled by environment; no run record")
        return
    for name in ("prefill", "decode"):
        reg = run.regions[name]
        print(f"{name} region: {reg.measurements.num_steps} steps, "
              f"dispatch efficiency {reg.pop.get('dispatch_efficiency', 0):.3f}")
    print(f"run record: {session.last_record_path}")


def main_traffic(chaos: bool = False):
    """Open-loop bursty load against a pool sized well under the demand
    peak: admission queueing, preemption + recompute-resume, and
    mid-stream cancellations, measured the way BENCH_serve.json reports
    them. With ``chaos`` a seeded fault schedule rides the same run and
    the scheduler must recover through retry/quarantine."""
    from repro.serve.traffic import TrafficConfig, generate_workload, replay

    cfg = smoke_config("tinyllama-1.1b")
    mesh = make_host_mesh()
    params = init_params(T.model_params(cfg), jax.random.PRNGKey(0),
                         cfg.param_dtype)
    session = repro.start(
        "serve-traffic", backend="monitor", lb_sample_every=1,
        resources=ResourceConfig(num_hosts=1,
                                 devices_per_host=len(jax.devices())),
    )
    workload = generate_workload(TrafficConfig(
        n_requests=12, seed=0, arrival="burst", rate=0.8, burst_mult=5.0,
        prompt_short=(4, 10), prompt_long=(12, 20), max_new_short=(4, 8),
        max_new_long=(8, 12), cancel_frac=0.0 if chaos else 0.2,
        vocab_hi=cfg.vocab,
    ))
    injector = None
    if chaos:
        from repro.serve.faults import FaultConfig, FaultInjector

        injector = FaultInjector(FaultConfig(seed=3, horizon_ticks=24,
                                             hang_s=0.2))
    with compat.use_mesh(mesh), session:
        sched = BatchScheduler(
            cfg, mesh,
            # 2 slots x 3 pages: bursts must queue, long requests must
            # preempt — graceful degradation instead of a RuntimeError
            ServeConfig(max_len=64, batch=2, prefill_chunk=8,
                        page_size=8, num_pages=6,
                        watchdog_deadline_s=0.05 if chaos else None),
            params, session=session,
        )
        m = replay(sched, workload, faults=injector)
    session.finalize("results/serve_traffic")
    print(f"bursty traffic: {m['completed']} completed, "
          f"{m['cancelled']} cancelled, {m['failed']} failed "
          f"of {m['requests']} in {m['ticks']} ticks")
    print(f"goodput {m['goodput_tokens_per_sec']} tok/s "
          f"({m['good_tokens']} tokens); TTFT p50/p95/p99 "
          f"{m['ttft_p50_s']}/{m['ttft_p95_s']}/{m['ttft_p99_s']} s; "
          f"queue depth peak {m['queue_depth_peak']}")
    print(f"pressure: {m['preemptions']} preemptions, {m['resumes']} "
          f"resumes, {m['cancellations']} cancellations "
          f"({m['kv']['pressure']['pages_freed_by_preempt']} pages freed "
          f"by preempt)")
    if chaos:
        rec = m["recovery"]
        print(f"chaos: injected {rec['injected']}; recovered with "
              f"{rec['retries']} retries ({rec['backoff_total_ticks']} "
              f"backoff ticks), {rec['watchdog_trips']} watchdog trips, "
              f"{rec['quarantined']} quarantined, {rec['shed']} shed")


def main_spec():
    """Speculative-decode A/B on a workload the drafter can actually
    predict: residual-zeroed "copy regime" weights make greedy decode a
    pure function of the last token, so generation cycles and the n-gram
    drafter locks on — the same trick ``benchmarks/serve_throughput.py``
    uses for its deterministic speedup gate. Random-weight generations
    are aperiodic; on those the drafter proposes nothing and speculation
    degrades gracefully to sequential decode (still bitwise identical)."""
    cfg = smoke_config("tinyllama-1.1b")
    mesh = make_host_mesh()
    params = init_params(T.model_params(cfg), jax.random.PRNGKey(0),
                         cfg.param_dtype)
    params = dict(params, slots=jax.tree_util.tree_map(
        lambda x: x * 0.0, params["slots"]))
    pat = [5, 9, 13, 7]
    prompts = [pat * 4, pat * 6, [2, 3] + pat * 5]

    def run(spec_on):
        with compat.use_mesh(mesh):
            sched = BatchScheduler(
                cfg, mesh,
                ServeConfig(max_len=256, batch=4, prefill_chunk=16,
                            page_size=16, num_pages=44,
                            spec_decode=spec_on, spec_k=4),
                params,
            )
            for rid, p in enumerate(prompts):
                sched.submit(p, request_id=rid, max_new=64)
            sched.drain()
        return sched

    plain, spec = run(False), run(True)
    toks = lambda s: {r["id"]: r["generated"] for r in s.completed}
    sp = spec.kv_cache_stats()["speculation"]
    print(f"plain decode: {plain.stats['decode_steps']} dispatches for "
          f"{sum(len(g) for g in toks(plain).values())} tokens")
    print(f"speculative:  {spec.stats['decode_steps']} dispatches "
          f"({sp['tokens_per_dispatch']} tokens/dispatch, acceptance rate "
          f"{sp['acceptance_rate']}, mean accepted len "
          f"{sp['mean_accepted_len']})")
    print(f"tokens bitwise identical: {toks(spec) == toks(plain)}")


if __name__ == "__main__":
    main()
