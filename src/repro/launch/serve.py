"""Serving launcher: continuous-batching decode over a paged KV cache,
chunked prefill-on-attach overlapped with in-flight decode, optional
temperature/top-k sampling, and monitoring of both phases.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --smoke --requests 8 --max-new 8 --prefill-chunk 16 \
        --page-size 16 --talp-out talp/serve

``--arrival poisson|burst`` swaps the fixed trace for the open-loop
traffic harness (seeded arrivals, mixed lengths, priority classes,
``--cancel-frac`` mid-stream cancellations) and reports goodput, TTFT
percentiles and queue depth; ``--preempt-policy`` picks the victim order
when the page pool exhausts (preempted requests park and recompute-resume
bitwise identically):

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --smoke --arrival burst --rate 0.8 --requests 16 \
        --num-pages 8 --page-size 8 --cancel-frac 0.2

``--chaos`` attaches the seeded fault injector (``--fault-seed``): NaN
logits, KV-page corruption, allocator spikes and hung dispatches land
mid-run and the scheduler retries/quarantines through them, reporting
the recovery counters next to the pressure stats:

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --smoke --arrival burst --requests 12 --chaos --fault-seed 3 \
        --watchdog-deadline 0.1 --checksum-pages

``--spec`` turns on speculative decoding: an n-gram
drafter proposes up to ``--spec-k`` tokens from each request's own
history and one batched verify dispatch scores them all, emitting every
accepted token — bitwise identical to plain decode, with the acceptance
rate reported next to the KV stats:

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --smoke --spec --spec-k 4 --max-new 32
"""

from __future__ import annotations

import argparse


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prefill token budget per scheduler tick")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="retire requests early on this token id")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (must divide --max-len)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="KV pool size in pages (default: every slot at "
                         "--max-len; size to the expected concurrent-token "
                         "peak for the memory win)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="cross-request prefix sharing: map previously "
                         "prefilled prompt pages into new requests' block "
                         "tables (copy-on-write at the divergence point)")
    ap.add_argument("--prefix-trie-capacity", type=int, default=None,
                    help="max pages the prefix trie may pin (LRU-trimmed); "
                         "default: unbounded (pool pressure still evicts)")
    ap.add_argument("--arrival", choices=("poisson", "burst"), default=None,
                    help="open-loop traffic instead of the fixed trace: "
                         "Poisson or Markov-modulated bursty arrivals from "
                         "the seeded repro.serve.traffic harness (mixed "
                         "lengths, priority classes, mid-stream cancels)")
    ap.add_argument("--rate", type=float, default=0.5,
                    help="mean arrivals per scheduler tick (calm state)")
    ap.add_argument("--cancel-frac", type=float, default=0.0,
                    help="fraction of traffic requests that cancel "
                         "mid-stream at a scheduled tick")
    ap.add_argument("--preempt-policy", default="priority",
                    choices=("priority", "pages", "progress", "never"),
                    help="victim selection when the page pool exhausts: "
                         "lowest-priority-first (default), most-pages, "
                         "least-progress, or never (exhaustion raises)")
    ap.add_argument("--chaos", action="store_true",
                    help="attach the seeded fault injector (NaN logits, "
                         "KV-page corruption, allocator spikes, hung "
                         "dispatches) — the scheduler must recover")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="FaultConfig seed: same seed, same fault schedule")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="fault retries per request before quarantine")
    ap.add_argument("--watchdog-deadline", type=float, default=None,
                    help="per-dispatch watchdog deadline in seconds "
                         "(default: off; --chaos defaults it to 0.5)")
    ap.add_argument("--checksum-pages", action="store_true",
                    help="per-page fingerprints validated at prefix-cache "
                         "sharing (catches silent bit flips)")
    ap.add_argument("--shed-queue-depth", type=int, default=None,
                    help="admission queue depth beyond which new lowest-"
                         "priority requests are shed (default: never)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative decoding: n-gram self-drafting + one "
                         "batched verify dispatch per tick (tokens stay "
                         "bitwise identical to plain decode)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens proposed per verify dispatch")
    ap.add_argument("--spec-min-match", type=int, default=2,
                    help="shortest history n-gram the drafter may match on")
    ap.add_argument("--sample", action="store_true",
                    help="temperature/top-k sampling instead of greedy argmax")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--sample-seed", type=int, default=0)
    ap.add_argument("--talp-out", default="")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from repro import compat
    from repro.configs import get_config, smoke_config
    from repro.core import ResourceConfig
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.layers.common import init_params
    from repro.models import transformer as T
    from repro.serve.serve import BatchScheduler, ServeConfig
    from repro.session import PerfSession, SessionConfig

    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.encoder_only:
        raise SystemExit(f"{args.arch} is encoder-only: no decode serving")
    mesh = make_host_mesh()
    params = init_params(T.model_params(cfg), jax.random.PRNGKey(0),
                         cfg.param_dtype)
    session = PerfSession(
        SessionConfig(app_name=f"serve-{args.arch}", backend="monitor",
                      lb_sample_every=1),
        ResourceConfig(num_hosts=1, devices_per_host=len(jax.devices())),
    )
    rng = np.random.default_rng(0)
    injector = None
    watchdog = args.watchdog_deadline
    if args.chaos:
        from repro.serve.faults import FaultConfig, FaultInjector

        injector = FaultInjector(FaultConfig(seed=args.fault_seed))
        if watchdog is None:
            watchdog = 0.5
    with compat.use_mesh(mesh), session:
        sched = BatchScheduler(
            cfg, mesh,
            ServeConfig(max_len=args.max_len, batch=args.batch,
                        prefill_chunk=args.prefill_chunk,
                        eos_id=args.eos_id, page_size=args.page_size,
                        num_pages=args.num_pages,
                        prefix_cache=args.prefix_cache,
                        prefix_trie_capacity=args.prefix_trie_capacity,
                        greedy=not args.sample,
                        temperature=args.temperature, top_k=args.top_k,
                        sample_seed=args.sample_seed,
                        preempt_policy=args.preempt_policy,
                        max_retries=args.max_retries,
                        watchdog_deadline_s=watchdog,
                        checksum_pages=args.checksum_pages,
                        shed_queue_depth=args.shed_queue_depth,
                        spec_decode=args.spec, spec_k=args.spec_k,
                        spec_min_match=args.spec_min_match),
            params, session=session, fault_injector=injector,
        )
        if args.arrival:
            # open-loop traffic: arrivals, lengths, priorities and cancels
            # are a pure function of the seeded TrafficConfig
            from repro.serve.traffic import (TrafficConfig, generate_workload,
                                             replay)

            workload = generate_workload(TrafficConfig(
                n_requests=args.requests, arrival=args.arrival,
                rate=args.rate, cancel_frac=args.cancel_frac,
                vocab_hi=cfg.vocab,
            ))
            metrics = replay(sched, workload)
            steps = metrics["ticks"]
        else:
            metrics = None
            # with prefix sharing on, give requests something to share: a
            # common system prompt spanning several pages, divergent tails
            system = (
                rng.integers(4, cfg.vocab,
                             size=min(4 * args.page_size, args.max_len // 2)).tolist()
                if args.prefix_cache else []
            )
            for rid in range(args.requests):
                prompt = system + rng.integers(4, cfg.vocab,
                                               size=rng.integers(3, 10)).tolist()
                sched.submit(prompt, request_id=rid, max_new=args.max_new)
            steps = 0
            while len(sched.completed) < args.requests and steps < 10 * args.max_len:
                sched.step()
                steps += 1
            sched.drain()
    print(f"[serve] completed {len(sched.completed)}/{args.requests} requests "
          f"in {steps} ticks ({sched.stats['decode_steps']} decode steps, "
          f"{sched.stats['prefill_chunks']} prefill chunks)")
    if metrics is not None:
        print(f"[serve] traffic ({args.arrival}): "
              f"goodput {metrics['goodput_tokens_per_sec']} tok/s "
              f"({metrics['good_tokens']} tokens), "
              f"{metrics['cancelled']} cancelled, {metrics['failed']} failed; "
              f"TTFT p50/p95/p99 {metrics['ttft_p50_s']}/"
              f"{metrics['ttft_p95_s']}/{metrics['ttft_p99_s']} s; "
              f"queue depth peak {metrics['queue_depth_peak']} "
              f"(mean {metrics['queue_depth_mean']})")
    kv = sched.kv_cache_stats()
    print(f"[serve] paged KV: {kv['kv_bytes']} pool bytes, "
          f"{kv['num_pages']} pages x {kv['page_size']} tokens, "
          f"peak {kv['peak_used_pages']} pages in use "
          f"(utilization {kv['pool_utilization']})")
    if "prefix_cache" in kv:
        pc = kv["prefix_cache"]
        print(f"[serve] prefix cache: hit rate {pc['hit_rate']} "
              f"({pc['hits']}/{pc['hits'] + pc['misses']} attaches), "
              f"{pc['prefill_tokens_skipped']} prefill tokens skipped, "
              f"{pc['pages_saved_by_sharing']} pages saved by sharing, "
              f"{pc['cow_copies']} copy-on-write pages, "
              f"{pc['trie_pages']} pages cached "
              f"({pc['evicted_pages']} evicted)")
    pr = kv["pressure"]
    print(f"[serve] pressure: {pr['preemptions']} preemptions "
          f"({pr['pages_freed_by_preempt']} pages freed), "
          f"{pr['resumes']} resumes, "
          f"{pr['evictions_for_preempt']} trie evictions for preempt, "
          f"{pr['cancellations']} cancellations, "
          f"peak queue depth {pr['peak_queue_depth']}")
    if args.spec:
        sp = kv["speculation"]
        print(f"[serve] speculation: acceptance rate {sp['acceptance_rate']} "
              f"({sp['accepted']}/{sp['drafted']} drafts), "
              f"{sp['tokens_per_dispatch']} tokens/dispatch over "
              f"{sp['verify_dispatches']} verify dispatches "
              f"(mean accepted len {sp['mean_accepted_len']})")
    rec = kv["recovery"]
    print(f"[serve] recovery: {rec['retries']} retries "
          f"({rec['backoff_total_ticks']} backoff ticks), "
          f"{rec['quarantined']} quarantined, {rec['shed']} shed, "
          f"{rec['watchdog_trips']} watchdog trips, "
          f"{rec['checksum_failures']} checksum failures"
          + (f"; injected {rec['injected']}" if "injected" in rec else ""))
    session.finalize(args.talp_out or None)
    if session.last_record_path:
        print(f"[serve] TALP record: {session.last_record_path}")
    elif args.talp_out:
        print("[serve] monitoring disabled by environment; no run record")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
