"""Traffic harness: workloads are pure functions of their config, and the
replay driver measures the scheduler without changing what it computes."""

import dataclasses

import jax
import pytest

from repro.configs import smoke_config
from repro.layers.common import init_params
from repro.models import transformer as T
from repro.launch.mesh import make_host_mesh
from repro.serve.serve import BatchScheduler, ServeConfig
from repro.serve.traffic import TrafficConfig, generate_workload, replay


def test_workload_is_pure_function_of_config():
    tcfg = TrafficConfig(n_requests=32, seed=7, arrival="burst",
                         cancel_frac=0.3)
    a, b = generate_workload(tcfg), generate_workload(tcfg)
    assert a == b, "same config must replay the same workload bit-for-bit"
    c = generate_workload(dataclasses.replace(tcfg, seed=8))
    assert a != c, "seed must actually drive the draw"


@pytest.mark.parametrize("arrival", ["poisson", "burst"])
def test_workload_shape(arrival):
    tcfg = TrafficConfig(n_requests=64, seed=3, arrival=arrival,
                         cancel_frac=0.25, priorities=(0, 5),
                         priority_weights=(0.8, 0.2))
    reqs = generate_workload(tcfg)
    assert len(reqs) == 64
    assert [r.request_id for r in reqs] == list(range(64))
    ticks = [r.arrival_tick for r in reqs]
    assert ticks == sorted(ticks)
    assert all(tcfg.prompt_short[0] <= len(r.prompt) <= tcfg.prompt_long[1]
               for r in reqs)
    assert all(tcfg.max_new_short[0] <= r.max_new <= tcfg.max_new_long[1]
               for r in reqs)
    assert {r.priority for r in reqs} <= {0, 5}
    cancels = [r for r in reqs if r.cancel_tick is not None]
    assert cancels, "cancel_frac=0.25 over 64 requests must schedule some"
    assert all(r.cancel_tick > r.arrival_tick for r in cancels)


def test_burst_arrivals_cluster_more_than_poisson():
    """The Markov-modulated process must actually produce bursts: for the
    same mean-ish load, its peak per-tick arrival count exceeds the
    memoryless baseline's (deterministic — both sides are seeded)."""
    def peak(arrival):
        reqs = generate_workload(TrafficConfig(
            n_requests=128, seed=11, arrival=arrival, rate=0.4,
            burst_mult=8.0,
        ))
        counts: dict[int, int] = {}
        for r in reqs:
            counts[r.arrival_tick] = counts.get(r.arrival_tick, 0) + 1
        return max(counts.values())

    assert peak("burst") > peak("poisson")


def test_invalid_configs_rejected():
    with pytest.raises(ValueError, match="arrival"):
        TrafficConfig(arrival="uniform")
    with pytest.raises(ValueError, match="weights"):
        TrafficConfig(priorities=(0, 1), priority_weights=(1.0,))


def test_replay_end_to_end_under_pressure():
    """A bursty workload with cancellations through a deliberately tight
    page pool: every request is accounted for (completed/cancelled), the
    pressure counters surface in the metrics, completed streams match the
    stop-the-world reference, and NOTHING leaks after drain."""
    cfg = smoke_config("tinyllama-1.1b").replace(
        compute_dtype_name="float32", param_dtype_name="float32"
    )
    mesh = make_host_mesh()
    params = init_params(T.model_params(cfg), jax.random.PRNGKey(0),
                         cfg.param_dtype)
    tcfg = TrafficConfig(
        n_requests=6, seed=5, arrival="burst", rate=1.5, burst_mult=4.0,
        prompt_short=(4, 8), prompt_long=(10, 14), max_new_short=(3, 5),
        max_new_long=(6, 8), cancel_frac=0.3, cancel_delay=(2, 6),
        vocab_hi=cfg.vocab,
    )
    workload = generate_workload(tcfg)

    def run(num_pages):
        with mesh:
            sched = BatchScheduler(
                cfg, mesh,
                ServeConfig(max_len=64, batch=2, prefill_chunk=4,
                            page_size=8, num_pages=num_pages), params,
            )
            metrics = replay(sched, workload)
        return sched, metrics

    sched, m = run(num_pages=4)  # 2 slots x 2 pages: pressure guaranteed
    assert m["completed"] + m["cancelled"] + m["failed"] == len(workload)
    assert m["failed"] == 0, "pressure must preempt, not fail"
    assert m["good_tokens"] > 0 and m["goodput_tokens_per_sec"] > 0
    # goodput accounting regression pin: goodput counts COMPLETED streams
    # only; work burned on later-cancelled streams is reported separately
    # as cancelled_tokens, never mixed into good_tokens
    assert m["good_tokens"] == sum(
        len(r["generated"]) for r in sched.completed
    )
    assert m["cancelled_tokens"] == sum(
        len(r["generated"]) for r in sched.cancelled
    )
    assert m["cancelled"] > 0, "workload must actually exercise cancels"
    assert m["ttft_p99_s"] >= m["ttft_p50_s"] >= 0
    assert m["cancellations"] == m["cancelled"]
    assert sched._alloc.used == 0, "pages leaked after drain"
    # the replay itself is deterministic in WHAT it computes (wall-clock
    # metrics aside): a second run generates the same streams
    _, m2 = run(num_pages=4)
    assert m2["generated"] == m["generated"]
    # ...and pool pressure never changes tokens, only timing: an ample run
    # of the same workload completes the same requests with the same bits
    _, ample = run(num_pages=16)
    assert ample["generated"] == m["generated"]


def test_chaos_replay_composes_faults_with_workload():
    """replay(faults=...) attaches the seeded injector: the same
    (TrafficConfig, FaultConfig) pair replays the same streams bit-for-bit,
    the recovery counters surface in the metrics, and recovery never
    changes WHAT a surviving request computed — only when."""
    from repro.serve.faults import FaultConfig, FaultInjector

    cfg = smoke_config("tinyllama-1.1b").replace(
        compute_dtype_name="float32", param_dtype_name="float32"
    )
    mesh = make_host_mesh()
    params = init_params(T.model_params(cfg), jax.random.PRNGKey(0),
                         cfg.param_dtype)
    tcfg = TrafficConfig(
        n_requests=5, seed=9, arrival="burst", rate=1.0,
        prompt_short=(4, 8), prompt_long=(10, 14), max_new_short=(3, 5),
        max_new_long=(6, 8), cancel_frac=0.0, vocab_hi=cfg.vocab,
    )
    workload = generate_workload(tcfg)
    fcfg = FaultConfig(seed=2, horizon_ticks=16, n_nan=1, n_page_corrupt=0,
                       n_alloc_spike=1, n_hang=0)

    def run(chaos):
        with mesh:
            sched = BatchScheduler(
                cfg, mesh,
                ServeConfig(max_len=64, batch=2, prefill_chunk=4,
                            page_size=8, num_pages=8), params,
            )
            m = replay(sched, workload,
                       faults=FaultInjector(fcfg) if chaos else None)
        return sched, m

    sched, m = run(chaos=True)
    assert m["recovery"]["retries"] >= 1
    assert m["recovery"]["injected"]["nan_injected"] == 1
    assert m["completed"] == len(workload) and m["failed"] == 0
    assert sched._alloc.used == 0, "chaos replay leaked pages"
    _, m2 = run(chaos=True)
    assert m2["generated"] == m["generated"], "chaos replay must be seeded"
    _, base = run(chaos=False)
    assert base["generated"] == m["generated"], \
        "fault recovery changed surviving streams"
