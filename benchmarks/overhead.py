"""Paper Table 1 — runtime overhead of the collection tools.

Trains the mini-app (reduced tinyllama) for N steps under five regimes, all
through the ONE ``PerfSession`` code path (the backends are pluggable, the
harness is not):

  baseline      plain loop, no session at all (reference)
  null          PerfSession null backend — must be indistinguishable from
                baseline (wrap_step returns the function unchanged)
  talp          monitor backend, sync_regions=True (paper's DLB row)
  talp-nosync   monitor backend without per-step output syncs (cheap mode)
  tracer        full event tracing (the Extrae/Score-P row)

Reports wall-time overhead % per regime — the paper's claim is low-single-
digit overhead for TALP vs tracing, and the null backend proves the session
facade itself costs nothing.
"""

from __future__ import annotations

import time

import jax

from benchmarks.common import csv_line, save_result
from repro import compat
from repro.configs import smoke_config
from repro.core import ResourceConfig
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.session import PerfSession, SessionConfig
from repro.train.train import TrainConfig, init_state, make_train_step


def _setup(steps: int):
    cfg = smoke_config("tinyllama-1.1b")
    mesh = make_host_mesh(devices=jax.devices()[:1])
    tcfg = TrainConfig()
    st = init_state(cfg, tcfg, jax.random.PRNGKey(0))
    state = {"params": st.params, "opt_state": st.opt_state, "step": st.step}
    data = SyntheticLM(DataConfig(global_batch=4, seq_len=64, vocab=cfg.vocab))
    with compat.use_mesh(mesh):
        step = jax.jit(make_train_step(cfg, mesh, tcfg))
        state, m = step(state, data.batch_at(0))  # warmup compile
        jax.block_until_ready(m["loss"])
    batches = [data.batch_at(i) for i in range(steps)]
    return mesh, step, state, batches


# the single harness, parameterized by backend — replaces the three
# hand-rolled loops the old benchmark maintained
def _run_instrumented(step, state0, batches, *, backend: str, sync: bool,
                      resources: ResourceConfig, trace_dir: str = "") -> None:
    session = PerfSession(
        SessionConfig(app_name="bench", backend=backend, sync_regions=sync,
                      lb_sample_every=1, trace_dir=trace_dir,
                      respect_env=False),
        resources,
    )
    wrapped = session.wrap_step(step, region="train")
    state = state0
    with session:
        for b in batches:
            state, metrics = wrapped(state, b)
    jax.block_until_ready(metrics["loss"])
    if backend == "monitor":
        # the O(regions) finalize is part of the monitor's runtime cost;
        # trace post-processing is Table 2's benchmark, not Table 1's
        session.finalize(save=False, git=False)


def run(steps: int = 30, tmpdir: str = "/tmp/repro_overhead") -> dict:
    res = ResourceConfig(num_hosts=1, devices_per_host=1)
    # the tracer writes one event stream per device it owns (Extrae's
    # per-rank .mpit files); simulate the 16-device host share
    res16 = ResourceConfig(num_hosts=1, devices_per_host=16)
    mesh, step, state0, batches = _setup(steps)

    def run_baseline():
        state = state0
        for b in batches:
            state, metrics = step(state, b)
        jax.block_until_ready(metrics["loss"])

    modes = {
        "null": lambda: _run_instrumented(
            step, state0, batches, backend="null", sync=True, resources=res),
        "talp": lambda: _run_instrumented(
            step, state0, batches, backend="monitor", sync=True, resources=res),
        "talp_nosync": lambda: _run_instrumented(
            step, state0, batches, backend="monitor", sync=False, resources=res),
        "tracer": lambda: _run_instrumented(
            step, state0, batches, backend="tracer", sync=True,
            resources=res16, trace_dir=tmpdir),
    }

    def best_of(fn, reps=3):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            with compat.use_mesh(mesh):  # fresh ctx: use_mesh is single-use
                fn()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    t_base = best_of(run_baseline)

    def ovh(t):
        return 100.0 * (t - t_base) / t_base

    result = {"steps": steps, "baseline_s": t_base}
    for name, fn in modes.items():
        t = best_of(fn)
        result[f"{name}_s"] = t
        result[f"{name}_overhead_pct"] = ovh(t)
    save_result("table1_overhead", result)
    return result


def main() -> list[str]:
    r = run()
    return [
        csv_line(f"table1_{name}_overhead", r[f"{name}_s"] / r["steps"] * 1e6,
                 f"overhead={r[f'{name}_overhead_pct']:.1f}%")
        for name in ("null", "talp", "talp_nosync", "tracer")
    ]


if __name__ == "__main__":
    print("\n".join(main()))
