"""Benchmark driver — one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.

  table1_*    runtime overhead of monitor vs tracer   (paper Table 1)
  table2_*    post-processing resources               (paper Table 2)
  tables67_*  weak/strong scaling-efficiency tables   (paper Tables 6/7)
  figure7_*   regression detect + explain             (paper Figure 7)
  roofline_*  §Roofline aggregation from the dry-run artifacts
  serve_*     serving throughput on the CPU           (BENCH_serve.json)

``--check`` is the CI gate: it runs the tier-1 suite
(``PYTHONPATH=src python -m pytest -x -q``) plus a cold-vs-cached
``analyze_hlo`` timing assertion (so the HLO parse cache cannot silently
regress even if the equivalent unit test is edited away) plus the cheap
shape of ``benchmarks/serve_throughput.py`` (overlapped chunked prefill
must leave no decode gap while prefilling, and cross-request prefix
sharing must keep tokens bitwise identical on/off in both decode modes
while strictly lowering peak live pages and skipping prefill chunks). It also forces a preemption (tiny
page pool vs ample pool) and asserts the recompute-resumed token streams
are bitwise identical — greedy AND sampled — with ``preemptions > 0`` and
zero allocator pages leaked after drain, plus a goodput sanity pass of
the open-loop traffic harness under Poisson and bursty arrivals (every
request completed or cancelled, none failed, TTFT percentiles ordered).
Finally the chaos gate: under a seeded fault schedule injecting every
fault kind at least once (NaN logits, KV-page corruption, allocator
spike, hung dispatch), every recovered request's tokens must be bitwise
identical to the fault-free run — greedy AND sampled — a retry-exhausted
request must be quarantined (terminal ``failed``, pages freed,
co-residents untouched), and zero pages may leak after drain. Last the
speculation gate: spec-decode on/off must produce bitwise-identical
tokens on a repetitive AND a non-repetitive trace, greedy AND sampled,
with a STRICT tokens/sec speedup and acceptance_rate > 0 on the
repetitive workload and zero pages leaked after drain.
"""

from __future__ import annotations

import os
import sys
import traceback


def _repo_paths() -> tuple[str, str]:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return root, os.path.join(root, "src")


def _check_cache_speedup(min_ratio: float = 5.0) -> str:
    """Assert a cached analyze_hlo call is >= min_ratio faster than the cold
    parse of the same module text. Returns a CSV summary line."""
    import time

    from benchmarks.common import synthetic_call_chain_hlo
    from repro.core import hlo as H

    text = synthetic_call_chain_hlo()
    H.clear_caches()
    t0 = time.perf_counter()
    cold_cost = H.analyze_hlo(text)
    cold = time.perf_counter() - t0
    warm = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        H.analyze_hlo(text)
        warm = min(warm, time.perf_counter() - t0)
    if cold_cost.hbm_bytes <= 0:
        raise AssertionError("analyze_hlo returned zero hbm_bytes for call-chain module")
    ratio = cold / max(warm, 1e-12)
    if ratio < min_ratio:
        raise AssertionError(
            f"analyze_hlo cache regressed: cold={cold * 1e3:.2f}ms "
            f"warm={warm * 1e3:.3f}ms ratio={ratio:.1f}x < {min_ratio}x"
        )
    return f"check_hlo_cache,{warm * 1e6:.1f},speedup={ratio:.0f}x"


def check() -> int:
    """CI gate: tier-1 suite green + the analyze_hlo cache guarantee."""
    import subprocess

    root, src = _repo_paths()
    env = dict(os.environ)
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    rc = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q"], cwd=root, env=env
    ).returncode
    if rc != 0:
        print(f"[check] tier-1 suite FAILED (rc={rc})", file=sys.stderr)
        return rc
    # invoked as `python benchmarks/run.py`: sys.path[0] is benchmarks/, so
    # both the repo root (for benchmarks.common) and src/ need inserting
    for p in (src, root):
        if p not in sys.path:
            sys.path.insert(0, p)
    try:
        line = _check_cache_speedup()
    except AssertionError as e:
        print(f"[check] {e}", file=sys.stderr)
        return 1
    print(line)
    from benchmarks import serve_throughput

    try:
        print(serve_throughput.check())
    except AssertionError as e:
        print(f"[check] serve: {e}", file=sys.stderr)
        return 1
    print("[check] tier-1 suite green, hlo cache OK, serve OK")
    return 0


def main() -> None:
    if "--check" in sys.argv[1:]:
        sys.exit(check())

    # every benchmark runs in this process (none starts a child that would
    # need the device); the scaling tables want four devices, which the CPU
    # backend only gets if asked before jax is imported
    root, src = _repo_paths()
    for p in (src, root):
        if p not in sys.path:
            sys.path.insert(0, p)
    from benchmarks.common import force_host_devices

    force_host_devices(4)
    from benchmarks import (
        overhead,
        postprocessing,
        regression,
        roofline,
        scaling_tables,
        serve_throughput,
    )

    lines: list[str] = []
    failures = 0
    for mod in (overhead, postprocessing, scaling_tables, regression, roofline,
                serve_throughput):
        name = mod.__name__.split(".")[-1]
        try:
            lines += mod.main()
        except Exception as e:
            failures += 1
            traceback.print_exc()
            lines.append(f"{name},0.0,FAILED:{type(e).__name__}:{e}")
    print("name,us_per_call,derived")
    for line in lines:
        print(line)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
