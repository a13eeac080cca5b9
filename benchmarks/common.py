"""Shared benchmark utilities."""

from __future__ import annotations

import json
import os
import sys
import time
import tracemalloc

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

RESULTS_DIR = os.environ.get("REPRO_RESULTS", "results/benchmarks")


def synthetic_call_chain_hlo(n_comps: int = 150) -> str:
    """A synthetic HLO module text: ENTRY calling a chain of ``n_comps``
    computations via ``call``/``to_apply``. Large enough that a cold
    ``analyze_hlo`` parse measurably dominates a cached hit; shared by the
    CI cache gate (benchmarks/run.py --check) and tests/test_hlo.py so the
    two cannot drift apart grammatically."""
    comps, calls = [], []
    for i in range(n_comps):
        comps.append(
            f"%w{i} (p{i}: f32[32,32]) -> f32[32,32] {{\n"
            f"  %p{i} = f32[32,32]{{1,0}} parameter(0)\n"
            f"  %m{i} = f32[32,32]{{1,0}} multiply(f32[32,32]{{1,0}} %p{i}, f32[32,32]{{1,0}} %p{i})\n"
            f"  %d{i} = f32[32,32]{{1,0}} dot(f32[32,32]{{1,0}} %m{i}, f32[32,32]{{1,0}} %p{i}), "
            f"lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}\n"
            f"  ROOT %a{i} = f32[32,32]{{1,0}} add(f32[32,32]{{1,0}} %d{i}, f32[32,32]{{1,0}} %p{i})\n"
            f"}}\n"
        )
        prev = "%p" if i == 0 else f"%c{i - 1}"
        root = "ROOT " if i == n_comps - 1 else ""
        calls.append(
            f"  {root}%c{i} = f32[32,32]{{1,0}} call(f32[32,32]{{1,0}} {prev}), to_apply=%w{i}"
        )
    return (
        "HloModule call_chain\n\n" + "\n".join(comps)
        + "\nENTRY %main (p: f32[32,32]) -> f32[32,32] {\n"
        + "  %p = f32[32,32]{1,0} parameter(0)\n"
        + "\n".join(calls) + "\n}\n"
    )


def force_host_devices(n: int) -> None:
    """Give the CPU backend ``n`` devices, so that a multi-device path runs
    in this one process. Only the CPU backend reads the flag, and only when
    JAX starts: call it before anything imports jax. On a chip it changes
    nothing (the chips are the devices)."""
    flag = f"--xla_force_host_platform_device_count={n}"
    if "jax" in sys.modules:
        raise RuntimeError(f"{flag} must be set before jax is imported")
    os.environ["XLA_FLAGS"] = f"{os.environ.get('XLA_FLAGS', '')} {flag}".strip()


def save_result(name: str, payload: dict) -> None:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, f"{name}.json"), "w") as f:
        json.dump(payload, f, indent=1, default=str)


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, time.perf_counter() - t0


def peak_memory(fn, *args, **kw):
    """Returns (result, seconds, peak_python_bytes)."""
    tracemalloc.start()
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    dt = time.perf_counter() - t0
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    return out, dt, peak


def csv_line(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"
