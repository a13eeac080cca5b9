"""Benchmark entry point.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine: builds
the system under test and its weights from the seed, warms every shape
the cell uses (set-up), measures for ``--seconds``, checks what the timed
path produced against the float32 reference, and prints one JSON line.
With ``--trace 0`` its metrics are the cell's end-to-end ones; with
``--trace 1`` the per-layer ones, read from a profiler trace of part of
the window. It needs TPU chips and exits with code 3 without them.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse
import os
import pathlib
import sys

# libtpu would otherwise log under /tmp, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402


def per_layer(cell, out: dict, peaks) -> tuple[dict, dict, dict]:
    """Per-layer metrics, the traced device block and the breakdown."""
    tr = harness.load_module(BENCH / "trace.py", "bench_trace")

    t = tr.reduce(out["trace_path"], chips=cell.chips) if out["trace_path"] else None
    ctx = {"cell": cell, "trace": t, "out": out, "peaks": peaks, "model": cell.model}
    metrics = {}
    for m in cell.per_layer:
        v = harness.metric_reader(cell, m["name"]).read(ctx, m["name"])
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev, brk = {}, None
    if t is not None:
        dev = {"busy_s": t.busy_s, "window_s": t.window_s}
        brk = {"device_ops": t.top_ops(10), "idle_gaps": t.top_gaps(10)}
    return metrics, dev, brk


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float = T_START) -> dict:
    """Everything but the look for chips: drive the cell and build its
    result line."""
    import peaks as pk

    harness.use_compile_cache()
    out = harness.driver(cell).run(cell, seed, seconds, trace, devices, t_start)
    result = {"correct": bool(out["correct"]), "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    dev = dict(out["dev"])
    if trace:
        # an unknown TPU kind raises; only the CPU rehearsal runs off a TPU
        peaks = pk.for_kind(dev["kind"]) if dev["platform"] == "tpu" else None
        metrics, tdev, brk = per_layer(cell, out, peaks)
        dev.update(tdev)
    else:
        metrics = {m["name"]: {"value": float(out["e2e"][m["name"]]), "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in out["e2e"]}
        brk = None
    result["metrics"] = metrics
    result["device"] = dev
    if brk is not None:
        result["breakdown"] = brk
    result["checks"] = harness.checks_line(out["checks"])
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    devices = harness.chips_or_exit(cell.chips)
    harness.emit(run_cell(cell, args.seed, args.seconds, bool(args.trace), devices))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
