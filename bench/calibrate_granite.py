"""Readings that ``granite-chat``'s correctness limit and rate are set from.

    python3 bench/calibrate_granite.py --seeds 1,2,3 [--control 1,2] \
        [--fault 1] [--seconds 30] [--rate R] [--sweep 0.5,1.0,1.5]

One process on one chip, the cell's own sizes, one JSON line a reading on
standard output, as ``bench/calibrate.py`` gives them for a serving cell:

- for each seed, the window at the cell's load for ``--seconds``, then the
  program's widest logit gap (what a run compares) and, for the seeds in
  ``--control``, the float8 control's gap at the same prompts and tokens;
- for the seeds in ``--fault``, the same with a fault planted in the
  program: a scan that starts every prefill chunk from a zero SSM state;
- ``--sweep``: instead, ``bench/calibrate.py``'s sweep, one window at each
  rate on the first seed, to find the highest rate the program sustains.

A benchmark run never calls this; PERF.md gives what it read.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402

CELL = "granite-chat"


def fault(cell, drv, seed, seconds, devices) -> dict:
    """The program's gap with every prefill chunk's SSM scan started from
    zero: the state is not carried from one chunk to the next."""
    import repro.layers.recurrent as R
    import repro.serve.serve as S

    orig = R._ssd_chunked
    R._ssd_chunked = lambda *a, init_state=None, **k: orig(*a, **k)
    S._serve_step_fns.cache_clear()
    try:
        t = time.time()
        sched, params = drv.build(cell, seed, devices)
        drv.warm(sched, sched.scfg.prefill_chunk)
        rec = drv.window(cell, sched, seed, seconds, None)
        chk = cell.config["check"]
        picked = drv.sample(rec, seed, chk["min_tokens"], chk["max_requests"])
        sched.caches = None
        del sched, rec
        gc.collect()
        return {"seed": seed, "fault": "ssm_state_reset_between_chunks",
                "requests": len(picked), "tokens": sum(len(s) for _, s in picked),
                "logit_gap": drv.check(cell, params, picked), "s": time.time() - t}
    finally:
        R._ssd_chunked = orig
        S._serve_step_fns.cache_clear()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--fault", default="")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--sweep", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control.split(",") if s}
    faults = [int(s) for s in args.fault.split(",") if s]
    cell = harness.load_cell(CELL)
    if args.rate is not None:
        cell.workload = dict(cell.workload, rate_rps=args.rate)
    devices = harness.chips_or_exit(cell.chips)
    harness.use_compile_cache()
    drv = harness.driver(cell)
    cal = harness.load_module(BENCH / "calibrate.py", "bench_calibrate")
    if args.sweep:
        cal.sweep(cell, drv, seeds[0], [float(r) for r in args.sweep.split(",")],
                  args.seconds, devices)
        return 0
    cal.serve(cell, drv, seeds, control, args.seconds, devices)
    for seed in faults:
        print(json.dumps(fault(cell, drv, seed, args.seconds, devices)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
