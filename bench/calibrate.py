"""Readings that the correctness limits and the chat rate are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --control 1,2,3 \
        [--seconds 30] [--rate R] [--sweep 1.0,1.5,2.0]

One process on the cell's chips, the cell's own sizes, one JSON line a
seed on standard output:

- serving: the window at the cell's load for ``--seconds``, then the
  program's widest logit gap (what a run compares) and, for the seeds in
  ``--control``, the float8 control's gap at the same prompts and tokens
  (``--rate`` replaces an open-loop cell's rate);
- training: the three checked steps through the window's own call, the
  program's numbers against the float32 reference, and for the seeds in
  ``--control`` the float8 control's and a half batch's (the reference
  fed every other row) against the same reference;
- ``--sweep`` (serving, open loop): instead, one window at each rate on
  the first seed, with the time to first token over the window's thirds,
  to find the highest rate the program sustains.

A benchmark run never calls this; PERF.md gives what it read.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402


def _emit(d: dict) -> None:
    print(json.dumps(d), flush=True)


def serve(cell, drv, seeds, control, seconds, devices):
    chk = cell.config["check"]
    for seed in seeds:
        t = time.time()
        sched, params = drv.build(cell, seed, devices)
        drv.warm(sched, sched.scfg.prefill_chunk)
        rec = drv.window(cell, sched, seed, seconds, None)
        picked = drv.sample(rec, seed, chk["min_tokens"], chk["max_requests"])
        e2e = drv.end_to_end(rec)
        sched.caches = None
        del sched, rec
        gc.collect()
        out = {"seed": seed, "requests": len(picked),
               "tokens": sum(len(s) for _, s in picked), "e2e": e2e,
               "logit_gap": drv.check(cell, params, picked)}
        if seed in control:
            out["control"] = drv.check(cell, params, picked, quant=True)
        out["s"] = time.time() - t
        _emit(out)
        del params
        gc.collect()


def sweep(cell, drv, seed, rates, seconds, devices):
    """The time to first token in each third of a window, at each rate:
    below the knee the thirds agree, above it they grow with the queue."""
    sched, params = drv.build(cell, seed, devices)
    drv.warm(sched, sched.scfg.prefill_chunk)
    for rate in rates:
        c = dataclasses.replace(cell, workload=dict(cell.workload, rate_rps=rate))
        rec = drv.window(c, sched, seed, seconds, None, drain_s=5.0)
        thirds = [[], [], []]
        for st in rec["requests"]:
            if st["times"]:
                thirds[min(2, int(3 * st["due"] / seconds))].append(
                    (st["times"][0] - st["due"]) * 1e3)
        ticks = rec["ticks"]
        tick_ms = [(b - a) * 1e3 for a, b, _, _ in ticks]
        _emit({"rate_rps": rate, "requests": len(rec["requests"]),
               "no_first_token": sum(1 for st in rec["requests"] if not st["times"]),
               "ttft_p50_by_third_ms": [harness.percentile(x, 50) if x else None for x in thirds],
               "ttft_p90_ms": drv.end_to_end(rec).get("ttft_p90_ms"),
               "itl_p95_ms": drv.end_to_end(rec).get("itl_p95_ms"),
               "tick_ms_p50": harness.percentile(tick_ms, 50),
               "busy_slots_p50": harness.percentile([len(l) for _, _, l, _ in ticks], 50)})
        for st in rec["requests"]:       # empty the scheduler for the next rate
            if not st["h"].done:
                st["h"].cancel()
        while any(r is not None for r in sched.active):
            sched.step()
            sched.flush()


def worst_leaves(prog: dict, ref: dict, names: list, k: int = 3) -> dict:
    """The ``k`` leaves with the widest gap of first gradient and of change,
    by ``compare``'s measure, with both norms."""
    import statistics

    out = {}
    for key in ("grad", "change"):
        med = statistics.median(ref[key])
        gap = [abs(a - b) / max(b, med) for a, b in zip(prog[key], ref[key])]
        top = sorted(range(len(gap)), key=gap.__getitem__, reverse=True)[:k]
        out[key] = [(names[i], gap[i], prog[key][i], ref[key][i]) for i in top]
    return out


def train(cell, drv, seeds, control, devices):
    import jax

    loop, state, step_fn, abstract, cfg, layout = drv.build(cell, seeds[0], devices)
    names = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(abstract)[0]]
    for i, seed in enumerate(seeds):
        t = time.time()
        if i:
            state = drv.seed_state(layout, seed, abstract, cfg.n_layers)
        state, prog = drv.setup_steps(cell, seed, state, step_fn, abstract, cfg.n_layers)
        del state
        gc.collect()
        ref = drv.reference_readings(cell, seed, abstract, cfg.n_layers)
        out = {"seed": seed, "program": drv.compare(prog, ref), "loss": prog["loss"],
               "ref_loss": ref["loss"], "worst": worst_leaves(prog, ref, names)}
        if seed in control:
            ctl = drv.reference_readings(cell, seed, abstract, cfg.n_layers, quant=True)
            out["control"] = drv.compare(ctl, ref)
            out["control_loss"] = ctl["loss"]
            full = drv.batch
            drv.batch = lambda c, s, k: {n: x[:, ::2] for n, x in full(c, s, k).items()}
            try:
                half = drv.reference_readings(cell, seed, abstract, cfg.n_layers)
            finally:
                drv.batch = full
            out["half_batch"] = drv.compare(half, ref)
        out["s"] = time.time() - t
        _emit(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--sweep", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control.split(",") if s}
    cell = harness.load_cell(args.workload)
    if args.rate is not None:
        cell.workload = dict(cell.workload, rate_rps=args.rate)
    devices = harness.chips_or_exit(cell.chips)
    harness.use_compile_cache()
    drv = harness.driver(cell)
    if args.sweep:
        sweep(cell, drv, seeds[0], [float(r) for r in args.sweep.split(",")],
              args.seconds, devices)
    elif cell.workload["driver"] == "serve":
        serve(cell, drv, seeds, control, args.seconds, devices)
    else:
        train(cell, drv, seeds, control, devices)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
