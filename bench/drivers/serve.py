"""Serving driver: one ``BatchScheduler`` over the cell's chips, driven by
the mix on the wall clock, then checked against the float32 reference.

The window calls ``submit`` when a request is due and ``step`` then
``flush`` once a tick (the scheduler's own streaming loop), so every token
reaches the host in the tick that made it; a token's time is when its
flush returned. Time to first token runs from when the request was due.
"""

from __future__ import annotations

import gc
import sys
import time

import numpy as np

from harness import Cell, CompileCounter, Tracer, now, percentile, program_config
import loadgen
import weights

WARM_PROMPTS = (40, 70)   # one whole and one partial chunk, then decode


def build(cell: Cell, seed: int, devices):
    """The scheduler the window drives, with bench-made weights."""
    import jax

    from repro.launch.mesh import make_host_mesh
    from repro.layers.common import abstract_params
    from repro.models import transformer as T
    from repro.serve.serve import BatchScheduler, ServeConfig

    c = cell.config
    cfg = program_config(cell)
    mesh = make_host_mesh(devices=devices)
    abstract = abstract_params(T.model_params(cfg), cfg.param_dtype)
    params = weights.make_params(abstract, seed, cfg.n_layers)
    jax.block_until_ready(params)
    s = c["serve"]
    scfg = ServeConfig(max_len=cell.workload["max_len"], batch=cell.workload["slots"],
                       page_size=s["page_size"], num_pages=s["num_pages"])
    return BatchScheduler(cfg, mesh, scfg, params), params


def _tick(sched, live, t0, ticks, prefill_q, chunk):
    """One scheduler tick; records what it dispatched and what reached the
    host. ``prefill_q``: requests whose prompt is not yet all prefilled, in
    submission order (attach and prefill are first come, first served)."""
    lens = [int(sched.pos[s]) + 1 for s, r in enumerate(sched.active) if r is not None]
    chunks = sched.stats["prefill_chunks"]
    t_a = now() - t0
    sched.step()
    sched.flush()
    t_b = now() - t0
    pre = None
    if sched.stats["prefill_chunks"] > chunks and prefill_q:
        r = prefill_q[0]
        L = min(chunk, len(r["req"].prompt) - r["done"])
        pre = (r["done"], L)
        r["done"] += L
        if r["done"] >= len(r["req"].prompt):
            prefill_q.pop(0)
    ticks.append((t_a, t_b, lens, pre))
    for st in live:
        n = len(st["h"].tokens)
        if n > st["n"]:
            st["times"].extend([t_b] * (n - st["n"]))
            st["n"] = n
    live[:] = [st for st in live if not st["h"].done]


def warm(sched, chunk):
    """Compile and warm every shape the window uses: the decode step, a
    whole and a partial prefill chunk, the token seeding and the readback."""
    hs = [sched.submit(list(range(1, 1 + n)), f"warm{n}", max_new=4)
          for n in WARM_PROMPTS]
    while not all(h.done for h in hs):
        sched.step()
        sched.flush()


def window(cell: Cell, sched, seed: int, seconds: float, tracer: Tracer | None,
           drain_s: float = 60.0):
    """Runs the mix for ``seconds``, then serves on for at most ``drain_s``
    until every request due in the window has its first token; returns the
    per-request records, the tick records and the traced tick range."""
    import jax

    tr = cell.traffic
    vocab = cell.model["vocab"]
    chunk = sched.scfg.prefill_chunk
    closed = tr["loop"] == "closed"
    if closed:
        queues = loadgen.closed_loop(tr, seed, cell.workload["per_client"], vocab)
        pending = [q.pop(0) for q in queues]
        for r in pending:
            r.due = 0.0
    else:
        pending = loadgen.open_loop(dict(tr, rate_rps=cell.workload["rate_rps"]),
                                    seed, seconds, vocab)
        queues = None
    live, done_all, ticks, prefill_q = [], [], [], []
    lateness = []
    t_trace = (max(0.0, seconds / 2 - 4.0), min(seconds, seconds / 2 + 4.0))
    traced = [None, None]
    with CompileCounter() as cc:
        t0 = now()
        while True:
            t = now() - t0
            if tracer is not None and traced[0] is None and t >= t_trace[0]:
                tracer.start()
                traced[0] = len(ticks)
            if tracer is not None and tracer.on and t >= t_trace[1]:
                traced[1] = len(ticks)
                tracer.stop()
            if t >= seconds:
                break
            while pending and pending[0].due is not None and pending[0].due <= t:
                r = pending.pop(0)
                st = {"req": r, "h": sched.submit(r.prompt, r.rid, max_new=r.max_new),
                      "n": 0, "times": [], "due": r.due, "done": 0}
                lateness.append(t - r.due)
                live.append(st)
                done_all.append(st)
                prefill_q.append(st)
            if live:
                with jax.profiler.TraceAnnotation("bench.step"):
                    _tick(sched, live, t0, ticks, prefill_q, chunk)
                if closed:
                    for st in done_all:
                        if st["h"].done and not st.get("next"):
                            st["next"] = True
                            q = queues[st["req"].client]
                            if q:
                                nxt = q.pop(0)
                                nxt.due = now() - t0
                                pending.append(nxt)
                    pending.sort(key=lambda r: r.due)
            elif pending:
                time.sleep(max(0.0, min(pending[0].due - (now() - t0), 0.002)))
            else:
                time.sleep(0.001)
        if tracer is not None and tracer.on:
            traced[1] = len(ticks)
            tracer.stop()
        # past the close: serve on (nothing new is sent) until every request
        # due in the window has its first token, for the time-to-first-token
        # tail
        while any(st["n"] == 0 for st in done_all) and now() - t0 < seconds + drain_s:
            _tick(sched, live, t0, ticks, prefill_q, chunk)
        compiles = cc.n
    return {"requests": done_all, "ticks": ticks, "traced": traced,
            "lateness": lateness, "compiles": compiles, "seconds": seconds,
            "drain_s": drain_s}


def end_to_end(rec: dict) -> dict:
    """The cell's user-visible numbers from the window's records."""
    s = rec["seconds"]
    reqs = rec["requests"]
    # a request with no first token by the end of the drain waited at least
    # that long
    end = s + rec["drain_s"]
    ttft = [((st["times"][0] if st["times"] else end) - st["due"]) * 1e3 for st in reqs]
    gaps = []
    toks = 0.0
    for st in reqs:
        ts = st["times"]
        if ts and ts[0] <= s:
            toks += len(st["req"].prompt)
        toks += sum(1 for x in ts if x <= s)
        gaps += [(b - a) * 1e3 for a, b in zip(ts, ts[1:]) if b <= s]
    out = {"serve_tok_s": toks / s, "itl_mean_ms": sum(gaps) / len(gaps) if gaps else 0.0,
           "ttft_p50_ms": percentile(ttft, 50) if ttft else 0.0}
    if reqs:
        out["ttft_p90_ms"] = percentile(ttft, 90)
    if gaps:
        out["itl_p95_ms"] = percentile(gaps, 95)
    return out


def sample(rec: dict, seed: int, min_tokens: int, max_requests: int):
    """Finished requests for the check, drawn from the seed: the one with
    the most served tokens and the longest one, then others until
    ``min_tokens`` served tokens are in."""
    fin = [st for st in rec["requests"] if st["h"].status == "done"]
    if not fin:
        return []
    rng = np.random.default_rng([int(seed), 11])
    by_out = max(fin, key=lambda st: len(st["h"].tokens))
    by_len = max(fin, key=lambda st: len(st["req"].prompt) + len(st["h"].tokens))
    pick = [by_out] + ([by_len] if by_len is not by_out else [])
    rest = [fin[i] for i in rng.permutation(len(fin)) if fin[i] not in pick]
    while rest and len(pick) < max_requests and \
            sum(len(st["h"].tokens) for st in pick) < min_tokens:
        pick.append(rest.pop(0))
    return [(st["req"].prompt, st["h"].tokens) for st in pick]


def check(cell: Cell, params, picked, quant=False) -> float:
    """Widest gap, over the served tokens of ``picked``, by which a served
    token's reference logit lies below the reference's best. With
    ``quant`` the gap is of the token that the float8 control puts first."""
    import jax
    import jax.numpy as jnp

    from reference import dense

    m = dense.frozen(cell.model)
    worst = 0.0
    for prompt, served in picked:
        ids = list(prompt) + list(served[:-1])
        rows = list(range(len(prompt) - 1, len(ids)))
        ref = dense.logits_at(params, m, ids, rows)
        if quant:
            tok = jnp.argmax(dense.logits_at(params, m, ids, rows, quant=True), -1)
        else:
            tok = jnp.asarray(served, jnp.int32)
        gap = jnp.max(ref, -1) - jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]
        worst = max(worst, float(jax.device_get(jnp.max(gap))))
    return worst


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices, t_start: float):
    import jax

    sched, params = build(cell, seed, devices)
    warm(sched, sched.scfg.prefill_chunk)
    tracer = Tracer(cell.root, cell.name, seed) if trace else None
    setup_s = time.time() - t_start
    rec = window(cell, sched, seed, seconds, tracer)
    from harness import device_info

    dev = device_info(devices)
    e2e = end_to_end(rec)
    e2e["setup_s"] = setup_s
    due = rec["requests"]
    failed = sum(1 for st in due if not st["times"] or st["h"].status in ("failed", "shed", "cancelled"))
    late = rec["lateness"]
    print(f"bench: {len(due)} requests, {len(rec['ticks'])} ticks, "
          f"{rec['compiles']} compiles in the window, generator late by "
          f"p50 {percentile(late, 50) * 1e3 if late else 0:.3f} ms / max "
          f"{max(late) * 1e3 if late else 0:.3f} ms", file=sys.stderr)
    chk = cell.config["check"]
    picked = sample(rec, seed, chk["min_tokens"], chk["max_requests"])
    # the program's state goes before the reference runs: keep only the
    # benchmark-made weights
    sched.caches = None
    del sched
    gc.collect()
    gap = check(cell, params, picked) if picked else 1e9   # nothing to check
    print(f"bench: end to end {e2e}", file=sys.stderr)
    print(f"bench: checked {len(picked)} requests, "
          f"{sum(len(s) for _, s in picked)} served tokens", file=sys.stderr)
    return {
        "e2e": e2e, "dev": dev, "attempted": len(due), "failed": failed,
        "checks": {"logit_gap": (gap, chk["logit_gap_limit"])},
        "correct": gap <= chk["logit_gap_limit"],
        "trace_path": tracer.path if tracer else None,
        "ticks": rec["ticks"], "traced": rec["traced"],
    }
