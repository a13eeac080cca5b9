#!/usr/bin/env python3
"""Bring-up check on the TPU: full-width serving and training, driven
through the objects a user calls.

    python3 chip_smoke.py            # one chip: phases 1 and 2
    python3 chip_smoke.py --chips 4  # four chips: the scaling phase only

Phase 1 serves tinyllama-1.1b at its published widths (random bf16 weights
from ``--seed``) through ``BatchScheduler`` with the default ServeConfig
behaviour — paged KV, chunked prefill overlapped with decode, greedy — and
the Pallas paged-attention kernels. Eight seeded prompts of 128 to 1024
tokens each generate 32 tokens. The same requests are then served again
with the jnp reference attention, and the two are compared: each request's
first generated token, and the prefill's last-position logits, which must
agree within ``LOGIT_RTOL`` when the model runs in float32. The compiled
decode step must hold the kernel (``tpu_custom_call``).

Phase 2 trains xlstm-350m for five steps through ``TrainLoop``, checks every
loss is finite, writes the TALP run record and builds the TALP-Pages report
from it with ``repro.core.pages ci-report``.

``--chips 4`` runs only the strong-scaling path: the xlstm-350m training
job at a fixed global batch on meshes over ``jax.devices()[:n]`` for
n = 1, 2, 4, all in this process, and the scaling-efficiency table built
from its records (paper Tables 6/7).

Every line before the last is a human-readable report. The last line of
standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase and check passed. Without a TPU the script
exits non-zero before doing any work; it has no CPU path.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

from repro.launch.cache import enable_compile_cache  # noqa: E402

SERVE_ARCH = "tinyllama-1.1b"
TRAIN_ARCH = "xlstm-350m"
# Pallas kernel vs jnp reference prefill logits, relative to the largest
# reference logit, with the model in float32 and f32 matmuls. In bf16 the
# comparison cannot tell a kernel fault from rounding: on a v5e, the random-
# weight tinyllama's bf16 logits move by 11-20% of their largest value when
# only the matmul precision of the reference path changes, while each kernel
# alone is within one bf16 rounding of a float64 attention. In float32 the
# two paths differ only in summation order (~1e-6 per layer); 1e-3 leaves
# that a wide margin, and a wrong page, mask or softmax moves logits by
# their whole scale.
LOGIT_RTOL = 1e-3


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CheckFailed(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    log(f"check ok: {what}")


def make_prompts(n: int, lo: int, hi: int, vocab: int, seed: int) -> list[list[int]]:
    """``n`` prompts with lengths spread over [lo, hi] (both ends included)."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi + 1, size=n)
    lens[0], lens[-1] = lo, hi
    return [rng.integers(4, vocab, size=int(m)).tolist() for m in lens]


# ---------------------------------------------------------------------------
# phase 1: serving
# ---------------------------------------------------------------------------


def serve_requests(cfg, params, prompts, *, impl: str, max_len: int,
                   page_size: int, num_pages: int, max_new: int) -> dict:
    """Serve ``prompts`` through ``BatchScheduler`` with the paged
    attention kernels at ``impl``. A one-request warm-up scheduler pays the
    compiles first (``compile_s``); the timed scheduler then serves every
    prompt to completion (``steady_s``). Both end in block_until_ready."""
    import jax

    from repro.launch.mesh import make_host_mesh
    from repro.serve.serve import BatchScheduler, ServeConfig

    cfg = cfg.replace(paged_attn_impl=impl)
    mesh = make_host_mesh(devices=jax.devices()[:1])
    scfg = ServeConfig(max_len=max_len, batch=len(prompts),
                       page_size=page_size, num_pages=num_pages)

    t0 = time.perf_counter()
    warm = BatchScheduler(cfg, mesh, scfg, params)
    warm.submit(prompts[0][: 2 * scfg.prefill_chunk], request_id="warmup",
                max_new=2)
    warm.drain()
    jax.block_until_ready(warm.caches)
    compile_s = time.perf_counter() - t0
    del warm

    sched = BatchScheduler(cfg, mesh, scfg, params)
    t0 = time.perf_counter()
    for rid, prompt in enumerate(prompts):
        sched.submit(prompt, request_id=rid, max_new=max_new)
    sched.drain()
    jax.block_until_ready(sched.caches)
    steady_s = time.perf_counter() - t0
    streams = {r["id"]: list(r["generated"]) for r in sched.completed}
    return {
        "scheduler": sched, "config": cfg, "mesh": mesh, "scfg": scfg,
        "streams": streams, "compile_s": compile_s, "steady_s": steady_s,
        "tokens": sum(len(s) for s in streams.values()),
    }


def decode_step_text(run: dict) -> str:
    """Optimized HLO of the decode step the scheduler dispatches, compiled
    for the scheduler's own argument shapes."""
    import jax
    import jax.numpy as jnp

    from repro import compat
    from repro.serve.serve import make_serve_decode_step

    sched, scfg = run["scheduler"], run["scfg"]
    batch = scfg.batch
    step = jax.jit(
        make_serve_decode_step(run["config"], run["mesh"]),
        donate_argnums=(4,),
    )
    with compat.use_mesh(run["mesh"]):
        lowered = step.lower(
            sched.params, sched.tokens,
            jax.ShapeDtypeStruct((batch,), jnp.int32),
            jax.ShapeDtypeStruct((batch,), jnp.bool_),
            sched.caches,
            jax.ShapeDtypeStruct((batch, scfg.max_len // scfg.page_size),
                                 jnp.int32),
            sched.rng_keys,
            jax.ShapeDtypeStruct((batch,), jnp.bool_),
        )
        return lowered.compile().as_text()


def prefill_logits(cfg, params, prompts, *, impl: str, max_len: int,
                   page_size: int, chunk: int) -> np.ndarray:
    """Last-position logits of each prompt's chunked paged prefill — the
    logits the scheduler samples the first token from — through
    ``T.prefill_chunk`` with the paged kernels at ``impl``. Returns
    (len(prompts), vocab) float32."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T

    cfg = cfg.replace(paged_attn_impl=impl)
    n_pages = max_len // page_size
    tables = jnp.arange(n_pages, dtype=jnp.int32)[None]

    @jax.jit
    def step(params, tokens, start, length, caches):
        return T.prefill_chunk(params, {"tokens": tokens}, cfg, caches,
                               start, length, block_tables=tables)

    out = []
    for prompt in prompts:
        caches = T.init_cache(cfg, 1, max_len, paged=True,
                              page_size=page_size, num_pages=n_pages)
        for start in range(0, len(prompt), chunk):
            part = prompt[start:start + chunk]
            toks = np.zeros((1, chunk), np.int32)
            toks[0, :len(part)] = part
            logits, caches = step(params, jnp.asarray(toks),
                                  jnp.int32(start), jnp.int32(len(part)), caches)
        out.append(np.asarray(logits[0, :cfg.vocab], np.float32))
    return np.stack(out)


def serve_phase(cfg, *, impl: str, seed: int, n_requests: int = 8,
                prompt_lens: tuple[int, int] = (128, 1024), max_len: int = 2048,
                page_size: int = 16, num_pages: int = 1024,
                max_new: int = 32) -> dict:
    """Phase 1: serve with the paged kernels at ``impl``, then again with
    the reference, and compare. Raises ``CheckFailed`` on any failed check."""
    import jax

    from repro.layers.common import init_params
    from repro.models import transformer as T

    params = init_params(T.model_params(cfg), jax.random.PRNGKey(seed),
                         cfg.param_dtype)
    prompts = make_prompts(n_requests, *prompt_lens, cfg.vocab, seed)
    log(f"serve {cfg.name}: {n_requests} requests, prompts "
        f"{min(map(len, prompts))}..{max(map(len, prompts))} tokens, "
        f"max_new {max_new}, max_len {max_len}, page_size {page_size}, "
        f"{num_pages} pages")
    kw = dict(max_len=max_len, page_size=page_size, num_pages=num_pages,
              max_new=max_new)

    runs = {}
    for name in (impl, "reference"):
        run = serve_requests(cfg, params, prompts, impl=name, **kw)
        runs[name] = run
        log(f"serve[{name}]: compile {run['compile_s']:.3f} s, steady "
            f"{run['steady_s']:.3f} s for {run['tokens']} tokens "
            f"({run['tokens'] / run['steady_s']:.1f} tok/s), "
            f"{len(run['streams'])}/{n_requests} completed")
        check(len(run["streams"]) == n_requests
              and all(len(s) == max_new for s in run["streams"].values()),
              f"serve[{name}]: all {n_requests} requests completed with "
              f"{max_new} tokens")

    found = "tpu_custom_call" in decode_step_text(runs[impl])
    log(f"tpu_custom_call in the compiled {impl} decode step: {found}")
    if impl == "pallas":  # interpret mode lowers the kernel to plain HLO
        check(found, "compiled decode step holds the Pallas kernel")
    for run in runs.values():
        del run["scheduler"]  # free the KV pools before the logits pass

    lkw = dict(max_len=max_len, page_size=page_size,
               chunk=runs[impl]["scfg"].prefill_chunk)

    def compare(cfg, params, dtype):
        got = prefill_logits(cfg, params, prompts, impl=impl, **lkw)
        ref = prefill_logits(cfg, params, prompts, impl="reference", **lkw)
        check(bool(np.isfinite(got).all() and np.isfinite(ref).all()),
              f"{dtype} prefill logits are finite")
        diff = np.abs(got - ref).max(axis=-1)
        scale = np.abs(ref).max(axis=-1)
        rel = float((diff / scale).max())
        log(f"{dtype} prefill last-position logits, {impl} vs reference: max "
            f"abs diff {float(diff.max())!r}, max |ref| {float(scale.max())!r}, "
            f"max relative {rel!r}")
        return ref, diff, rel

    ref, diff, rel_bf16 = compare(cfg, params, "bf16")
    cfg32 = cfg.replace(compute_dtype_name="float32", param_dtype_name="float32")
    params32 = jax.tree_util.tree_map(lambda x: x.astype(np.float32), params)
    with jax.default_matmul_precision("highest"):
        _, _, rel = compare(cfg32, params32, "f32")
    del params32
    check(rel <= LOGIT_RTOL, f"f32 logits within {LOGIT_RTOL} of the reference")

    # a first token (bf16, as served) may differ only where the reference's
    # top two logits are closer than the measured difference can explain
    top2 = np.sort(ref, axis=-1)[:, -2:]
    gap = top2[:, 1] - top2[:, 0]
    firsts = [(runs[impl]["streams"][i][0], runs["reference"]["streams"][i][0])
              for i in range(n_requests)]
    agree = sum(a == b for a, b in firsts)
    explained = all(a == b or gap[i] <= 2 * diff[i]
                    for i, (a, b) in enumerate(firsts))
    log(f"first generated token: {agree}/{n_requests} agree with the reference")
    check(explained, "every first-token difference is a near-tie of the "
          "reference logits")
    return {"runs": runs, "logits_rel_diff": rel,
            "logits_rel_diff_bf16": rel_bf16, "first_token_agree": agree}


# ---------------------------------------------------------------------------
# phase 2: training
# ---------------------------------------------------------------------------


def train_phase(cfg, *, steps: int = 5, global_batch: int = 4,
                seq_len: int = 2048, out_dir: str) -> dict:
    """Phase 2: ``steps`` steps of ``cfg`` through ``TrainLoop`` on one
    device; the loss must be finite at every step, the TALP record must be
    written and ``ci-report`` must build a report from it. (xlstm-350m at
    8 x 2048 needs 18.6 GB of the chip's 15.75 GB; 4 x 2048 fits.)"""
    import jax

    from repro.core.pages import main as talp_cli
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import build_loop

    log(f"train {cfg.name}: {steps} steps, global batch {global_batch}, "
        f"seq_len {seq_len}")
    loop = build_loop(cfg, make_host_mesh(devices=jax.devices()[:1]),
                      steps=steps, global_batch=global_batch, seq_len=seq_len)
    loop.run()
    losses = [h["loss"] for h in loop.metrics_history]
    talp_dir = os.path.join(out_dir, "talp", cfg.name)
    record = loop.finalize_run(talp_dir)
    check(record is not None and loop.session.last_record_path is not None,
          f"TALP record written ({loop.session.last_record_path})")
    regions = record.regions
    compile_s = regions["initialize"].measurements.elapsed_s
    steady_s = regions["train_step"].measurements.elapsed_s
    tokens = steps * global_batch * seq_len
    log(f"train: compile {compile_s:.3f} s (initialize region), steady "
        f"{steady_s:.3f} s for {steps} steps ({steady_s / steps:.4f} s/step, "
        f"{tokens / steady_s:.1f} tok/s); losses {losses}")
    check(len(losses) == steps and all(math.isfinite(x) for x in losses),
          f"loss finite at all {steps} steps")
    site = os.path.join(out_dir, "site", cfg.name)
    rc = talp_cli(["ci-report", "-i", os.path.dirname(talp_dir), "-o", site,
                   "--regions", "train_step"])
    check(rc == 0 and os.path.exists(os.path.join(site, "index.html")),
          f"ci-report built {site}/index.html")
    return {"losses": losses, "record": record}


# ---------------------------------------------------------------------------
# four chips: the strong-scaling path
# ---------------------------------------------------------------------------


def scaling_phase(cfg, *, counts=(1, 2, 4), global_batch: int = 8,
                  seq_len: int = 1024, steps: int = 4, out_dir: str) -> dict:
    """The training job at a fixed global batch on meshes over the first
    1, 2 and 4 devices, in this process; builds the strong-scaling table.
    The largest mesh must hold its train state on every one of its
    devices."""
    import jax

    from benchmarks.scaling_tables import measure
    from repro.core import build_table, render_text

    log(f"strong scaling {cfg.name}: devices {counts}, global batch "
        f"{global_batch}, seq_len {seq_len}, {steps} steps each")
    runs = measure(cfg, counts, global_batch=lambda n: global_batch,
                   seq_len=seq_len, steps=steps,
                   out_dir=os.path.join(out_dir, "talp", "strong_scaling"))
    for r in runs:
        log(f"{r['devices']} device(s): losses {r['losses']}, state on "
            f"devices {r['state_devices']}")
        check(all(math.isfinite(x) for x in r["losses"]),
              f"loss finite at every step on {r['devices']} device(s)")
    want = sorted(d.id for d in jax.devices()[:counts[-1]])
    check(runs[-1]["state_devices"] == want,
          f"the {counts[-1]}-device mesh holds shards on devices {want}")
    # the steady steps: the Global region also holds each mesh's compile,
    # whose time depends on what the compile cache already held
    table = build_table([r["record"] for r in runs], region="train_step")
    print(render_text(table), flush=True)
    check(table.mode == "strong", "table detected as strong scaling")
    return {"runs": runs, "table": table}


# ---------------------------------------------------------------------------


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 1/2/4-device strong-scaling path")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the prompts")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "chip_smoke"),
                    help="where the TALP records and the report go")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"[chip_smoke] --chips {args.chips} needs {args.chips} devices; "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    warm = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"device: {dev.platform} / {dev.device_kind} x {len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache_dir} "
        f"({warm} entries at start)")

    from repro.configs import get_config

    try:
        if args.chips == 4:
            scaling_phase(get_config(TRAIN_ARCH), out_dir=args.out)
        else:
            serve_phase(get_config(SERVE_ARCH), impl="pallas", seed=args.seed)
            log(f"peak_bytes_in_use after serving: {peak_bytes(dev)}")
            train_phase(get_config(TRAIN_ARCH), out_dir=args.out)
        log(f"peak_bytes_in_use: {peak_bytes(dev)}")
    except CheckFailed as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
