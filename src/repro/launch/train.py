"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --smoke --steps 10 --ckpt-dir /tmp/ckpt --talp-out talp/case/strong

Runs on the devices of this one host (``--smoke`` gives a reduced config
that runs on the CPU). Re-running with the same --ckpt-dir resumes from the
latest checkpoint (crash = restart the process; the data pipeline is
step-indexed).
"""

from __future__ import annotations

import argparse


def build_loop(cfg, mesh, *, steps: int, global_batch: int, seq_len: int,
               accum: int = 1, lr: float = 3e-4, pad_fraction: float = 0.05,
               ckpt_dir: str = "", ckpt_every: int = 0,
               app_name: str | None = None):
    """The ``TrainLoop`` this launcher runs: synthetic LM data, AdamW, the
    monitored train step. Shared with ``chip_smoke.py`` and the scaling
    benchmark so that they drive the same objects."""
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.optim import AdamWConfig
    from repro.train.loop import LoopConfig, TrainLoop
    from repro.train.train import TrainConfig

    data = SyntheticLM(DataConfig(
        global_batch=global_batch, seq_len=seq_len,
        vocab=cfg.vocab, accum_steps=accum, pad_fraction=pad_fraction,
        frontend_tokens=cfg.n_frontend_tokens if cfg.frontend == "vlm" else 0,
        d_model=cfg.d_model,
    ))
    return TrainLoop(
        cfg, mesh,
        TrainConfig(optimizer=AdamWConfig(lr=lr), total_steps=steps),
        data,
        LoopConfig(steps=steps, ckpt_every=ckpt_every, ckpt_dir=ckpt_dir,
                   lb_sample_every=1, monitor_app_name=app_name or cfg.name),
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--optimized", action="store_true",
                    help="apply the §Perf-optimized preset")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--talp-out", default="",
                    help="directory for the TALP run record (CI artifact)")
    ap.add_argument("--model-axis", type=int, default=1,
                    help="model-parallel degree of the host mesh")
    args = ap.parse_args(argv)

    from repro.configs import get_config, optimized_config, smoke_config
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh

    enable_compile_cache()
    if args.smoke:
        cfg = smoke_config(args.arch)
    elif args.optimized:
        cfg = optimized_config(args.arch)
    else:
        cfg = get_config(args.arch)

    loop = build_loop(
        cfg, make_host_mesh(model=args.model_axis), steps=args.steps,
        global_batch=args.global_batch, seq_len=args.seq_len,
        accum=args.accum, lr=args.lr, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, app_name=args.arch,
    )
    loop.run()
    h = loop.metrics_history
    print(f"[launch] {args.arch}: steps {h[0]['step']}..{h[-1]['step']} "
          f"loss {h[0]['loss']:.3f} -> {h[-1]['loss']:.3f}")
    # git metadata + CI folder layout in one call (repro.session); writes
    # only when a destination resolves (--talp-out or TALP_OUT)
    loop.finalize_run(args.talp_out or None)
    if loop.session.last_record_path:
        print(f"[launch] TALP record: {loop.session.last_record_path}")
    elif args.talp_out:
        print("[launch] monitoring disabled by environment; no run record")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
