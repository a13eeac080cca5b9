"""Paper Figure 7 / §Reports — detect AND explain a performance change.

Simulates a CI history of commits on the mini-app where commit c2 introduces
a host-side stall (dispatch bug) and commit c4 doubles the executed FLOPs
(remat/recompute bug). The report must flag both elapsed-time regressions
and attribute each to the right factor — the paper's core qualitative claim
(wall-clock-only monitoring cannot do the second part).
"""

from __future__ import annotations

import json
import os
import shutil
import time

import jax

from benchmarks.common import csv_line, save_result
from repro import compat
from repro.configs import smoke_config
from repro.core import ResourceConfig, StepProfile, generate_report, scan
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.session import PerfSession, SessionConfig
from repro.train.train import TrainConfig, init_state, make_train_step


def _train_once(commit: str, ts: str, out: str, *, stall_s: float = 0.0,
                flop_scale: float = 1.0, steps: int = 8):
    cfg = smoke_config("tinyllama-1.1b")
    mesh = make_host_mesh(devices=jax.devices()[:1])
    tcfg = TrainConfig()
    st = init_state(cfg, tcfg, jax.random.PRNGKey(0))
    state = {"params": st.params, "opt_state": st.opt_state, "step": st.step}
    data = SyntheticLM(DataConfig(global_batch=2, seq_len=32, vocab=cfg.vocab))
    session = PerfSession(
        SessionConfig(app_name="miniapp", backend="monitor", lb_sample_every=1,
                      respect_env=False),
        ResourceConfig(num_hosts=1, devices_per_host=1),
        metadata={"git_commit_short": commit, "git_commit_timestamp": ts},
    )
    # static profile from the compiled step; the flop bug shows up here
    # exactly as it would through the HLO counters of the buggy binary
    with compat.use_mesh(mesh):
        step = jax.jit(make_train_step(cfg, mesh, tcfg))
        example = data.batch_at(0)
        compiled = step.lower(state, example).compile()
    profile = StepProfile.from_compiled(compiled, num_devices=1)
    profile.flops *= flop_scale
    profile.model_flops = profile.dot_flops
    session.attach_static("train_step", profile)

    # warm up outside the monitored window: compile time must not pollute
    # the elapsed-time series (it would on real CI too — the paper's runs
    # measure the solver, not the build)
    with compat.use_mesh(mesh):
        _s, _m = step(state, data.batch_at(0))
        jax.block_until_ready(_m["loss"])

    with compat.use_mesh(mesh), session:
        for s in range(steps):
            with session.region("train_step"):
                state, metrics = step(state, data.batch_at(s))
                if flop_scale > 1.0:
                    # the recompute bug also costs real time
                    t0 = time.perf_counter()
                    while time.perf_counter() - t0 < 0.15:
                        pass
                session.observe_step(metrics)
                if stall_s:
                    time.sleep(stall_s)  # host-side stall (input pipeline bug)
    run = session.finalize(git=False)
    run.save(out)
    return run


def run(root: str = "/tmp/repro_regression") -> dict:
    shutil.rmtree(root, ignore_errors=True)
    hist = os.path.join(root, "talp", "miniapp", "history")
    commits = [
        ("c0", {}, "2026-07-01"),
        ("c1", {}, "2026-07-02"),
        ("c2", {"stall_s": 0.25}, "2026-07-03"),       # dispatch bug
        ("c3", {}, "2026-07-04"),                      # fixed
        ("c4", {"flop_scale": 2.0}, "2026-07-05"),     # recompute bug
    ]
    for commit, kw, day in commits:
        _train_once(commit, f"{day}T12:00:00", os.path.join(hist, f"{commit}.json"), **kw)

    out = os.path.join(root, "site")
    generate_report(scan(os.path.join(root, "talp")), out, regions=["train_step"])
    findings = json.load(open(os.path.join(out, "findings.json")))

    def find(commit, kind):
        return [
            f for f in findings
            if f["commit"] == commit and f["kind"] == kind
            and f["region"] == "train_step"
        ]

    c2 = find("c2", "regression")
    c4 = find("c4", "regression")
    c2_explained = any("dispatch_efficiency" in f["explanation"] for f in c2)
    c4_explained = any(
        "flop_scaling" in f["explanation"] or "computation_scalability" in f["explanation"]
        for f in c4
    )
    result = {
        "n_findings": len(findings),
        "c2_detected": bool(c2), "c2_explained_as_dispatch": c2_explained,
        "c4_detected": bool(c4), "c4_explained_as_flops": c4_explained,
        "findings": findings,
    }
    save_result("figure7_regression", result)
    return result


def main() -> list[str]:
    r = run()
    return [
        csv_line(
            "figure7_detect_explain", 0.0,
            f"dispatch_bug detected={r['c2_detected']} explained={r['c2_explained_as_dispatch']}; "
            f"recompute_bug detected={r['c4_detected']} explained={r['c4_explained_as_flops']}",
        )
    ]


if __name__ == "__main__":
    print("\n".join(main()))
