"""Full CI workflow simulation — the paper's GENE-X integration (§CI
Workflow, listings 5/6) end to end on the mini-app:

  for each "commit":                          (performance job)
      run the performance experiment at two resource configurations
      write talp/<case>/<experiment>/talp_*.json
      talp metadata  (inject commit info)
  then:                                       (talp-pages job)
      talp merge-history  (previous pipeline's artifacts)
      talp ci-report -i talp -o public/talp --regions train_step
      -> static site with badges, scaling tables, time series, findings

    PYTHONPATH=src python examples/ci_workflow.py
"""

import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Both resource configurations run in this one process, on meshes over
# jax.devices()[:n] (a child process could not reach a chip this process
# holds). The CPU backend only gets two devices if asked before jax is
# imported; on a chip the flag changes nothing.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
).strip()

import jax  # noqa: E402

from repro.configs import smoke_config  # noqa: E402
from repro.core.pages import main as talp_cli  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.train import build_loop  # noqa: E402

ROOT = "results/ci_workflow"


def performance_job(commit: str, ts: str, slow: bool, pipeline_dir: str):
    """The paper's matrix job: one run per resource configuration."""
    for ndev in (1, 2):
        loop = build_loop(
            smoke_config("tinyllama-1.1b"),
            make_host_mesh(devices=jax.devices()[:ndev]),
            steps=6, global_batch=4, seq_len=64, pad_fraction=0.0,
            app_name="miniapp",
        )
        if slow:  # this commit has a host-stall bug
            observe = loop.session.observe_step

            def slow_observe(*a, _observe=observe, **k):
                time.sleep(0.03)
                return _observe(*a, **k)

            loop.session.observe_step = slow_observe
        loop.run()
        run = loop.finalize_run()
        if run is None:
            raise SystemExit(
                "ci_workflow needs collection enabled — unset TALP_ENABLE=0"
            )
        run.metadata.update({"git_commit_short": commit,
                             "git_commit_timestamp": ts})
        run.timestamp = ts
        run.save(os.path.join(pipeline_dir, "talp", "salpha", "strong_scaling",
                              f"talp_1x{ndev}_{commit}.json"))
        print("performance job done:", run.resources.label)


def main():
    shutil.rmtree(ROOT, ignore_errors=True)
    commits = [("aaa111", False), ("bbb222", False), ("ccc333", True)]
    prev_pipeline = None
    for i, (commit, slow) in enumerate(commits):
        pipeline = os.path.join(ROOT, f"pipeline_{i}")
        ts = f"2026-07-{10 + i:02d}T12:00:00"
        print(f"=== pipeline {i} (commit {commit}{' — buggy' if slow else ''}) ===")
        performance_job(commit, ts, slow, pipeline)

        talp_dir = os.path.join(pipeline, "talp")
        # talp metadata (already injected by the loop here; idempotent)
        talp_cli(["metadata", "-i", talp_dir, "--extra", f"pipeline={i}"])
        # talp merge-history (download previous pipeline artifacts)
        if prev_pipeline:
            talp_cli(["merge-history",
                      "--history", os.path.join(prev_pipeline, "talp"),
                      "--current", talp_dir])
        # talp ci-report
        site = os.path.join(pipeline, "public", "talp")
        talp_cli(["ci-report", "-i", talp_dir, "-o", site,
                  "--regions", "train_step", "--region-for-badge", "train_step"])
        prev_pipeline = pipeline

    findings = json.load(open(os.path.join(site, "findings.json")))
    print(f"\nfinal report: {os.path.join(site, 'index.html')}")
    print(f"findings ({len(findings)}):")
    for f in findings:
        print("  -", f["description"])
    regressions = [f for f in findings if f["kind"] == "regression"
                   and f["commit"] == "ccc333"]
    assert regressions, "the buggy commit must be detected"
    print("\nCI workflow reproduced: buggy commit ccc333 detected "
          f"and explained via {regressions[0]['explanation']}")


if __name__ == "__main__":
    main()
