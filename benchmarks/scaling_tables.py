"""Paper Tables 6/7 — weak and strong scaling-efficiency tables from real
executions of the training job at 1, 2 and 4 devices.

Every device count runs in this one process, on a mesh over
``jax.devices()[:n]``: a child process could not reach a chip this process
already holds. On the CPU the devices are forced host devices, which must
be set before jax is imported (running this file does that). The weak run
scales the global batch with devices; the strong run keeps it fixed.

    PYTHONPATH=src:. python benchmarks/scaling_tables.py
"""

from __future__ import annotations

import os
import shutil

from benchmarks.common import RESULTS_DIR, csv_line, force_host_devices, save_result

DEVICE_COUNTS = (1, 2, 4)


def measure(cfg, counts, *, global_batch, seq_len: int, steps: int,
            out_dir: str, pad_fraction: float = 0.1) -> list[dict]:
    """Train ``cfg`` for ``steps`` steps on a mesh over the first ``n``
    devices, for each ``n`` in ``counts``, and save each run record as
    ``out_dir/talp_1x{n}.json``. ``global_batch(n)`` is the batch at ``n``
    devices. Returns per count: ``devices``, the step ``losses``, the ids of
    the devices holding the final train state (``state_devices``) and the
    run ``record``."""
    import jax

    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import build_loop

    results = []
    for n in counts:
        devices = jax.devices()[:n]
        if len(devices) < n:
            raise RuntimeError(
                f"{n} devices asked for, {len(jax.devices())} present"
            )
        loop = build_loop(
            cfg, make_host_mesh(devices=devices), steps=steps,
            global_batch=global_batch(n), seq_len=seq_len,
            pad_fraction=pad_fraction, app_name="miniapp",
        )
        loop.run()
        record = loop.finalize_run()
        record.save(os.path.join(out_dir, f"talp_1x{n}.json"))
        held = {
            d.id for leaf in jax.tree_util.tree_leaves(loop.final_state)
            for d in leaf.sharding.device_set
        }
        results.append({
            "devices": n,
            "losses": [h["loss"] for h in loop.metrics_history],
            "state_devices": sorted(held),
            "record": record,
        })
        # free this count's train state before the next count allocates
        del loop
    return results


def run(root: str = os.path.join(RESULTS_DIR, "scaling"), steps: int = 10) -> dict:
    from repro.configs import smoke_config
    from repro.core import build_table, render_text

    cfg = smoke_config("tinyllama-1.1b")
    shutil.rmtree(root, ignore_errors=True)
    result = {}
    for kind, batch in (("strong", lambda n: 8), ("weak", lambda n: 4 * n)):
        runs = measure(cfg, DEVICE_COUNTS, global_batch=batch, seq_len=64,
                       steps=steps, out_dir=os.path.join(root, f"{kind}_scaling"))
        table = build_table([r["record"] for r in runs])
        result[f"{kind}_mode_detected"] = table.mode
        result[f"{kind}_table"] = table.to_json()
        result[f"{kind}_text"] = render_text(table)
    save_result("tables67_scaling", result)
    return result


def main() -> list[str]:
    r = run()
    print(r["strong_text"])
    print()
    print(r["weak_text"])
    ok_modes = (r["strong_mode_detected"] == "strong"
                and r["weak_mode_detected"] == "weak")
    return [
        csv_line("tables67_scaling_modes", 0.0,
                 f"strong={r['strong_mode_detected']} weak={r['weak_mode_detected']} "
                 f"detection_correct={ok_modes}"),
    ]


if __name__ == "__main__":
    force_host_devices(max(DEVICE_COUNTS))
    print("\n".join(main()))
