"""Test-only cells at smoke size, registered as new files in a copy of the
benchmark tree (the way a later change adds a cell)."""

from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]

TINY_GLM = {
    "name": "tiny-glm", "arch": "glm4-9b",
    "overrides": {"d_model": 128, "n_heads": 4, "n_kv_heads": 2, "head_dim": 32,
                  "d_ff": 256, "vocab": 512, "repeats": 2, "q_chunk": 64,
                  "kv_chunk": 64, "paged_attn_impl": "interpret"},
    "model": {"d_model": 128, "n_heads": 4, "n_kv_heads": 2, "head_dim": 32,
              "d_ff": 256, "vocab": 512, "vocab_padded": 512, "n_layers": 2,
              "rope_theta": 10000.0, "partial_rotary": 0.5, "attn_bias": True,
              "norm_eps": 1.5625e-07, "tie_embeddings": False},
    "serve": {"page_size": 16, "num_pages": 64},
    "check": {"logit_gap_limit": 0.1, "min_tokens": 60, "max_requests": 12},
}

TINY_XLSTM = {
    "name": "tiny-xlstm", "arch": "xlstm-350m",
    "overrides": {"d_model": 64, "n_heads": 4, "n_kv_heads": 4, "vocab": 256,
                  "repeats": 1, "remat": "none", "xlstm": {"chunk": 16},
                  "pattern": ["mlstm", "slstm"]},
    "model": {"d_model": 64, "n_heads": 4, "vocab": 256, "vocab_padded": 256,
              "pattern": ["mlstm", "slstm"], "repeats": 1,
              "proj_factor": 2.0, "d_conv": 4, "slstm_ff": 85,
              "norm_eps": 1e-06, "z_loss": 0.0001},
    "optimizer": {"lr": 0.0003, "b1": 0.9, "b2": 0.95, "eps": 1e-08,
                  "weight_decay": 0.1, "grad_clip": 1.0, "warmup_steps": 100,
                  "total_steps": 10000},
    "check": {"loss_gap_limit": 0.002, "grad_median_gap_limit": 0.2, "change_gap_limit": 0.5},
}

CELLS = {
    "tiny-chat": ({"driver": "serve", "slots": 4, "max_len": 256, "rate_rps": 12.0},
                  "tiny-glm", "tiny-chat-mix"),
    "tiny-docs": ({"driver": "serve", "slots": 2, "max_len": 256, "per_client": 4},
                  "tiny-glm", "tiny-docs-mix"),
    "tiny-train": ({"driver": "train"}, "tiny-xlstm", "tiny-train-mix"),
}

MIXES = {
    "tiny-chat-mix": {"loop": "open",
                      "prompt": {"median": 24, "sigma": 1.0, "min": 8, "max": 80},
                      "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}},
    "tiny-docs-mix": {"loop": "closed", "clients": 2,
                      "prompt": {"median": 48, "sigma": 0.6, "min": 16, "max": 100},
                      "output": {"median": 4, "sigma": 0.5, "min": 2, "max": 8}},
    "tiny-train-mix": {"global_batch": 2, "seq_len": 32},
}


def tree(tmp: pathlib.Path, overrides: dict | None = None) -> pathlib.Path:
    """A copy of the benchmark with the test cells added as new files and
    new entries. ``overrides`` replaces fields of the tiny configurations."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for cfg in (TINY_GLM, TINY_XLSTM):
        c = dict(cfg, **(overrides or {}).get(cfg["name"], {}))
        (root / "bench" / "configs" / f"{c['name']}.json").write_text(json.dumps(c))
        bench["configs"].append({"name": c["name"], "source": "test", "why": "test",
                                 "file": f"bench/configs/{c['name']}.json", "reduced": []})
    for name, mix in MIXES.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    for name, (wl, cfg, mix) in CELLS.items():
        (root / "bench" / "workloads" / f"{name}.json").write_text(json.dumps(wl))
        bench["workloads"].append({"name": name, "config": cfg, "traffic": mix,
                                   "chips": 1, "why": "test"})
    per_cell = {"tiny-chat": "glm4-chat", "tiny-train": "xlstm-train"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [t for t, real in per_cell.items() if real in m["workloads"]]
    # the closed-loop cell's throughput, added as a new entry
    bench["end_to_end"].append({"name": "serve_tok_s", "unit": "tokens/s", "better": "higher",
                                "bound": 0.25, "source": "host_clock", "workloads": ["tiny-docs"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
