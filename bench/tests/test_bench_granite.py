"""The hybrid Mamba2/attention MoE cell's pieces on the CPU: its driver end
to end at a small size (a test-only cell added to a copy of the benchmark
as new files and new entries), its two readers on that run's trace read
as a chip's would be, the check against a scan that drops the SSM state
between prefill chunks, and ``flops_hybrid``'s arithmetic against hand
counts."""

import json
import pathlib
import sys
import time

import jax
import pytest

import flops_hybrid as F
import harness
import peaks
import smoke

BENCH = pathlib.Path(__file__).resolve().parents[1]
SEED = 2**31 + 4099
sys.path.insert(0, str(BENCH / "metrics"))

# Granite's equations at a small size, in bf16 as the cell serves: 2 Mamba2
# layers and 1 attention layer, 8 experts with 3 a token, 4 of them (2-5)
# held. Logits are about 1e-3 here: the program's gap reads about 1e-5 (a
# bf16 rounding that flips a near-tie), the state-reset fault's 5e-4
TINY = {
    "name": "tiny-granite", "arch": "granite-4.0-h-small",
    "overrides": {"d_model": 64, "n_heads": 8, "n_kv_heads": 2, "head_dim": 16,
                  "vocab": 512, "pattern": ["mamba2_moe", "moe", "mamba2_moe"],
                  "repeats": 1, "sm_scale": 0.0625, "q_chunk": 64, "kv_chunk": 64,
                  "paged_attn_impl": "interpret",
                  "ssm": {"d_state": 16, "head_dim": 16, "chunk": 16},
                  "moe": {"n_experts": 8, "top_k": 3, "d_ff": 32, "held": [2, 4]}},
    "model": {"d_model": 64, "n_heads": 8, "n_kv_heads": 2, "head_dim": 16,
              "vocab": 512, "vocab_padded": 512, "n_layers": 3,
              "layer_types": ["mamba", "attention", "mamba"],
              "ssm_heads": 8, "ssm_head_dim": 16, "d_state": 16, "d_conv": 4,
              "n_groups": 1, "n_experts": 8, "top_k": 3, "expert_d_ff": 32,
              "shared_d_ff": 64, "experts_held": [2, 4],
              "attention_multiplier": 0.0625, "embedding_multiplier": 12.0,
              "residual_multiplier": 0.22, "logits_scaling": 16.0,
              "norm_eps": 1e-05, "tie_embeddings": True},
    "serve": {"page_size": 16, "num_pages": 64},
    "check": {"logit_gap_limit": 1e-4, "min_tokens": 60, "max_requests": 12},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = smoke.tree(tmp_path_factory.mktemp("bench"))
    (root / "bench" / "configs" / "tiny-granite.json").write_text(json.dumps(TINY))
    (root / "bench" / "workloads" / "tiny-granite-chat.json").write_text(json.dumps(
        {"driver": "granite", "slots": 4, "max_len": 256, "rate_rps": 12.0}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-granite", "source": "test", "why": "test",
                             "file": "bench/configs/tiny-granite.json", "reduced": []})
    bench["workloads"].append({"name": "tiny-granite-chat", "config": "tiny-granite",
                               "traffic": "tiny-chat-mix", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "granite-chat" in m.get("workloads", ()):
            m["workloads"].append("tiny-granite-chat")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def drive(root, trace=False, seconds=1.0):
    cell = harness.load_cell("tiny-granite-chat", root)
    out = harness.driver(cell).run(cell, SEED, seconds, trace, jax.devices()[:1],
                                   time.time())
    return cell, out


def read(name, ctx):
    return harness.load_module(BENCH / "metrics" / f"{name.split('.')[0]}.py",
                               "bench_granite_reader").read(ctx, name)


def test_granite_cell_end_to_end(root, capsys):
    runmod = harness.load_module(BENCH / "run.py", "bench_run_granite")
    cell = harness.load_cell("tiny-granite-chat", root)
    res = runmod.run_cell(cell, SEED, 1.0, False, jax.devices()[:1])
    harness.emit(res)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"ttft_p90_ms", "setup_s"}
    assert 0 <= line["checks"]["logit_gap"]["value"] <= 1e-4


def test_readers_on_a_traced_run(root):
    """The CPU trace read as a chip's would be: the program's MoE
    counters are there, so both readers read; the trace of a program
    without them (a recorded v5e trace) reads nothing."""
    cell, out = drive(root, trace=True, seconds=2.0)
    tr = harness.load_module(BENCH / "trace.py", "bench_trace_granite")
    ctx = {"cell": cell, "trace": tr.reduce(out["trace_path"]), "out": out,
           "peaks": peaks.V5E, "model": cell.model}
    mfu = read("mfu_hybrid.granite", ctx)
    imb = read("moe_imbalance.granite", ctx)
    assert 0 < mfu < 100
    assert 1.0 <= imb <= 4.0          # 4 held experts: at most all pairs on one
    small = str(BENCH / "tests" / "data" / "v5e_small.xplane.pb")
    ctx = {"cell": cell, "trace": tr.reduce(small), "out": dict(out, trace_path=small),
           "peaks": peaks.V5E, "model": cell.model}
    assert read("mfu_hybrid.granite", ctx) is None
    assert read("moe_imbalance.granite", ctx) is None


def test_state_reset_between_chunks_is_not_correct(root, monkeypatch):
    """A scan that starts every prefill chunk from a zero SSM state."""
    import repro.layers.recurrent as R
    import repro.serve.serve as S

    orig = R._ssd_chunked
    monkeypatch.setattr(R, "_ssd_chunked",
                        lambda *a, init_state=None, **k: orig(*a, **k))
    S._serve_step_fns.cache_clear()
    try:
        _, out = drive(root)
    finally:
        S._serve_step_fns.cache_clear()
    gap, limit = out["checks"]["logit_gap"]
    assert out["correct"] is False and gap > limit


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

# small enough to count by hand: 2 Mamba2 layers and 1 attention layer
M = dict(TINY["model"], d_model=8, n_heads=2, n_kv_heads=1, head_dim=4, vocab=32,
         vocab_padded=32, ssm_heads=2, ssm_head_dim=4, d_state=2, d_conv=3,
         n_experts=4, top_k=2, expert_d_ff=6, shared_d_ff=10, experts_held=[1, 2])


def test_hybrid_counts_by_hand():
    # d_inner 8, conv channels 8 + 2 x 2 = 12, in_proj 2 x 8 + 4 + 2 = 22
    mamba = 8 * 22 + 3 * 12 + 12 + 3 * 2 + 8 + 8 * 8
    assert F.mamba_params(M) == mamba == 302
    assert F.attention_params(M) == 2 * 8 * 8 + 2 * 8 * 4 == 192
    # each layer: router 8 x 4, 2 held experts of 3 x 8 x 6, shared 3 x 8 x
    # 10, two norms; a final norm; the tied table 32 x 8
    ffn = 32 + 2 * 144 + 240 + 16
    assert F.param_count(M) == 2 * mamba + 192 + 3 * ffn + 8 + 256
    # per slot: 2 layers of float32 state 2 x 4 x 2 and bf16 conv state 2 x 12
    assert F.state_bytes_per_slot(M) == 2 * (4 * 16 + 2 * 2 * 12) == 224
    assert F.kv_bytes_per_token(M) == 1 * 2 * 1 * 4 * 2


def test_hybrid_decode_and_prefill():
    lens, pairs = [3, 10], 5
    tok = (2 * (2 * (8 * 22 + 8 * 8) + 2 * 3 * 12 + 6 * 16)
           + 2 * 192 + 3 * 2 * (8 * 4 + 3 * 8 * 10))
    assert F.token_flops(M) == tok
    assert F.decode_flops(M, lens, pairs) == (
        tok * 2 + 2 * 144 * pairs + 4 * 2 * 4 * 13 + 2 * 8 * 32 * 2)
    w = 2 * (F.param_count(M) + 2 * 8)
    assert F.decode_bytes(M, lens) == w + 2 * 224 * 2 + 16 * 13 + 16 * 2
    assert F.prefill_flops(M, 5, 3, pairs) == (
        tok * 3 + 2 * 144 * pairs + 4 * 2 * 4 * (6 + 7 + 8) + 2 * 8 * 32)
    assert F.prefill_bytes(M, 5, 3) == 2 * (F.param_count(M) + 3 * 8) + 2 * 224 + 16 * 8 + 16 * 3


def test_granite_10l_sizes():
    """The configuration's own numbers: 3.26 billion parameters, the
    decode step's roofline about 14 ms at 64 slots (mostly bytes)."""
    m = json.loads((BENCH / "configs" / "granite-4.0-h-small-10l.json").read_text())["model"]
    assert F.n_mamba(m) == 9 and F.n_attention(m) == 1
    assert F.param_count(m) == 3_264_039_552
    assert F.state_bytes_per_slot(m) == 9 * (4 * 128 * 64 * 128 + 2 * 3 * 8448)
    assert F.kv_bytes_per_token(m) == 4096
    lens = [300] * 64
    t = peaks.roofline_s(F.decode_flops(m, lens, 64 * 10 * 10 / 4),
                         F.decode_bytes(m, lens), peaks.V5E)
    assert 0.013 < t < 0.015
