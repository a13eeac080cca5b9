"""The 10 assigned architectures (exact configs from the assignment table),
granite-4.0-h-small, and reduced smoke variants. ``[source; tier]`` noted per arch.

Deviations from upstream checkpoints (documented in DESIGN.md §5):
  * hubert uses RoPE instead of its conv relative positional embedding
    (frontend is a stub per the assignment; pos-emb choice does not change
    the backbone's compute/communication shape);
  * zamba2's shared attention blocks are materialized per repeat (no
    cross-layer weight tying) — same compute, slightly more parameters;
  * vocab sizes are padded up to multiples of 256 for sharding (e.g.
    hubert 504 -> 512); loss masks the padded ids.
"""

from __future__ import annotations

from repro.configs.base import ModelConfig, MoEConfig, SSMConfig, XLSTMConfig

FULL_ATTN_SKIP = (
    ("long_500k",
     "pure full-attention arch: O(L^2) attention at 524288 tokens"),
)


def hubert_xlarge() -> ModelConfig:
    # [arXiv:2106.07447; unverified] encoder-only audio (w2v2 arch)
    return ModelConfig(
        name="hubert-xlarge",
        d_model=1280, n_heads=16, n_kv_heads=16, d_ff=5120, vocab=504,
        pattern=("attn",), repeats=48,
        act="gelu", encoder_only=True, frontend="audio",
        rope_theta=10000.0, attn_bias=True,
        norm_eps=1e-5,
    )


def dbrx_132b() -> ModelConfig:
    # [hf:databricks/dbrx-base; unverified] 16 experts top-4, fine-grained
    return ModelConfig(
        name="dbrx-132b",
        d_model=6144, n_heads=48, n_kv_heads=8, d_ff=0, vocab=100352,
        pattern=("moe",), repeats=40,
        moe=MoEConfig(n_experts=16, top_k=4, d_ff=10752, normalize_topk=True),
        rope_theta=500000.0,
        skips=FULL_ATTN_SKIP,
    )


def qwen3_moe_30b() -> ModelConfig:
    # [hf:Qwen/Qwen3-30B-A3B; hf] 128 experts top-8
    return ModelConfig(
        name="qwen3-moe-30b-a3b",
        d_model=2048, n_heads=32, n_kv_heads=4, d_ff=0, vocab=151936,
        head_dim=128,
        pattern=("moe",), repeats=48,
        moe=MoEConfig(n_experts=128, top_k=8, d_ff=768, normalize_topk=True),
        rope_theta=1000000.0, qk_norm=True,
        skips=FULL_ATTN_SKIP,
    )


def zamba2_2p7b() -> ModelConfig:
    # [arXiv:2411.15242; hf] Mamba2 backbone + (shared) attention blocks
    return ModelConfig(
        name="zamba2-2.7b",
        d_model=2560, n_heads=32, n_kv_heads=32, d_ff=10240, vocab=32000,
        pattern=("mamba2", "mamba2", "mamba2", "mamba2", "mamba2", "attn"),
        repeats=9,  # 54 layers
        ssm=SSMConfig(d_state=64, d_conv=4, expand=2, head_dim=64, n_groups=1),
        act="gelu",
        rope_theta=10000.0,
    )


def gemma2_2b() -> ModelConfig:
    # [arXiv:2408.00118; hf] local+global alternating, logit softcaps
    return ModelConfig(
        name="gemma2-2b",
        d_model=2304, n_heads=8, n_kv_heads=4, d_ff=9216, vocab=256000,
        head_dim=256,
        pattern=("local_attn", "attn"), repeats=13,  # 26 layers
        window=4096, attn_softcap=50.0, final_softcap=30.0,
        act="geglu", tie_embeddings=True, embed_scale=True,
        zero_centered_norm=True, rope_theta=10000.0,
        sharding="fsdp",  # 8 heads cannot TP over 16-way model axis
        skips=FULL_ATTN_SKIP,
    )


def tinyllama_1b() -> ModelConfig:
    # [arXiv:2401.02385; hf] llama2-arch small
    return ModelConfig(
        name="tinyllama-1.1b",
        d_model=2048, n_heads=32, n_kv_heads=4, d_ff=5632, vocab=32000,
        pattern=("attn",), repeats=22,
        rope_theta=10000.0,
        skips=FULL_ATTN_SKIP,
    )


def glm4_9b() -> ModelConfig:
    # [hf:THUDM/glm-4-9b; hf] GQA kv=2, partial RoPE, qkv bias
    return ModelConfig(
        name="glm4-9b",
        d_model=4096, n_heads=32, n_kv_heads=2, d_ff=13696, vocab=151552,
        pattern=("attn",), repeats=40,
        rope_theta=10000.0, partial_rotary=0.5, attn_bias=True,
        norm_eps=1.5625e-7,
        skips=FULL_ATTN_SKIP,
    )


def command_r_35b() -> ModelConfig:
    # [hf:CohereForAI/c4ai-command-r-v01; unverified] no-bias, tied embeds
    return ModelConfig(
        name="command-r-35b",
        d_model=8192, n_heads=64, n_kv_heads=8, d_ff=22528, vocab=256000,
        pattern=("attn",), repeats=40,
        rope_theta=8000000.0, tie_embeddings=True,
        norm_eps=1e-5,
        skips=FULL_ATTN_SKIP,
    )


def llava_next_mistral_7b() -> ModelConfig:
    # [hf:llava-hf/llava-v1.6-mistral-7b-hf; unverified] anyres tiling stub
    return ModelConfig(
        name="llava-next-mistral-7b",
        d_model=4096, n_heads=32, n_kv_heads=8, d_ff=14336, vocab=32000,
        pattern=("attn",), repeats=32,
        rope_theta=1000000.0,
        frontend="vlm", n_frontend_tokens=1152,  # anyres patches (stub)
        skips=FULL_ATTN_SKIP,
    )


def granite_4_h_small() -> ModelConfig:
    # [hf:ibm-granite/granite-4.0-h-small; hf] 36 Mamba2 + 4 NoPE GQA
    # mixers, each followed by a 72-expert top-10 MoE and a shared expert
    return ModelConfig(
        name="granite-4.0-h-small",
        d_model=4096, n_heads=32, n_kv_heads=8, d_ff=0, vocab=100352,
        head_dim=128,
        pattern=("mamba2_moe",) * 5 + ("moe",) + ("mamba2_moe",) * 4,
        repeats=4,  # 40 layers, attention at 5, 15, 25, 35
        ssm=SSMConfig(d_state=128, d_conv=4, expand=2, head_dim=64,
                      n_groups=1, chunk=256),
        moe=MoEConfig(n_experts=72, top_k=10, d_ff=768, n_shared_experts=2,
                      normalize_topk=True),  # shared expert 2 x 768 = 1536
        rope_theta=0.0,  # NoPE
        sm_scale=0.0078125,  # attention_multiplier
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=16.0,
        tie_embeddings=True,
        norm_eps=1e-5,
    )


def xlstm_350m() -> ModelConfig:
    # [arXiv:2405.04517; unverified] 7:1 mLSTM:sLSTM blocks; d_ff=0 ->
    # projections live inside the cells (xLSTM pre-up-projection blocks)
    return ModelConfig(
        name="xlstm-350m",
        d_model=1024, n_heads=4, n_kv_heads=4, d_ff=0, vocab=50304,
        pattern=("mlstm",) * 7 + ("slstm",), repeats=3,  # 24 layers
        xlstm=XLSTMConfig(proj_factor=2.0, d_conv=4),
        act="geglu",
        rope_theta=0.0,
        sharding="fsdp",  # 4 heads cannot TP over 16-way model axis
    )


ARCHS = {
    "hubert-xlarge": hubert_xlarge,
    "dbrx-132b": dbrx_132b,
    "qwen3-moe-30b-a3b": qwen3_moe_30b,
    "zamba2-2.7b": zamba2_2p7b,
    "gemma2-2b": gemma2_2b,
    "tinyllama-1.1b": tinyllama_1b,
    "glm4-9b": glm4_9b,
    "command-r-35b": command_r_35b,
    "llava-next-mistral-7b": llava_next_mistral_7b,
    "xlstm-350m": xlstm_350m,
    "granite-4.0-h-small": granite_4_h_small,
}


def smoke_config(name: str) -> ModelConfig:
    """Reduced same-family config: runs a forward/train step on CPU."""
    cfg = ARCHS[name]()
    kw: dict = dict(
        d_model=128,
        n_heads=4,
        n_kv_heads=min(4, cfg.n_kv_heads),
        head_dim=32,
        d_ff=0 if cfg.d_ff == 0 else 256,
        vocab=512,
        repeats=2,
        q_chunk=64,
        kv_chunk=64,
        remat="none",
        n_frontend_tokens=16 if cfg.frontend == "vlm" else 0,
    )
    if cfg.moe:
        kw["moe"] = MoEConfig(
            n_experts=8, top_k=2, d_ff=64,
            normalize_topk=cfg.moe.normalize_topk,
            n_shared_experts=cfg.moe.n_shared_experts,
        )
    if cfg.ssm:
        kw["ssm"] = SSMConfig(d_state=16, d_conv=4, expand=2, head_dim=16, chunk=32)
    if cfg.xlstm:
        kw["xlstm"] = XLSTMConfig(proj_factor=2.0, d_conv=4, chunk=32)
    return cfg.replace(**kw)


# ---------------------------------------------------------------------------
# Optimized presets — the §Perf hillclimbing outcomes (EXPERIMENTS.md).
# Baselines stay paper-faithful; these are the beyond-paper configurations,
# selectable via ``--optimized`` in repro.launch.dryrun / benchmarks.hillclimb.
# ---------------------------------------------------------------------------

def optimized_config(name: str) -> ModelConfig:
    import dataclasses as _dc

    cfg = ARCHS[name]()
    small_active = cfg.active_param_count() < 5e9
    kw: dict = {"causal_skip": not cfg.encoder_only,
                "q_chunk": 1024, "kv_chunk": 1024}
    if small_active and cfg.sharding == "megatron":
        # <5B active: activation gathers dominate param gathers (cell A/B)
        kw["sharding"] = "fsdp"
    if cfg.moe:
        kw["moe"] = _dc.replace(cfg.moe, capacity_factor=1.0)
    return cfg.replace(**kw)
