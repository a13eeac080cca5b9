"""Model / run configuration schema.

One ``ModelConfig`` describes any architecture in ``configs/archs.py``
through a *block pattern*: the layer stack is ``pattern`` (a short period of
block kinds) repeated ``repeats`` times — scanned over ``repeats`` so the HLO
stays compact (period blocks are materialized once, stacked over repeats).

Block kinds: "attn" (global attention + FFN), "local_attn" (sliding-window +
FFN), "moe" (attention + MoE FFN), "mamba2", "mamba2_moe" (Mamba2 mixer +
MoE FFN), "mlstm", "slstm".
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax.numpy as jnp

BLOCK_KINDS = ("attn", "local_attn", "moe", "mamba2", "mamba2_moe", "mlstm",
               "slstm")
MOE_KINDS = ("moe", "mamba2_moe")
RECURRENT_KINDS = ("mamba2", "mamba2_moe", "mlstm", "slstm")


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    normalize_topk: bool = True
    gated: bool = True
    # the experts this chip holds, (first, count); None: every expert. The
    # router keeps all n_experts outputs; pairs routed elsewhere add nothing
    held: tuple[int, int] | None = None

    def __post_init__(self):
        if self.held is not None:   # any pair, e.g. a JSON list; kept hashable
            object.__setattr__(self, "held", tuple(self.held))

    @property
    def n_held(self) -> int:
        return self.n_experts if self.held is None else self.held[1]

    @property
    def first_held(self) -> int:
        return 0 if self.held is None else self.held[0]

    def capacity(self, n_tokens: int) -> int:
        c = math.ceil(n_tokens * self.top_k * self.capacity_factor / self.n_experts)
        # MXU-friendly multiple of 128, never above total routed pairs
        c = min(max(128, -(-c // 128) * 128), n_tokens)
        return c


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 64
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk: int = 256

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class XLSTMConfig:
    proj_factor: float = 2.0
    d_conv: int = 4
    chunk: int = 256
    slstm_ff_factor: float = 4.0 / 3.0

    def d_inner(self, d_model: int) -> int:
        return int(self.proj_factor * d_model)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    pattern: tuple[str, ...]
    repeats: int
    head_dim: int = 0  # 0 -> d_model // n_heads

    # attention details
    rope_theta: float = 10000.0
    partial_rotary: float = 1.0
    window: int | None = None          # sliding window for "local_attn"
    attn_softcap: float | None = None
    final_softcap: float | None = None
    attn_bias: bool = False
    qk_norm: bool = False
    sm_scale: float | None = None      # score scale; None: 1/sqrt(head_dim)

    # families
    moe: MoEConfig | None = None
    ssm: SSMConfig | None = None
    xlstm: XLSTMConfig | None = None

    # model shape/behaviour
    encoder_only: bool = False
    frontend: str | None = None        # None | "audio" | "vlm" (stub embeddings)
    n_frontend_tokens: int = 0         # patches/frames provided by the stub
    tie_embeddings: bool = False
    embed_scale: bool = False          # gemma-style sqrt(d) embedding scale
    # granite-style scalars: the embedding times embedding_multiplier, each
    # sublayer's output into the residual times residual_multiplier, the
    # logits divided by logits_scaling
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    zero_centered_norm: bool = False   # gemma-style (1+scale) rmsnorm
    act: str = "swiglu"
    norm_eps: float = 1e-6

    # numerics
    param_dtype_name: str = "bfloat16"
    compute_dtype_name: str = "bfloat16"

    # attention chunking (flash-style scan) + perf knobs
    q_chunk: int = 512
    kv_chunk: int = 1024
    causal_skip: bool = False
    # paged decode-attention kernel implementation (serving):
    # auto (Pallas on TPU, reference elsewhere) | pallas | interpret | reference
    paged_attn_impl: str = "auto"

    # distribution
    sharding: str = "megatron"         # megatron | fsdp  (auto-checked)
    remat: str = "full"                # none | full | dots
    scan_layers: bool = True

    # which input shapes are skipped, mapping shape-name -> reason
    skips: tuple[tuple[str, str], ...] = ()

    # training details
    z_loss: float = 1e-4
    moe_lb_coef: float = 0.01
    moe_z_coef: float = 1e-3

    # -- derived --

    @property
    def n_layers(self) -> int:
        return len(self.pattern) * self.repeats

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def compute_dtype(self):
        return jnp.dtype(self.compute_dtype_name)

    @property
    def param_dtype(self):
        return jnp.dtype(self.param_dtype_name)

    @property
    def vocab_padded(self) -> int:
        """vocab rounded up so the logits dim shards over 256 devices."""
        return -(-self.vocab // 256) * 256

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # parameter count estimate (for 6ND model-FLOPs and logging)
    def param_count(self) -> int:
        from repro.models.transformer import model_params
        from repro.layers.common import count_params

        return count_params(model_params(self))

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: top_k of n_experts)."""
        total = self.param_count()
        if self.moe is None:
            return total
        m = self.moe
        per_expert = 3 * self.d_model * m.d_ff if m.gated else 2 * self.d_model * m.d_ff
        n_moe_layers = sum(1 for k in self.pattern if k in MOE_KINDS) * self.repeats
        inactive = n_moe_layers * per_expert * (m.n_held - m.top_k)
        return total - inactive


# registry filled by configs/__init__.py
_REGISTRY: dict[str, Any] = {}


def register(name: str, fn) -> None:
    _REGISTRY[name] = fn


def get_config(name: str) -> ModelConfig:
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]()


def list_archs() -> list[str]:
    return sorted(_REGISTRY)
