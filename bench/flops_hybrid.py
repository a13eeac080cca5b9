"""Useful-work arithmetic of the hybrid Mamba2/attention MoE model
(Granite-4.0-H): the operations and bytes of one serving dispatch, from
the sizes in a configuration file's ``model`` block.

Counted as the algorithm needs them, as in ``bench/flops.py``: Mamba2's
projections, its causal convolution and its scan in the recurrent form
(per token and head: the state's decay, the ``dt B x`` update and the
``C h`` read, 6 flops an element of the (head_dim, d_state) state);
each SSM state read and written once per live slot; the held experts by
the token-expert pairs routed to them (each held expert's weights read
once a dispatch: at 64 slots x 10 picks of 72 an expert goes untouched
with probability below 1e-4, at a 32-token chunk below 1%); the shared
expert and the router for every token; attention at the live lengths.
The head is the tied embedding: a whole-table read and a matmul a row
of logits; the input rows are a gather.
"""

from __future__ import annotations

BF16, F32 = 2, 4


def n_attention(m: dict) -> int:
    return m["layer_types"][: m["n_layers"]].count("attention")


def n_mamba(m: dict) -> int:
    return m["n_layers"] - n_attention(m)


def _mamba_dims(m: dict) -> tuple[int, int, int]:
    """(d_inner, conv channels, in_proj width)."""
    di = m["ssm_heads"] * m["ssm_head_dim"]
    gn = m["n_groups"] * m["d_state"]
    return di, di + 2 * gn, 2 * di + 2 * gn + m["ssm_heads"]


def mamba_params(m: dict) -> int:
    """One Mamba2 mixer: in_proj, conv weights and bias, A_log, D,
    dt_bias, the gated norm and out_proj."""
    d = m["d_model"]
    di, conv, proj = _mamba_dims(m)
    return d * proj + m["d_conv"] * conv + conv + 3 * m["ssm_heads"] + di + di * d


def attention_params(m: dict) -> int:
    d, hq, hkv, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return 2 * d * hq * hd + 2 * d * hkv * hd


def expert_params(m: dict) -> int:
    return 3 * m["d_model"] * m["expert_d_ff"]


def param_count(m: dict) -> int:
    """Every parameter held on the chip: the mixers, each layer's router,
    held experts, shared expert and two norms, the final norm and the
    (tied) embedding."""
    d = m["d_model"]
    ffn = (d * m["n_experts"] + m["experts_held"][1] * expert_params(m)
           + 3 * d * m["shared_d_ff"] + 2 * d)
    return (n_mamba(m) * mamba_params(m) + n_attention(m) * attention_params(m)
            + m["n_layers"] * ffn + d + m["vocab_padded"] * d)


def weight_bytes(m: dict, rows: int) -> float:
    """Weights one dispatch reads: every weight once, the embedding as the
    tied head's whole table, and ``rows`` rows of it gathered as input."""
    return BF16 * (param_count(m) + rows * m["d_model"])


def state_bytes_per_slot(m: dict) -> int:
    """A slot's recurrent state: float32 SSM state and bf16 conv state of
    every Mamba2 layer."""
    _, conv, _ = _mamba_dims(m)
    ssm = F32 * m["ssm_heads"] * m["ssm_head_dim"] * m["d_state"]
    return n_mamba(m) * (ssm + BF16 * (m["d_conv"] - 1) * conv)


def kv_bytes_per_token(m: dict) -> int:
    return n_attention(m) * 2 * m["n_kv_heads"] * m["head_dim"] * BF16


def token_flops(m: dict) -> float:
    """A token's work outside the routed experts, attention scores and the
    head: the mixers' projections, conv and scan, each layer's router and
    shared expert."""
    d = m["d_model"]
    di, conv, proj = _mamba_dims(m)
    state = m["ssm_heads"] * m["ssm_head_dim"] * m["d_state"]
    mamba = 2.0 * (d * proj + di * d) + 2.0 * m["d_conv"] * conv + 6.0 * state
    ffn = 2.0 * (d * m["n_experts"] + 3 * d * m["shared_d_ff"])
    return (n_mamba(m) * mamba + n_attention(m) * 2.0 * attention_params(m)
            + m["n_layers"] * ffn)


def _attn_flops(m: dict, keys: float) -> float:
    return 4.0 * m["n_heads"] * m["head_dim"] * keys * n_attention(m)


def _expert_flops(m: dict, pairs: float) -> float:
    return 2.0 * expert_params(m) * pairs


def _head_flops(m: dict, rows: int) -> float:
    return 2.0 * m["d_model"] * m["vocab"] * rows


def decode_flops(m: dict, lens, pairs: float) -> float:
    """One decode step. ``lens``: visible cache length of each live slot,
    the new token included; ``pairs``: token-expert pairs routed to held
    experts, over all layers."""
    n = len(lens)
    return (token_flops(m) * n + _expert_flops(m, pairs)
            + _attn_flops(m, float(sum(lens))) + _head_flops(m, n))


def decode_bytes(m: dict, lens) -> float:
    n = len(lens)
    kv = kv_bytes_per_token(m)
    return (weight_bytes(m, n) + 2.0 * state_bytes_per_slot(m) * n
            + kv * float(sum(lens)) + kv * n)


def prefill_flops(m: dict, start: int, length: int, pairs: float) -> float:
    """One prefill chunk of ``length`` tokens after ``start`` (logits at its
    last position only)."""
    keys = sum(start + i + 1 for i in range(length))
    return (token_flops(m) * length + _expert_flops(m, pairs)
            + _attn_flops(m, float(keys)) + _head_flops(m, 1))


def prefill_bytes(m: dict, start: int, length: int) -> float:
    kv = kv_bytes_per_token(m)
    return (weight_bytes(m, length) + 2.0 * state_bytes_per_slot(m)
            + kv * (start + length) + kv * length)
