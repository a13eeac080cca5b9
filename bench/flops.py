"""Useful-work arithmetic: the operations and bytes a step or a kernel call
needs, from the sizes in a configuration file's ``model`` block.

Counted as the algorithm needs them, never as the program happens to do
them: no recomputation, no padding, no masked slots. The embedding table is
a gather of the rows used (never a matmul or a whole-table read), and
attention and cache terms use the live context lengths, not ``max_len``.
"""

from __future__ import annotations

BF16 = 2


# ---------------------------------------------------------------------------
# dense decoder (attention + gated MLP)
# ---------------------------------------------------------------------------


def dense_layer_matmul_params(m: dict) -> int:
    d, hq, hkv, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                         m["head_dim"], m["d_ff"])
    return d * hq * hd * 2 + d * hkv * hd * 2 + 3 * d * f


def dense_param_count(m: dict) -> int:
    """Every parameter of the model, as held on the chip."""
    d, hq, hkv, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    per_layer = dense_layer_matmul_params(m) + 2 * d
    if m.get("attn_bias"):
        per_layer += (hq + 2 * hkv) * hd
    head = 0 if m.get("tie_embeddings") else d * m["vocab_padded"]
    return m["n_layers"] * per_layer + m["vocab_padded"] * d + head + d


def dense_weight_bytes(m: dict, rows: int) -> float:
    """Bytes of weights one step reads: every weight once, and ``rows``
    rows of the embedding table."""
    embed = m["vocab_padded"] * m["d_model"]
    return BF16 * (dense_param_count(m) - embed + rows * m["d_model"])


def kv_bytes_per_token(m: dict) -> int:
    return m["n_layers"] * 2 * m["n_kv_heads"] * m["head_dim"] * BF16


def _attn_flops(m: dict, q_rows: int, keys: float) -> float:
    """Scores and weighted values of ``q_rows`` queries over ``keys``
    visible keys in total, over all layers (2 flops a multiply-add)."""
    return 4.0 * m["n_heads"] * m["head_dim"] * keys * m["n_layers"]


def decode_flops(m: dict, lens) -> float:
    """One decode step. ``lens``: visible cache length of each live slot,
    the new token included."""
    per_tok = 2.0 * (m["n_layers"] * dense_layer_matmul_params(m)
                     + m["d_model"] * m["vocab"])
    return per_tok * len(lens) + _attn_flops(m, len(lens), float(sum(lens)))


def decode_bytes(m: dict, lens) -> float:
    n = len(lens)
    kv = kv_bytes_per_token(m)
    return dense_weight_bytes(m, n) + kv * float(sum(lens)) + kv * n


def prefill_flops(m: dict, start: int, length: int) -> float:
    """One prefill chunk of ``length`` tokens after ``start`` cached ones;
    logits at the chunk's last position only."""
    keys = sum(start + i + 1 for i in range(length))
    return (2.0 * m["n_layers"] * dense_layer_matmul_params(m) * length
            + 2.0 * m["d_model"] * m["vocab"]
            + _attn_flops(m, length, float(keys)))


def prefill_bytes(m: dict, start: int, length: int) -> float:
    kv = kv_bytes_per_token(m)
    return dense_weight_bytes(m, length) + kv * (start + length) + kv * length


def paged_decode_kernel(m: dict, lens) -> tuple[float, float]:
    """(flops, bytes) of the decode attention kernel, all layers: read the
    live K/V once, read q and write o for each slot."""
    flops = _attn_flops(m, len(lens), float(sum(lens)))
    qo = 2 * m["n_heads"] * m["head_dim"] * BF16 * m["n_layers"]
    return flops, kv_bytes_per_token(m) * float(sum(lens)) + qo * len(lens)


def paged_prefill_kernel(m: dict, start: int, length: int) -> tuple[float, float]:
    keys = sum(start + i + 1 for i in range(length))
    flops = _attn_flops(m, length, float(keys))
    qo = 2 * m["n_heads"] * m["head_dim"] * BF16 * m["n_layers"] * length
    return flops, kv_bytes_per_token(m) * (start + length) + qo


# ---------------------------------------------------------------------------
# xLSTM (mLSTM and sLSTM blocks)
# ---------------------------------------------------------------------------


def _xlstm_dims(m: dict) -> tuple[int, int, int, int]:
    d, h = m["d_model"], m["n_heads"]
    di = int(m["proj_factor"] * d)
    return d, h, di, di // h


def xlstm_matmul_params(m: dict) -> int:
    """Parameters that each take part in one multiply-add per token: the
    projections, the depthwise convolution, the sLSTM recurrence and its
    MLP, and the head over the real vocabulary (the embedding is a gather)."""
    d, h, di, _ = _xlstm_dims(m)
    mlstm = d * 2 * di + m["d_conv"] * di + 3 * di * di + di * 2 * h + di * d
    dh = d // h
    slstm = d * 4 * d + h * dh * 4 * dh + 3 * d * m["slstm_ff"]
    n_m = sum(k == "mlstm" for k in m["pattern"]) * m["repeats"]
    n_s = sum(k == "slstm" for k in m["pattern"]) * m["repeats"]
    return n_m * mlstm + n_s * slstm + d * m["vocab"]


def xlstm_mixing_flops_per_token(m: dict) -> float:
    """Forward mLSTM cell work per token in its recurrent form: the state
    update C += i k v^T and the read q^T C (2 flops a multiply-add), with
    the normalizer n alongside."""
    _, h, _, p = _xlstm_dims(m)
    n_m = sum(k == "mlstm" for k in m["pattern"]) * m["repeats"]
    return n_m * (4.0 * h * p * p + 4.0 * h * p)


def train_flops_per_token(m: dict) -> float:
    """Forward and backward: 6 flops a matmul parameter, and three times
    the forward mixing work."""
    return 6.0 * xlstm_matmul_params(m) + 3.0 * xlstm_mixing_flops_per_token(m)
