"""Useful-work arithmetic against hand counts."""

import pytest

import flops
import peaks

# a dense model small enough to count by hand
M = dict(d_model=8, n_heads=2, n_kv_heads=1, head_dim=4, d_ff=16, vocab=32,
         vocab_padded=32, n_layers=2, attn_bias=True, tie_embeddings=False)


def test_dense_counts_by_hand():
    # per layer: wq 8x8 + wo 8x8 + wk 8x4 + wv 8x4 + mlp 3x8x16 = 576 matmul
    assert flops.dense_layer_matmul_params(M) == 576
    # + norms 2x8 + biases (2+2x1)x4 = 16 + 16 -> 608 a layer; embed 256,
    # head 256, final norm 8
    assert flops.dense_param_count(M) == 2 * 608 + 256 + 256 + 8
    assert flops.kv_bytes_per_token(M) == 2 * 2 * 1 * 4 * 2  # layers x k,v x heads x dim x bf16


def test_decode_bytes_at_live_lengths():
    lens = [3, 10]
    weights = 2 * (2 * 608 + 256 + 8) + 2 * 2 * 8       # all but the table, 2 rows of it
    kv = 32 * (3 + 10) + 32 * 2                          # read live K/V, write one token each
    assert flops.decode_bytes(M, lens) == weights + kv
    # flops: 2 a matmul parameter per token (layers and head), and
    # 4 x heads x dim x visible keys x layers for attention
    assert flops.decode_flops(M, lens) == 2 * (2 * 576 + 8 * 32) * 2 + 4 * 2 * 4 * 13 * 2


def test_prefill_counts_keys_causally():
    f, b = flops.paged_prefill_kernel(M, start=5, length=3)
    assert f == 4 * 2 * 4 * (6 + 7 + 8) * 2
    assert b == 32 * 8 + 2 * 2 * 4 * 2 * 2 * 3


def test_glm4_9b_20l_size():
    glm = dict(d_model=4096, n_heads=32, n_kv_heads=2, head_dim=128, d_ff=13696,
               vocab=151552, vocab_padded=151552, n_layers=20, attn_bias=True)
    assert flops.dense_param_count(glm) == 5_320_734_720
    assert flops.kv_bytes_per_token(glm) == 20_480


def test_xlstm_350m_train_flops_per_token():
    x = dict(d_model=1024, n_heads=4, vocab=50304, vocab_padded=50432,
             pattern=["mlstm"] * 7 + ["slstm"], repeats=3, proj_factor=2.0,
             d_conv=4, slstm_ff=1365)
    mlstm = 1024 * 4096 + 4 * 2048 + 3 * 2048 ** 2 + 2048 * 8 + 2048 * 1024
    slstm = 1024 * 4096 + 4 * 256 * 1024 + 3 * 1024 * 1365
    head = 1024 * 50304
    assert flops.xlstm_matmul_params(x) == 21 * mlstm + 3 * slstm + head == 476_697_600
    mix = 21 * (4 * 4 * 512 * 512 + 4 * 4 * 512)
    assert flops.train_flops_per_token(x) == 6 * 476_697_600 + 3 * mix


def test_peaks_by_kind():
    assert peaks.for_kind("TPU v5 lite").flops == 197e12
    with pytest.raises(KeyError):
        peaks.for_kind("TPU v9 imaginary")
    assert peaks.roofline_s(197e12, 0, peaks.V5E) == pytest.approx(1.0)
    assert peaks.roofline_s(0, 819e9, peaks.V5E) == pytest.approx(1.0)
