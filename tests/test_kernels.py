"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_reference
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.paged_attention.ref import (
    gather_pages,
    paged_attention_reference,
)
from repro.kernels.rmsnorm.kernel import rmsnorm_pallas
from repro.kernels.rmsnorm.ref import rmsnorm_reference

KEY = jax.random.PRNGKey(7)


def _qkv(B, Sq, Sk, Hq, Hkv, D, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (B, Sq, Hq, D), jnp.float32).astype(dtype)
    k = jax.random.normal(k2, (B, Sk, Hkv, D), jnp.float32).astype(dtype)
    v = jax.random.normal(k3, (B, Sk, Hkv, D), jnp.float32).astype(dtype)
    return q, k, v


FLASH_CASES = [
    # B, Sq, Sk, Hq, Hkv, D, causal, window, softcap
    (2, 256, 256, 4, 2, 64, True, None, None),
    (1, 128, 128, 4, 4, 64, False, None, None),
    (1, 256, 256, 2, 1, 64, True, 64, None),      # sliding window
    (2, 64, 64, 8, 2, 32, True, None, 30.0),      # softcap (gemma2)
    (1, 200, 200, 2, 2, 48, True, None, None),    # non-multiple-of-block seq
    (1, 96, 96, 2, 1, 100, False, 32, 50.0),      # padding in D + win + cap
]


@pytest.mark.parametrize("case", FLASH_CASES, ids=[str(c[:6]) for c in FLASH_CASES])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_reference(case, dtype):
    B, Sq, Sk, Hq, Hkv, D, causal, window, softcap = case
    q, k, v = _qkv(B, Sq, Sk, Hq, Hkv, D, dtype)
    out = flash_attention_pallas(
        q, k, v, causal=causal, window=window, softcap=softcap,
        block_q=64, block_kv=64, interpret=True,
    )
    ref = attention_reference(q, k, v, causal=causal, window=window, softcap=softcap)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol,
    )


def test_flash_block_shape_independence():
    """Block size is a tuning knob — results must not depend on it."""
    q, k, v = _qkv(1, 192, 192, 2, 2, 64, jnp.float32)
    outs = [
        flash_attention_pallas(q, k, v, causal=True, block_q=bq, block_kv=bk,
                               interpret=True)
        for bq, bk in [(32, 32), (64, 128), (128, 64)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o), atol=1e-5)


def test_flash_matches_model_flash_path():
    """The model's chunked-scan attention and the kernel agree (so the
    kernel can be swapped in on TPU without changing semantics)."""
    from repro.layers.attention import flash_attention as model_flash

    q, k, v = _qkv(2, 128, 128, 4, 2, 64, jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(128)[None], (2, 128))
    a = flash_attention_pallas(q, k, v, causal=True, block_q=64, block_kv=64,
                               interpret=True)
    b = model_flash(q, k, v, q_positions=pos, k_positions=pos, causal=True,
                    q_chunk=64, kv_chunk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


# ---------------------------------------------------------------------------
# paged decode attention: Pallas scalar-prefetch kernel vs gather oracle
# ---------------------------------------------------------------------------


def _pools(key, P, psize, Hkv, D, dtype, layers, layer):
    """Random stacked K and V pools (layers, Hkv, P, psize, D), as the
    model step keeps them; every layer but ``layer`` is NaN, so that a
    read of another layer cannot pass."""
    k1, k2 = jax.random.split(key)
    shape = (layers, Hkv, P, psize, D)
    other = (jnp.arange(layers) != layer)[:, None, None, None, None]
    return [jnp.where(other, jnp.nan, jax.random.normal(k, shape, jnp.float32)
                      ).astype(dtype) for k in (k1, k2)]


def _paged_case(B, Hq, Hkv, D, psize, nL, P, lens, dtype, seed=0, free=(),
                deal=False, poison=(), hole=None, layers=1, layer=0):
    """Random pool + a scrambled (non-identity) block table + ragged lens.
    The pool has ``layers`` layers and the case reads ``layer``.

    Slots in ``free`` keep their ``lens`` but own no page (a table of -1,
    as a released slot). ``deal`` hands the shuffled pages out one at a
    time round the slots, so that each slot's pages interleave with the
    others' across the pool; otherwise each slot takes a run of the
    shuffled order. The values of the slots in ``poison`` are NaN, but
    for physical page 0, which the oracle reads for every -1. ``hole``,
    a (slot, logical page), is unallocated below that slot's length."""
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, D), jnp.float32).astype(dtype)
    k_pages, v_pages = _pools(ks[1], P, psize, Hkv, D, dtype, layers, layer)
    perm = iter(rng.permutation(P))
    need = [0 if b in free else -(-ln // psize) for b, ln in enumerate(lens)]
    tbl = np.full((B, nL), -1, np.int32)
    if deal:
        for j in range(max(need)):
            for b in range(B):
                if j < need[b]:
                    tbl[b, j] = next(perm)
    else:
        for b, n in enumerate(need):
            tbl[b, :n] = [next(perm) for _ in range(n)]
    for b in poison:
        bad = tbl[b, :need[b]]
        v_pages = v_pages.at[:, :, bad[bad != 0]].set(jnp.nan)
    if hole is not None:
        tbl[hole] = -1
    lens = jnp.asarray(lens, jnp.int32)
    return q, k_pages, v_pages, jnp.asarray(tbl), lens, lens - 1


PAGED_CASES = [
    # B, Hq, Hkv, D, psize, nL, P, lens, window, softcap[, options]
    (3, 4, 2, 64, 4, 4, 12, (6, 3, 11), None, None),
    (2, 4, 4, 64, 16, 4, 9, (50, 17), None, None),
    (2, 2, 1, 64, 4, 8, 20, (29, 13), 6, None),     # window crosses pages
    (2, 8, 2, 32, 8, 3, 8, (20, 9), None, 30.0),    # softcap (gemma2)
    (1, 2, 2, 100, 8, 4, 6, (27,), 11, 50.0),       # D padding + win + cap
    # 16 pages (256 rows) a loop step at psize 16: several blocks, partial
    # last blocks and pages, 36 logical pages that 16 does not divide
    (3, 8, 2, 64, 16, 36, 80, (530, 257, 17), None, None),
    (3, 4, 2, 64, 8, 6, 12, (0, 1, 20), None, None),  # cache_len 0 and 1
    # released slots (tables all -1) at cache_len 1 and a stale 77
    (4, 4, 2, 64, 16, 8, 20, (40, 1, 100, 77), None, None, {"free": (1, 3)}),
    (2, 4, 1, 64, 8, 10, 12, (9, 30), None, None),    # long -1 tails
    # window of 40 at 600 and 300 tokens: 35 and 16 leading pages (two
    # and one whole blocks) are never visited; 40 logical pages
    (2, 4, 2, 64, 16, 40, 80, (600, 300), 40, None),
    (2, 8, 2, 128, 16, 20, 48, (310, 45), None, 30.0),  # softcap, 2 blocks
    # pages dealt round the slots: interleaved, shuffled, none shared
    (3, 4, 2, 64, 16, 24, 64, (370, 129, 200), None, None, {"deal": True}),
    # slot 0's NaN values fill both buffer halves; slot 1's unfetched
    # pages must not carry them into its output
    (2, 4, 2, 64, 16, 40, 64, (640, 40), None, None, {"poison": (0,)}),
    # a -1 entry below cache_len inside a block: not fetched, not attended
    (2, 4, 2, 64, 16, 24, 64, (300, 150), None, None, {"hole": (0, 5)}),
    # layer 2 of 3 read in place, and layer 1 of 2 with the head dim
    # padded: the other layers are NaN
    (2, 8, 2, 128, 16, 24, 48, (310, 45), None, None,
     {"layers": 3, "layer": 2}),
    (2, 4, 2, 100, 8, 6, 16, (40, 13), 11, 50.0, {"layers": 2, "layer": 1}),
]


@pytest.mark.parametrize("case", PAGED_CASES, ids=[str(c[:7]) for c in PAGED_CASES])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_matches_reference(case, dtype):
    """Kernel-vs-ref parity in interpret mode: the kernel's walk of each
    slot's live pages (double-buffered copies, several pages a step) and
    online softmax must agree with the gather oracle across GQA, ragged
    lengths over several blocks, windows that straddle pages or drop
    whole blocks, softcap, head-dim padding, -1 tails and holes, and
    scattered pages. A slot with no live key (cache_len 0, or a released
    slot whose table is all -1) gets zeros, where the oracle attends junk."""
    B, Hq, Hkv, D, psize, nL, P, lens, window, softcap, *opt = case
    opt = opt[0] if opt else {}
    q, kp, vp, tbl, lens, qpos = _paged_case(B, Hq, Hkv, D, psize, nL, P,
                                             lens, dtype, **opt)
    layer = opt.get("layer", 0)
    owned = np.asarray(tbl)[np.asarray(tbl) >= 0]
    assert len(set(owned.tolist())) == len(owned)  # no page in two slots
    out = paged_attention_pallas(
        q, kp, vp, tbl, layer=layer, q_position=qpos, cache_len=lens,
        window=window, softcap=softcap, interpret=True,
    )
    ref_tbl, ref_lens = np.array(tbl), np.array(lens)
    if "hole" in opt:
        # the oracle attends page 0 at a -1 below cache_len; attention over
        # a set of keys ignores their order, so the oracle over the table
        # without that page is what the kernel must give
        b, j = opt["hole"]
        ref_tbl[b] = np.append(np.delete(ref_tbl[b], j), -1)
        ref_lens[b] -= psize
    ref = paged_attention_reference(
        q, kp, vp, jnp.asarray(ref_tbl), layer=layer, q_position=qpos,
        cache_len=jnp.asarray(ref_lens), window=window, softcap=softcap,
    )
    assert out.shape == ref.shape and out.dtype == ref.dtype
    dead = (np.asarray(lens) == 0) | np.isin(np.arange(B), opt.get("free", ()))
    ref = jnp.where(dead[:, None, None, None], 0.0, ref).astype(ref.dtype)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol,
    )


def test_paged_reference_bitwise_matches_dense_decode_attention():
    """The bridge that makes scheduler-level paged-vs-dense token identity
    hold: the paged oracle over (pool, table) is BITWISE equal to the
    model's dense ``decode_attention`` over the gathered dense view —
    including with garbage (another slot's data) in the masked tail."""
    from repro.layers.attention import decode_attention

    for window, softcap in [(None, None), (5, None), (None, 30.0), (7, 30.0)]:
        q, kp, vp, tbl, lens, qpos = _paged_case(
            3, 4, 2, 64, 4, 4, 12, (6, 3, 11), jnp.float32, seed=2
        )
        ref = paged_attention_reference(
            q, kp, vp, tbl, layer=0, q_position=qpos, cache_len=lens,
            window=window, softcap=softcap,
        )
        dense = decode_attention(
            q, gather_pages(kp, tbl, 0), gather_pages(vp, tbl, 0),
            q_position=qpos, cache_len=lens, window=window, softcap=softcap,
        )
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(dense))


def test_paged_ops_wrapper_routes_to_reference_on_cpu():
    from repro.kernels import paged_attention

    q, kp, vp, tbl, lens, qpos = _paged_case(
        2, 4, 2, 64, 4, 4, 10, (9, 5), jnp.float32, seed=3
    )
    out = paged_attention(q, kp, vp, tbl, layer=0, q_position=qpos,
                          cache_len=lens)
    ref = paged_attention_reference(q, kp, vp, tbl, layer=0, q_position=qpos,
                                    cache_len=lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


# ---------------------------------------------------------------------------
# paged prefill attention (S>1): multi-token chunk reads over block tables
# ---------------------------------------------------------------------------


def _paged_prefill_case(B, C, Hq, Hkv, D, psize, nL, P, starts, dtype, seed=0,
                        layers=1, layer=0):
    """A prefill chunk of C tokens per sequence at ragged start offsets,
    over a random pool + scrambled block table (like ``_paged_case`` but
    with multi-row queries: the serve path's chunked-prefill reads)."""
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed + 11), 3)
    q = jax.random.normal(ks[0], (B, C, Hq, D), jnp.float32).astype(dtype)
    k_pages, v_pages = _pools(ks[1], P, psize, Hkv, D, dtype, layers, layer)
    perm = rng.permutation(P)
    lens = [s + C for s in starts]
    tbl = np.full((B, nL), -1, np.int32)
    used = 0
    for b, ln in enumerate(lens):
        n = -(-ln // psize)
        tbl[b, :n] = perm[used : used + n]
        used += n
    qpos = np.asarray(starts)[:, None] + np.arange(C)[None]
    return (q, k_pages, v_pages, jnp.asarray(tbl),
            jnp.asarray(qpos, jnp.int32), jnp.asarray(lens, jnp.int32))


PREFILL_CASES = [
    # B, C, Hq, Hkv, D, psize, nL, P, starts, window, softcap
    (2, 8, 4, 2, 64, 4, 6, 14, (0, 8), None, None),     # ragged starts, GQA
    (1, 16, 4, 4, 64, 16, 2, 3, (16,), None, None),     # page == chunk
    (2, 8, 2, 1, 64, 4, 8, 18, (4, 12), 6, None),       # window crosses pages
    (2, 8, 8, 2, 32, 8, 3, 7, (0, 16), None, 30.0),     # softcap (gemma2)
    (1, 8, 2, 2, 100, 8, 4, 5, (8,), 5, 50.0),          # D padding + win + cap
    # layer 2 of 3 read in place; layer 1 of 2 with the head dim padded
    (2, 8, 4, 2, 128, 4, 6, 14, (0, 8), None, None, {"layers": 3, "layer": 2}),
    (1, 8, 2, 2, 100, 8, 4, 6, (8,), 5, 50.0, {"layers": 2, "layer": 1}),
]


@pytest.mark.parametrize("case", PREFILL_CASES,
                         ids=[str(c[:9]) for c in PREFILL_CASES])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_prefill_matches_reference(case, dtype):
    """S>1 kernel-vs-ref parity in interpret mode: per-row causal masking
    inside the chunk (row r attends through start+r, not just cache_len)
    across GQA, ragged starts, windows crossing page boundaries, softcap,
    and head-dim padding."""
    from repro.kernels.paged_attention.kernel import paged_prefill_attention_pallas
    from repro.kernels.paged_attention.ref import paged_prefill_attention_reference

    B, C, Hq, Hkv, D, psize, nL, P, starts, window, softcap, *opt = case
    opt = opt[0] if opt else {}
    q, kp, vp, tbl, qpos, lens = _paged_prefill_case(
        B, C, Hq, Hkv, D, psize, nL, P, starts, dtype, **opt
    )
    layer = opt.get("layer", 0)
    out = paged_prefill_attention_pallas(
        q, kp, vp, tbl, layer=layer, q_positions=qpos, cache_len=lens,
        causal=True, window=window, softcap=softcap, interpret=True,
    )
    ref = paged_prefill_attention_reference(
        q, kp, vp, tbl, layer=layer, q_positions=qpos, cache_len=lens,
        causal=True, window=window, softcap=softcap,
    )
    assert out.shape == ref.shape and out.dtype == ref.dtype
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol,
    )


def test_paged_prefill_reference_bitwise_matches_dense_flash():
    """The S>1 bridge behind scheduler-level paged-vs-dense token identity:
    the paged prefill oracle over (pool, table) is BITWISE equal to the
    model's dense ``flash_attention`` over the gathered view with the same
    chunk grid — including garbage (another slot's data) past cache_len."""
    from repro.kernels.paged_attention.ref import paged_prefill_attention_reference
    from repro.layers.attention import flash_attention as model_flash

    for window, softcap in [(None, None), (6, None), (None, 30.0), (5, 30.0)]:
        q, kp, vp, tbl, qpos, lens = _paged_prefill_case(
            2, 8, 4, 2, 64, 4, 6, 14, (0, 8), jnp.float32, seed=5
        )
        ref = paged_prefill_attention_reference(
            q, kp, vp, tbl, layer=0, q_positions=qpos, cache_len=lens,
            window=window, softcap=softcap, q_chunk=64, kv_chunk=64,
        )
        k_dense, v_dense = gather_pages(kp, tbl, 0), gather_pages(vp, tbl, 0)
        Smax = k_dense.shape[1]
        kpos = jnp.broadcast_to(jnp.arange(Smax)[None], (q.shape[0], Smax))
        dense = model_flash(
            q, k_dense, v_dense, q_positions=qpos, k_positions=kpos,
            kv_len=lens, causal=True, causal_skip=False,
            window=window, softcap=softcap, q_chunk=64, kv_chunk=64,
        )
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(dense))


def test_paged_prefill_ops_wrapper_routes_to_reference_on_cpu():
    from repro.kernels import paged_prefill_attention
    from repro.kernels.paged_attention.ref import paged_prefill_attention_reference

    q, kp, vp, tbl, qpos, lens = _paged_prefill_case(
        2, 8, 4, 2, 64, 4, 6, 14, (0, 8), jnp.float32, seed=6
    )
    out = paged_prefill_attention(q, kp, vp, tbl, layer=0, q_positions=qpos,
                                  cache_len=lens)
    ref = paged_prefill_attention_reference(q, kp, vp, tbl, layer=0,
                                            q_positions=qpos, cache_len=lens)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # and the interpret route runs the kernel end to end through the wrapper
    interp = paged_prefill_attention(q, kp, vp, tbl, layer=0, q_positions=qpos,
                                     cache_len=lens, impl="interpret")
    np.testing.assert_allclose(np.asarray(interp), np.asarray(ref), atol=2e-5)


RMS_CASES = [(4, 128), (3, 300), (1, 1024), (17, 96)]


@pytest.mark.parametrize("rows,d", RMS_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("zero_centered", [False, True])
def test_rmsnorm_matches_reference(rows, d, dtype, zero_centered):
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (rows, d), jnp.float32).astype(dtype)
    s = jax.random.normal(k2, (d,), jnp.float32)
    out = rmsnorm_pallas(x, s, zero_centered=zero_centered, block_rows=64,
                         interpret=True)
    ref = rmsnorm_reference(x, s, zero_centered=zero_centered)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol,
    )


def test_rmsnorm_3d_shape():
    x = jax.random.normal(KEY, (2, 5, 256), jnp.float32)
    s = jnp.ones((256,))
    out = rmsnorm_pallas(x, s, interpret=True)
    assert out.shape == x.shape
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(rmsnorm_reference(x, s)), atol=1e-5
    )


def test_ops_wrappers_route_to_reference_on_cpu():
    from repro.kernels import flash_attention, rmsnorm

    q, k, v = _qkv(1, 64, 64, 2, 2, 32, jnp.float32)
    out = flash_attention(q, k, v, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
    x = jax.random.normal(KEY, (4, 128))
    np.testing.assert_allclose(
        np.asarray(rmsnorm(x, jnp.ones(128))),
        np.asarray(rmsnorm_reference(x, jnp.ones(128))), atol=1e-6,
    )


def test_flash_fully_masked_block_with_negative_scores():
    """Regression: a fully-masked kv block must not poison the running max.

    With true row maxima << 0, returning a 0-sentinel from the masked block
    made max(m,0)=0 underflow the rescale factor, collapsing l to zero —
    silently wrong outputs and NaN gradients (hit by any multi-block causal
    run at init scale). The block must report its TRUE masked max.
    """
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 32)) * 40   # |scores| ~ 1e3
    k = jax.random.normal(ks[1], (2, 128, 2, 32)) * 40
    v = jax.random.normal(ks[2], (2, 128, 2, 32))
    from repro.layers.attention import flash_attention as model_flash

    pos = jnp.broadcast_to(jnp.arange(128)[None], (2, 128))
    ref = attention_reference(q, k, v, causal=True)
    for skip in (False, True):
        def loss(q):
            o = model_flash(q, k, v, q_positions=pos, k_positions=pos,
                            causal=True, q_chunk=64, kv_chunk=64,
                            causal_skip=skip)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        out = model_flash(q, k, v, q_positions=pos, k_positions=pos,
                          causal=True, q_chunk=64, kv_chunk=64,
                          causal_skip=skip)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-4)
        g = jax.grad(loss)(q)
        assert np.isfinite(np.asarray(g)).all()


# ---------------------------------------------------------------------------
# a score scale from the configuration, at GQA group 4
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernels_take_the_score_scale(kernel, dtype):
    """Both paged kernels at Granite's score scale (1/128, not 1/sqrt(D))
    and its GQA group of 4 query heads a KV head, in interpret mode,
    against the oracles given the same scale; the decode case spans two
    256-row blocks of the group-4 ``pages_per_step``. The default scale
    gives another result, so the scale is not ignored."""
    from repro.kernels.paged_attention.kernel import (
        pages_per_step, paged_prefill_attention_pallas)
    from repro.kernels.paged_attention.ref import paged_prefill_attention_reference

    scale = 0.0078125
    assert pages_per_step(16, 40, 2, 128, 4, 2) == 16
    if kernel == "decode":
        q, kp, vp, tbl, lens, qpos = _paged_case(
            3, 8, 2, 128, 16, 40, 96, (530, 257, 17), dtype, seed=5)
        args = dict(layer=0, q_position=qpos, cache_len=lens)
        run = lambda s: paged_attention_pallas(
            q, kp, vp, tbl, **args, interpret=True, sm_scale=s)
        ref = paged_attention_reference(q, kp, vp, tbl, **args, sm_scale=scale)
    else:
        q, kp, vp, tbl, qpos, lens = _paged_prefill_case(
            2, 16, 8, 2, 128, 16, 6, 14, (0, 48), dtype, seed=5)
        args = dict(layer=0, q_positions=qpos, cache_len=lens, causal=True)
        run = lambda s: paged_prefill_attention_pallas(
            q, kp, vp, tbl, **args, interpret=True, sm_scale=s)
        ref = paged_prefill_attention_reference(q, kp, vp, tbl, **args,
                                                sm_scale=scale)
    out = run(scale)
    # one bf16 rounding of the output in the bf16 case, as above
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)
    default = np.asarray(run(None), np.float32)
    assert np.max(np.abs(default - np.asarray(out, np.float32))) > 10 * tol
