"""The paged-attention Pallas kernels compile for a described TPU v5e chip
at real serving widths, and the compiled program holds the kernel.

Nothing runs: the TPU compiler is given shapes for a chip that is described,
not attached, so these tests guard against what interpret mode cannot see
(unaligned slices, too much fast memory, unpartitionable kernels) at no
chip time. The topology is described inside a fixture, never while a
module is imported; every test that needs it lives in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.kernel import (
    paged_attention_pallas,
    paged_prefill_attention_pallas,
)

# serving shapes: 8 slots, max_len 2048 in 16-token pages, a 1024-page
# pool, 32-token prefill chunks (the ServeConfig default)
BATCH, PAGE, N_LOGICAL, POOL, CHUNK = 8, 16, 2048 // 16, 1024, 32

WIDTHS = {
    # name: (Hq, Hkv, D, window, softcap[, score scale])
    "tinyllama-1.1b": (32, 4, 64, None, None),
    "gemma2-2b": (8, 4, 256, 4096, 50.0),
    "glm4-9b": (32, 2, 128, None, None),
    # GQA group 4 and the configuration's score scale (1/128)
    "granite-4.0-h-small": (32, 8, 128, None, None, 0.0078125),
}

# the decode kernel at a serving cell's own shape where one is benchmarked:
# (slots, logical pages, pool pages); GLM-4-9B's is 32 slots, max_len 5120
# in 16-token pages and a 7,680-page pool, Granite's 64 slots and 20,480
DECODE_SHAPES = {"glm4-9b": (32, 5120 // 16, 7680),
                 "granite-4.0-h-small": (64, 5120 // 16, 20480)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(kernel, q_len, widths, one_chip,
             shape=(BATCH, N_LOGICAL, POOL)):
    Hq, Hkv, D, window, softcap, *scale = widths
    batch, n_logical, pool = shape
    pages = _shape((pool, PAGE, Hkv, D), jnp.bfloat16, one_chip)
    if kernel is paged_attention_pallas:
        pos_name, pos_shape = "q_position", (batch,)
    else:
        pos_name, pos_shape = "q_positions", (batch, q_len)

    def call(q, k_pages, v_pages, tables, lens, pos):
        return kernel(q, k_pages, v_pages, tables, cache_len=lens,
                      window=window, softcap=softcap, **{pos_name: pos},
                      **({"sm_scale": scale[0]} if scale else {}))

    return jax.jit(call).lower(
        _shape((batch, q_len, Hq, D), jnp.bfloat16, one_chip), pages, pages,
        _shape((batch, n_logical), jnp.int32, one_chip),
        _shape((batch,), jnp.int32, one_chip),
        _shape(pos_shape, jnp.int32, one_chip),
    ).compile()


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_paged_decode_kernel_compiles_for_v5e(arch, one_chip, no_compile_cache):
    """One kernel a call, named ``paged_attention`` wherever it is called
    from: the profiler's op events carry that name."""
    compiled = _compile(paged_attention_pallas, 1, WIDTHS[arch], one_chip,
                        **({"shape": DECODE_SHAPES[arch]}
                           if arch in DECODE_SHAPES else {}))
    calls = [line.split(" = ", 1)[0].strip().lstrip("%")
             for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line and " = " in line]
    assert len(calls) == 1 and calls[0].startswith("paged_attention"), calls


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_paged_prefill_kernel_compiles_for_v5e(arch, one_chip, no_compile_cache):
    compiled = _compile(paged_prefill_attention_pallas, CHUNK, WIDTHS[arch],
                        one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_held_expert_layer_compiles_for_v5e(one_chip, no_compile_cache):
    """Granite's MoE layer at its widths (4096 in, 72 routed, 10 a token,
    18 held, experts of 768, a shared expert of 1536) over a 64-slot decode
    batch: the held experts run as the chip's grouped matmul."""
    import dataclasses

    from repro.configs import get_config
    from repro.layers import ffn as FFN
    from repro.layers.common import abstract_params

    cfg = get_config("granite-4.0-h-small")
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, held=(0, 18)))
    params = jax.tree_util.tree_map(
        lambda a: _shape(a.shape, a.dtype, one_chip),
        abstract_params(FFN.moe_params(cfg), jnp.bfloat16))
    compiled = jax.jit(lambda p, x: FFN.moe_dropless(p, x, cfg)).lower(
        params, _shape((64, 1, 4096), jnp.bfloat16, one_chip)).compile()
    text = compiled.as_text()
    assert "ragged-dot" in text and "tpu_custom_call" in text
