"""The paged-attention Pallas kernels compile for a described TPU v5e chip
at real serving widths, and the compiled program holds the kernel.

Nothing runs: the TPU compiler is given shapes for a chip that is described,
not attached, so these tests guard against what interpret mode cannot see
(unaligned slices, too much fast memory, unpartitionable kernels) at no
chip time. The topology is described inside a fixture, never while a
module is imported; every test that needs it lives in this one file.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.kernel import (
    paged_attention_pallas,
    paged_prefill_attention_pallas,
)

# serving shapes: 8 slots, max_len 2048 in 16-token pages, a 1024-page
# pool, 32-token prefill chunks (the ServeConfig default); the kernels read
# one layer of a stacked pool of LAYERS
BATCH, PAGE, N_LOGICAL, POOL, CHUNK, LAYERS = 8, 16, 2048 // 16, 1024, 32, 2

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
    pages = _shape((LAYERS, Hkv, pool, PAGE, D), jnp.bfloat16, one_chip)
    if kernel is paged_attention_pallas:
        pos_name, pos_shape = "q_position", (batch,)
    else:
        pos_name, pos_shape = "q_positions", (batch, q_len)

    def call(q, k_pages, v_pages, tables, layer, lens, pos):
        return kernel(q, k_pages, v_pages, tables, layer=layer, cache_len=lens,
                      window=window, softcap=softcap, **{pos_name: pos},
                      **({"sm_scale": scale[0]} if scale else {}))

    return jax.jit(call).lower(
        _shape((batch, q_len, Hq, D), jnp.bfloat16, one_chip), pages, pages,
        _shape((batch, n_logical), jnp.int32, one_chip),
        _shape((), jnp.int32, one_chip),
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


# glm4-chat's serving shapes: GLM-4-9B at 20 layers, 32 slots, max_len 5120
# in 16-token pages, a 7,680-page pool
GLM4_CHAT = dict(layers=20, slots=32, max_len=5120, pages=7680)


def _glm4_steps(one_chip):
    """glm4-chat's compiled paged decode step (caches donated) and its
    prefill-chunk step, with the Pallas kernels forced: off the chip,
    ``impl="auto"`` would take the gather oracle."""
    from repro import compat
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.layers.common import abstract_params
    from repro.models import transformer as T
    from repro.serve.serve import make_prefill_chunk_step, make_serve_decode_step

    g = GLM4_CHAT
    cfg = get_config("glm4-9b").replace(repeats=g["layers"],
                                        paged_attn_impl="pallas")
    mesh = make_host_mesh(devices=[one_chip.device_set.pop()])
    placed = lambda tree: jax.tree_util.tree_map(
        lambda a: _shape(a.shape, a.dtype, one_chip), tree)
    params = placed(abstract_params(T.model_params(cfg), cfg.param_dtype))
    caches = placed(jax.eval_shape(lambda: T.init_cache(
        cfg, g["slots"], g["max_len"], paged=True, page_size=PAGE,
        num_pages=g["pages"])))
    B, n_logical = g["slots"], g["max_len"] // PAGE
    i32 = lambda *shape: _shape(shape, jnp.int32, one_chip)
    flags = _shape((B,), jnp.bool_, one_chip)
    keys = _shape((B, 2), jnp.uint32, one_chip)
    with compat.use_mesh(mesh):
        decode = jax.jit(make_serve_decode_step(cfg, mesh),
                         donate_argnums=(4,)).lower(
            params, i32(B, 1), i32(B), flags, caches, i32(B, n_logical), keys,
            flags).compile()
        chunk = jax.jit(make_prefill_chunk_step(cfg, mesh),
                        donate_argnums=(5,)).lower(
            params, i32(1, CHUNK), i32(1), i32(1), i32(), caches,
            i32(B, n_logical), keys).compile()
    return cfg, decode.as_text(), chunk.as_text()


def test_glm4_steps_address_the_paged_pool_in_place(one_chip, no_compile_cache):
    """The stacked paged pool rides through the layer scan whole and the
    kernels read their layer of it in place: no copy, dynamic slice or
    dynamic update slice in either compiled step moves a buffer the size
    of one layer's pool or of the whole stacked pool, synchronously or
    not (at glm4-chat's shapes 63 MB or 1.26 GB; per dispatch such moves
    cost far more than the attention itself). The kernels are still
    there, one each."""
    cfg, decode, chunk = _glm4_steps(one_chip)
    layer = GLM4_CHAT["pages"] * PAGE * cfg.n_kv_heads * cfg.head_dim_
    sizes = {layer, layer * GLM4_CHAT["layers"]}
    # name = result type(s) opcode(operands...)
    op = re.compile(r"\s*(?:ROOT )?%?([\w.-]+) = (.*?) ([\w-]+)\(")
    movers = ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice")
    for text, kernel in ((decode, "paged_attention"),
                         (chunk, "paged_prefill_attention")):
        moved = []
        for line in text.splitlines():
            m = op.match(line)
            if m and m.group(3) in movers and any(
                    math.prod(int(d) for d in dims.split(",") if d) in sizes
                    for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(2))):
                moved.append(m.group(1))
        assert not moved, (kernel, moved)
        calls = [line.split(" = ", 1)[0].strip().lstrip("%")
                 for line in text.splitlines()
                 if "tpu_custom_call" in line and " = " in line]
        assert len(calls) == 1 and re.fullmatch(
            kernel + r"(\.\d+)?", calls[0]), calls
