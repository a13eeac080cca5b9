"""Per-architecture smoke tests (required deliverable f): reduced config of
the same family, one forward + one train step on CPU, asserting output
shapes and absence of NaNs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, list_archs, smoke_config
from repro.layers.common import init_params
from repro.models import transformer as T
from repro.train.train import TrainConfig, init_state, make_train_step
from repro.launch.mesh import make_host_mesh

B, S = 2, 64


def _batch(cfg, accum=1):
    shape = (accum, B, S) if accum else (B, S)
    labels = jnp.where(
        jnp.arange(S)[None, :] % 5 == 0, -1,
        jnp.ones((B, S), jnp.int32),
    )
    if accum:
        labels = jnp.broadcast_to(labels[None], (accum, B, S))
    batch = {"labels": labels}
    if cfg.frontend == "audio":
        fe_shape = shape + (cfg.d_model,)
        batch["frontend"] = jnp.full(fe_shape, 0.01, jnp.bfloat16)
    elif cfg.frontend == "vlm":
        nf = cfg.n_frontend_tokens
        fe_shape = ((accum, B, nf, cfg.d_model) if accum else (B, nf, cfg.d_model))
        batch["frontend"] = jnp.full(fe_shape, 0.01, jnp.bfloat16)
        tshape = (accum, B, S - nf) if accum else (B, S - nf)
        batch["tokens"] = jnp.ones(tshape, jnp.int32)
    else:
        batch["tokens"] = jnp.ones(shape, jnp.int32)
    return batch


@pytest.mark.parametrize("arch", list_archs())
def test_full_config_matches_assignment(arch):
    cfg = get_config(arch)
    spec = {
        "hubert-xlarge": (48, 1280, 16, 16, 5120, 504),
        "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151936),
        "zamba2-2.7b": (54, 2560, 32, 32, 10240, 32000),
        "gemma2-2b": (26, 2304, 8, 4, 9216, 256000),
        "tinyllama-1.1b": (22, 2048, 32, 4, 5632, 32000),
        "glm4-9b": (40, 4096, 32, 2, 13696, 151552),
        "command-r-35b": (40, 8192, 64, 8, 22528, 256000),
        "llava-next-mistral-7b": (32, 4096, 32, 8, 14336, 32000),
        "xlstm-350m": (24, 1024, 4, 4, 0, 50304),
        "granite-4.0-h-small": (40, 4096, 32, 8, 768, 100352),
    }[arch]
    L, d, hq, hkv, dff, vocab = spec
    assert cfg.n_layers == L
    assert cfg.d_model == d
    assert cfg.n_heads == hq and cfg.n_kv_heads == hkv
    assert (cfg.moe.d_ff if cfg.moe else cfg.d_ff) == dff
    assert cfg.vocab == vocab
    if arch == "dbrx-132b":
        assert cfg.moe.n_experts == 16 and cfg.moe.top_k == 4
    if arch == "qwen3-moe-30b-a3b":
        assert cfg.moe.n_experts == 128 and cfg.moe.top_k == 8
    if arch == "zamba2-2.7b":
        assert cfg.ssm.d_state == 64
    if arch == "granite-4.0-h-small":
        assert cfg.moe.n_experts == 72 and cfg.moe.top_k == 10
        assert cfg.ssm.d_state == 128


@pytest.mark.parametrize("arch", list_archs())
def test_smoke_forward_and_train_step(arch):
    cfg = smoke_config(arch)
    mesh = make_host_mesh()
    tcfg = TrainConfig()
    st = init_state(cfg, tcfg, jax.random.PRNGKey(0))
    state = {"params": st.params, "opt_state": st.opt_state, "step": st.step}
    batch = _batch(cfg, accum=1)

    with mesh:
        logits, _ = jax.jit(lambda p, b: T.apply_logits(p, b, cfg))(
            state["params"], jax.tree_util.tree_map(lambda x: x[0], batch)
        )
        assert logits.shape == (B, S, cfg.vocab_padded)
        assert np.isfinite(np.asarray(logits, np.float32)).all()

        step = jax.jit(make_train_step(cfg, mesh, tcfg))
        new_state, metrics = step(state, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and 0.0 < loss < 50.0
    # the optimizer saw non-zero gradients (bf16 params may not change at
    # warmup-suppressed lr in one step)
    m0 = jax.tree_util.tree_leaves(new_state["opt_state"]["m"])[0]
    assert float(np.abs(np.asarray(m0, np.float32)).sum()) > 0.0
    assert int(new_state["step"]) == 1


def test_param_counts_are_plausible():
    """Full configs should land near their nameplate sizes."""
    expectations = {
        "tinyllama-1.1b": (0.9e9, 1.4e9),
        "gemma2-2b": (2.0e9, 3.3e9),
        "glm4-9b": (8e9, 11e9),
        "command-r-35b": (30e9, 40e9),
        "llava-next-mistral-7b": (6.5e9, 8e9),
        "dbrx-132b": (110e9, 140e9),
        "qwen3-moe-30b-a3b": (26e9, 34e9),
        "xlstm-350m": (0.25e9, 0.6e9),  # full qkv vs block-diag: +0.1B
        "zamba2-2.7b": (2.2e9, 3.4e9),
        "hubert-xlarge": (0.8e9, 1.2e9),
        "granite-4.0-h-small": (30e9, 34e9),
    }
    for arch, (lo, hi) in expectations.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B not in [{lo/1e9}, {hi/1e9}]"


def test_moe_active_params_much_smaller_than_total():
    cfg = get_config("qwen3-moe-30b-a3b")
    assert cfg.active_param_count() < 0.2 * cfg.param_count()
