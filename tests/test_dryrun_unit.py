"""Dry-run machinery units (no 512-device compile here — that's the
launch-level sweep): shape specs, skip rules, batch-axis divisibility."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import (
    SHAPE_BY_NAME, SHAPES, effective_mode, get_config, list_archs, skip_reason,
)
from repro.data.pipeline import batch_specs
from repro.distributed import sharding as SH
from repro.launch.mesh import make_host_mesh


def test_40_cells_defined():
    # the ten assigned architectures and granite-4.0-h-small
    assert len(list_archs()) == 11
    assert len(SHAPES) == 4


def test_skip_rules():
    enc = get_config("hubert-xlarge")
    assert skip_reason(enc, SHAPE_BY_NAME["decode_32k"]) is not None
    assert skip_reason(enc, SHAPE_BY_NAME["long_500k"]) is not None
    assert skip_reason(enc, SHAPE_BY_NAME["train_4k"]) is None
    assert effective_mode(enc, SHAPE_BY_NAME["prefill_32k"]) == "encoder"

    dense = get_config("tinyllama-1.1b")
    assert "full-attention" in skip_reason(dense, SHAPE_BY_NAME["long_500k"])

    for arch in ("zamba2-2.7b", "xlstm-350m", "granite-4.0-h-small"):
        assert skip_reason(get_config(arch), SHAPE_BY_NAME["long_500k"]) is None


def test_expected_cell_counts():
    """44 cells: count runnable vs skipped explicitly."""
    runnable = skipped = 0
    for arch in list_archs():
        cfg = get_config(arch)
        for shape in SHAPES:
            if skip_reason(cfg, shape):
                skipped += 1
            else:
                runnable += 1
    assert runnable + skipped == 44
    # 11 train + 11 prefill + 10 decode (hubert out) + 3 long (zamba,
    # xlstm, granite)
    assert runnable == 35
    assert skipped == 9


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "hubert-xlarge",
                                  "llava-next-mistral-7b"])
@pytest.mark.parametrize("mode", ["train", "prefill", "decode"])
def test_batch_specs_cover_every_model_input(arch, mode):
    cfg = get_config(arch)
    shape = SHAPE_BY_NAME["train_4k"]
    specs = batch_specs(cfg, shape, mode)
    assert all(isinstance(v, jax.ShapeDtypeStruct) for v in specs.values())
    if mode == "train":
        assert specs["labels"].shape == (1, shape.global_batch, shape.seq_len)
        if cfg.frontend == "vlm":
            total = specs["frontend"].shape[2] + specs["tokens"].shape[2]
            assert total == shape.seq_len
    if mode == "decode":
        assert specs["tokens"].shape == (shape.global_batch, 1)


class _FakeDevices:
    def __init__(self, shape):
        self.shape = shape
        self.size = 1
        for s in shape:
            self.size *= s


class _FakeMesh:
    """Duck-typed mesh for sharding-rule tests (1 real device in-process)."""

    def __init__(self, shape, names):
        self.axis_names = names
        self.devices = _FakeDevices(shape)


def test_divisible_batch_axes():
    mesh = _FakeMesh((2, 2), ("data", "model"))
    assert SH.divisible_batch_axes(mesh, 4) == "data"
    assert SH.divisible_batch_axes(mesh, 1) is None
    assert SH.divisible_batch_axes(mesh, 3) is None
    mp = _FakeMesh((2, 4, 2), ("pod", "data", "model"))
    assert SH.divisible_batch_axes(mp, 16) == ("pod", "data")
    assert SH.divisible_batch_axes(mp, 2) == "pod"


def test_effective_strategy_fallback():
    mesh = make_host_mesh()  # 1 device: model axis = 1 -> all divisible
    assert SH.effective_strategy(get_config("tinyllama-1.1b"), mesh) == "megatron"
    assert SH.effective_strategy(get_config("gemma2-2b"), mesh) == "fsdp"


def test_shape_aware_pspec_backoff():
    from repro.layers.common import LogicalConstraints

    mesh = _FakeMesh((2, 2), ("data", "model"))
    lc = LogicalConstraints(mesh, {"batch": ("data", "model")})
    # divisible by 4 -> both axes
    assert lc.pspec_for((8, 3), "batch", None)[0] == ("data", "model")
    # divisible by 2 only -> back off to ("data",)
    assert lc.pspec_for((2, 3), "batch", None)[0] == "data"
    # not divisible -> replicated
    assert lc.pspec_for((3, 3), "batch", None)[0] is None


def test_vocab_padding_divisible_by_256():
    for arch in list_archs():
        cfg = get_config(arch)
        assert cfg.vocab_padded % 256 == 0
        assert cfg.vocab_padded >= cfg.vocab
