"""The control: the reference computed in float8 (the precision below the
configuration's bf16) put in the program's place fails the comparison, at
smoke size. On the chip the same control is read at the cells' own sizes
(PERF.md gives the readings the limits were set from)."""

import pytest

import harness
import smoke

SEED = 2**31 + 4242


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return smoke.tree(tmp_path_factory.mktemp("bench"))


def test_serving_control_fails(root):
    import jax

    cell = harness.load_cell("tiny-chat", root)
    harness.use_compile_cache()
    drv = harness.driver(cell)
    sched, params = drv.build(cell, SEED, jax.devices()[:1])
    drv.warm(sched, sched.scfg.prefill_chunk)
    rec = drv.window(cell, sched, SEED, 1.0, None)
    chk = cell.config["check"]
    picked = drv.sample(rec, SEED, chk["min_tokens"], chk["max_requests"])
    program = drv.check(cell, params, picked)
    control = drv.check(cell, params, picked, quant=True)
    assert program <= chk["logit_gap_limit"] < control


def test_training_control_fails(root):
    import jax

    cell = harness.load_cell("tiny-train", root)
    harness.use_compile_cache()
    drv = harness.driver(cell)
    cfg = harness.program_config(cell)
    from repro.layers.common import abstract_params
    from repro.models import transformer as T

    abstract = abstract_params(T.model_params(cfg), cfg.param_dtype)
    ref = drv.reference_readings(cell, SEED, abstract, cfg.n_layers)
    ctl = drv.reference_readings(cell, SEED, abstract, cfg.n_layers, quant=True)
    got = drv.compare(ctl, ref)
    lim = cell.config["check"]
    assert any(v > lim[k + "_limit"] for k, v in got.items()), got
