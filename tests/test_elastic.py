"""Elastic scaling: checkpoints restore across different mesh sizes.

These run in subprocesses because the forced host-device count must be set
before jax initializes (tests in this process stay single-device).
"""

import subprocess
import sys
import textwrap


def _run(ndev: int, code: str) -> str:
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
        import sys
        sys.path.insert(0, {repr(sys.path[0] + "/../src")})
        sys.path.insert(0, "src")
    """) + textwrap.dedent(code)
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


COMMON = """
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.configs import smoke_config
from repro.layers.common import init_params, param_pspecs
from repro.models import transformer as T
from repro.distributed import sharding as SH
from repro.checkpoint import save_checkpoint, load_checkpoint
from jax.sharding import NamedSharding
cfg = smoke_config("tinyllama-1.1b")
mesh = compat.make_mesh(MESH_SHAPE, ("data", "model"))
pspecs = param_pspecs(T.model_params(cfg), SH.param_rules(cfg, mesh), mesh)
shardings = jax.tree_util.tree_map(lambda p: NamedSharding(mesh, p), pspecs,
    is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
"""


def test_checkpoint_reshards_across_meshes(tmp_path):
    # save on 8 devices (4x2)
    _run(8, f"MESH_SHAPE=(4,2)\n{COMMON}" + f"""
params = init_params(T.model_params(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
params = jax.tree_util.tree_map(jax.device_put, params, shardings)
save_checkpoint(params, {str(tmp_path)!r}, 1)
print("saved", sum(x.size for x in jax.tree_util.tree_leaves(params)))
""")
    # restore on 4 devices (2x2) with resharding, verify values
    out = _run(4, f"MESH_SHAPE=(2,2)\n{COMMON}" + f"""
template = init_params(T.model_params(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
restored, step = load_checkpoint(template, {str(tmp_path)!r}, shardings=shardings)
ok = all(
    np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    for a, b in zip(jax.tree_util.tree_leaves(template),
                    jax.tree_util.tree_leaves(restored))
)
shards = jax.tree_util.tree_leaves(restored)[0].sharding
print("restored step", step, "values_equal", ok, "ndev", len(jax.devices()))
""")
    assert "values_equal True" in out
    assert "ndev 4" in out


def test_train_state_survives_mesh_growth(tmp_path):
    """Shrink->grow: 4-device optimizer state restores on 8 devices and one
    further train step runs (the elastic-scaling end-to-end path)."""
    save = """
from repro.train.train import TrainConfig, init_state, make_train_step, train_state_pspecs
from repro.data.pipeline import DataConfig, SyntheticLM
tcfg = TrainConfig()
st = init_state(cfg, tcfg, jax.random.PRNGKey(0))
state = {"params": st.params, "opt_state": st.opt_state, "step": st.step}
data = SyntheticLM(DataConfig(global_batch=4, seq_len=32, vocab=cfg.vocab))
with compat.use_mesh(mesh):
    step = jax.jit(make_train_step(cfg, mesh, tcfg))
    state, _ = step(state, data.batch_at(0))
save_checkpoint(state, CKPT, 1)
print("saved")
"""
    _run(4, f"MESH_SHAPE=(2,2)\nCKPT={str(tmp_path)!r}\n{COMMON}{save}")
    out = _run(8, f"MESH_SHAPE=(4,2)\nCKPT={str(tmp_path)!r}\n{COMMON}" + """
from repro.train.train import TrainConfig, init_state, make_train_step, train_state_pspecs
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.distributed.elastic import reshard_state
tcfg = TrainConfig()
st = init_state(cfg, tcfg, jax.random.PRNGKey(0))
template = {"params": st.params, "opt_state": st.opt_state, "step": st.step}
sp = train_state_pspecs(cfg, mesh, tcfg)
sh = jax.tree_util.tree_map(lambda p: NamedSharding(mesh, p), sp,
    is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
state, step_no = load_checkpoint(template, CKPT, shardings=sh)
data = SyntheticLM(DataConfig(global_batch=4, seq_len=32, vocab=cfg.vocab))
with compat.use_mesh(mesh):
    stepf = jax.jit(make_train_step(cfg, mesh, tcfg))
    state, metrics = stepf(state, data.batch_at(1))
import numpy as np
print("resumed_step", step_no, "loss", float(metrics["loss"]),
      "finite", bool(np.isfinite(float(metrics["loss"]))))
""")
    assert "resumed_step 1" in out and "finite True" in out


def test_initialize_lays_out_state_for_the_step():
    """The state leaves the initialize region sharded as the compiled step
    takes it, so the first step does not reshard it inside the train_step
    region (a strong-scaling table would count that as step time)."""
    out = _run(4, """
import jax
from repro.configs import smoke_config
from repro.launch.mesh import make_host_mesh
from repro.launch.train import build_loop
loop = build_loop(smoke_config("tinyllama-1.1b"), make_host_mesh(), steps=1,
                  global_batch=4, seq_len=32)
state, _, _ = loop._initialize()
held = {d.id for leaf in jax.tree_util.tree_leaves(state)
        for d in leaf.sharding.device_set}
print("held", sorted(held))
""")
    assert "held [0, 1, 2, 3]" in out


def test_host_lb_measured_on_multidevice_mesh():
    """Host load-balance observables flow end-to-end on a multi-device mesh."""
    out = _run(8, """
import jax, jax.numpy as jnp
from repro.configs import smoke_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.train.loop import LoopConfig, TrainLoop
from repro.train.train import TrainConfig
cfg = smoke_config("qwen3-moe-30b-a3b")
data = SyntheticLM(DataConfig(global_batch=4, seq_len=32, vocab=cfg.vocab,
                              pad_fraction=0.2))
loop = TrainLoop(cfg, make_host_mesh(model=2), TrainConfig(), data,
                 LoopConfig(steps=3, lb_sample_every=1))
loop.run()
run = loop.finalize_run()
m = run.regions["train_step"].measurements
print("steps", m.num_steps, "data_lb", m.data_lb, "expert_lb", m.expert_lb)
assert m.num_steps == 3 and m.data_lb is not None and m.expert_lb is not None
assert 0 < m.expert_lb <= 1.0
print("OK")
""")
    assert "OK" in out
