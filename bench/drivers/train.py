"""Training driver: the program's ``TrainLoop`` (synthetic-LM job, AdamW
with a float32 master copy, the monitored train step) over the cell's
chips, fed benchmark-made batches, then checked against the float32
reference over its first three steps.

Set-up builds the loop and its compiled step, puts the benchmark-made
weights into its state, and drives that same step through the three
checked steps and one more. The window then calls the same step on fresh
batches until ``seconds`` have passed and the step under way has ended.
"""

from __future__ import annotations

import gc
import statistics
import sys
import time

import numpy as np

from harness import Cell, CompileCounter, Tracer, device_info, now, program_config
import weights

CHECKED = 3   # steps the reference follows


def batch(cell: Cell, seed: int, step: int) -> dict:
    """Uniform ids over the vocabulary, next-token labels; every row of
    every step differs."""
    tr = cell.traffic
    rng = np.random.default_rng([int(seed), 13, step])
    toks = rng.integers(0, cell.model["vocab"], size=(1, tr["global_batch"], tr["seq_len"]),
                        dtype=np.int32)
    labels = np.roll(toks, -1, axis=-1)
    labels[..., -1] = -1
    return {"tokens": toks, "labels": labels}


def _norms(tree):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                              for x in jax.tree_util.tree_leaves(t)])(tree)


def build(cell: Cell, seed: int, devices):
    import jax

    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import build_loop
    from repro.layers.common import abstract_params
    from repro.models import transformer as T

    cfg = program_config(cell)
    mesh = make_host_mesh(devices=devices)
    tr, o = cell.traffic, cell.config["optimizer"]
    loop = build_loop(cfg, mesh, steps=o["total_steps"], global_batch=tr["global_batch"],
                      seq_len=tr["seq_len"], lr=o["lr"], pad_fraction=0.0)
    loop.session.start()
    with loop.session.region("initialize"):
        state, _, step_fn = loop._initialize()
    layout = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding), state)
    del state
    abstract = abstract_params(T.model_params(cfg), cfg.param_dtype)
    return loop, seed_state(layout, seed, abstract, cfg.n_layers), step_fn, abstract, cfg, layout


def seed_state(layout, seed: int, abstract, n_layers: int):
    """The loop's state at step 0 as ``layout`` lays it out, with the
    benchmark-made weights for ``seed``: the float32 master copy of them,
    both moments and the step counts zero."""
    import jax
    import jax.numpy as jnp

    tmap = jax.tree_util.tree_map
    shard = lambda t: tmap(lambda a: a.sharding, t)
    zeros = lambda t: jax.jit(lambda: tmap(lambda a: jnp.zeros(a.shape, a.dtype), t),
                              out_shardings=shard(t))()
    p0 = weights.make_params(abstract, seed, n_layers, shardings=shard(layout["params"]))
    opt = zeros({k: v for k, v in layout["opt_state"].items() if k != "master"})
    opt["master"] = jax.jit(lambda t: tmap(lambda x: x.astype(jnp.float32), t),
                            out_shardings=shard(layout["opt_state"]["master"]))(p0)
    rest = zeros({k: v for k, v in layout.items() if k not in ("params", "opt_state")})
    return dict(rest, params=p0, opt_state=opt)


def change_norms(master, abstract, seed, n_layers):
    """Per-leaf norm of the master weights' change from the initial ones,
    the initial ones made anew from the seed."""
    import jax
    import jax.numpy as jnp

    init = weights.make_params(abstract, seed, n_layers)
    return jax.jit(lambda a, b: [jnp.sqrt(jnp.sum(jnp.square(x - y.astype(jnp.float32))))
                                 for x, y in zip(jax.tree_util.tree_leaves(a),
                                                 jax.tree_util.tree_leaves(b))])(master, init)


def setup_steps(cell, seed, state, step_fn, abstract, n_layers):
    """The checked steps, through the window's own call and feed. Returns
    the state, losses, optimizer-side gradient norms after step 1, and
    change norms after the last checked step."""
    import jax

    b1 = cell.config["optimizer"]["b1"]
    losses = []
    grads = change = None
    for s in range(CHECKED):
        state, met = step_fn(state, batch(cell, seed, s))
        losses.append(float(met["loss"]))
        if s == 0:
            grads = [float(x) / (1 - b1) for x in jax.device_get(_norms(state["opt_state"]["m"]))]
    change = [float(x) for x in jax.device_get(
        change_norms(state["opt_state"]["master"], abstract, seed, n_layers))]
    state, met = step_fn(state, batch(cell, seed, CHECKED))   # one more: warm
    float(met["loss"])
    return state, {"loss": losses, "grad": grads, "change": change}


def window(cell, seed, seconds, state, step_fn, tracer):
    import jax

    step = CHECKED + 1
    ends = []
    t_trace = (max(0.0, seconds / 2 - 4.0), min(seconds, seconds / 2 + 4.0))
    traced = [None, None]
    with CompileCounter() as cc:
        t0 = now()
        while True:
            t = now() - t0
            if tracer is not None and traced[0] is None and t >= t_trace[0]:
                tracer.start()
                traced[0] = len(ends)
            if tracer is not None and tracer.on and t >= t_trace[1]:
                traced[1] = len(ends)
                tracer.stop()
            if t >= seconds:
                break
            with jax.profiler.TraceAnnotation("bench.step"):
                state, met = step_fn(state, batch(cell, seed, step))
                float(met["loss"])
            ends.append(now() - t0)
            step += 1
        if tracer is not None and tracer.on:
            traced[1] = len(ends)
            tracer.stop()
    return state, {"ends": ends, "traced": traced, "compiles": cc.n}


def reference_readings(cell, seed, abstract, n_layers, quant=False):
    """The reference's loss per checked step, its clipped first gradient
    and its change after the checked steps, per leaf. It holds one float32
    copy of the weights and of each moment; the initial weights are made
    anew from the seed for the change."""
    import jax
    import jax.numpy as jnp

    from reference import xlstm as X
    from reference.dense import frozen

    m = frozen(cell.model)
    o = cell.config["optimizer"]
    ofz = frozen(o)
    f32 = jax.jit(lambda t: jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), t))
    p = f32(weights.make_params(abstract, seed, n_layers))
    mom = jax.tree_util.tree_map(jnp.zeros_like, p)
    vel = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, grads = [], None
    for s in range(CHECKED):
        b = batch(cell, seed, s)
        loss, g = X.loss_and_grad(p, jnp.asarray(b["tokens"][0]), jnp.asarray(b["labels"][0]),
                                  m, quant=quant)
        losses.append(float(loss))
        lr = o["lr"] * X.lr_scale(s, o["warmup_steps"], o["total_steps"])
        p, mom, vel, gn = X.adamw(p, g, mom, vel, jnp.float32(s + 1), jnp.float32(lr), ofz)
        del g
        if s == 0:
            grads = [float(x) for x in jax.device_get(gn)]
    del mom, vel
    change = [float(x) for x in jax.device_get(
        change_norms(p, abstract, seed, n_layers))]
    return {"loss": losses, "grad": grads, "change": change}


def compare(prog: dict, ref: dict) -> dict:
    """The three numbers compared: loss (worst step, relative); first
    gradient (the median leaf's gap) and change (the worst leaf's gap),
    a leaf's gap being the gap of its two norms over the larger of its
    and the median leaf's reference norm. The gradient takes the median
    leaf: the gate and convolution biases' gradients are sums over every
    token that cancel to a few thousandths, and any change of rounding
    moves them by tens of percent (PERF.md). Leaves whose reference
    gradient is under a thousandth of the median leaf's move by round-off
    alone and are left out of the change."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))
    gmed = statistics.median(ref["grad"])
    grad = statistics.median_high(abs(a - b) / max(b, gmed)
                                  for a, b in zip(prog["grad"], ref["grad"]))
    keep = [i for i, g in enumerate(ref["grad"]) if g >= 1e-3 * gmed]
    cmed = statistics.median(ref["change"][i] for i in keep)
    change = max(abs(prog["change"][i] - ref["change"][i]) / max(ref["change"][i], cmed)
                 for i in keep)
    return {"loss_gap": loss, "grad_median_gap": grad, "change_gap": change}


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices, t_start: float):
    loop, state, step_fn, abstract, cfg, _ = build(cell, seed, devices)
    state, prog = setup_steps(cell, seed, state, step_fn, abstract, cfg.n_layers)
    tracer = Tracer(cell.root, cell.name, seed) if trace else None
    setup_s = time.time() - t_start
    state, rec = window(cell, seed, seconds, state, step_fn, tracer)
    dev = device_info(devices)
    loop.session.stop()
    tr = cell.traffic
    ends = rec["ends"]
    tokens = tr["global_batch"] * tr["seq_len"]
    print(f"bench: {len(ends)} steps in {ends[-1] if ends else 0:.3f} s, "
          f"{rec['compiles']} compiles in the window", file=sys.stderr)
    del state, step_fn, loop
    gc.collect()
    ref = reference_readings(cell, seed, abstract, cfg.n_layers)
    got = compare(prog, ref)
    lim = cell.config["check"]
    # a number without a limit separates no fault from sound runs (PERF.md)
    checks = {k: (v, lim[k + "_limit"]) for k, v in got.items() if k + "_limit" in lim}
    print(f"bench: readings {got}", file=sys.stderr)
    print(f"bench: losses {prog['loss']} reference {ref['loss']}", file=sys.stderr)
    return {
        "e2e": {"train_tok_s": tokens * len(ends) / ends[-1], "setup_s": setup_s},
        "dev": dev, "attempted": len(ends), "failed": 0,
        "checks": checks,
        "correct": all(v <= l for v, l in checks.values()),
        "trace_path": tracer.path if tracer else None,
        "step_ends": ends, "traced": rec["traced"],
        "flops_per_step": None,
    }
