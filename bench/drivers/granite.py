"""Serving driver for the hybrid Mamba2/attention MoE configuration
(Granite-4.0-H): the serving driver's scheduler, warm-up, window and
end-to-end numbers, with two changes. Its weights follow
``bench/weights.py`` but for three leaves (``granite_leaves``); and its
check runs the Granite reference (``bench/reference/granite.py``).
"""

from __future__ import annotations

import math
import pathlib
import zlib

import numpy as np

from harness import Cell, load_module
import weights

serve = load_module(pathlib.Path(__file__).with_name("serve.py"),
                    "bench_driver_serve_granite")

warm, window, end_to_end, sample = serve.warm, serve.window, serve.end_to_end, serve.sample

DT_MIN, DT_MAX = 1e-3, 1e-1    # Mamba2's dt range
A_MIN, A_MAX = 1.0, 16.0       # and its A range (A = -U(A_MIN, A_MAX))


def granite_leaves(params, seed: int, embedding_multiplier: float):
    """``params`` with three leaves of its own:

    - each Mamba2 layer's ``A_log`` and ``dt_bias`` drawn from the seed as
      Mamba2 initializes them, the ranges the recurrence depends on:
      ``A_log = log U(1, 16)``; ``dt_bias`` the inverse softplus of a dt
      log-uniform in 0.001-0.1;
    - the (tied) embedding divided by the embedding multiplier, so that
      the residual stream starts at ``bench/weights.py``'s embedding scale
      (0.02). At 12 times that, the input token's own row would dominate
      the tied head at every position, and greedy decoding would echo the
      input token whatever the layers computed: a check on the served
      tokens could then see no fault in the layers.
    """
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(weights.seed32(seed, 7))

    def one(path, x):
        name = weights._leaf_name(path)
        if name == "embed":
            return (x.astype(jnp.float32) / embedding_multiplier).astype(x.dtype)
        if name not in ("A_log", "dt_bias"):
            return x
        k = jax.random.fold_in(key, zlib.crc32(jax.tree_util.keystr(path).encode()) & 0x7FFFFFFF)
        u = jax.random.uniform(k, x.shape, jnp.float32)
        if name == "A_log":
            v = jnp.log(A_MIN + (A_MAX - A_MIN) * u)
        else:
            dt = jnp.exp(math.log(DT_MIN) + u * (math.log(DT_MAX) - math.log(DT_MIN)))
            v = dt + jnp.log(-jnp.expm1(-dt))
        return v.astype(x.dtype)

    return jax.tree_util.tree_map_with_path(one, params)


def build(cell: Cell, seed: int, devices):
    """The scheduler the window drives, with bench-made weights."""
    import jax

    from repro.launch.mesh import make_host_mesh
    from repro.layers.common import abstract_params
    from repro.models import transformer as T
    from repro.serve.serve import BatchScheduler, ServeConfig

    cfg = serve.program_config(cell)
    mesh = make_host_mesh(devices=devices)
    abstract = abstract_params(T.model_params(cfg), cfg.param_dtype)
    params = granite_leaves(weights.make_params(abstract, seed, cfg.n_layers), seed,
                            cfg.embedding_multiplier)
    jax.block_until_ready(params)
    s = cell.config["serve"]
    scfg = ServeConfig(max_len=cell.workload["max_len"], batch=cell.workload["slots"],
                       page_size=s["page_size"], num_pages=s["num_pages"])
    return BatchScheduler(cfg, mesh, scfg, params), params


def check(cell: Cell, params, picked, quant=False) -> float:
    """Widest gap, over the served tokens of ``picked``, by which a served
    token's reference logit lies below the reference's best. With
    ``quant`` the gap is of the token that the float8 control puts first."""
    import jax
    import jax.numpy as jnp

    from reference import granite

    m = granite.frozen(cell.model)
    worst = 0.0
    for prompt, served in picked:
        ids = list(prompt) + list(served[:-1])
        rows = list(range(len(prompt) - 1, len(ids)))
        ref = granite.logits_at(params, m, ids, rows)
        if quant:
            tok = jnp.argmax(granite.logits_at(params, m, ids, rows, quant=True), -1)
        else:
            tok = jnp.asarray(np.asarray(served), jnp.int32)
        gap = jnp.max(ref, -1) - jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]
        worst = max(worst, float(jax.device_get(jnp.max(gap))))
    return worst


# the serving driver's run, with this build and this check
serve.build, serve.check = build, check
run = serve.run
