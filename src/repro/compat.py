"""The one place this repo touches JAX APIs that move between releases.

Written against the installed JAX (0.9). Every access to an attribute that
has changed shape across releases — the mesh constructor's ``axis_types``,
the ambient-mesh context, the compiled-executable accessors and the
experimental Pallas namespaces — lives HERE, so that the next API move is a
one-file change. The tier-1 suite greps for violations
(tests/test_compat.py).

* ``make_mesh``        — ``jax.make_mesh`` with every axis marked
                         ``AxisType.Auto`` (the GSPMD behaviour this
                         codebase is written against).
* ``use_mesh``         — ``jax.set_mesh`` used as a context manager.
* ``named_sharding``   — NamedSharding from a PartitionSpec or axis tuple.
* ``device_put``       — placement with an optional sharding.
* ``cost_analysis`` /
  ``memory_stats``     — flat str->float views of the compiled executable's
                         analyses (some backends raise; those give ``{}``).
* ``compiled_text``    — optimized-HLO text of a compiled executable.
* ``pallas`` /
  ``pallas_tpu``       — the Pallas kernel namespaces (``pl``/``pltpu``).
                         Kernel code imports them through these accessors;
                         everything else stays behind the ``repro.kernels``
                         ops wrappers.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
from jax.experimental import pallas as _pallas_mod
from jax.experimental.pallas import tpu as _pallas_tpu_mod


def pallas():
    """The Pallas core module (``pl`` by convention)."""
    return _pallas_mod


def pallas_tpu():
    """The Pallas TPU module (``pltpu`` by convention)."""
    return _pallas_tpu_mod


# ---------------------------------------------------------------------------
# mesh construction
# ---------------------------------------------------------------------------


def make_mesh(
    axis_shapes: Sequence[int],
    axis_names: Sequence[str],
    *,
    devices: Sequence[Any] | None = None,
):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes), names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(names),
        devices=devices,
    )


def use_mesh(mesh):
    """Enter ``mesh`` as the ambient mesh (``with compat.use_mesh(m):``)."""
    return jax.set_mesh(mesh)


def named_sharding(mesh, spec):
    """NamedSharding constructor (``spec``: PartitionSpec or axis tuple)."""
    if not isinstance(spec, jax.sharding.PartitionSpec):
        spec = jax.sharding.PartitionSpec(*spec) if isinstance(spec, (tuple, list)) \
            else jax.sharding.PartitionSpec(spec)
    return jax.sharding.NamedSharding(mesh, spec)


def device_put(x, sharding=None):
    """``jax.device_put`` with an optional sharding (None = default device)."""
    if sharding is None:
        return jax.device_put(x)
    return jax.device_put(x, sharding)


# ---------------------------------------------------------------------------
# compiled-executable accessors
# ---------------------------------------------------------------------------


def _is_num(v) -> bool:
    try:
        float(v)
        return True
    except (TypeError, ValueError):
        return False


def cost_analysis(compiled) -> dict[str, float]:
    """``compiled.cost_analysis()`` as a flat str->float dict (``{}`` on a
    backend that cannot produce one)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    return {str(k): float(v) for k, v in dict(ca or {}).items() if _is_num(v)}


def memory_stats(compiled) -> dict[str, float]:
    """``compiled.memory_analysis()`` (absent/raising on some backends) as a
    flat str->float dict of the stable field names."""
    try:
        ms = compiled.memory_analysis()
    except Exception:
        return {}
    out: dict[str, float] = {}
    for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        v = getattr(ms, k, None)
        if v is not None:
            out[k] = float(v)
    return out


def compiled_text(compiled) -> str:
    """Optimized HLO text of a compiled executable.

    Deliberately raises when the accessor is missing or failing instead of
    returning '': an empty string flows into ``analyze_hlo`` as an all-zero
    HloCost — exactly the silent-zero failure mode the call-graph engine
    exists to prevent. Callers that can tolerate absence must catch.
    """
    return compiled.as_text()
