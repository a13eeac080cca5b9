"""Hardware specs: the peaks every roofline term, modeled communication
time and "hardware counter" analogue (the PAPI replacement, see DESIGN.md
§3) is derived against.

On a TPU the spec is looked up by the chip's ``device_kind``
(``SPECS_BY_KIND``); a kind missing from the table is an error, never a
default. Off the chip (CPU tests, the 512-device dry-run) nothing is
measured on a chip, so the factors are computed against ``DEFAULT_TARGET``
and the record says so (``hardware_basis = "modeled"``).

v5e peaks: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16 per
chip, 16 GB HBM at 819 GB/s; ~50 GB/s/link ICI. DCN bandwidth is an
estimate for pod-to-pod traffic and only enters the multi-pod
communication model, never the required roofline table (which is
single-pod / ICI only).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_flops_bf16: float     # FLOP/s per chip
    hbm_bandwidth: float       # bytes/s per chip
    hbm_bytes: float           # HBM capacity per chip
    ici_bandwidth: float       # bytes/s per link (one direction)
    ici_links: int             # ICI links per chip (2D torus -> 4)
    dcn_bandwidth: float       # bytes/s per chip for cross-pod traffic
    clock_ghz: float           # nominal clock; TPUs do not DVFS under load
    vmem_bytes: float          # VMEM per core


TPU_V5E = ChipSpec(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    hbm_bandwidth=819e9,
    hbm_bytes=16 * 1024**3,
    ici_bandwidth=50e9,
    ici_links=4,
    dcn_bandwidth=6.25e9,
    clock_ghz=0.94,
    vmem_bytes=128 * 1024**2,
)

# Used by unit tests that need a second target to assert spec-independence.
TPU_V5P = ChipSpec(
    name="tpu_v5p",
    peak_flops_bf16=459e12,
    hbm_bandwidth=2765e9,
    hbm_bytes=95 * 1024**3,
    ici_bandwidth=100e9,
    ici_links=6,
    dcn_bandwidth=6.25e9,
    clock_ghz=1.75,
    vmem_bytes=128 * 1024**2,
)

TARGETS = {s.name: s for s in (TPU_V5E, TPU_V5P)}
DEFAULT_TARGET = TPU_V5E


#: ``device.device_kind`` as JAX reports it (the names JAX's own
#: ``mesh_utils`` knows) -> that chip's spec. A v5e reports "TPU v5 lite".
SPECS_BY_KIND = {
    "TPU v5 lite": TPU_V5E,
    "TPU v5e": TPU_V5E,
    "TPU v5p": TPU_V5P,
}


def spec_for_kind(kind: str) -> ChipSpec:
    """The spec of a TPU by its ``device_kind``; an unknown kind raises."""
    try:
        return SPECS_BY_KIND[kind]
    except KeyError:
        raise KeyError(
            f"no peak table for device_kind {kind!r}; known: "
            f"{sorted(SPECS_BY_KIND)} (add it to SPECS_BY_KIND with its source)"
        ) from None


def resolve_target(name: str | None) -> tuple[str, str]:
    """``(target name, basis)`` for a run record.

    An explicit ``name`` is used as given (basis ``"configured"``). Without
    one, the attached device decides: a TPU is looked up by its
    ``device_kind`` (basis ``"device_kind=<kind>"``); any other platform
    measures no chip, so the default target is a modeled one (basis
    ``"modeled"``).
    """
    if name is not None:
        get_target(name)
        return name, "configured"
    import jax

    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return spec_for_kind(dev.device_kind).name, f"device_kind={dev.device_kind}"
    return DEFAULT_TARGET.name, "modeled"


def get_target(name: str | None) -> ChipSpec:
    if name is None:
        return DEFAULT_TARGET
    try:
        return TARGETS[name]
    except KeyError:
        raise KeyError(f"unknown hardware target {name!r}; known: {sorted(TARGETS)}")
