"""JAX compat layer: each helper against the installed JAX, plus the
repo-wide policy check that version-gated attribute access lives only in
compat.py."""

import contextlib
import pathlib
import re

import jax
import pytest

from repro import compat


# ---------------------------------------------------------------------------
# mesh construction and the ambient mesh
# ---------------------------------------------------------------------------


def _mesh_fingerprint(mesh):
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape))


def test_make_mesh_new_api_passes_auto_axis_types():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    assert _mesh_fingerprint(mesh) == (("data", "model"), (1, 1))
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,) * 2


def test_make_mesh_over_a_device_subset():
    dev = jax.devices()[:1]
    mesh = compat.make_mesh((1, 1), ("data", "model"), devices=dev)
    assert [d.id for d in mesh.devices.flat] == [dev[0].id]


def test_use_mesh_prefers_new_api(monkeypatch):
    used = []

    @contextlib.contextmanager
    def fake_set_mesh(mesh):
        used.append(mesh)
        yield mesh

    monkeypatch.setattr(jax, "set_mesh", fake_set_mesh)
    mesh = object()  # never entered directly -> no __enter__ needed
    with compat.use_mesh(mesh) as m:
        assert m is mesh
    assert used == [mesh]


def test_use_mesh_does_not_swallow_body_exceptions():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    with pytest.raises(TypeError, match="from the body"):
        with compat.use_mesh(mesh):
            raise TypeError("from the body")


def test_use_mesh_sets_and_restores_the_ambient_mesh():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    before = jax.sharding.get_abstract_mesh()
    with compat.use_mesh(mesh):
        assert jax.sharding.get_abstract_mesh().axis_names == ("data", "model")
    assert jax.sharding.get_abstract_mesh() == before


# ---------------------------------------------------------------------------
# compiled-executable accessors
# ---------------------------------------------------------------------------


def test_cost_analysis_normalizes_dict_and_errors():
    class D:
        def cost_analysis(self):
            return {"flops": 5.0, "bytes accessed": 7}

    class E:
        def cost_analysis(self):
            raise RuntimeError("unsupported backend")

    assert compat.cost_analysis(D()) == {"flops": 5.0, "bytes accessed": 7.0}
    assert compat.cost_analysis(E()) == {}


def test_memory_stats_normalizes_and_survives_absence():
    class MS:
        argument_size_in_bytes = 128
        temp_size_in_bytes = 64

    class C:
        def memory_analysis(self):
            return MS()

    class E:
        def memory_analysis(self):
            raise NotImplementedError

    out = compat.memory_stats(C())
    assert out == {"argument_size_in_bytes": 128.0, "temp_size_in_bytes": 64.0}
    assert compat.memory_stats(E()) == {}


def test_compiled_text_raises_instead_of_returning_empty():
    """'' would flow into analyze_hlo as a silent all-zero cost — the
    accessor must fail loudly instead."""

    class Broken:
        def as_text(self):
            raise RuntimeError("backend cannot dump HLO")

    with pytest.raises(RuntimeError):
        compat.compiled_text(Broken())
    with pytest.raises(AttributeError):
        compat.compiled_text(object())


def test_accessors_on_real_compiled_executable():
    import jax.numpy as jnp

    compiled = jax.jit(lambda a, b: a @ b).lower(
        jax.ShapeDtypeStruct((8, 16), jnp.float32),
        jax.ShapeDtypeStruct((16, 4), jnp.float32),
    ).compile()
    assert compat.cost_analysis(compiled).get("flops", 0) > 0
    assert "argument_size_in_bytes" in compat.memory_stats(compiled)
    assert "ENTRY" in compat.compiled_text(compiled)


# ---------------------------------------------------------------------------
# policy: version-gated JAX access only inside compat.py
# ---------------------------------------------------------------------------


def test_no_version_gated_jax_access_outside_compat():
    root = pathlib.Path(__file__).resolve().parent.parent
    gated = re.compile(
        r"jax\.sharding\.AxisType|axis_types\s*=|\bjax\.make_mesh\b"
        r"|jax\.sharding\.use_mesh|\bjax\.set_mesh\b"
    )
    offenders = []
    for sub in ("src", "benchmarks", "examples"):
        for p in (root / sub).rglob("*.py"):
            if p.name == "compat.py":
                continue
            if gated.search(p.read_text()):
                offenders.append(str(p.relative_to(root)))
    # tests may *simulate* the APIs (this file); production trees may not
    for p in (root / "tests").rglob("*.py"):
        if p.name == "test_compat.py":
            continue
        if "jax.sharding.AxisType" in p.read_text():
            offenders.append(str(p.relative_to(root)))
    assert not offenders, f"version-gated JAX access outside compat.py: {offenders}"


def test_pallas_imported_only_via_compat():
    """Kernel code (flash_attention, rmsnorm, paged_attention, and whatever
    comes next) reaches the Pallas modules through ``compat.pallas()`` /
    ``compat.pallas_tpu()`` — the experimental namespace moves between JAX
    releases, so the import is a version-gated access like any other and
    lives only in compat.py."""
    root = pathlib.Path(__file__).resolve().parent.parent
    gated = re.compile(
        r"from\s+jax\.experimental\s+import\s+pallas|jax\.experimental\.pallas"
    )
    offenders = []
    for sub in ("src", "benchmarks", "examples", "tests"):
        for p in (root / sub).rglob("*.py"):
            if p.name in ("compat.py", "test_compat.py"):
                continue
            if gated.search(p.read_text()):
                offenders.append(str(p.relative_to(root)))
    assert not offenders, f"Pallas imported outside compat.py: {offenders}"


def test_pallas_accessors_return_modules_when_present():
    assert hasattr(compat.pallas(), "pallas_call")
    assert hasattr(compat.pallas_tpu(), "PrefetchScalarGridSpec")


# ---------------------------------------------------------------------------
# policy: one instrumentation surface — collectors are constructed only
# behind the repro.session facade (same grep style as the compat rule)
# ---------------------------------------------------------------------------


def test_collectors_constructed_only_behind_the_session_facade():
    """All code — production AND tests — reaches instrumentation through
    PerfSession; the concrete ``TalpMonitor``/``TraceRecorder`` constructors
    are private to the session module and their defining modules. The
    one-release deprecation shims in ``repro.core`` are gone (PR 3's window
    ended), so the former tests-may-exercise-the-legacy-path carve-out is
    gone with them."""
    root = pathlib.Path(__file__).resolve().parent.parent
    construct = re.compile(r"\b(?:TalpMonitor|TraceRecorder)\s*\(")
    allowed = {
        "src/repro/session.py",       # the facade's backends
        "src/repro/core/monitor.py",  # the implementations themselves
        "src/repro/core/tracer.py",
    }
    offenders = []
    for sub in ("src", "benchmarks", "examples", "tests"):
        for p in (root / sub).rglob("*.py"):
            rel = str(p.relative_to(root))
            if rel in allowed:
                continue
            if construct.search(p.read_text()):
                offenders.append(rel)
    assert not offenders, (
        f"direct collector construction outside repro.session: {offenders}"
    )
