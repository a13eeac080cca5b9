"""What every cell shares: finding a cell's files by name, the device, the
compile cache, tracing a window, and the result line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``. Its files are
found by name under ``<root>/bench``: ``workloads/<cell>.json`` (driver
kind, configuration, traffic, chips), ``configs/<config>.json`` (the sizes
run), ``traffic/<traffic>.json`` (the mix's parameters),
``drivers/<kind>.py`` and ``metrics/<metric>.py`` (one reader per
per-layer metric; ``a.b`` falls back to ``a.py``). Adding a cell, a mix, a
configuration or a metric is adding files and entries.
"""

from __future__ import annotations

import dataclasses
import glob
import importlib.util
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    root: pathlib.Path
    chips: int
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    @property
    def model(self) -> dict:
        return self.config["model"]


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    root = pathlib.Path(root)
    bench = _json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(entries)}")
    entry = entries[name]
    wl = _json(root / "bench" / "workloads" / f"{name}.json")
    return Cell(
        name=name, root=root, chips=int(entry["chips"]), workload=wl,
        config=_json(root / "bench" / "configs" / f"{entry['config']}.json"),
        traffic=_json(root / "bench" / "traffic" / f"{entry['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def program_config(cell: Cell):
    """The program's ``ModelConfig``: its named architecture with the
    configuration file's overrides (lists become tuples, a dict replaces
    fields of the nested group of that name)."""
    import dataclasses

    from repro.configs import get_config

    cfg = get_config(cell.config["arch"])
    kw = {}
    for k, v in cell.config.get("overrides", {}).items():
        if isinstance(v, dict):
            v = dataclasses.replace(getattr(cfg, k), **v)
        elif isinstance(v, list):
            v = tuple(v)
        kw[k] = v
    return cfg.replace(**kw)


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(cell: Cell):
    kind = cell.workload["driver"]
    return load_module(cell.root / "bench" / "drivers" / f"{kind}.py",
                       f"bench_driver_{kind}")


def metric_reader(cell: Cell, metric: str):
    d = cell.root / "bench" / "metrics"
    if str(d) not in sys.path:
        sys.path.insert(0, str(d))
    for stem in (metric, metric.split(".", 1)[0]):
        if (d / f"{stem}.py").exists():
            return load_module(d / f"{stem}.py", "bench_metric_" + stem.replace(".", "_"))
    raise FileNotFoundError(f"no reader for metric {metric!r} under {d}")


def use_compile_cache() -> None:
    """JAX's persistent cache where the program keeps it (``JAX_COMPILATION_CACHE_DIR``
    if set, else ``.jax_cache`` at the checkout's root), holding every
    program, so that only a cell's first run there compiles."""
    import jax

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips_or_exit(n: int):
    """The first ``n`` TPU chips; exits with code 3 on any other device."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        print(f"bench: needs {n} TPU chip(s); found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(3)
    return devs[:n]


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts backend compilations while entered (a window should hold
    none)."""

    def __init__(self):
        self.n = 0

    def _listen(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)


class Tracer:
    """Starts and stops the profiler around part of a window, in the
    checkout's ``.bench_trace`` directory."""

    def __init__(self, root: pathlib.Path, cell: str, seed: int):
        self.dir = root / ".bench_trace" / f"{cell}-{seed}"
        self.on = False
        self.path = None

    def start(self):
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.dir))
        self.on = True

    def stop(self):
        import jax

        jax.profiler.stop_trace()
        self.on = False
        found = glob.glob(str(self.dir / "**" / "*.xplane.pb"), recursive=True)
        self.path = found[0] if found else None
        return self.path


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (``q`` in 0..100) of a non-empty list."""
    v = sorted(values)
    k = max(0, min(len(v) - 1, -(-len(v) * q // 100) - 1))
    return float(v[int(k)])


def checks_line(checks: dict) -> dict:
    return {k: {"value": float(v), "limit": float(lim)}
            for k, (v, lim) in checks.items()}


def emit(result: dict) -> None:
    """Each compared number beside its limit as the last lines on standard
    error, then the result as the last line of standard output."""
    for k, c in result.get("checks", {}).items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def now() -> float:
    return time.perf_counter()
