"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per executed operation (a ``while`` event spans the operations
of its body), and their ``XLA Modules`` line one event per executed
program (``jit_<function>(<hash>)``). The benchmark's own host spans
(``bench.*``, written by ``jax.profiler.TraceAnnotation``) are on a host
thread of ``/host:CPU``, on the same clock. The traced window runs from
the first such span's start to the last one's end.

The device clock can lag the host's by a millisecond or so. Each program
is launched on the host (``TpuLoadedExecutable::ExecuteLaunch``) before
it starts on the device, so the device events are shifted by the largest
lead of a launch over the start of its program, pairing launches and
programs in order (on one chip, where their counts agree).
"""

from __future__ import annotations

import collections
import re

CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = re.compile(r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] ...`` -> ``fusion``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def module_name(event_name: str) -> str:
    """``jit_decode_step(123)`` -> ``jit_decode_step``."""
    return event_name.split("(", 1)[0]


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(merged, a: float, b: float) -> float:
    """Length of [a, b] covered by the merged intervals."""
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in merged)


class Reduced:
    """One trace, reduced. Times in seconds."""

    def __init__(self, spans, devices):
        self.spans = spans                 # [(name, start_s, end_s)] host
        self.devices = devices             # [{"ops": [...], "modules": [...]}]
        self.t0 = spans[0][1] if spans else 0.0
        self.t1 = spans[-1][2] if spans else 0.0
        self.window_s = self.t1 - self.t0
        self.busy = [union((a, b) for _, a, b in d["ops"]
                           if a < self.t1 and b > self.t0) for d in devices]

    @property
    def busy_s(self) -> float:
        """Seconds of the window in which an operation ran, mean over chips."""
        vals = [covered(m, self.t0, self.t1) for m in self.busy]
        return sum(vals) / len(vals) if vals else 0.0

    def in_window(self, events):
        return [e for e in events if e[1] >= self.t0 and e[2] <= self.t1]

    def op_time(self, name: str, dev: int = 0) -> tuple[float, int]:
        """(seconds, count) of operations called ``name`` in the window."""
        ev = [e for e in self.in_window(self.devices[dev]["ops"]) if e[0] == name]
        return sum(b - a for _, a, b in ev), len(ev)

    def module_times(self, name: str, dev: int = 0) -> list[float]:
        return [b - a for n, a, b in self.in_window(self.devices[dev]["modules"]) if n == name]

    def span_idle(self, name: str = "bench.step", dev: int = 0) -> list[float]:
        """Device-idle seconds inside each host span called ``name``."""
        m = self.busy[dev]
        return [(b - a) - covered(m, a, b) for n, a, b in self.spans if n == name]

    def collective_exposed_s(self, dev: int = 0) -> float:
        """Seconds of collectives during which no other operation runs."""
        ev = self.in_window(self.devices[dev]["ops"])
        coll = union((a, b) for n, a, b in ev if COLLECTIVES.match(n))
        comp = union((a, b) for n, a, b in ev
                     if not COLLECTIVES.match(n) and n not in CONTAINERS)
        return sum((b - a) - covered(comp, a, b) for a, b in coll)

    def top_ops(self, k: int = 10) -> list:
        """The operations that took most device time, mean over chips
        (containers left out: their bodies are counted)."""
        tot = collections.Counter()
        for d in self.devices:
            for n, a, b in self.in_window(d["ops"]):
                if n not in CONTAINERS:
                    tot[n] += (b - a) / len(self.devices)
        return [[n, t] for n, t in tot.most_common(k)]

    def top_gaps(self, k: int = 10) -> list:
        """The longest idle gaps of device 0, by the host span they fell in."""
        m = [(a, b) for a, b in self.busy[0]] if self.busy else []
        edges = [self.t0] + [x for ab in m for x in ab] + [self.t1]
        gaps = []
        for a, b in zip(edges[0::2], edges[1::2]):
            a, b = max(a, self.t0), min(b, self.t1)
            if b > a:
                mid = (a + b) / 2
                where = next((n for n, x, y in self.spans if x <= mid <= y), "between spans")
                gaps.append([where, b - a])
        gaps.sort(key=lambda g: -g[1])
        return gaps[:k]


LAUNCH = "TpuLoadedExecutable::ExecuteLaunch"


def clock_shift(launches, modules) -> float:
    """Seconds to add to device times so that no program starts before
    its launch."""
    if not launches or len(launches) != len(modules):
        return 0.0
    lead = max(a - m[1] for a, m in zip(sorted(launches), sorted(modules, key=lambda e: e[1])))
    return max(0.0, lead)


def _shifted(events, dt):
    return [(n, a + dt, b + dt) for n, a, b in events]


def reduce(path: str, chips: int = 1, span_prefix: str = "bench.") -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, devices, launches = [], {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            idx = int(plane.name.rsplit(":", 1)[1])
            d = devices.setdefault(idx, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    d["ops"] += [(op_name(e.name), e.start_ns * 1e-9, e.end_ns * 1e-9)
                                 for e in line.events]
                elif line.name == "XLA Modules":
                    d["modules"] += [(module_name(e.name), e.start_ns * 1e-9, e.end_ns * 1e-9)
                                     for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        spans.append((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9))
                    elif e.name == LAUNCH:
                        launches.append(e.start_ns * 1e-9)
    spans.sort(key=lambda s: s[1])
    devs = [devices[i] for i in sorted(devices)][:chips]
    if len(devs) == 1:
        dt = clock_shift(launches, devs[0]["modules"])
        devs = [{k: _shifted(v, dt) for k, v in devs[0].items()}]
    return Reduced(spans, devs)
