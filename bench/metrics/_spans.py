"""What the readers of the program's own spans share.

The program marks its phases with profiler spans named ``repro.*``
(``repro.session.span``): the serving scheduler's ``repro.serve.*``, whose
tick span carries the tick's counters as arguments, and the TALP regions'
``repro.talp.*``. They are on the host plane of the same trace as the
benchmark's ``bench.step`` spans, so ``bench/trace.py`` reduces it once
more with that prefix, on the clock the device events are shifted to. A
reader counts only the spans that start inside the benchmark's window
(its first ``bench.step`` to its last) and returns nothing where there are
none, as in a trace of a program without them. Like every traced reading
here, these are read from a chip's trace only (``_common.usable``).
"""

from __future__ import annotations

import functools
import pathlib

from _common import usable

import harness  # noqa: E402  (on the path through _common)

PREFIX = "repro."
tr = harness.load_module(pathlib.Path(__file__).resolve().parents[1] / "trace.py",
                         "bench_trace_spans")


@functools.lru_cache(maxsize=2)
def reduced(path: str, chips: int = 1):
    """The trace at ``path`` reduced with the program's spans."""
    return tr.reduce(path, chips, span_prefix=PREFIX)


@functools.lru_cache(maxsize=2)
def arguments(path: str, name: str) -> list:
    """``(start_s, {argument: value})`` of each host span called ``name``,
    in start order."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(e.start_ns * 1e-9, dict(e.stats)) for e in line.events
                        if e.name == name]
    return sorted(out, key=lambda a: a[0])


def window(ctx):
    """The benchmark's window ``(t0, t1)``, or None off a chip or without
    a trace."""
    if not usable(ctx) or not ctx["out"].get("trace_path"):
        return None
    return ctx["trace"].t0, ctx["trace"].t1


def program(ctx):
    """The trace reduced with the program's spans, and those spans that
    start in the benchmark's window; None where there are none."""
    w = window(ctx)
    if w is None:
        return None
    red = reduced(ctx["out"]["trace_path"], ctx["cell"].chips)
    spans = [s for s in red.spans if w[0] <= s[1] <= w[1]]
    return (red, spans) if spans else None


def named(spans, name: str) -> list:
    return [(a, b) for n, a, b in spans if n == name]


def self_s(parents, children) -> list:
    """Host seconds of each parent span less the union of the child spans
    inside it."""
    kids = tr.union(children)
    return [(b - a) - tr.covered(kids, a, b) for a, b in parents]


def idle_split(busy, outer, inner) -> tuple[float, float]:
    """Device-idle seconds inside the ``outer`` spans, split into the part
    inside ``inner`` spans and the rest. ``busy``: the merged operation
    intervals of one device."""
    ins = tr.union(inner)
    idle_in = idle_out = 0.0
    for a, b in outer:
        idle = (b - a) - tr.covered(busy, a, b)
        part = sum((y - x) - tr.covered(busy, x, y)
                   for x, y in ((max(a, x), min(b, y)) for x, y in ins) if y > x)
        idle_in += part
        idle_out += idle - part
    return idle_in, idle_out


def train_idle_ms(ctx, part: str):
    """Mean device-idle ms per traced ``repro.talp.train_step`` span:
    ``"observe"``, inside its ``repro.talp.observe``; ``"dispatch"``, in the
    rest of it."""
    got = program(ctx)
    if got is None:
        return None
    red, spans = got
    steps = named(spans, "repro.talp.train_step")
    if not steps or not red.busy:
        return None
    inside, rest = idle_split(red.busy[0], steps, named(spans, "repro.talp.observe"))
    return 1e3 * (inside if part == "observe" else rest) / len(steps)
