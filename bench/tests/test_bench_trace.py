"""The trace reduction, checked on a small trace recorded on a TPU v5e:
four ``bench.step`` spans, each around one jitted matmul program."""

import pathlib

import pytest

from harness import load_module

HERE = pathlib.Path(__file__).resolve().parent
TRACE = str(HERE / "data" / "v5e_small.xplane.pb")
tr = load_module(HERE.parent / "trace.py", "bench_trace_test")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(TRACE)


def test_spans_and_window(reduced):
    assert [n for n, _, _ in reduced.spans] == ["bench.step"] * 4
    assert reduced.window_s == pytest.approx(reduced.spans[-1][2] - reduced.spans[0][1])
    assert 0.005 < reduced.window_s < 0.05


def test_programs_start_after_their_launch(reduced):
    mods = reduced.devices[0]["modules"]
    assert [n for n, _, _ in mods] == ["jit__lambda"] * 4
    # after the clock shift each program lies inside the span that launched it
    for (_, a, b), (_, x, y) in zip(mods, reduced.spans):
        assert x <= a and b <= y


def test_busy_is_the_union_of_operations(reduced):
    ops = sorted((a, b) for _, a, b in reduced.devices[0]["ops"])
    total, end = 0.0, float("-inf")
    for a, b in ops:            # plain sweep, independent of trace.union
        a = max(a, end)
        if b > a:
            total += b - a
        end = max(end, b)
    assert reduced.busy_s == pytest.approx(total, rel=1e-9)
    assert reduced.busy_s < reduced.window_s


def test_idle_inside_spans(reduced):
    idle = reduced.span_idle("bench.step")
    assert len(idle) == 4
    for (_, x, y), i in zip(reduced.spans, idle):
        busy = sum(max(0.0, min(y, b) - max(x, a)) for a, b in reduced.busy[0])
        assert i == pytest.approx((y - x) - busy)
        assert 0 < i < y - x


def test_breakdown(reduced):
    top = reduced.top_ops(10)
    assert top[0][0] == "fusion" and len(top) <= 10
    gaps = reduced.top_gaps(10)
    assert len(gaps) <= 10 and gaps == sorted(gaps, key=lambda g: -g[1])
    assert sum(g for _, g in reduced.top_gaps(100)) == pytest.approx(
        reduced.window_s - reduced.busy_s)
    assert reduced.collective_exposed_s() == 0.0


def test_names():
    assert tr.op_name("%paged_attention.10 = bf16[4,2] custom-call(...)") == "paged_attention"
    assert tr.op_name("%all-gather-start.3 = (bf16[8]) all-gather-start(x)") == "all-gather-start"
    assert tr.module_name("jit_chunk_step(1087)") == "jit_chunk_step"
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
