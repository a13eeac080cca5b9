"""The readers of the program's own spans (``bench/metrics/_spans.py`` and
the four metrics on it), checked on a small trace recorded on a TPU v5e:
three ``bench.step`` spans around a monitored step (``repro.talp.train_step``
holding ``repro.talp.observe``), then three around a serving tick with its
phases and a flush. Then a traced CPU rehearsal of the tiny chat cell,
read by the same readers."""

import glob
import json
import pathlib
import sys

import jax
import pytest

import harness
import peaks
import smoke

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
SPANS = str(HERE / "data" / "v5e_spans.xplane.pb")
SMALL = str(HERE / "data" / "v5e_small.xplane.pb")
SEED = 2**31 + 1013
NEW = {"prefill_backlog_tok.chat", "sched_self_ms.chat",
       "monitor_idle_ms.train", "dispatch_idle_ms.train"}

tr = harness.load_module(BENCH / "trace.py", "bench_trace_spans_test")
sys.path.insert(0, str(BENCH / "metrics"))
import _spans  # noqa: E402


class _Cell:
    chips = 1


def ctx_for(path, pk=peaks.V5E):
    return {"cell": _Cell(), "trace": tr.reduce(path), "out": {"trace_path": path},
            "peaks": pk, "model": {}}


def read(name, ctx):
    return harness.load_module(BENCH / "metrics" / f"{name.split('.')[0]}.py",
                               "bench_spans_reader").read(ctx, name)


def sweep_union_len(intervals, a, b):
    """Length of [a, b] covered by the intervals: a plain sweep,
    independent of trace.union."""
    total, end = 0.0, a
    for x, y in sorted(intervals):
        x, y = max(x, end), min(y, b)
        if y > x:
            total += y - x
        end = max(end, y)
    return total


@pytest.fixture(scope="module")
def red():
    return _spans.reduced(SPANS)


def test_recorded_spans(red):
    names = [n for n, _, _ in red.spans]
    assert names.count("repro.talp.train_step") == names.count("repro.talp.observe") == 3
    for n in ("tick", "attach", "pages", "decode", "prefill", "flush", "readback"):
        assert names.count("repro.serve." + n) == 3, n
    assert not [n for n in names if not n.startswith("repro.")]
    args = _spans.arguments(SPANS, "repro.serve.tick")
    assert [a["backlog_tok"] for _, a in args] == [100, 70, 40]


def test_idle_inside_and_outside_adds_up(red):
    steps = _spans.named(red.spans, "repro.talp.train_step")
    inside, rest = _spans.idle_split(red.busy[0], steps,
                                     _spans.named(red.spans, "repro.talp.observe"))
    assert inside >= 0 and rest > 0
    assert inside + rest == pytest.approx(sum(red.span_idle("repro.talp.train_step")), rel=1e-9)
    ticks = _spans.named(red.spans, "repro.serve.tick")
    a, b = _spans.idle_split(red.busy[0], ticks, _spans.named(red.spans, "repro.serve.decode"))
    assert a + b == pytest.approx(sum(red.span_idle("repro.serve.tick")), rel=1e-9)


def test_self_time_is_parent_less_union_of_children(red):
    ticks = _spans.named(red.spans, "repro.serve.tick")
    kids = [(a, b) for n, a, b in red.spans
            if n in ("repro.serve.decode", "repro.serve.prefill", "repro.serve.attach")]
    got = _spans.self_s(ticks, kids)
    for (a, b), s in zip(ticks, got):
        assert s == pytest.approx((b - a) - sweep_union_len(kids, a, b), rel=1e-9)
        assert 0 < s < b - a


def test_readers_on_the_recorded_trace(red):
    ctx = ctx_for(SPANS)
    assert read("prefill_backlog_tok.chat", ctx) == pytest.approx(70.0)
    # every flush of the trace lies outside its tick
    ticks = _spans.named(red.spans, "repro.serve.tick")
    flushes = _spans.named(red.spans, "repro.serve.flush")
    kids = [(a, b) for n, a, b in red.spans if n in (
        "repro.serve.decode", "repro.serve.prefill", "repro.serve.readback")]
    want = 1e3 * sum(_spans.self_s(ticks + flushes, kids)) / len(ticks)
    assert read("sched_self_ms.chat", ctx) == pytest.approx(want)
    mon, disp = read("monitor_idle_ms.train", ctx), read("dispatch_idle_ms.train", ctx)
    assert mon >= 0 and disp > 0
    assert mon + disp == pytest.approx(1e3 * sum(red.span_idle("repro.talp.train_step")) / 3)


def test_readers_need_the_program_spans_and_a_chip():
    for name in NEW:
        assert read(name, ctx_for(SMALL)) is None, name      # no repro.* span
        assert read(name, ctx_for(SPANS, pk=None)) is None, name   # off a chip


def test_only_spans_in_the_benchmark_window_count():
    ctx = ctx_for(SPANS)
    t = ctx["trace"]
    ticks = _spans.arguments(SPANS, "repro.serve.tick")
    t.t1 = ticks[1][0]        # the window closes at the second tick's start
    assert read("prefill_backlog_tok.chat", ctx) == pytest.approx(85.0)
    t.t0 = t.t1 = ticks[-1][0] + 1.0
    assert all(read(name, ctx) is None for name in NEW)


@pytest.fixture(scope="module")
def chat_trace(tmp_path_factory):
    """A traced tiny-chat run through the benchmark's own path, in a copy
    of the benchmark with the test cells added; its line and its trace."""
    root = smoke.tree(tmp_path_factory.mktemp("bench"))
    runmod = harness.load_module(BENCH / "run.py", "bench_run_spans")
    cell = harness.load_cell("tiny-chat", root)
    res = runmod.run_cell(cell, SEED, 2.0, True, jax.devices()[:1])
    (path,) = glob.glob(str(root / ".bench_trace" / f"tiny-chat-{SEED}" / "**" / "*.xplane.pb"),
                        recursive=True)
    return cell, res, path


def test_rehearsal_traced_chat(chat_trace, capsys):
    cell, res, path = chat_trace
    harness.emit(res)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    # the new entries apply to the test cell; off a chip they read nothing
    assert {"prefill_backlog_tok.chat", "sched_self_ms.chat"} <= {m["name"] for m in cell.per_layer}
    assert not NEW & set(line["metrics"])
    # the CPU trace, read as a chip's would be: the scheduler's spans are there
    ctx = ctx_for(path)
    backlog = read("prefill_backlog_tok.chat", ctx)
    own = read("sched_self_ms.chat", ctx)
    assert backlog > 0 and own > 0
    ticks = _spans.named(_spans.reduced(path).spans, "repro.serve.tick")
    assert own < 1e3 * sum(b - a for a, b in ticks) / len(ticks)
    # no device plane on the CPU: the device readers read nothing
    assert read("monitor_idle_ms.train", ctx) is None
    assert read("dispatch_idle_ms.train", ctx) is None
