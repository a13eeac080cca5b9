"""Program spans on the profiler's clock: ``repro.session.span``, the TALP
regions as ``repro.talp.*`` spans, and the serving scheduler's phases as
``repro.serve.*`` spans with the tick's counters. Each test records a CPU
profiler trace and reads it back with ``ProfileData``."""

import glob

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.configs import smoke_config
from repro.launch.mesh import make_host_mesh
from repro.layers.common import init_params
from repro.models import transformer as T
from repro.serve.serve import BatchScheduler, ServeConfig
from repro.session import ENV_ENABLE, PerfSession, SessionConfig, span


def record(tmp_path, fn):
    """Run ``fn`` under the profiler; the ``repro.*`` host spans as
    ``(name, start_ns, end_ns, args)`` in start order."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                        for e in line.events if e.name.startswith("repro.")]
    return sorted(out, key=lambda s: s[1])


def named(spans, name):
    return [s for s in spans if s[0] == name]


def inside(child, parents) -> bool:
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def test_span_prefix_and_counters(tmp_path):
    def run():
        with span("x.y", a=3, b=4):
            jnp.ones(4).block_until_ready()

    spans = record(tmp_path, run)
    assert [(n, a) for n, _, _, a in spans] == [("repro.x.y", {"a": 3, "b": 4})]


@pytest.fixture(scope="module")
def serve_spans(tmp_path_factory):
    """Two prompts of 40 and 70 tokens submitted together, 32-token
    prefill chunks, paged; each tick followed by a flush, as a streaming
    caller does."""
    cfg = smoke_config("tinyllama-1.1b").replace(
        compute_dtype_name="float32", param_dtype_name="float32")
    mesh = make_host_mesh()
    params = init_params(T.model_params(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    with mesh:
        sched = BatchScheduler(cfg, mesh, ServeConfig(
            max_len=128, batch=2, prefill_chunk=32, page_size=16), params)

        def run():
            for rid, n in enumerate((40, 70)):
                sched.submit(list(range(1, n + 1)), request_id=rid, max_new=3)
            while len(sched.completed) < 2:
                sched.step()
                sched.flush()

        spans = record(tmp_path_factory.mktemp("serve"), run)
    return sched, spans


def test_tick_counters(serve_spans):
    sched, spans = serve_spans
    ticks = named(spans, "repro.serve.tick")
    assert len(ticks) == sched.stats["ticks"]
    for _, _, _, args in ticks:
        assert set(args) == {"queued", "prefilling", "backlog_tok", "decoding", "free_pages"}
    # one chunk a tick: 32 and 8 tokens of the first prompt, then 32, 32
    # and 6 of the second
    assert [a["backlog_tok"] for *_, a in ticks][:6] == [110, 78, 70, 38, 6, 0]
    assert [a["queued"] for *_, a in ticks][:2] == [2, 0]
    assert [a["prefilling"] for *_, a in ticks][:6] == [0, 2, 1, 1, 1, 0]
    assert ticks[0][3]["free_pages"] == 16 and ticks[0][3]["decoding"] == 0
    prefills = named(spans, "repro.serve.prefill")
    assert [(a["start"], a["tokens"]) for *_, a in prefills] == [
        (0, 32), (32, 8), (0, 32), (32, 32), (64, 6)]


def test_decode_pages_counters(serve_spans):
    """Each decode dispatch counts the pages the kernel walks and the
    table entries the whole grid holds (2 slots x 8 pages)."""
    _, spans = serve_spans
    decodes = named(spans, "repro.serve.decode")
    # the first prompt decodes at 41 and 42 tokens (3 pages) while the
    # second's first chunk has 0, then 1 page below its cache_len of 1;
    # then the second at 71 and 72 tokens (5 pages) beside the released
    # first slot, whose stale position walks none of its -1 entries
    assert [a for *_, a in decodes] == [
        {"live_pages": n, "grid_pages": 16} for n in (3, 4, 5, 5)]


def test_phases_nest_in_their_tick_or_flush(serve_spans):
    sched, spans = serve_spans
    ticks = named(spans, "repro.serve.tick")
    flushes = named(spans, "repro.serve.flush")
    for name in ("attach", "pages", "decode", "prefill"):
        found = named(spans, "repro.serve." + name)
        assert found and all(inside(s, ticks) for s in found), name
    assert len(named(spans, "repro.serve.attach")) == len(ticks)
    assert len(named(spans, "repro.serve.decode")) == sched.stats["decode_steps"]
    # every readback is a flush's; the caller's flushes lie between ticks,
    # and a tick flushes itself when a request's last token is due
    reads = named(spans, "repro.serve.readback")
    assert len(reads) == len(flushes)
    assert all(inside(r, flushes) for r in reads)
    own = [f for f in flushes if inside(f, ticks)]
    assert own and len(own) < len(flushes)


def test_spec_readbacks_nest(tmp_path):
    """Speculative decode reads back in the verify tick and at the first
    token, inside the tick and the prefill that caused them."""
    cfg = smoke_config("tinyllama-1.1b").replace(
        compute_dtype_name="float32", param_dtype_name="float32")
    mesh = make_host_mesh()
    params = init_params(T.model_params(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    with mesh:
        sched = BatchScheduler(cfg, mesh, ServeConfig(
            max_len=64, batch=2, prefill_chunk=8, page_size=8,
            spec_decode=True, spec_k=2), params)

        def run():
            sched.submit([1, 2, 3, 1, 2, 3, 1, 2, 3, 1], request_id=0, max_new=6)
            while not sched.completed:
                sched.step()

        spans = record(tmp_path, run)
    ticks = named(spans, "repro.serve.tick")
    prefills = named(spans, "repro.serve.prefill")
    reads = named(spans, "repro.serve.readback")
    assert all(inside(r, ticks) for r in reads)
    assert sum(inside(r, prefills) for r in reads) == 1   # the first token
    assert len(reads) == 1 + sched.stats["spec_dispatches"]
    assert len(named(spans, "repro.serve.decode")) == sched.stats["spec_dispatches"]
    assert not named(spans, "repro.serve.flush")


def test_monitor_train_step_holds_observe(tmp_path, monkeypatch):
    from repro.launch.train import build_loop

    monkeypatch.delenv(ENV_ENABLE, raising=False)
    loop = build_loop(smoke_config("tinyllama-1.1b"), make_host_mesh(), steps=2,
                      global_batch=2, seq_len=16, pad_fraction=0.0)
    assert loop.session.backend == "monitor"
    spans = record(tmp_path, loop.run)
    init = named(spans, "repro.talp.initialize")
    steps = named(spans, "repro.talp.train_step")
    observe = named(spans, "repro.talp.observe")
    assert len(init) == 1 and len(steps) == len(observe) == 2
    for st, ob in zip(steps, observe):
        assert st[1] < ob[1] and ob[2] <= st[2]
    assert all(s[2] <= steps[0][1] for s in init)


def test_event_and_region_spans(tmp_path):
    ses = PerfSession(SessionConfig(backend="monitor", respect_env=False, sync_regions=False))

    outer = ses.region("outer")

    def run():
        with outer:
            with outer:   # the same handle, nested
                ses.event("recovery")

    spans = record(tmp_path, run)
    assert [n for n, *_ in spans] == ["repro.talp.outer", "repro.talp.outer",
                                      "repro.talp.recovery", "repro.talp.observe"]
    outer, inner, rec, obs = spans
    assert outer[1] <= inner[1] <= rec[1] <= obs[1] <= obs[2] <= rec[2] <= inner[2] <= outer[2]


def test_null_backend_emits_no_talp_span(tmp_path):
    ses = PerfSession(SessionConfig(backend="null", respect_env=False))
    f = jax.jit(lambda x: x * 2)
    assert ses.wrap_step(f, region="train_step") is f

    def run():
        with ses.region("train_step"):
            f(jnp.ones(4)).block_until_ready()
        ses.observe_step(None)
        ses.event("recovery")

    assert not [s for s in record(tmp_path, run) if s[0].startswith("repro.talp.")]
