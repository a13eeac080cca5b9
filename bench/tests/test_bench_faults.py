"""A run with the timed path broken underneath comes out not correct: the
harness's look for chips is skipped, the rest of a run is driven at smoke
size with a fault planted in the program, once for each fault the cells
can have. The cells run on one chip, so no exchange between chips exists
to leave out."""

import jax
import pytest

import harness
import smoke
from test_bench_rehearsal import run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return smoke.tree(tmp_path_factory.mktemp("bench"))


def test_token_altered_where_produced(root, monkeypatch):
    import repro.serve.serve as S

    orig = S._sample_tokens

    def altered(logits, *a, **k):      # the greedy pick, moved one id up
        return (orig(logits, *a, **k) + 1) % logits.shape[-1]

    S._serve_step_fns.cache_clear()
    monkeypatch.setattr(S, "_sample_tokens", altered)
    try:
        _, res = run(root, "tiny-chat")
    finally:
        S._serve_step_fns.cache_clear()
    assert res["correct"] is False
    c = res["checks"]["logit_gap"]
    assert c["value"] > c["limit"]


def _broken_step(monkeypatch, wrap):
    import repro.train.train as TT

    orig = TT.make_train_step

    def make(cfg, mesh, tcfg):
        return wrap(orig(cfg, mesh, tcfg))

    monkeypatch.setattr(TT, "make_train_step", make)


def test_step_returns_state_unchanged(root, monkeypatch):
    def wrap(f):
        def step(state, batch):
            _, metrics = f(state, batch)
            return state, metrics
        return step

    _broken_step(monkeypatch, wrap)
    _, res = run(root, "tiny-train", seconds=0.5)
    assert res["correct"] is False
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)
    assert res["checks"]["grad_median_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_left_out(root, monkeypatch):
    def wrap(f):
        def step(state, batch):
            half = batch["labels"].shape[1] // 2
            return f(state, jax.tree_util.tree_map(lambda x: x[:, :half], batch))
        return step

    _broken_step(monkeypatch, wrap)
    _, res = run(root, "tiny-train", seconds=0.5)
    assert res["correct"] is False
