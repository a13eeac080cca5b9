"""The traffic generator: a pure function of the mix and the seed, the
same work in another order for every seed, and timed on the wall clock."""

import json
import pathlib

import numpy as np
import pytest

import loadgen

TRAFFIC = pathlib.Path(__file__).resolve().parents[1] / "traffic"
CHAT = json.loads((TRAFFIC / "chat.json").read_text())
DOCS = json.loads((TRAFFIC / "docs.json").read_text())
SEEDS = [1, 2**31 + 5, 2**33 + 17]


def test_same_seed_same_requests():
    a = loadgen.open_loop(dict(CHAT, rate_rps=2.0), 2**31 + 5, 51, 151552)
    b = loadgen.open_loop(dict(CHAT, rate_rps=2.0), 2**31 + 5, 51, 151552)
    assert [(r.prompt, r.max_new, r.due) for r in a] == [(r.prompt, r.max_new, r.due) for r in b]


@pytest.mark.parametrize("seed", SEEDS)
def test_open_loop_schedule(seed):
    reqs = loadgen.open_loop(dict(CHAT, rate_rps=2.0), seed, 51, 151552)
    due = [r.due for r in reqs]
    assert len(reqs) == 102 and due == sorted(due)
    assert due[0] == 0.0 and due[-1] < 51
    for r in reqs:
        assert CHAT["prompt"]["min"] <= len(r.prompt) <= CHAT["prompt"]["max"]
        assert CHAT["output"]["min"] <= r.max_new <= CHAT["output"]["max"]
        assert 0 <= min(r.prompt) and max(r.prompt) < 151552


def test_every_seed_gets_the_same_work():
    tot = []
    for seed in SEEDS:
        reqs = loadgen.open_loop(dict(CHAT, rate_rps=2.0), seed, 51, 151552)
        tot.append((sum(len(r.prompt) for r in reqs), sum(r.max_new for r in reqs)))
    assert len(set(tot)) == 1
    runs = [loadgen.open_loop(dict(CHAT, rate_rps=2.0), seed, 51, 151552) for seed in SEEDS]
    for size in (lambda r: len(r.prompt), lambda r: r.max_new):
        sets = [sorted(map(size, reqs)) for reqs in runs]
        assert sets[0] == sets[1] == sets[2]
    # and the medians sit where the mix puts them
    med = np.median([len(r.prompt) for r in loadgen.open_loop(dict(CHAT, rate_rps=2.0), 3, 51, 151552)])
    assert 0.85 * CHAT["prompt"]["median"] < med < 1.15 * CHAT["prompt"]["median"]


def test_closed_loop_rounds():
    qs = loadgen.closed_loop(DOCS, 2**31 + 5, 16, 151552)
    assert len(qs) == DOCS["clients"] and all(len(q) == 16 for q in qs)
    first = [len(q[0].prompt) for q in qs]       # the first round covers the range
    assert min(first) < DOCS["prompt"]["median"] < max(first)
    assert all(r.client == c for c, q in enumerate(qs) for r in q)
