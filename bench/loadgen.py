"""Traffic from a mix's parameters and the seed.

Every seed gets the same set of sizes and gaps in another order: ``n``
requests take the quantiles ``(j + 0.5) / n`` of the mix's prompt, answer
and gap distributions. A fixed interleave spreads each set's ranks evenly
over the window, and the seed shuffles ranks only among neighbours of
similar size, the largest tenth keeping its place: with one prefill at a
time, a shuffle that moves the longest prompts moves the tail of the time
to first token by a fifth, so every seed loads the window alike. Token
ids are uniform over the vocabulary, drawn from the seed.

Open loop: requests are due on a schedule of exponential gaps at the mix's
rate, whether or not earlier ones have finished. Closed loop: each client
sends its next request when its previous one has completed. Either way a
request is timed from when it was due.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np

_SPREAD = ((5 ** 0.5 - 1) / 2, 2 ** 0.5 - 1, (3 ** 0.5 - 1) / 2)   # one a set
STRATUM = 4      # a rank trades places only with the neighbours of its stratum
KEEP_TOP = 0.1   # the largest tenth of each set keeps its place


@dataclasses.dataclass
class Request:
    rid: str
    prompt: list
    max_new: int
    due: float | None = None   # seconds after the window opened
    client: int = 0


def _quantiles(n: int, rng: np.random.Generator, k: int) -> np.ndarray:
    """The ``n`` mid-point quantiles, one for each position of the window,
    for the ``k``-th set of the mix."""
    spread = np.mod(np.arange(n) * _SPREAD[k], 1.0)
    rank_at = np.argsort(np.argsort(spread))      # the fixed interleave
    top = n - int(np.ceil(KEEP_TOP * n))
    rank = np.arange(n)
    for a in range(0, top, STRATUM):
        rank[a : min(a + STRATUM, top)] = rng.permutation(rank[a : min(a + STRATUM, top)])
    return ((rank[rank_at] + 0.5) / n).astype(np.float64)


def lengths(spec: dict, q: np.ndarray) -> np.ndarray:
    """Lognormal lengths at quantiles ``q``: median, sigma, clipped."""
    z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _requests(traffic: dict, n: int, seed: int, vocab: int, tag: str):
    rng = np.random.default_rng([int(seed), 7])
    p_len = lengths(traffic["prompt"], _quantiles(n, rng, 0))
    o_len = lengths(traffic["output"], _quantiles(n, rng, 1))
    reqs = []
    for j in range(n):
        ids = rng.integers(0, vocab, size=int(p_len[j]), dtype=np.int64)
        reqs.append(Request(f"{tag}{j}", ids.tolist(), int(o_len[j])))
    return reqs, rng


def open_loop(traffic: dict, seed: int, seconds: float, vocab: int,
              tag: str = "r") -> list[Request]:
    """Requests due in ``[0, seconds)`` at ``rate_rps``; the gaps sum to the
    window, so every seed sends the same number."""
    n = max(1, int(round(traffic["rate_rps"] * seconds)))
    reqs, rng = _requests(traffic, n, seed, vocab, tag)
    gaps = -np.log1p(-_quantiles(n, rng, 2))
    due = np.cumsum(gaps) - gaps[0]
    due = due * (seconds * (n - 1) / n) / max(due[-1], 1e-9) if n > 1 else due
    for r, t in zip(reqs, due):
        r.due = float(t)
    return reqs


def closed_loop(traffic: dict, seed: int, per_client: int, vocab: int,
                tag: str = "r") -> list[list[Request]]:
    """``per_client`` requests for each of the mix's clients, dealt out in
    turn from the shuffled set."""
    c = traffic["clients"]
    reqs, _ = _requests(traffic, c * per_client, seed, vocab, tag)
    out = [[] for _ in range(c)]
    for j, r in enumerate(reqs):
        r.client = j % c
        out[j % c].append(r)
    return out
