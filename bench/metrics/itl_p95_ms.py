"""Scheduler, on the host's clock: 95th percentile over all gaps between
consecutive tokens of a request as they reach the host, gaps ending in
the window (ms). It sits on the edge between the ticks that carry a
prefill chunk and those that do not, so it swings by a fifth between runs
of one schedule: a per-layer reading, not an end-to-end bound."""


def read(ctx, name):
    return ctx["out"].get("e2e", {}).get("itl_p95_ms")
