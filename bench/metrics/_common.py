"""Helpers the per-layer metric readers share."""

from __future__ import annotations

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import flops  # noqa: E402
import peaks  # noqa: E402


def traced_ticks(ctx) -> list:
    i0, i1 = ctx["out"]["traced"]
    return ctx["out"]["ticks"][i0:i1] if i0 is not None and i1 is not None else []


def traced_steps(ctx) -> int:
    i0, i1 = ctx["out"]["traced"]
    return (i1 - i0) if i0 is not None and i1 is not None else 0


def usable(ctx) -> bool:
    t = ctx["trace"]
    return t is not None and t.window_s > 0 and ctx["peaks"] is not None
