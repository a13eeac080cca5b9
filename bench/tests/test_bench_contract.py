"""BENCHMARK.json keeps to the format the benchmark's driver reads, and
every name in it finds its files."""

import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}


def test_top_level():
    assert set(BENCH) == TOP
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_configs_and_cells():
    cfgs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        body = json.loads((ROOT / c["file"]).read_text())
        assert sorted(body["reduced"]) == sorted(c["reduced"])
    used = set()
    four = 0
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["config"] in cfgs
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in used
        used.add((w["config"], w["traffic"]))
        four += w["chips"] == 4
        for sub in (f"workloads/{w['name']}.json", f"traffic/{w['traffic']}.json"):
            assert (ROOT / "bench" / sub).is_file(), sub
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    assert {w["config"] for w in BENCH["workloads"]} == set(cfgs)


def test_metrics():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names)) and "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        reports = e2e[m["moves"]].get("workloads", cells)
        assert set(m["workloads"]) <= set(reports)
        stem = m["name"].split(".", 1)[0]
        assert (ROOT / "bench" / "metrics" / f"{stem}.py").is_file()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for cell in cells:   # every cell reports setup_s, another end-to-end and a per-layer metric
        assert any(cell in m.get("workloads", cells) for m in BENCH["end_to_end"] if m["name"] != "setup_s")
        assert any(cell in m["workloads"] for m in BENCH["per_layer"])
