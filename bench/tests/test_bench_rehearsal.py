"""Each driver end to end at smoke size on the CPU, through the same code
as a chip run but for the look for chips, with test-only cells added to a
copy of the benchmark as new files and new entries."""

import json
import pathlib

import jax
import pytest

import harness
import smoke

BENCH = pathlib.Path(__file__).resolve().parents[1]
SEED = 2**31 + 977          # seeds may exceed 32 signed bits
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = smoke.tree(tmp_path_factory.mktemp("bench"))
    # a per-layer metric added as a new reader file and a new entry
    (root / "bench" / "metrics" / "traced_ticks.py").write_text(
        "from _common import traced_ticks\n\n\n"
        "def read(ctx, name):\n    return float(len(traced_ticks(ctx))) or None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "traced_ticks.chat", "unit": "ticks", "better": "higher",
                               "source": "program_counter", "layer": "scheduler",
                               "moves": "itl_p95_ms", "workloads": ["tiny-chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run(root, name, trace=False, seconds=1.0):
    runmod = harness.load_module(BENCH / "run.py", "bench_run_rehearsal")
    cell = harness.load_cell(name, root)
    return cell, runmod.run_cell(cell, SEED, seconds, trace, jax.devices()[:1])


def last_line(res, capsys) -> dict:
    harness.emit(res)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("name", ["tiny-chat", "tiny-docs", "tiny-train"])
def test_cell_end_to_end(root, name, capsys):
    cell, res = run(root, name)
    line = last_line(res, capsys)
    assert list(line)[:5] == KEYS and list(line)[-1] == "checks"
    assert set(line) == set(KEYS) | {"checks"}
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert line["device"]["kind"]
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


def test_traced_run_reads_new_metric(root, capsys):
    _, res = run(root, "tiny-chat", trace=True, seconds=2.0)
    line = last_line(res, capsys)
    # on the CPU no device metric exists; the counter added as a file and
    # the host clock's tail of token gaps do
    assert set(line["metrics"]) == {"traced_ticks.chat", "itl_p95_ms.chat"}
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("name", ["glm4-chat", "xlstm-train"])
def test_real_cell_refuses_cpu(name, capsys):
    runmod = harness.load_module(BENCH / "run.py", "bench_run_refuse")
    with pytest.raises(SystemExit) as e:
        runmod.main(["--workload", name, "--seed", "1", "--seconds", "1"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""
