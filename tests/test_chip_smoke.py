"""Rehearsal of ``chip_smoke.py`` on the CPU: its phase functions run the
same objects at smoke size with the paged kernels in interpret mode, and
``main()`` refuses to run without a TPU."""

import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

from repro.configs import smoke_config

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_rehearsal(chip_smoke):
    out = chip_smoke.serve_phase(
        smoke_config("tinyllama-1.1b"), impl="interpret", seed=0,
        n_requests=3, prompt_lens=(8, 40), max_len=64, page_size=8,
        num_pages=32, max_new=4,
    )
    assert out["logits_rel_diff"] <= chip_smoke.LOGIT_RTOL
    for run in out["runs"].values():
        assert sorted(run["streams"]) == [0, 1, 2]
        assert run["tokens"] == 12


def test_train_phase_rehearsal(chip_smoke, tmp_path):
    out = chip_smoke.train_phase(smoke_config("xlstm-350m"), steps=2,
                                 global_batch=2, seq_len=64,
                                 out_dir=str(tmp_path))
    assert len(out["losses"]) == 2
    assert (tmp_path / "site" / "xlstm-350m" / "index.html").exists()
    assert out["record"].metadata["hardware_basis"] == "modeled"


def test_scaling_phase_rehearsal_on_four_host_devices(tmp_path):
    """The --chips 4 path on four forced host devices, in a process of its
    own: the device count is fixed when JAX starts."""
    code = f"""
import sys
sys.path.insert(0, {str(ROOT)!r})
import chip_smoke
from repro.configs import smoke_config
out = chip_smoke.scaling_phase(smoke_config("tinyllama-1.1b"), global_batch=8,
                               seq_len=64, steps=2, out_dir={str(tmp_path)!r})
print("MODE", out["table"].mode, [r["state_devices"] for r in out["runs"]])
"""
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"),
                                           os.environ.get("PYTHONPATH", "")]))
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=str(tmp_path),
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MODE strong [[0], [0, 1], [0, 1, 2, 3]]" in r.stdout


def test_main_refuses_the_cpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    for line in out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
