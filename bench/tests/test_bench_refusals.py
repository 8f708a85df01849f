"""What the harness refuses: an unknown chip, a machine without a TPU, a
checkout without the program."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import run

ROOT = Path(__file__).resolve().parents[2]


def test_peaks_refuse_an_unknown_device_kind():
    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(run.Refused):
        run.peaks_for("TPU v99")


def _cell():
    return json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    cmd = [sys.executable, "bench/run.py", "--workload", _cell()["name"],
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {"PYTHONPATH": ""}
    p = _run(tmp_path, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not beside the benchmark" in p.stderr


def test_refuses_an_unknown_workload():
    with pytest.raises(run.Refused):
        run.load_cell("no-such-cell")


def test_every_cell_resolves_to_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        spec = run.load_cell(w["name"])
        assert spec["cfg"]["chips"] == w["chips"]
        run.reference_module(spec["cfg"])
        names = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert spec["per_layer"]
    for m in bench["per_layer"]:
        assert callable(run.metric_reader(m["name"]).read)
