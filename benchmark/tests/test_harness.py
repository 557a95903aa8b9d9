"""The rest of a run without the look for a chip (`rehearsal`: tiny
volumes, host coder): every cell comes out correct as it stands, not
correct under its control, and not correct with the timed path broken
underneath it."""

import json
import os

import pytest

import run as harness
from conftest import ROOT

CELLS = ["degraded-get-rs10-4"]


def run_cell(cell: str, control: bool = False, seed: int = 2 ** 31 + 77):
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    out = harness.run_cell(bench, cell, seed, 3.0, trace=False,
                           rehearsal=True, control=control)
    json.dumps(out)  # the result line has to serialise
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = run_cell(cell)
    assert out["correct"] and out["failed"] == 0, out["checks"]
    assert out["attempted"] > 0
    assert all(c["value"] <= c["limit"] for c in out["checks"])


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    out = run_cell(cell, control=True)
    assert not out["correct"], out["checks"]


def flip_first(data):
    data = bytearray(bytes(data))
    data[0] ^= 0x5A
    return bytes(data)


def break_reconstruct(monkeypatch):
    """A degraded GET's interval altered where it is produced."""
    from seaweedfs_tpu.ec.ec_volume import EcVolume
    real = EcVolume._reconstruct_interval

    def reconstruct(self, *a, **kw):
        return flip_first(real(self, *a, **kw))
    monkeypatch.setattr(EcVolume, "_reconstruct_interval", reconstruct)


@pytest.mark.parametrize("cell,fault", [
    ("degraded-get-rs10-4", break_reconstruct)])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    out = run_cell(cell)
    assert not out["correct"], out["checks"]


def test_no_program_no_result(tmp_path):
    """A directory with only BENCHMARK.json and benchmark/ exits non-zero
    and prints no result."""
    import shutil
    import subprocess
    import sys
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "degraded-get-rs10-4", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
