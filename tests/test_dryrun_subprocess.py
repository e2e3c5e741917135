"""End-to-end dry-run integration: run launch/dryrun.py as a subprocess
(so the 512-device XLA flag applies) for one fast cell on both meshes, and
check the JSON record has every §Roofline input."""
import json
import os
import subprocess
import sys

import pytest


@pytest.mark.parametrize("mesh", ["single", "multi"])
def test_dryrun_one_cell_subprocess(tmp_path, mesh):
    env = dict(os.environ)
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env["PYTHONPATH"] = src
    env.pop("XLA_FLAGS", None)  # dryrun.py must set it itself
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "rwkv6-7b", "--shape", "long_500k",
         "--mesh", mesh, "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stdout + r.stderr
    rec = json.load(open(tmp_path / f"rwkv6-7b__long_500k__{mesh}__baseline"
                                    ".json"))
    assert rec["status"] == "ok"
    assert rec["chips"] == (512 if mesh == "multi" else 256)
    for key in ("flops_per_device", "bytes_per_device", "collective_bytes",
                "compute_s", "memory_s", "collective_s", "dominant",
                "model_flops", "useful_ratio", "roofline_fraction"):
        assert key in rec, key
    assert rec["flops_per_device"] > 0
    assert rec["compile_s"] > 0


def test_dryrun_list_enumerates_40_cells(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--list"],
        env=env, capture_output=True, text=True, timeout=540)
    assert r.returncode == 0
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 44     # 11 archs x 4 shapes
    assert sum(1 for ln in lines if "SKIP" in ln) == 9
