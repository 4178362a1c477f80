"""chip_smoke.py's phases at a tiny size on the CPU, called directly (the
script itself refuses to run without a TPU — checked here too)."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


@pytest.fixture()
def scanned(tmp_path, monkeypatch):
    """A few hundred files (plus photos and planted duplicates) and a node
    to scan them; the phases take it from here."""
    from spacedrive_tpu.config import BackendFeature
    from spacedrive_tpu.node import Node

    monkeypatch.setenv("SD_SERVE_WORKERS", "2")
    fixture = chip_smoke.build_fixture(tmp_path, n_files=300, n_images=6,
                                       n_dup_pairs=4)
    node = Node(tmp_path / "data", watch_locations=False)
    node.config.toggle_feature(BackendFeature.TPU_THUMBNAILS)
    yield node, fixture
    node.shutdown()


@pytest.mark.parametrize("hasher", ["tpu", "hybrid"])
def test_scan_phase(scanned, hasher, capsys):
    node, fixture = scanned
    line = chip_smoke.scan_phase(node, fixture, hasher, timeout_s=120)
    assert line["files"] == fixture["files"] == 310
    assert line["objects"] == 306  # four planted duplicate pairs
    printed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert printed["phase"] == f"scan[{hasher}]"


def test_dedup_and_serve_phases(scanned):
    node, fixture = scanned
    scan = chip_smoke.scan_phase(node, fixture, "tpu", timeout_s=120)
    lib, loc = scan["library"], scan["location_id"]
    dedup = chip_smoke.dedup_phase(lib, loc, fixture)
    assert dedup["pairs"] == dedup["cpu_pairs"]
    serve = chip_smoke.serve_phase(node, lib, loc)
    assert serve["pool_alive"] == 2


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in_repo", "alone_in_a_directory"])
def test_exits_nonzero_without_a_chip(tmp_path, alone):
    script = REPO / "chip_smoke.py"
    cwd = REPO
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    if alone:
        cwd = tmp_path
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
        env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(script)], cwd=str(cwd),
                          env=env, capture_output=True, text=True,
                          timeout=180)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
