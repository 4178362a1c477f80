"""The device gate, the compile-cache placement, the roofline table and
the bench's refusal to measure without a chip."""

import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from spacedrive_tpu.utils import jax_guard

REPO = Path(__file__).resolve().parent.parent


def _no_processes(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("the device gate started a process")

    monkeypatch.setattr(subprocess, "Popen", refuse)


def test_ensure_jax_safe_never_changes_platforms(monkeypatch):
    import jax

    _no_processes(monkeypatch)
    before = jax.config.jax_platforms
    assert jax_guard.ensure_jax_safe() is False  # the suite runs on the CPU
    assert jax_guard.ensure_jax_safe() is False
    assert jax.config.jax_platforms == before


def test_ensure_jax_safe_reports_an_accelerator_backend(monkeypatch):
    import jax

    _no_processes(monkeypatch)
    updates = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    assert jax_guard.ensure_jax_safe() is True
    assert updates == []


def test_device_info_reports_what_jax_reports():
    assert jax_guard.device_info() == {"platform": "cpu", "kind": "cpu",
                                       "count": 8}


def test_node_probes_its_accelerator_in_process(monkeypatch):
    from spacedrive_tpu import node

    _no_processes(monkeypatch)
    monkeypatch.setattr(subprocess, "run", subprocess.Popen)
    assert node._probe_accelerator() == {"platform": "cpu", "kind": "cpu",
                                         "devices": 0, "mesh": []}


def test_compile_cache_honours_the_env_var(monkeypatch, tmp_path):
    import jax

    updates = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    assert jax_guard.configure_compile_cache() == str(tmp_path)
    assert updates == []  # left to JAX: no other directory set in code


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = jax_guard.configure_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("kind,expect", [
    ("TPU v5 lite", "mfu"), ("TPU v99", "raises"), ("cpu", None)])
def test_roofline_peaks_by_device_kind(kind, expect):
    from spacedrive_tpu.ops import roofline

    dev = SimpleNamespace(platform="cpu" if kind == "cpu" else "tpu",
                          device_kind=kind)
    if expect == "raises":
        with pytest.raises(roofline.UnknownDeviceKind):
            roofline.mfu(1e9, dev)
    elif expect is None:
        assert roofline.peak(dev) is None and roofline.mfu(1e9, dev) is None
    else:
        assert 0 < roofline.mfu(1e9, dev) < 1


def test_native_artifact_key_follows_sources_and_flags(tmp_path):
    from spacedrive_tpu.native import _artifact_key

    src = tmp_path / "a.cc"
    src.write_text("int f() { return 1; }\n")
    key = _artifact_key(["g++", "-O3"], [src])
    assert key == _artifact_key(["g++", "-O3"], [src])
    assert key != _artifact_key(["g++", "-O2"], [src])
    src.write_text("int f() { return 2; }\n")
    assert key != _artifact_key(["g++", "-O3"], [src])


@pytest.mark.parametrize("mode", ["device_kernel", "identify", "dedup",
                                  "combined"])
def test_device_bench_modes_refuse_without_a_chip(mode):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "SD_BENCH_MODE": mode,
           "SD_BENCH_NO_ANALYSIS": "1"}
    proc = subprocess.run([sys.executable, str(REPO / "bench.py")], env=env,
                          cwd=str(REPO), capture_output=True, text=True,
                          timeout=180)
    assert proc.returncode != 0
    assert "FAILED PRECONDITION" in proc.stderr
    assert proc.stdout.strip() == ""  # no record, no CPU number
