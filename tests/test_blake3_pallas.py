"""Pallas BLAKE3 kernel: parity with the XLA kernel and the pure-Python
oracle at every edge of the chunk/block geometry, in interpret mode (the
CPU-provable form of the deliverable — same kernel code compiles for TPU).

Geometry edges covered (the places tree-chaining bugs hide): empty input,
exactly one chunk (1024 B), one byte over (1025 B — the first parent
compression), partial final block, block boundaries, and the sampled
57,352-byte cas_id layout from objects/cas.py.
"""

import random
import struct
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from spacedrive_tpu.objects import cas
from spacedrive_tpu.objects.blake3_ref import blake3, blake3_recursive
from spacedrive_tpu.ops import blake3_jax

REPO = Path(__file__).resolve().parent.parent

#: empty, one block, partial block, block boundary, exactly one chunk,
#: 1025 (first parent merge), partial final block in chunk 2, two chunks,
#: and a capacity-filling four-chunk message
EDGE_LENS = (0, 64, 100, 128, 1024, 1025, 1500, 2048, 4096)


@pytest.fixture(scope="module")
def rng():
    return random.Random(23)


def test_kernel_resolution_env_and_arg(monkeypatch):
    monkeypatch.delenv("SD_BLAKE3_KERNEL", raising=False)
    assert blake3_jax.resolve_kernel() == "xla"
    assert blake3_jax.resolve_kernel("pallas") == "pallas"
    monkeypatch.setenv("SD_BLAKE3_KERNEL", "pallas")
    assert blake3_jax.resolve_kernel() == "pallas"
    assert blake3_jax.resolve_kernel("xla") == "xla"  # explicit wins
    monkeypatch.setenv("SD_BLAKE3_KERNEL", "warp-drive")
    assert blake3_jax.resolve_kernel() == "xla"  # unknown → safe default


def test_compress_primitive_parity(rng):
    """The two compression primitives agree word-for-word on random lanes
    (list-form message, broadcast counter/len/flags — both call shapes the
    orchestration uses)."""
    import jax.numpy as jnp

    from spacedrive_tpu.ops.blake3_pallas import compress_pallas

    shape = (3, 5)
    r = np.random.default_rng(7)
    cv = [jnp.asarray(r.integers(0, 2**32, shape, dtype=np.uint32))
          for _ in range(8)]
    m = [jnp.asarray(r.integers(0, 2**32, shape, dtype=np.uint32))
         for _ in range(16)]
    counter = jnp.asarray(r.integers(0, 57, shape, dtype=np.uint32))
    block_len = jnp.asarray(np.full(shape, 64, np.uint32))
    flags = jnp.asarray(np.full(shape, 1, np.uint32))
    want = blake3_jax.compress(cv, m, counter, block_len, flags)
    got = compress_pallas(cv, m, counter, block_len, flags)
    for w, g in zip(want, got):
        assert g.shape == shape
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


def test_edge_geometry_parity_both_kernels(rng):
    """Every geometry edge, all three implementations: Pallas-interpret ==
    XLA == both oracle constructions."""
    msgs = [rng.randbytes(n) for n in EDGE_LENS]
    want = [blake3(m).hex() for m in msgs]
    assert want == [blake3_recursive(m).hex() for m in msgs]
    got_pallas = blake3_jax.blake3_batch_hex(msgs, max_chunks=4,
                                             kernel="pallas")
    got_xla = blake3_jax.blake3_batch_hex(msgs, max_chunks=4, kernel="xla")
    assert got_pallas == want
    assert got_xla == want


def test_sampled_cas_layout_parity(rng):
    """The production hot path: the 57,352-byte sampled message from
    objects/cas.py, hashed at the 57-chunk shape the hasher compiles —
    cas_ids must match the scalar CPU path byte-for-byte."""
    from spacedrive_tpu.objects.hasher import SAMPLED_CHUNKS

    datas = [rng.randbytes(n) for n in (150_000, 102_401)]
    msgs = [cas.cas_message_from_bytes(d) for d in datas]
    assert all(len(m) == cas.SAMPLED_MESSAGE_LEN for m in msgs)
    want_ids = [cas.generate_cas_id_from_bytes(d) for d in datas]
    got = blake3_jax.blake3_batch_hex(msgs, max_chunks=SAMPLED_CHUNKS,
                                      kernel="pallas")
    assert [h[:16] for h in got] == want_ids


def test_small_whole_file_cas_golden(rng):
    """Small-file (≤100KiB) cas messages: size prefix + whole content —
    pallas output must match objects/cas.py's scalar golden."""
    datas = [b"", rng.randbytes(500), rng.randbytes(1016), rng.randbytes(1017)]
    msgs = [struct.pack("<Q", len(d)) + d for d in datas]
    want = [cas.generate_cas_id_from_bytes(d) for d in datas]
    got = blake3_jax.blake3_batch_hex(msgs, max_chunks=4, kernel="pallas")
    assert [h[:16] for h in got] == want


def test_msg_schedule_matches_permutation():
    """The baked schedule is exactly the iterated MSG_PERMUTATION."""
    from spacedrive_tpu.objects.blake3_ref import MSG_PERMUTATION
    from spacedrive_tpu.ops.blake3_pallas import MSG_SCHEDULE

    assert MSG_SCHEDULE[0] == tuple(range(16))
    for r in range(1, 7):
        assert MSG_SCHEDULE[r] == tuple(
            MSG_SCHEDULE[r - 1][p] for p in MSG_PERMUTATION)


def test_dryrun_multichip_pallas_interpret():
    """The acceptance gate: the full sharded identify step (8-device
    virtual mesh, (data, seq)=(4, 2)) with the Pallas kernel in interpret
    mode — byte-identical cas_ids, dedup collective intact. Subprocess so
    the env-selected kernel cannot leak into this process's jit caches."""
    env = {"SD_BLAKE3_KERNEL": "pallas", "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    import os

    full_env = {**os.environ, **env}
    full_env.pop("SD_DRYRUN_CHILD", None)
    code = ("import jax; jax.config.update('jax_platforms', 'cpu'); "
            "import __graft_entry__ as g; g.dryrun_multichip(8)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=str(REPO),
                          env=full_env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "dryrun_multichip OK" in proc.stdout


def test_roofline_mfu_model():
    from types import SimpleNamespace

    from spacedrive_tpu.ops import roofline

    assert roofline.OPS_PER_BYTE == 12.5
    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    peak = roofline.peak(v5e)
    # published 197 TFLOP/s over 4 MXUs of 128x128 MACs: a ~1.5 GHz clock
    assert peak.clock_hz == pytest.approx(1.503e9, rel=1e-3)
    assert peak.u32_ops == pytest.approx(8 * 128 * 4 * peak.clock_hz)
    roof = peak.u32_ops / roofline.OPS_PER_BYTE
    # the full roofline rate maps to MFU 1.0; half rate to 0.5
    assert roofline.mfu(roof, v5e) == pytest.approx(1.0)
    assert roofline.mfu(roof / 2, v5e) == pytest.approx(0.5)
    assert roofline.mfu(0, v5e) == 0.0
