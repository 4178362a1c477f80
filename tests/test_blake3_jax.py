"""TPU/JAX BLAKE3 kernel parity vs the pure-Python oracle.

Runs on the CPU backend with the virtual-device conftest; the same jitted
code path runs on real TPU (bench.py / __graft_entry__). Two compiled shapes
only (57-chunk sampled path, 101-chunk small-file bucket) to bound compile
time.
"""

import random

import numpy as np
import pytest

from spacedrive_tpu.objects.blake3_ref import blake3
from spacedrive_tpu.objects.cas import SAMPLED_MESSAGE_LEN, generate_cas_id_from_bytes
from spacedrive_tpu.ops import blake3_jax


@pytest.fixture(scope="module")
def rng():
    return random.Random(11)


def test_sampled_length_parity(rng):
    """The large-file hot path: every message exactly 57,352 bytes."""
    msgs = [rng.randbytes(SAMPLED_MESSAGE_LEN) for _ in range(8)]
    got = blake3_jax.blake3_batch_hex(msgs)
    assert got == [blake3(m).hex() for m in msgs]


def test_varlen_parity_all_boundaries(rng):
    """Small-file bucket: single/multi block, single/multi chunk, exact
    boundaries, the 101-chunk cas maximum, and a zero-length lane."""
    lens = [0, 1, 8, 63, 64, 65, 127, 128, 1023, 1024, 1025, 2047, 2048, 2049,
            3 * 1024, 4096, 5000, 65 * 1024, 102408]
    msgs = [rng.randbytes(n) for n in lens]
    got = blake3_jax.blake3_batch_hex(msgs, max_chunks=101)
    want = [blake3(m).hex() for m in msgs]
    assert got == want


def test_cas_ids_match_cpu_path(rng):
    """cas_id = digest[:16] — TPU batch must agree with objects/cas.py."""
    from spacedrive_tpu.objects import cas

    datas = [rng.randbytes(n) for n in (500, 1024 * 50, 102400)]
    msgs = []
    for d in datas:
        import struct

        msgs.append(struct.pack("<Q", len(d)) + d)  # small-file message form
    got = [h[:16] for h in blake3_jax.blake3_batch_hex(msgs, max_chunks=101)]
    want = [cas.generate_cas_id_from_bytes(d) for d in datas]
    assert got == want


@pytest.mark.parametrize("cap,n,kernel", [
    (1, 9, None),
    (4, 3, None),
    (4, 3, "xla"),
    (64, 3, None),
    (101, 9, None),
])
def test_batch_hex_parity_per_bucket(rng, cap, n, kernel):
    """Every bucket cap the hasher uses, at lane counts that are not tiers
    (so padding lanes are hashed and cut off); cap 64 carries the sampled
    57,352-byte message the photo scan hashes."""
    if cap == 64:
        lens = [SAMPLED_MESSAGE_LEN] * n
    else:
        lens = [rng.randrange(0, cap * 1024 + 1) for _ in range(n - 1)]
        lens.append(cap * 1024)  # a message that fills its bucket
    msgs = [rng.randbytes(k) for k in lens]
    got = blake3_jax.blake3_batch_hex(msgs, max_chunks=cap, kernel=kernel)
    assert got == [blake3(m).hex() for m in msgs]


def test_batch_hex_packs_rows_without_host_transpose(rng, monkeypatch):
    """The one-shot path packs row-major and permutes on the device: the
    batch-minor host packer is never called."""
    def no_host_transpose(*args, **kwargs):
        raise AssertionError("blake3_batch_hex called pack_messages")

    monkeypatch.setattr(blake3_jax, "pack_messages", no_host_transpose)
    msgs = [rng.randbytes(SAMPLED_MESSAGE_LEN) for _ in range(3)] + [b""]
    got = blake3_jax.blake3_batch_hex(msgs, max_chunks=64)
    assert got == [blake3(m).hex() for m in msgs]
    # the next call packs into the same host buffer: shorter messages must
    # not hash the previous call's bytes behind them
    short = [rng.randbytes(n) for n in (1, 1000, 30_000)]
    assert blake3_jax.blake3_batch_hex(short, max_chunks=64) == \
        [blake3(m).hex() for m in short]


def test_pack_messages_layout():
    msgs = [b"\x01\x02\x03\x04" + b"\x00" * 60, b"\xff" * 8]
    words, lengths = blake3_jax.pack_messages(msgs, 1)
    assert words.shape == (16, 16, 1, 2)
    assert list(lengths) == [64, 8]
    # little-endian word assembly: first word of msg0 = 0x04030201
    assert words[0, 0, 0, 0] == 0x04030201
    assert words[0, 0, 0, 1] == 0xFFFFFFFF
    with pytest.raises(ValueError):
        blake3_jax.pack_messages([b"x" * 2000], 1)


def test_pack_rows_layout():
    msgs = [bytes(range(1, 9)), b"\xff" * 1030, b""]
    rows, lengths = blake3_jax.pack_rows(msgs, 2)
    assert rows.shape == (3, 2 * 256) and rows.dtype == np.uint32
    assert rows.flags.c_contiguous
    assert list(lengths) == [8, 1030, 0]
    # row i holds message i's bytes in order, little-endian words
    assert rows[0, 0] == 0x04030201 and rows[0, 1] == 0x08070605
    for msg, row in zip(msgs, rows):
        raw = row.tobytes()
        assert raw[: len(msg)] == msg
        assert not any(raw[len(msg):])  # zero tail
    # the batch-minor packer's words, permuted back, are the same rows
    words, words_lengths = blake3_jax.pack_messages(msgs, 2)
    np.testing.assert_array_equal(words.transpose(3, 2, 0, 1).reshape(3, -1), rows)
    np.testing.assert_array_equal(words_lengths, lengths)
    # packing into a used buffer overwrites every byte: no stale tail
    out = np.full(4 * 2 * 1024, 0xAB, np.uint8)
    reused, _ = blake3_jax.pack_rows(msgs, 2, out=out)
    np.testing.assert_array_equal(reused, rows)
    assert np.shares_memory(reused, out)
    with pytest.raises(ValueError):
        blake3_jax.pack_rows([b"x" * 2049], 2)


def test_hybrid_hasher_adaptive_routing(tmp_path):
    """HybridHasher: byte-exact results across the probe and both routing
    outcomes; forcing the router's verdict either way must not change
    correctness."""
    from spacedrive_tpu.objects.cas import generate_cas_id, read_sampled_batch
    from spacedrive_tpu.objects.hasher import HybridHasher

    rng = random.Random(9)
    paths, sizes = [], []
    for i in range(40):  # 20 sampled-class: enough to carry the probe
        size = rng.choice([500, 50_000, 150_000, 200_000])
        p = tmp_path / f"h{i}.bin"
        p.write_bytes(rng.randbytes(size))
        paths.append(str(p))
        sizes.append(size)
    expect = [generate_cas_id(p, s) for p, s in zip(paths, sizes)]
    messages = read_sampled_batch(paths, sizes)

    hy = HybridHasher()
    assert hy.router.unprobed
    assert hy.hash_gathered(messages) == expect  # runs the probe inline
    assert not hy.router.unprobed
    assert hy.router.cpu_bps > 0 and hy.router.dev_bps is not None

    # force both verdicts and re-hash
    hy.router.seed(1.0, 0.0)
    assert hy.hash_gathered(messages) == expect
    hy.router.seed(1.0, 10.0)
    assert hy.hash_gathered(messages) == expect


def test_hybrid_router_provably_picks_fastest(tmp_path, monkeypatch):
    """The router's core guarantee (tpu-backend.md, ceiling section): when
    the CPU holds the route, NO sampled message is dispatched to the device
    — hybrid throughput equals the best engine by construction — and when
    the device holds it, only sampled messages reach it and every file
    still gets its cas_id."""
    from spacedrive_tpu.objects import hasher as hmod
    from spacedrive_tpu.objects.cas import generate_cas_id, read_sampled_batch

    rng = random.Random(11)
    paths, sizes = [], []
    for i in range(30):
        size = 150_000 + i if i % 5 else 500 + i  # 24 sampled, 6 small
        p = tmp_path / f"r{i}.bin"
        p.write_bytes(rng.randbytes(size))
        paths.append(str(p))
        sizes.append(size)
    expect = [generate_cas_id(p, s) for p, s in zip(paths, sizes)]
    messages = read_sampled_batch(paths, sizes)

    hy = hmod.HybridHasher()
    device_calls = []

    def spy(msgs, signatures=None):
        device_calls.append(list(msgs))
        return hy._cpu.hash_gathered(msgs)  # correct values, fake engine

    monkeypatch.setattr(hy._tpu, "hash_gathered", spy)

    # the CPU won: the sampled set must never reach the device
    hy.router.seed(1000.0, 10.0)
    assert hy.hash_gathered(messages) == expect
    assert device_calls == []

    # the device won: it takes the sampled messages, and only those, and
    # every index still resolves to the right cas_id
    hy.router.seed(10.0, 1000.0)
    assert hy.hash_gathered(messages) == expect
    sent = [m for call in device_calls for m in call]
    assert sent and all(len(m) == SAMPLED_MESSAGE_LEN for m in sent)
