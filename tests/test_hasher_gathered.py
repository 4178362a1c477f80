"""The device hashers' gathered path against the CPU hasher.

``hash_gathered`` takes the messages the identify pipeline's prefetcher
gathered: sampled 57,352-byte messages of large files, whole-file messages
of small ones, and gather failures, which pass through in place. The
single-device and the mesh-sharded hasher must give the CPU path's cas_ids
position by position (the sharded one on the suite's 8 virtual devices),
and the program's reference BLAKE3's. The hybrid hasher probes its
engines on those same messages and reads no file itself.

The bucket-edge page holds the cas message of a file at each edge of a
chunk-capacity bucket (size + 8 bytes fills 1, 4, 16, 32, 64 or 101
chunks of 1,024 bytes) and of one byte more, which takes the next bucket,
and one sampled file: one call hashes every bucket.
"""

import random
from pathlib import Path

import pytest

from spacedrive_tpu.objects.blake3_ref import blake3
from spacedrive_tpu.objects.cas import (SAMPLED_MESSAGE_LEN,
                                        cas_message_from_bytes,
                                        generate_cas_id, read_sampled_batch)
from spacedrive_tpu.objects.hasher import (CpuHasher, HybridHasher,
                                           ShardedHasher, TpuHasher)

#: file sizes at each bucket edge and one byte past it, up to the
#: whole-file limit (100 KiB)
EDGE_SIZES = (1016, 1017, 4088, 4089, 16376, 16377, 32760, 32761,
              65528, 65529, 102400)


def _gathered_messages() -> list:
    rng = random.Random(24)
    msgs: list = [rng.randbytes(SAMPLED_MESSAGE_LEN) for _ in range(5)]
    msgs += [rng.randbytes(n) for n in (8, 500, 1024, 1025, 4000)]
    msgs += [OSError("gather failed: a.arw"), OSError("gather failed: b.arw")]
    rng.shuffle(msgs)
    return msgs


def _bucket_edge_messages() -> list:
    rng = random.Random(25)
    msgs: list = [cas_message_from_bytes(rng.randbytes(n))
                  for n in EDGE_SIZES + (250_000,)]
    msgs.append(OSError("gather failed: c.dll"))
    rng.shuffle(msgs)
    return msgs


@pytest.mark.parametrize("backend,messages", [
    (TpuHasher, _gathered_messages), (ShardedHasher, _gathered_messages),
    (TpuHasher, _bucket_edge_messages), (ShardedHasher, _bucket_edge_messages),
], ids=["tpu", "tpu-sharded", "tpu-bucket_edges", "tpu-sharded-bucket_edges"])
def test_hash_gathered_matches_cpu_hasher(backend, messages):
    msgs = messages()
    want = CpuHasher().hash_gathered(msgs)
    got = backend().hash_gathered(msgs)
    assert len(got) == len(msgs)
    for msg, g, w in zip(msgs, got, want):
        if isinstance(msg, Exception):
            assert g is msg and w is msg
        else:
            assert g == w == blake3(msg).hex()[:16]


def test_bucket_edge_page_hashes_every_bucket():
    """One call over the bucket-edge page makes one device call per
    bucket: a message that fills a bucket stays in it, one byte more takes
    the next, and the sampled message takes the 64-chunk bucket."""
    hasher = TpuHasher()
    calls = []
    # the digests are the parametrised test's; here only the routing
    hasher._hash_bucket = lambda msgs, cap, lanes: (
        calls.append((cap, sorted(len(m) - 8 for m in msgs)))
        or (["0" * 64] * len(msgs), {}))
    hasher.hash_gathered(_bucket_edge_messages())
    assert calls == [(1, [1016]), (4, [1017, 4088]), (16, [4089, 16376]),
                     (32, [16377, 32760]), (64, [32761, 57344, 65528]),
                     (101, [65529, 102400])]


def test_bucket_calls_do_not_depend_on_earlier_calls():
    """Every call of a bucket hashes ``bucket_rows(cap)`` rows, whatever
    came before: a tiny first call does not shrink the calls of a 4,096-file
    page, a bucket with more messages is split, and no page after the
    first compiles a new shape."""
    from spacedrive_tpu.objects.hasher import SMALL_BUCKETS, bucket_rows
    from spacedrive_tpu.ops.blake3_jax import _blake3_batch_rows_impl

    assert [bucket_rows(cap) for cap in SMALL_BUCKETS] == [
        1024, 1024, 1024, 1024, 512, 256]
    rng = random.Random(27)
    hasher = TpuHasher()

    def page(n):
        msgs = [rng.randbytes(600) for _ in range(n)]
        assert hasher.hash_gathered(msgs) == CpuHasher().hash_gathered(msgs)

    page(3)  # cap 1: three messages
    compiled = _blake3_batch_rows_impl._cache_size()
    calls = []
    real = hasher._hash_bucket
    hasher._hash_bucket = lambda msgs, cap, lanes: (
        calls.append((cap, len(msgs), lanes)) or real(msgs, cap, lanes))
    page(4096)
    page(1500)
    assert calls == [(1, 1024, 1024)] * 4 + [(1, 1024, 1024), (1, 476, 1024)]
    assert _blake3_batch_rows_impl._cache_size() == compiled


def _refuse(*_a, **_k):
    raise AssertionError("a hasher read a file")


def _write_tree(tree, sizes, seed) -> list[str]:
    rng = random.Random(seed)
    tree.mkdir(parents=True)
    paths = []
    for i, n in enumerate(sizes):
        p = tree / f"f{i:02d}.bin"
        p.write_bytes(rng.randbytes(n))
        paths.append(str(p))
    return paths


def test_hybrid_probe_runs_on_gathered_messages(tmp_path, monkeypatch):
    """An unprobed hybrid probes both engines on one page of gathered
    messages: the router is seeded, the ids are the CPU oracle's, the
    device slice signs, and no file is read again — the files are gone,
    and the fused CPU path and the native gather refuse."""
    from spacedrive_tpu.native import cas_native

    sizes = [150_000 + i for i in range(20)] + [500, 40_000, 1]
    paths = _write_tree(tmp_path / "t", sizes, 28)
    expect = [generate_cas_id(p, n) for p, n in zip(paths, sizes)]
    messages = read_sampled_batch(paths, sizes)
    failed = OSError("gather failed: g.bin")
    messages.append(failed)
    for path in paths:
        Path(path).unlink()
    monkeypatch.setattr(cas_native, "gather_batch", _refuse)
    monkeypatch.setattr(cas_native, "hash_batch", _refuse)
    monkeypatch.setattr(CpuHasher, "hash_batch", _refuse)

    hy = HybridHasher()
    assert hy._cpu._fast is _refuse and hy.router.unprobed
    signatures: dict = {}
    got = hy.hash_gathered(messages, signatures)
    assert got[:-1] == expect and got[-1] is failed
    assert not hy.router.unprobed and not hy.router.degraded
    assert hy.router.cpu_bps > 0 and hy.router.dev_bps > 0
    # k = 10 messages a engine: the device's are signed, the CPU's not
    assert len(signatures) >= 10 and set(signatures) <= set(range(20))


def test_hybrid_scan_reads_each_file_once(tmp_path, tmp_data_dir,
                                          monkeypatch):
    """A ``hasher="hybrid"`` scan whose first page carries the probe
    gathers each file once in the identify job (no second read of the
    probe page), and commits the files a ``hasher="cpu"`` scan of the same
    tree commits."""
    from spacedrive_tpu.locations import create_location, scan_location
    from spacedrive_tpu.node import Node
    from spacedrive_tpu.objects import file_identifier, hasher

    sizes = [150_000 + 7 * i for i in range(24)] + [300 + i for i in range(8)]
    paths = _write_tree(tmp_path / "tree", sizes, 29)
    (tmp_path / "tree" / "copy.bin").write_bytes(Path(paths[0]).read_bytes())
    paths.append(str(tmp_path / "tree" / "copy.bin"))

    reads: list[list[str]] = []
    gather = file_identifier.read_sampled_batch

    def counted(batch_paths, *args, **kwargs):
        reads.append([str(p) for p in batch_paths])
        return gather(batch_paths, *args, **kwargs)

    fused: list = []

    def fused_read(self, batch_paths, sizes):
        fused.append(batch_paths)  # the identify swallows a hasher's raise
        raise AssertionError("the fused path read a file")

    monkeypatch.setattr(file_identifier, "read_sampled_batch", counted)
    monkeypatch.setattr(CpuHasher, "hash_batch", fused_read)
    hy = HybridHasher()
    monkeypatch.setitem(hasher._instances, "hybrid", hy)

    def scan(lib, name):
        loc = create_location(lib, str(tmp_path / "tree"), hasher=name)
        scan_location(lib, loc["id"])
        assert node.jobs.wait_idle(300)
        return lib.db.query(
            "SELECT f.materialized_path, f.name, f.size_in_bytes, f.cas_id, "
            "o.kind FROM file_path f JOIN object o ON o.id = f.object_id "
            "WHERE f.is_dir = 0 ORDER BY f.materialized_path, f.name")

    node = Node(tmp_data_dir, probe_accelerator=False, watch_locations=False)
    try:
        hybrid_rows = scan(node.libraries.create("hybrid"), "hybrid")
        read = sorted(p for batch in reads for p in batch)
        assert read == sorted(str(Path(p).resolve()) for p in paths)
        assert fused == []
        assert not hy.router.unprobed
        cpu_rows = scan(node.libraries.create("cpu"), "cpu")
    finally:
        node.shutdown()
    assert len(hybrid_rows) == len(paths)
    assert [dict(r) for r in hybrid_rows] == [dict(r) for r in cpu_rows]
    assert hybrid_rows[0]["cas_id"] == generate_cas_id(paths[0], sizes[0])
