"""MinHash signatures from the identify page's device rows.

A device bucket call that holds a sampled cas message also computes, in
the same program over the same rows, each row's MinHash signature; the
identify commit stores one per object (``object_minhash``), and the dedup
detector reads it instead of gathering the file again. Rows with no stored
signature take the detector's own gather, which the signatures must match
bit for bit.
"""

import random

import numpy as np
import pytest

pytest.importorskip("jax")

from spacedrive_tpu import telemetry
from spacedrive_tpu.locations import create_location, scan_location
from spacedrive_tpu.node import Node
from spacedrive_tpu.objects import dedup, hasher
from spacedrive_tpu.objects.cas import SAMPLED_MESSAGE_LEN, read_sampled_batch
from spacedrive_tpu.objects.file_identifier import shallow_identify
from spacedrive_tpu.objects.hasher import CpuHasher, HybridHasher, TpuHasher
from spacedrive_tpu.ops.minhash import K

from .test_pipeline import _decoded
from .test_telemetry import _spans_named


def _write(tree, sizes, seed):
    rng = random.Random(seed)
    tree.mkdir()
    paths = []
    for i, n in enumerate(sizes):
        p = tree / f"f{i}.bin"
        p.write_bytes(rng.randbytes(n))
        paths.append(str(p))
    return paths


def test_page_signatures_equal_the_dedup_gather(tmp_path):
    """Sampled files hashed through ``TpuHasher.hash_gathered``: each
    signature equals the one ``minhash_rows`` gives over the dedup
    detector's own gather of the file, bit for bit."""
    sizes = [150_000, 210_000, 333_333, 1_000_000, 120_001]
    paths = _write(tmp_path / "t", sizes, 1)
    messages = read_sampled_batch(paths, sizes)
    signatures = {}
    got = TpuHasher().hash_gathered(messages, signatures)
    assert got == CpuHasher().hash_gathered(messages)
    assert sorted(signatures) == list(range(len(sizes)))
    errors = []
    want, lengths = dedup._signatures(paths, sizes, errors)
    assert not errors and (lengths == SAMPLED_MESSAGE_LEN).all()
    stack = np.stack([signatures[i] for i in range(len(sizes))])
    assert stack.dtype == np.uint32 and stack.shape == (len(sizes), K)
    assert (stack == want).all()


def test_mixed_cap64_call_signs_sampled_rows_only(tmp_path):
    """Whole 40 KiB files share the 64-chunk bucket call with sampled
    ones: only the sampled messages get signatures. A page of whole files
    alone gets none."""
    sizes = [40_960, 200_000, 41_000, 150_000, 40_000]
    paths = _write(tmp_path / "t", sizes, 2)
    messages = read_sampled_batch(paths, sizes)
    calls = []
    tpu = TpuHasher()
    real = tpu._hash_bucket
    tpu._hash_bucket = lambda msgs, cap, lanes: (
        calls.append(cap) or real(msgs, cap, lanes))
    signatures = {}
    assert tpu.hash_gathered(messages, signatures) == \
        CpuHasher().hash_gathered(messages)
    assert calls == [64]
    assert sorted(signatures) == [1, 3]
    whole = [m for m in messages if len(m) != SAMPLED_MESSAGE_LEN]
    signatures = {}
    assert tpu.hash_gathered(whole, signatures) == \
        CpuHasher().hash_gathered(whole)
    assert signatures == {}


def test_whole_file_of_the_sampled_length_gets_no_signature(
        tmp_path, monkeypatch):
    """A whole file of SAMPLED_MESSAGE_LEN - 8 bytes has a cas message as
    long as a sampled one. Its size prefix, not its length, says it is
    whole: alone, its call runs the digest program and signs nothing;
    beside a sampled file, only the sampled one is signed."""
    from spacedrive_tpu.ops import blake3_jax

    sizes = [SAMPLED_MESSAGE_LEN - 8, 200_000]
    messages = read_sampled_batch(_write(tmp_path / "t", sizes, 6), sizes)
    assert {len(m) for m in messages} == {SAMPLED_MESSAGE_LEN}
    signatures = {}
    assert TpuHasher().hash_gathered(messages, signatures) == \
        CpuHasher().hash_gathered(messages)
    assert sorted(signatures) == [1]

    def refuse(*_a, **_k):
        raise AssertionError("a call of whole files computed signatures")

    monkeypatch.setattr(blake3_jax, "hash_sign_rows_hex", refuse)
    signatures = {}
    assert TpuHasher().hash_gathered(messages[:1], signatures) == \
        CpuHasher().hash_gathered(messages[:1])
    assert signatures == {}


def test_gather_failures_keep_their_place(tmp_path):
    """A message the gather could not read passes through in place, and
    the signatures keep the indices of the page's messages."""
    sizes = [150_000, 160_000]
    paths = _write(tmp_path / "t", sizes, 3)
    messages = read_sampled_batch(paths, sizes)
    page = [OSError("vanished")] + messages
    signatures = {}
    out = TpuHasher().hash_gathered(page, signatures)
    assert out[0] is page[0]
    assert sorted(signatures) == [1, 2]
    solo = {}
    TpuHasher().hash_gathered(messages, solo)
    assert all((signatures[i + 1] == solo[i]).all() for i in solo)


def test_hybrid_device_slice_signs(tmp_path):
    """The hybrid hasher's device slice brings its signatures back under
    the page's indices; its CPU slice brings none."""
    sizes = [150_000, 30_000, 170_000]
    paths = _write(tmp_path / "t", sizes, 4)
    messages = read_sampled_batch(paths, sizes)
    hy = HybridHasher()
    if hy._cpu._fast is None:
        pytest.skip("no native CPU hasher to route against")
    hy.router.seed(1.0, 2.0)  # the device holds the route
    signatures = {}
    assert hy.hash_gathered(messages, signatures) == \
        CpuHasher().hash_gathered(messages)
    assert sorted(signatures) == [0, 2]
    hy.router.seed(2.0, 1.0)  # the CPU holds the route
    signatures = {}
    hy.hash_gathered(messages, signatures)
    assert signatures == {}


def test_hash_messages_computes_no_signatures(tmp_path, monkeypatch):
    """The H_HASH service's device calls run the digest program alone,
    sampled messages and all: its peers keep no signatures."""
    from spacedrive_tpu.ops import blake3_jax, minhash

    def refuse(*_a, **_k):
        raise AssertionError("the H_HASH service computed signatures")

    monkeypatch.setattr(hasher, "_accelerator_available", lambda: True)
    monkeypatch.setattr(blake3_jax, "hash_sign_rows_hex", refuse)
    monkeypatch.setattr(minhash, "minhash_rows", refuse)
    sizes = [150_000, 500, 41_000]
    messages = read_sampled_batch(_write(tmp_path / "t", sizes, 5), sizes)
    assert hasher.hash_messages(messages) == \
        CpuHasher().hash_gathered(messages)


def _pair_ids(res):
    return (sorted((p["a"]["id"], p["b"]["id"], p["similarity"])
                   for p in res["pairs"]),
            sorted(sorted(r["id"] for r in g) for g in res["groups"]),
            res["pair_count"])


def _family_tree(tree):
    """Two folders: the originals in ``a``, their edits (near copies) and
    one unrelated file in ``b``, so each near pair crosses the folders."""
    rng = random.Random(26)
    (tree / "a").mkdir(parents=True)
    (tree / "b").mkdir()
    for i, n in enumerate((180_000, 220_000, 150_000)):
        original = bytearray(rng.randbytes(n))
        (tree / "a" / f"orig{i}.raw").write_bytes(original)
        if i < 2:
            for _ in range(10 + 10 * i):
                original[rng.randrange(n)] ^= 0xFF
            (tree / "b" / f"edit{i}.raw").write_bytes(original)
    (tree / "b" / "other.raw").write_bytes(rng.randbytes(160_000))


def test_near_duplicates_same_from_stored_gathered_and_mixed(
        tmp_path, tmp_data_dir):
    """``find_near_duplicates`` finds the same pairs, groups and pair
    count with every signature stored by the identify page, with half the
    objects re-identified by the CPU hasher (half reused, half gathered),
    and with the table emptied (all gathered)."""
    from spacedrive_tpu.models import Location

    tree = tmp_path / "tree"
    _family_tree(tree)
    node = Node(tmp_data_dir, probe_accelerator=False, watch_locations=False)
    try:
        lib = node.libraries.create("signatures")
        loc = create_location(lib, str(tree), hasher="tpu")
        scan_location(lib, loc["id"])
        assert node.jobs.wait_idle(300)
        db = lib.db
        assert db.query("SELECT COUNT(*) n FROM object_minhash")[0]["n"] == 6
        stored = db.query("SELECT signature FROM object_minhash")
        assert all(len(r["signature"]) == K * 4 for r in stored)
        meta = _decoded(db.query("SELECT metadata FROM job WHERE name = "
                                 "'dedup_detector'")[0]["metadata"])
        assert meta["signatures"] == {"reused": 6, "gathered": 0}

        def probe():
            before = {s: telemetry.value("sd_dedup_signatures_total",
                                         source=s) or 0
                      for s in ("identify", "gather")}
            trace = telemetry.start_trace("job.dedup_detector")
            with telemetry.span(trace, "job.step"):
                res = dedup.find_near_duplicates(lib, loc["id"])
            moved = {s: (telemetry.value("sd_dedup_signatures_total",
                                         source=s) or 0) - before[s]
                     for s in before}
            assert moved == {"identify": res["signatures"]["reused"],
                             "gather": res["signatures"]["gathered"]}
            assert not res["errors"]
            # the gather's spans open only around a real gather, each
            # with the files it gathered
            step = _spans_named(trace.tree(), "job.step")[0]
            for name in ("dedup.gather", "dedup.minhash"):
                spans = _spans_named(step, name)
                assert sum(s["attrs"]["files"] for s in spans) == \
                    res["signatures"]["gathered"], name
                assert bool(spans) == bool(res["signatures"]["gathered"])
            return res

        all_stored = probe()
        assert all_stored["signatures"] == {"reused": 6, "gathered": 0}
        assert len(all_stored["pairs"]) == 2

        # the objects of folder b go, their signatures with them, and the
        # CPU hasher identifies b's files again: new objects, no signature
        db.execute("DELETE FROM object WHERE id IN (SELECT object_id FROM "
                   "file_path WHERE materialized_path = '/b/')")
        assert db.query("SELECT COUNT(*) n FROM object_minhash")[0]["n"] == 3
        db.update(Location, {"id": loc["id"]}, {"hasher": "cpu"})
        assert shallow_identify(lib, loc["id"], "b")["identified"] == 3
        assert db.query("SELECT COUNT(*) n FROM object_minhash")[0]["n"] == 3
        mixed = probe()
        assert mixed["signatures"] == {"reused": 3, "gathered": 3}

        db.execute("DELETE FROM object_minhash")
        gathered = probe()
        assert gathered["signatures"] == {"reused": 0, "gathered": 6}

        want = _pair_ids(all_stored)
        assert _pair_ids(mixed) == want
        assert _pair_ids(gathered) == want
    finally:
        node.shutdown()


def test_deleting_an_object_cascades_its_signature(tmp_path):
    import uuid

    from spacedrive_tpu.models import (ALL_MODELS, Database, Object,
                                       ObjectMinhash, utc_now)

    db = Database(tmp_path / "lib.db", ALL_MODELS)
    try:
        oids = [db.insert(Object, {"pub_id": str(uuid.uuid4()),
                                   "date_created": utc_now()})
                for _ in range(2)]
        sig = np.arange(K, dtype="<u4").tobytes()
        assert db.insert_many(ObjectMinhash, [
            {"object_id": oid, "signature": sig} for oid in oids]) == 2
        # an object that has a signature keeps it
        db.insert_many(ObjectMinhash, [{"object_id": oids[0],
                                        "signature": bytes(K * 4)}],
                       or_ignore=True)
        assert db.query("SELECT signature FROM object_minhash WHERE "
                        "object_id = ?", [oids[0]])[0]["signature"] == sig
        db.delete(Object, {"id": oids[0]})
        rows = db.query("SELECT object_id, signature FROM object_minhash")
        assert [(r["object_id"], r["signature"]) for r in rows] == \
            [(oids[1], sig)]
    finally:
        db.close()
