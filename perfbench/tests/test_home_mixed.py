"""The home-folder cell at a CPU test size, and its bucket-path readers.

The run drives the whole cell but the look for a chip: every class of
``configs/home_mixed.json`` with a few files each, the program's CPU
hasher, and once its device hasher (JAX on the CPU) with buckets split
into calls of a few rows. A run whose cap-16 digests are altered
underneath comes out not correct. The readers are checked on the run metadata the program's
``file_identifier`` job writes (``hash_buckets``, ``trace.spans``).
"""
import json

import pytest

from harness import cell, spec

SEED = 2**31 + 1234567
CONFIG = json.loads((spec.BENCH / "configs" / "home_mixed.json").read_text())

#: files a location per class: at least 5 of each, about 90 in all
COUNTS = {"b1": 12, "b4": 12, "b16": 14, "b32": 10, "b64": 10, "b101": 8,
          "s1m": 14, "s16m": 5, "s512m": 5}

SMALL = {"config": {
    "settings": {"hasher": "cpu"},
    "tree": {"locations": 3, "files_per_location": sum(COUNTS.values()),
             "files_per_dir": 20},
    "classes": [{**c, "count": COUNTS[c["name"]]} for c in CONFIG["classes"]],
    "copies": {"exact": [{"class": c, "count": 1} for c in COUNTS
                         if c != "s512m"],
               "near": [{"class": "s1m", "count": 2, "jaccard": 0.975},
                        {"class": "s1m", "count": 2, "jaccard": 0.45}]}}}


def _run(hasher="cpu"):
    overrides = json.loads(json.dumps(SMALL))
    overrides["config"]["settings"]["hasher"] = hasher
    return cell.run("home_mixed.scan", SEED, 1.0, False, 0.0,
                    require_tpu=False, overrides=overrides)


def _cap(msg: bytes) -> int:
    chunks = max(1, -(-len(msg) // 1024))
    return next(b for b in (1, 4, 16, 32, 64, 101) if b >= chunks)


def test_every_class_keeps_its_bucket():
    """Each dense class fills one chunk-capacity bucket of its cas message
    (size + 8 bytes); the sparse classes take the sampled message."""
    want = {"b1": 1, "b4": 4, "b16": 16, "b32": 32, "b64": 64, "b101": 101}
    for c in CONFIG["classes"]:
        lo, hi = c["size"]["lo"], c["size"]["hi"]
        if c["name"] in want:
            assert c["content"] == "dense"
            assert {_cap(bytes(lo + 8)), _cap(bytes(hi + 8))} == {want[c["name"]]}
        else:
            assert c["content"] == "sparse" and lo > 100 * 1024
    assert sum(c["count"] for c in CONFIG["classes"]) == \
        CONFIG["tree"]["files_per_location"]


def test_sound_run_is_correct():
    res, numbers = _run()
    assert res["correct"], res["checks"]
    assert numbers["cas_id_wrong"]["value"] == 0


def test_split_buckets_on_the_device_hasher_are_correct(monkeypatch):
    """The device hasher's bucket path with every bucket split into calls
    of 8 rows: each call holds at most 8 files, buckets larger than that
    take several, and the run is correct."""
    from spacedrive_tpu.objects import hasher

    calls = []
    real = hasher.TpuHasher._hash_bucket

    def spy(self, msgs, cap, lanes):
        calls.append((cap, len(msgs), lanes))
        return real(self, msgs, cap, lanes)
    monkeypatch.setattr(hasher, "BUCKET_MAX_ROWS", 8)
    monkeypatch.setattr(hasher.TpuHasher, "_hash_bucket", spy)
    res, numbers = _run("tpu")
    assert res["correct"], res["checks"]
    assert numbers["cas_id_wrong"]["value"] == 0
    assert {c for c, _n, _l in calls} == {1, 4, 16, 32, 64, 101}
    assert all(lanes == 8 and n <= 8 for _c, n, lanes in calls)
    assert sum(n == 8 for _c, n, _l in calls) >= 6  # full, split calls


def test_one_bucket_altered_fails(monkeypatch):
    """Every cap-16 digest zeroed: the planted cap-16 copies and the
    sampled files of that bucket read wrong against the reference."""
    from spacedrive_tpu.objects import hasher

    real = hasher.CpuHasher.hash_gathered

    def altered(self, messages):
        out = list(real(self, messages))
        for j, m in enumerate(messages):
            if isinstance(out[j], str) and _cap(m) == 16:
                out[j] = "0" * len(out[j])
        return out
    monkeypatch.setattr(hasher.CpuHasher, "hash_gathered", altered)
    res, numbers = _run()
    assert not res["correct"]
    assert numbers["cas_id_wrong"]["value"] > 0


def _ctx(buckets_by_job: list[dict], committed=2000, spans_s=0.0) -> dict:
    meta = {}
    for k, buckets in enumerate(buckets_by_job):
        meta[f"fi-{k}"] = ("file_identifier", {
            "hash_buckets": buckets,
            "trace": {"spans": {"hash.bucket": {"count": 6,
                                                "total_s": spans_s}}}})
    # another job's tallies (the H_HASH service, say) do not count
    meta["other"] = ("dedup_detector", {"hash_buckets": {
        "1": {"calls": 9, "files": 9, "lanes": 9, "payload_bytes": 9}}})
    return {"job_metadata": meta, "files": committed,
            "files_committed": committed, "trace": None}


BUCKETS = {"1": {"calls": 2, "files": 300, "lanes": 1024,
                 "payload_bytes": 256 * 1024},
           "101": {"calls": 1, "files": 50, "lanes": 64,
                   "payload_bytes": 32 * 101 * 1024}}


def test_bucket_readers_sum_the_window_jobs():
    ctx = _ctx([BUCKETS, BUCKETS], spans_s=0.5)
    rows = 2 * (1024 * 1024 + 64 * 101 * 1024)
    payload = 2 * (256 * 1024 + 32 * 101 * 1024)
    assert spec.reader("hasher.bucket_pad_share").read(ctx) == \
        pytest.approx(1 - payload / rows)
    assert spec.reader("hasher.bucket_calls_per_kfile").read(ctx) == \
        pytest.approx(6 / 2000 * 1e3)
    assert spec.reader("hasher.bucket_us_per_file").read(ctx) == \
        pytest.approx(1.0 / 2000 * 1e6)


@pytest.mark.parametrize("metric", ["hasher.bucket_pad_share",
                                    "hasher.bucket_calls_per_kfile",
                                    "hasher.bucket_us_per_file",
                                    "blake3_bucket_roofline"])
def test_bucket_readers_read_nothing_without_the_program_s_tallies(metric):
    """A program without ``hash_buckets`` and ``hash.bucket`` (the parent
    commit) or an untraced run leaves the metric out."""
    ctx = _ctx([{}])
    for _name, meta in ctx["job_metadata"].values():
        meta.pop("trace", None)
    assert spec.reader(metric).read(ctx) is None


def test_the_new_cell_reports_only_its_own_metrics():
    bm = spec.load_benchmark()
    layer = {m["name"] for m in spec.metrics(bm, "home_mixed.scan", True)}
    assert layer == {"hasher.bucket_us_per_file", "hasher.bucket_pad_share",
                     "hasher.bucket_calls_per_kfile", "blake3_bucket_roofline"}
    (wl,) = [w for w in bm["workloads"] if w["name"] == "home_mixed.scan"]
    assert (wl["config"], wl["traffic"], wl["chips"]) == (
        "home_mixed", "scan", 1)
