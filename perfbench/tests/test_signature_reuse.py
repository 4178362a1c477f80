"""The dedup detector's signature reuse share, read from its jobs' run
metadata."""
from harness import spec

METRIC = "dedup.signature_reuse_share"


def _ctx(*metas):
    return {"job_metadata": {f"j{i}": m for i, m in enumerate(metas)},
            "files": 10_000, "files_committed": 10_000}


def test_reuse_share_over_the_window_jobs():
    """Reused over reused + gathered, summed over the window's
    dedup_detector jobs; other jobs do not count."""
    ctx = _ctx(("dedup_detector", {"signatures": {"reused": 5000,
                                                  "gathered": 0}}),
               ("dedup_detector", {"signatures": {"reused": 1000,
                                                  "gathered": 4000}}),
               ("file_identifier", {"signatures": {"reused": 0,
                                                   "gathered": 9000}}))
    assert spec.reader(METRIC).read(ctx) == 0.6


def test_reuse_share_reads_nothing_without_counts():
    """A program that records no signatures (the parent commit), or a
    window whose dedup jobs compared none, leaves the metric out."""
    read = spec.reader(METRIC).read
    assert read(_ctx()) is None
    assert read(_ctx(("dedup_detector", {"pairs_found": 3}))) is None
    assert read(_ctx(("dedup_detector", {"signatures": {
        "reused": 0, "gathered": 0}}))) is None


def test_reuse_share_is_declared_for_both_scan_cells():
    bm = spec.load_benchmark()
    entry = next(m for m in bm["per_layer"] if m["name"] == METRIC)
    assert entry["workloads"] == ["photo_library.scan", "home_mixed.scan"]
    assert entry["moves"] == "scan_files_per_s"
