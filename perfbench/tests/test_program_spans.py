"""Per-layer metrics read from the program's span totals, and device-idle
gaps named by the program span on the host plane."""
import json
import threading
from pathlib import Path

import pytest

from harness import span_gaps, spec, trace

DATA = Path(__file__).parent / "data"

#: metric → (job, spans summed, divisor key)
READERS = {
    "identify.select_us_per_file": ("file_identifier", ("identifier.select",), "files_committed"),
    "identify.gather_us_per_file": ("file_identifier", ("identifier.gather",), "files_committed"),
    "hasher.pack_us_per_file": ("file_identifier", ("hash.pack",), "files_committed"),
    "hasher.put_us_per_file": ("file_identifier", ("hash.put",), "files_committed"),
    "hasher.wait_us_per_file": ("file_identifier", ("hash.wait",), "files_committed"),
    "indexer.rows_us_per_file": ("indexer", ("indexer.rows",), "files"),
    "dedup.gather_us_per_file": ("dedup_detector", ("dedup.gather",), "files"),
    "dedup.device_us_per_file": ("dedup_detector", ("dedup.minhash", "dedup.sweep"), "files"),
    "dedup.verify_us_per_file": ("dedup_detector", ("dedup.verify",), "files"),
    "dedup.persist_us_per_file": ("dedup_detector", ("dedup.persist",), "files"),
}

SPAN_NAMES = sorted({s for _j, spans, _f in READERS.values() for s in spans})


def _ctx(with_spans: bool, files=5000, committed=4000) -> dict:
    """Two locations' jobs; each span reads 0.25 s in the first location's
    job and 0.5 s in the second's, plus a job of another name that must
    not count."""
    meta = {}
    for k, secs in enumerate((0.25, 0.5)):
        for job in ("indexer", "file_identifier", "dedup_detector"):
            spans = {s: {"count": 3, "total_s": secs} for s in SPAN_NAMES}
            meta[f"{job}-{k}"] = (job, {"trace": {"spans": spans}}
                                  if with_spans else {"scan_read_time": 1.0})
    meta["media"] = ("media_processor",
                     {"trace": {"spans": {s: {"count": 1, "total_s": 9.0}
                                          for s in SPAN_NAMES}}})
    return {"job_metadata": meta, "files": files, "files_committed": committed}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_sums_its_spans_per_file(metric):
    _job, spans, files = READERS[metric]
    ctx = _ctx(True)
    want = 0.75 * len(spans) / ctx[files] * 1e6
    assert spec.reader(metric).read(ctx) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_reads_nothing_without_its_spans(metric):
    """A program without these spans (the parent commit) or a window with
    no committed file leaves the metric out."""
    assert spec.reader(metric).read(_ctx(False)) is None
    assert spec.reader(metric).read({"job_metadata": {}, "files": 10,
                                     "files_committed": 10}) is None
    assert spec.reader(metric).read(_ctx(True, files=0, committed=0)) is None


def test_every_new_metric_is_in_the_benchmark():
    bm = spec.load_benchmark()
    entries = {m["name"]: m for m in bm["per_layer"]}
    for metric in READERS:
        m = entries[metric]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "us/file", "lower", "program_span", "scan_files_per_s")
        assert m["workloads"] == ["photo_library.scan"]


# -- gap naming ----------------------------------------------------------------

def test_only_innermost_spans_vote():
    spans = [(0.0, 10.0, "job.step"), (2.0, 6.0, "dedup.gather"),
             (3.0, 4.0, "dedup.inner"), (7.0, 9.0, "dedup.verify")]
    assert span_gaps.leaves(spans) == [(3.0, 4.0, "dedup.inner"),
                                      (7.0, 9.0, "dedup.verify")]
    got = span_gaps.cover(span_gaps.leaves(spans), 1.0, 8.0)
    assert got == pytest.approx({"dedup.inner": 1.0, "dedup.verify": 1.0})
    assert span_gaps.cover(spans, 20.0, 30.0) == {}


def test_name_gaps_largest_wall_cover_and_fallback():
    """The job thread waits inside pipeline.run through every gap and
    never votes for it. Gap 1: two shards gather at once (0.9 s of wall
    between them) against 0.5 s of packing. Gap 2: the job thread's walk
    (1.2 s) against four shards' overlapping gathers (0.5 s of wall, 2 s
    of thread time). Gap 3 lies in the commit alone; gap 4 in no span."""
    threads = {
        ("/host:CPU", 0): [(0.0, 10.0, "pipeline.run"),
                           (5.0, 6.0, "pipeline.commit"),
                           (19.0, 21.25, "job.init"),
                           (20.0, 21.2, "indexer.walk")],
        ("/host:CPU", 1): [(0.9, 1.9, "pipeline.gather"),
                           (0.95, 1.85, "identifier.gather")],
        ("/host:CPU", 2): [(1.1, 2.0, "pipeline.gather"),
                           (1.1, 1.9, "identifier.gather")],
        ("/host:CPU", 3): [(0.9, 2.0, "pipeline.hash"),
                           (1.0, 1.5, "hash.pack")],
        **{("/host:CPU", 4 + k): [(21.3, 21.8, "identifier.gather")]
           for k in range(4)},
    }
    gaps = [(1.0, 2.0), (20.0, 22.0), (5.2, 5.8), (12.0, 13.0)]
    named = span_gaps.name_gaps(
        gaps, ["file_identifier", "indexer", "file_identifier",
               "between jobs"], threads)
    assert [n for n, _ in named] == ["file_identifier/identifier.gather",
                                     "indexer/indexer.walk",
                                     "file_identifier/pipeline.commit",
                                     "between jobs"]
    assert [s for _, s in named] == pytest.approx([1.0, 2.0, 0.6, 1.0])


def _device(name, s, d):
    return {"plane": "/device:TPU:0", "line": "XLA Ops", "name": name,
            "start_ns": s, "dur_ns": d}


def test_synthetic_trace_names_gaps_on_the_host_clock():
    """Device ops with two idle gaps; host spans on two threads in ns on
    the same clock. Names go below the job; lengths are reduce()'s."""
    ns = 1_000_000
    rows = [{"plane": "/host:CPU", "line": "python3",
             "name": "perfbench.location", "start_ns": 0, "dur_ns": 100 * ns},
            _device("fusion", 0, 10 * ns), _device("fusion", 40 * ns, 10 * ns),
            _device("fusion", 90 * ns, 10 * ns)]
    t0, t1 = trace.mark(rows, "perfbench.location")
    red = trace.reduce(rows, t0, t1)
    gaps = sorted(red["gaps"], key=lambda g: g[0] - g[1])[:10]
    assert gaps == [pytest.approx((0.05, 0.09)), pytest.approx((0.01, 0.04))]
    threads = {("/host:CPU", 0): [(0.045, 0.1, "job.step"),
                                  (0.05, 0.088, "dedup.verify")],
               ("/host:CPU", 1): [(0.012, 0.039, "indexer.walk")]}
    named = span_gaps.name_gaps(gaps, ["dedup_detector", "indexer"], threads)
    assert named == [["dedup_detector/dedup.verify", pytest.approx(0.04)],
                     ["indexer/indexer.walk", pytest.approx(0.03)]]


@pytest.mark.skipif(not (DATA / "trace_v5e.json").exists(),
                    reason="recorded trace not present")
def test_recorded_trace_gaps_unchanged_without_spans():
    """On the recorded v5e trace (no program spans in it) the ten gaps,
    their lengths and their job names are exactly the job-level naming."""
    doc = json.loads((DATA / "trace_v5e.json").read_text())
    red = trace.reduce(doc["rows"], *doc["mark"])
    gaps = sorted(red["gaps"], key=lambda g: g[0] - g[1])[:10]
    jobs = [f"job{i}" for i in range(len(gaps))]
    named = span_gaps.name_gaps(gaps, jobs, {})
    assert named == [[j, g1 - g0] for j, (g0, g1) in zip(jobs, gaps)]


def test_recorded_v5e_spans_name_the_gaps():
    """A traced location on the chip: the indexer's gap is its walk, the
    identify gaps the hash stage's host packing, the dedup gap its
    gather; lengths pass through."""
    doc = json.loads((DATA / "spans_v5e.json").read_text())
    gaps = sorted((tuple(g) for g in doc["gaps"]),
                  key=lambda g: g[0] - g[1])[:10]
    threads = {k: [tuple(sp) for sp in v] for k, v in doc["threads"].items()}
    named = span_gaps.name_gaps(gaps, doc["jobs"], threads)
    assert [n for n, _ in named] == [n for n, _ in doc["expect"]]
    assert [n for n, _ in named[:3]] == ["indexer/indexer.walk",
                                         "dedup_detector/dedup.gather",
                                         "file_identifier/hash.pack"]
    assert [s for _, s in named] == [g1 - g0 for g0, g1 in gaps]


def test_host_spans_read_from_a_real_profile(tmp_path):
    """The program's spans, profiled on the CPU: one key per thread, the
    ``sd/`` prefix stripped, times from the given origin."""
    import jax

    from spacedrive_tpu import telemetry

    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span(None, "t.outer"):
            with telemetry.span(None, "t.inner"):
                pass
        worker = threading.Thread(
            target=lambda: telemetry.span(None, "t.other").__enter__()
            .__exit__(None, None, None))
        worker.start()
        worker.join()
    finally:
        jax.profiler.stop_trace()
    threads = span_gaps.host_spans(str(tmp_path), 0)
    where = {name: key for key, spans in threads.items()
             for _s, _e, name in spans}
    assert where["t.outer"] == where["t.inner"] != where["t.other"]
    (outer,) = [sp for sp in threads[where["t.outer"]] if sp[2] == "t.outer"]
    (inner,) = [sp for sp in threads[where["t.inner"]] if sp[2] == "t.inner"]
    assert outer[0] <= inner[0] <= inner[1] <= outer[1]
