"""Unified telemetry gate (ISSUE 5): registry semantics, the span tree a
pipelined scan produces (and its reconciliation with the scan report's
stage timings), the spans' bridge onto the profiler's host plane, the
Prometheus text round-trip on GET /metrics, the SD_TELEMETRY=off no-op,
and chaos-counter agreement with the fault suite's report metadata."""

import json
import os
import random
import re
import subprocess
import sys
import threading
import urllib.request
from pathlib import Path

import pytest

from spacedrive_tpu import faults, telemetry
from spacedrive_tpu.jobs import JobStatus
from spacedrive_tpu.models import JobRow
from spacedrive_tpu.objects import file_identifier as fi

from .test_faults import _identify
from .test_pipeline import _decoded, _seed_library


@pytest.fixture(autouse=True)
def _fresh_registry():
    """Counters are process-global; every test starts from zero and
    leaves the enabled flag as the environment set it."""
    telemetry.reset()
    telemetry.set_enabled(True)
    yield
    faults.clear()
    telemetry.reset()
    telemetry.reload_enabled()


# -- registry semantics --------------------------------------------------------


def test_counter_gauge_labels_and_validation():
    c = telemetry.counter("sd_t_ops_total", "ops", labels=("kind",))
    c.inc(kind="a")
    c.inc(2.5, kind="a")
    c.inc(kind="b")
    assert telemetry.value("sd_t_ops_total", kind="a") == 3.5
    assert telemetry.value("sd_t_ops_total", kind="b") == 1.0
    assert telemetry.value("sd_t_ops_total", kind="absent") == 0.0

    g = telemetry.gauge("sd_t_depth")
    g.set(7)
    g.inc()
    assert telemetry.value("sd_t_depth") == 8.0

    with pytest.raises(ValueError):
        c.labels(wrong="x")  # label-set mismatch
    with pytest.raises(ValueError):
        telemetry.counter("not_sd_prefixed")  # name vocabulary
    with pytest.raises(ValueError):
        telemetry.gauge("sd_t_ops_total")  # re-declare as another type
    with pytest.raises(ValueError):
        c.labels(kind="x").inc(-1)  # counters only go up


def test_histogram_fixed_buckets_and_snapshot():
    h = telemetry.histogram("sd_t_lat_seconds", "lat",
                            buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.labels().observe(v)
    snap = telemetry.snapshot()["metrics"]["sd_t_lat_seconds"]
    (series,) = snap["series"]
    assert series["count"] == 5
    assert series["buckets"] == {"0.01": 1, "0.1": 2, "1.0": 1, "+Inf": 1}
    assert series["sum"] == pytest.approx(5.605)


def test_concurrent_increments_from_threads():
    """The pipeline-stage shape: many threads hammering one family; the
    per-series lock must not lose increments (float += races under the
    GIL without it)."""
    c = telemetry.counter("sd_t_race_total", labels=("stage",))
    page = c.labels(stage="page")

    def worker():
        for _ in range(2000):
            page.inc()
            c.inc(0.5, stage="hash")

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert telemetry.value("sd_t_race_total", stage="page") == 16000
    assert telemetry.value("sd_t_race_total", stage="hash") == 8000


# -- the pipelined-scan span tree ---------------------------------------------


@pytest.fixture(scope="module", params=[1, 4], ids=["shards1", "shards4"])
def span_tree_scan(request, tmp_path_factory):
    """One pipelined 2k-file identify, with the classic single-thread
    prefetch (SD_SCAN_SHARDS=1) and the sharded one (4); returns (tree,
    report metadata, bytes hashed per the registry, the JSONL export, the
    scan-rate gauge, the shard count)."""
    telemetry.reset()
    telemetry.set_enabled(True)
    rng = random.Random(11)
    root = tmp_path_factory.mktemp("telemetry") / "tree"
    for d in range(4):
        p = root / f"d{d}"
        p.mkdir(parents=True)
        for i in range(500):
            if i % 100 == 0:
                body = rng.randbytes(150_000 + i)  # sampled-class
            elif i % 77 == 0:
                body = b""  # empties ride along
            else:
                body = rng.randbytes(300 + (i * 13) % 1200)
            (p / f"f{i:03d}.dat").write_bytes(body)

    old_env = {k: os.environ.get(k) for k in ("SD_PIPELINE", "SD_SCAN_SHARDS")}
    os.environ["SD_PIPELINE"] = "1"
    os.environ["SD_SCAN_SHARDS"] = str(request.param)
    old_batch = fi.BATCH_SIZE
    fi.BATCH_SIZE = 256
    try:
        data_dir = tmp_path_factory.mktemp("telemetry_data")
        node, lib, loc_id = _seed_library(data_dir, root, "spans")
        jid = _identify(node, lib, loc_id)
        row = lib.db.find_one(JobRow, {"id": jid})
        meta = _decoded(row["metadata"])
        tree = node.router.resolve("telemetry.jobTrace", jid)
        trace_file = (data_dir / "logs" / "traces" / f"{jid}.jsonl")
        hashed_bytes = telemetry.value("sd_hash_bytes_total", backend="cpu")
        scan_rate = telemetry.value("sd_scan_files_per_sec")
        node.shutdown()
    finally:
        fi.BATCH_SIZE = old_batch
        for key, old in old_env.items():
            if old is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = old
    return tree, meta, hashed_bytes, trace_file, scan_rate, request.param


def _spans_named(node, name, out=None):
    out = [] if out is None else out
    if node["name"] == name:
        out.append(node)
    for child in node.get("children", []):
        _spans_named(child, name, out)
    return out


def test_span_tree_shape_and_stage_reconciliation(span_tree_scan):
    tree, meta, _bytes, trace_file, _rate, shards = span_tree_scan
    assert tree["name"] == "job.file_identifier"
    batches = meta["pipeline_batches"]
    assert batches == 8  # ceil(2000/256)

    pages = _spans_named(tree, "pipeline.page")
    hashes = _spans_named(tree, "pipeline.hash")
    commits = _spans_named(tree, "pipeline.commit")
    # one page span per batch (the step budget exhausts exactly at the
    # last batch, so no terminal empty page runs); commit spans are per
    # GROUP transaction — their `pages` attrs must account for every batch
    assert len(pages) == batches
    assert len(hashes) == batches
    txns = meta["commit_txns"]
    assert len(commits) == txns
    assert 1 <= txns <= batches
    assert sum(c.get("attrs", {}).get("pages", 0) for c in commits) == batches
    # stage spans are children of the job's pipeline.run span — including
    # page/hash, which open on OTHER threads and pin the run span as
    # their explicit parent (the documented classification, observability.md)
    runs = _spans_named(tree, "pipeline.run")
    assert len(runs) == 1
    run_children = {c["name"] for c in runs[0]["children"]}
    assert {"pipeline.page", "pipeline.hash",
            "pipeline.commit"} <= run_children

    # the gather rides INSIDE the page span (nesting, not just presence):
    # directly with one prefetch thread, under the page's per-slice
    # pipeline.gather spans when the prefetch is sharded
    if shards == 1:
        holders = pages
    else:
        holders = [c for p in pages for c in p["children"]
                   if c["name"] == "pipeline.gather"]
        assert len(holders) >= batches
    gathers = [c for h in holders for c in h["children"]
               if c["name"] == "identifier.gather"]
    assert len(gathers) == len(holders)

    # reconciliation: report stage timings ARE the span sums (±5% per the
    # acceptance criterion; equality by construction here)
    for span_name, key, spans in (("pipeline.page", "pipeline_page_s", pages),
                                  ("pipeline.hash", "pipeline_hash_s", hashes),
                                  ("pipeline.commit", "pipeline_commit_s",
                                   commits)):
        total = sum(s["duration_s"] for s in spans)
        assert total == pytest.approx(meta[key], rel=0.05), (span_name, total)

    # ... and the summarized form in the report metadata agrees too
    assert meta["trace"]["spans"]["pipeline.page"]["count"] == batches

    # the JSONL export exists and rebuilds the same tree
    assert trace_file.exists()
    lines = [json.loads(x) for x in
             trace_file.read_text().splitlines() if x.strip()]
    assert {r["name"] for r in lines} >= {"pipeline.page", "pipeline.hash",
                                          "pipeline.commit",
                                          "identifier.gather"}


def test_span_attrs_sum_to_report_totals(span_tree_scan):
    tree, meta, hashed_bytes, _tf, scan_rate, _shards = span_tree_scan
    gathers = _spans_named(tree, "identifier.gather")
    gathered_files = sum(g["attrs"]["files"] for g in gathers)
    gathered_bytes = sum(g["attrs"]["bytes"] for g in gathers)
    empties = 2000 - gathered_files
    assert gathered_files + empties == meta["total_orphan_paths"] == 2000
    assert 0 < empties < 60  # the fixture's i%77 empties
    # every gathered byte was hashed exactly once on the cpu backend
    assert gathered_bytes == hashed_bytes
    assert scan_rate > 0


# -- spans on the profiler's host plane ----------------------------------------


def _host_events(logdir: Path) -> dict[tuple[int, str], list[tuple[int, int]]]:
    """(line index, name) → [(start_ns, end_ns)] of the ``sd/`` events on
    the host planes of the profile under ``logdir``."""
    from jax.profiler import ProfileData

    (path,) = logdir.glob("**/*.xplane.pb")
    out: dict = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for idx, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("sd/"):
                    out.setdefault((idx, ev.name), []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def test_spans_show_on_the_profiler_host_plane(tmp_path):
    """A thread-bound span is an ``sd/<name>`` event on its thread's line,
    traced or bare, nested by time inside its parent; a detached span
    (entered on one thread, exited on another) emits nothing."""
    import jax

    trace = telemetry.start_trace("job.t")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span(trace, "t.outer"):
            with telemetry.span(None, "t.bare"):
                pass
        detached = telemetry.span(trace, "t.detached", detached=True)
        detached.__enter__()
        closer = threading.Thread(
            target=lambda: detached.__exit__(None, None, None))
        closer.start()
        closer.join()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    lines = {name: idx for idx, name in events}
    assert lines["sd/t.outer"] == lines["sd/t.bare"]
    ((o0, o1),) = events[(lines["sd/t.outer"], "sd/t.outer")]
    ((b0, b1),) = events[(lines["sd/t.bare"], "sd/t.bare")]
    assert o0 <= b0 <= b1 <= o1
    assert "sd/t.detached" not in lines
    # the trace records all three, the detached one included
    assert {"t.outer", "t.detached"} <= set(trace.totals())


def test_telemetry_import_loads_no_jax():
    """The bridge resolves the profiler lazily: importing telemetry in a
    fresh process leaves JAX unloaded."""
    root = Path(__file__).resolve().parents[1]
    code = ("import sys, spacedrive_tpu.telemetry as t\n"
            "with t.span(None, 'x'):\n"
            "    pass\n"
            "print(sorted(m for m in sys.modules if m.split('.')[0] == 'jax'))")
    out = subprocess.run([sys.executable, "-c", code], cwd=root,
                         capture_output=True, text=True, timeout=120,
                         env={**os.environ, "PYTHONPATH": str(root)})
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"


#: span name → the job whose trace holds it, for the chain below
CHAIN_SPANS = {
    "indexer": ("indexer.walk", "indexer.rows", "indexer.save"),
    "file_identifier": ("identifier.select", "identifier.gather",
                        "hash.pack", "hash.put", "hash.wait"),
    "dedup_detector": ("dedup.select", "dedup.sweep", "dedup.verify",
                       "dedup.persist"),
}


#: the dedup job's spans around its own gather, which opens only for
#: files with no stored signature
DEDUP_GATHER_SPANS = ("dedup.gather", "dedup.minhash")


def _scan_chain(tmp_path, tmp_data_dir, monkeypatch, hasher):
    """Scan a tree of six sampled files (one near pair) with ``hasher``;
    returns the job rows and each chain job's span totals."""
    from spacedrive_tpu.locations import create_location, scan_location
    from spacedrive_tpu.node import Node

    monkeypatch.setenv("SD_PIPELINE", "1")
    rng = random.Random(23)
    tree = tmp_path / "tree"
    tree.mkdir()
    base = bytearray(rng.randbytes(160_000))
    for i in range(4):
        (tree / f"f{i}.dat").write_bytes(rng.randbytes(150_000 + i))
    (tree / "a.dat").write_bytes(base)
    base[1000] ^= 0xFF
    (tree / "a_copy.dat").write_bytes(base)

    node = Node(tmp_data_dir, probe_accelerator=False, watch_locations=False)
    try:
        lib = node.libraries.create("chain-spans")
        loc = create_location(lib, str(tree), hasher=hasher)
        scan_location(lib, loc["id"])
        assert node.jobs.wait_idle(300)
        rows = lib.db.query("SELECT name, metadata FROM job")
    finally:
        node.shutdown()
    spans = {r["name"]: _decoded(r["metadata"])["trace"]["spans"]
             for r in rows if r["name"] in CHAIN_SPANS}
    assert set(spans) == set(CHAIN_SPANS)
    return rows, spans


def _dedup_meta(rows) -> dict:
    return next(_decoded(r["metadata"]) for r in rows
                if r["name"] == "dedup_detector")


def test_scan_chain_records_every_span(tmp_path, tmp_data_dir, monkeypatch):
    """indexer → identifier (device hasher, on the CPU here) → dedup: each
    job's trace totals carry its per-batch spans, and the children sum to
    no more than the span they nest in. The identify page signed every
    file, so the dedup job gathers nothing: no ``dedup.gather`` or
    ``dedup.minhash`` span opens."""
    rows, spans = _scan_chain(tmp_path, tmp_data_dir, monkeypatch, "tpu")
    for job, names in CHAIN_SPANS.items():
        for name in names:
            assert spans[job].get(name, {}).get("count", 0) > 0, (job, name)
    assert not set(DEDUP_GATHER_SPANS) & set(spans["dedup_detector"])
    assert _dedup_meta(rows)["signatures"] == {"reused": 6, "gathered": 0}

    def total(job, name):
        return spans[job][name]["total_s"]

    ident = sum(total("file_identifier", f"hash.{p}")
                for p in ("pack", "put", "wait"))
    assert ident <= total("file_identifier", "pipeline.hash")
    dedup = sum(total("dedup_detector", n)
                for n in CHAIN_SPANS["dedup_detector"])
    assert dedup <= total("dedup_detector", "job.step")


def test_scan_chain_on_the_cpu_hasher_gathers_in_dedup(
        tmp_path, tmp_data_dir, monkeypatch):
    """The same chain with the CPU hasher: the identify page signs
    nothing, so the dedup job gathers and minhashes every file itself,
    and those spans nest within its ``job.step`` with the others."""
    rows, spans = _scan_chain(tmp_path, tmp_data_dir, monkeypatch, "cpu")
    dedup_spans = spans["dedup_detector"]
    for name in CHAIN_SPANS["dedup_detector"] + DEDUP_GATHER_SPANS:
        assert dedup_spans.get(name, {}).get("count", 0) > 0, name
    assert _dedup_meta(rows)["signatures"] == {"reused": 0, "gathered": 6}
    dedup = sum(dedup_spans[n]["total_s"]
                for n in CHAIN_SPANS["dedup_detector"] + DEDUP_GATHER_SPANS)
    assert dedup <= dedup_spans["job.step"]["total_s"]


def _bucket_sums(spans) -> dict:
    out: dict = {}
    for s in spans:
        a = s["attrs"]
        t = out.setdefault(str(a["cap"]), {"calls": 0, "files": 0, "lanes": 0,
                                           "payload_bytes": 0})
        t["calls"] += 1
        for key in ("files", "lanes", "payload_bytes"):
            t[key] += a[key]
    return out


def test_bucket_page_records_a_span_per_bucket():
    """One gathered page through the device hasher (on the CPU here): one
    ``hash.bucket`` span per bucket it uses, holding that bucket's
    ``hash.pack``/``put``/``wait``, with files, the bucket's fixed lanes and
    payload bytes that add up to the page and to the by-cap counters."""
    from spacedrive_tpu.objects.hasher import TpuHasher, bucket_rows

    rng = random.Random(25)
    sizes = [300] * 9 + [3000] * 3 + [20_000] * 2 + [90_000] + [57_352] * 4
    msgs = [rng.randbytes(n) for n in sizes]
    trace = telemetry.start_trace("job.t")
    with telemetry.span(trace, "pipeline.hash"):
        TpuHasher().hash_gathered(msgs)
    tree = trace.tree()
    spans = _spans_named(tree, "hash.bucket")
    sums = _bucket_sums(spans)
    assert sums == {
        "1": {"calls": 1, "files": 9, "lanes": 1024, "payload_bytes": 2700},
        "4": {"calls": 1, "files": 3, "lanes": 1024, "payload_bytes": 9000},
        "32": {"calls": 1, "files": 2, "lanes": 1024,
               "payload_bytes": 40_000},
        "64": {"calls": 1, "files": 4, "lanes": 512,
               "payload_bytes": 229_408},
        "101": {"calls": 1, "files": 1, "lanes": 256,
                "payload_bytes": 90_000},
    }
    for s in spans:
        assert s["attrs"]["lanes"] == bucket_rows(s["attrs"]["cap"])
        assert sorted(c["name"] for c in s["children"]) == [
            "hash.pack", "hash.put", "hash.wait"]
    for cap, t in sums.items():
        assert telemetry.value("sd_hash_bucket_calls_total", cap=cap) == 1
        for key in ("files", "lanes", "payload_bytes"):
            assert telemetry.value(f"sd_hash_bucket_{key}_total",
                                   cap=cap) == t[key]


def test_identifier_hash_buckets_sum_its_spans(tmp_path, monkeypatch):
    """The ``file_identifier`` report's ``hash_buckets`` are its own
    ``hash.bucket`` spans summed by cap, page after page."""
    monkeypatch.setattr(fi, "BATCH_SIZE", 16)
    monkeypatch.setenv("SD_PIPELINE", "1")
    rng = random.Random(26)
    tree = tmp_path / "tree"
    tree.mkdir()
    for i, n in enumerate([500] * 20 + [5000] * 10 + [70_000] * 4
                          + [150_000] * 6):
        (tree / f"f{i:02d}.dat").write_bytes(rng.randbytes(n))
    node, lib, loc_id = _seed_library(tmp_path / "data", tree, "buckets")
    try:
        from spacedrive_tpu.models import Location

        lib.db.update(Location, {"id": loc_id}, {"hasher": "tpu"})
        jid = _identify(node, lib, loc_id)
        meta = _decoded(lib.db.find_one(JobRow, {"id": jid})["metadata"])
        tree_ = node.router.resolve("telemetry.jobTrace", jid)
    finally:
        node.shutdown()
    sums = _bucket_sums(_spans_named(tree_, "hash.bucket"))
    assert meta["hash_buckets"] == sums
    assert set(sums) == {"1", "16", "101", "64"}
    assert sum(t["files"] for t in sums.values()) == 40
    assert sum(t["calls"] for t in sums.values()) > 4  # pages of 16


def test_trace_resume_continues_open_trace():
    """The worker's pause path leaves the trace OPEN; a resume under the
    same id continues it (span sums keep reconciling with accumulated
    report metadata), while a finished trace is never resumed."""
    t1 = telemetry.start_trace("job.x", trace_id="r1")
    with telemetry.span(t1, "stage"):
        pass
    # in-process pause: worker does NOT finish the trace
    t2 = telemetry.start_trace("job.x", trace_id="r1", resume=True)
    assert t2 is t1
    with telemetry.span(t2, "stage"):
        pass
    summary = telemetry.finish_trace(t2)
    assert summary["spans"]["stage"]["count"] == 2
    # terminal: a finished trace is replaced, not continued
    t3 = telemetry.start_trace("job.x", trace_id="r1", resume=True)
    assert t3 is not t1


# -- GET /metrics round-trip ---------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [0-9eE.+-]+$')


def test_metrics_endpoint_prometheus_roundtrip(tmp_data_dir):
    from spacedrive_tpu.node import Node
    from spacedrive_tpu.server.shell import Server

    telemetry.counter("sd_t_http_total", "x", labels=("route",)).inc(
        3, route="/spacedrive")
    telemetry.gauge("sd_scan_files_per_sec").set(1234.5)
    telemetry.histogram("sd_t_http_seconds").labels().observe(0.2)

    node = Node(tmp_data_dir, probe_accelerator=False, watch_locations=False)
    server = Server(node, port=0)
    server.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics", timeout=15) as r:
            assert r.status == 200
            assert r.headers["content-type"].startswith("text/plain")
            body = r.read().decode()
    finally:
        server.stop()
        node.shutdown()

    # exposition validity: every non-comment line is one sample
    families: dict[str, str] = {}
    for line in body.strip().splitlines():
        if line.startswith("# TYPE "):
            _, _, name, typ = line.split(" ", 3)
            families[name] = typ
            continue
        if line.startswith("#"):
            continue
        assert _SAMPLE_RE.match(line), line

    # the acceptance vocabulary is served
    for required in ("sd_scan_files_per_sec", "sd_pipeline_stage_busy_seconds",
                     "sd_retry_attempts_total", "sd_faults_fired_total",
                     "sd_hash_batches_total"):
        assert required in families, required
    # the roofline-model gauges nothing read are gone
    assert not [f for f in families if "mfu" in f]

    # round-trip: scraped values equal registry values
    assert 'sd_t_http_total{route="/spacedrive"} 3' in body
    assert "sd_scan_files_per_sec 1234.5" in body
    assert 'sd_t_http_seconds_bucket{le="0.25"} 1' in body
    assert "sd_t_http_seconds_count 1" in body


# -- SD_TELEMETRY=off no-op ----------------------------------------------------


def test_disabled_telemetry_is_a_noop(tmp_path):
    telemetry.set_enabled(False)
    c = telemetry.counter("sd_t_off_total")
    c.inc(5)
    telemetry.gauge("sd_t_off_gauge").set(9)
    telemetry.histogram("sd_t_off_seconds").labels().observe(1.0)
    telemetry.event("t.off")
    assert telemetry.value("sd_t_off_total") == 0.0
    assert telemetry.value("sd_t_off_gauge") == 0.0
    assert telemetry.snapshot()["events"] == []
    assert telemetry.start_trace("job.x") is None

    # spans still measure (report timings must not depend on the switch),
    # they just record nothing
    sp = telemetry.span(None, "anything")
    with sp:
        pass
    assert sp.duration_s >= 0.0
    assert telemetry.job_trace("nope", data_dir=tmp_path) is None


def test_disabled_scan_still_reports_stage_timings(tmp_path, monkeypatch):
    """With SD_TELEMETRY=off the scan report keeps its pipeline_*_s keys
    (span objects degrade to bare timers) but carries no trace."""
    telemetry.set_enabled(False)
    monkeypatch.setattr(fi, "BATCH_SIZE", 64)
    monkeypatch.setenv("SD_PIPELINE", "1")
    rng = random.Random(4)
    tree = tmp_path / "tree"
    tree.mkdir()
    for i in range(120):
        (tree / f"f{i:03d}.dat").write_bytes(rng.randbytes(400 + i))

    node, lib, loc_id = _seed_library(tmp_path / "off", tree, "off")
    jid = _identify(node, lib, loc_id)
    row = lib.db.find_one(JobRow, {"id": jid})
    meta = _decoded(row["metadata"])
    assert node.router.resolve("telemetry.jobTrace", jid) is None
    node.shutdown()

    assert row["status"] == JobStatus.COMPLETED
    assert "trace" not in meta
    assert meta["pipeline_batches"] == 2  # ceil(120/64)
    assert meta["pipeline_wall_s"] > 0
    assert meta["gather_s"] > 0


# -- chaos agreement with the fault suite --------------------------------------


def test_chaos_counters_match_report_metadata(tmp_path, monkeypatch):
    """sd_faults_fired_total mirrors faults.fired() and
    sd_quarantined_files_total mirrors the report's quarantined_files —
    the same numbers tests/test_faults.py asserts from metadata."""
    monkeypatch.setattr(fi, "BATCH_SIZE", 32)
    monkeypatch.setenv("SD_PIPELINE", "1")
    rng = random.Random(6)
    tree = tmp_path / "tree"
    tree.mkdir()
    for i in range(80):
        (tree / f"f{i:02d}.dat").write_bytes(rng.randbytes(500 + i))

    node, lib, loc_id = _seed_library(tmp_path / "chaos", tree, "chaos")
    faults.install("gather:enoent:4;hash:wedge:once", seed=77)
    try:
        jid = _identify(node, lib, loc_id)
        fired = dict(faults.fired())
    finally:
        faults.clear()
    row = lib.db.find_one(JobRow, {"id": jid})
    meta = _decoded(row["metadata"])
    node.shutdown()

    assert row["status"] == JobStatus.COMPLETED_WITH_ERRORS
    assert fired.get("gather:enoent") == 4
    assert fired.get("hash:wedge") == 1

    by_rule = {f"{lbl['seam']}:{lbl['kind']}": int(v)
               for lbl, v in telemetry.series_values("sd_faults_fired_total")
               if v}
    assert by_rule == fired
    assert telemetry.value("sd_quarantined_files_total") \
        == meta["quarantined_files"] == 4
    assert telemetry.value("sd_recovered_batches_total") \
        == meta["recovered_batches"] == 1
    assert telemetry.value("sd_retry_attempts_total") >= 0
