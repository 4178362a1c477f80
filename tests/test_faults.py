"""Chaos suite: the fault-injection framework and the resilience layer it
drives (ISSUE 4 tentpole).

The acceptance gate: a 2k-file scan under an injected fault storm
(`gather:eio`, `commit:sqlite_busy`, a one-shot mid-batch hash wedge)
completes COMPLETED_WITH_ERRORS with byte-identical cas_ids/DB rows and an
identical CRDT op order vs. a fault-free run — recovery must be invisible
in the database. Around it: per-item quarantine, stage supervision's
checkpoint-pause, pause-during-backoff promptness, the bounded drain
hard-join, the cold-resume failure path, and the retry/plan primitives.
"""

import random
import time

import pytest

from spacedrive_tpu import faults
from spacedrive_tpu.faults import DeviceWedgeError, FaultInjected, FaultPlan, FaultSpecError
from spacedrive_tpu.jobs import JobStatus
from spacedrive_tpu.jobs.report import JobReport
from spacedrive_tpu.models import JobRow, Notification, Tag
from spacedrive_tpu.models import base as models_base
from spacedrive_tpu.node import Node
from spacedrive_tpu.objects import file_identifier as fi
from spacedrive_tpu.objects import hasher as hasher_mod
from spacedrive_tpu.pipeline import executor as executor_mod
from spacedrive_tpu.sync import Ingester
from spacedrive_tpu.utils.retry import RetryPolicy, is_transient, retry_call

from .test_pipeline import _decoded, _seed_library, _snapshot


@pytest.fixture()
def clean_faults():
    """The plan is process-global: every chaos test arms through this."""
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(scope="module")
def chaos_tree(tmp_path_factory):
    """2,000 deterministic files: mostly small whole-file cas messages, a
    sampled-class slice, cross-directory duplicates, and empties."""
    rng = random.Random(7)
    root = tmp_path_factory.mktemp("chaos") / "tree"
    dup = rng.randbytes(1500)
    for d in range(8):
        p = root / f"d{d}"
        p.mkdir(parents=True)
        for i in range(250):
            if i == 0:
                body = dup                       # cross-dir duplicate
            elif i == 1:
                body = b""                       # empty
            elif i % 40 == 0:
                body = rng.randbytes(150_000 + d * 64 + i)  # sampled-class
            else:
                body = rng.randbytes(300 + (i * 13) % 1200)
            (p / f"f{i:03d}.dat").write_bytes(body)
    return root


def _identify(node, lib, loc_id, timeout=300.0):
    jid = node.jobs.spawn(lib, [fi.FileIdentifierJob({"location_id": loc_id})])
    assert node.jobs.wait_idle(timeout)
    return jid


# -- the acceptance gate -------------------------------------------------------


def test_chaos_scan_equivalent_to_fault_free(tmp_path, chaos_tree,
                                             monkeypatch, clean_faults):
    """gather:eio + commit:sqlite_busy + one-shot hash wedge over 2k files:
    the job lands COMPLETED_WITH_ERRORS (the wedge recovery is a report
    soft error), nothing quarantines (EIO reads retry clean, busy commits
    retry clean), and rows + CRDT op order match the fault-free run."""
    monkeypatch.setattr(fi, "BATCH_SIZE", 256)
    monkeypatch.setenv("SD_PIPELINE", "1")

    node_a, lib_a, loc_a = _seed_library(tmp_path / "clean", chaos_tree, "clean")
    _identify(node_a, lib_a, loc_a)
    clean = _snapshot(lib_a)
    node_a.shutdown()

    node_b, lib_b, loc_b = _seed_library(tmp_path / "chaos", chaos_tree, "chaos")
    faults.install("gather:eio:0.02;commit:sqlite_busy:3;hash:wedge:once",
                   seed=1234)
    jid = _identify(node_b, lib_b, loc_b)
    fired = faults.fired()
    faults.clear()
    chaos = _snapshot(lib_b)
    row = lib_b.db.find_one(JobRow, {"id": jid})
    meta = _decoded(row["metadata"])
    node_b.shutdown()

    # the storm actually happened
    assert fired.get("gather:eio", 0) > 0, fired
    assert fired.get("hash:wedge") == 1, fired
    assert fired.get("commit:sqlite_busy") == 3, fired

    # ... and was absorbed where the design says it is absorbed
    assert row["status"] == JobStatus.COMPLETED_WITH_ERRORS
    assert "recovered on native CPU" in (row["errors_text"] or "")
    assert meta["quarantined_files"] == 0
    assert meta["recovered_batches"] == 1
    assert meta["pipeline_batches"] == 8  # ceil(2000/256)

    assert chaos[0] == clean[0], "cas_id rows diverge under faults"
    assert chaos[1] == clean[1], "object linkage diverges under faults"
    assert chaos[2] == clean[2], "CRDT op order diverges under faults"


def test_group_commit_chaos_byte_identical(tmp_path, chaos_tree, monkeypatch,
                                           clean_faults):
    """The group-commit chaos gate: a busy storm on the (now per-GROUP)
    commit seam plus a one-shot hash-dispatch wedge, with SD_COMMIT_GROUP=8,
    must stay byte-identical to the fault-free run. The busy count (6)
    exhausts the inner TXN_RETRY budget exactly, so the whole-group
    rollback + restore + COMMIT_RETRY escalation path runs for real.
    (`hash_dispatch` is the spec alias for the identifier's hash seam.)"""
    monkeypatch.setattr(fi, "BATCH_SIZE", 256)
    monkeypatch.setenv("SD_PIPELINE", "1")
    monkeypatch.setenv("SD_COMMIT_GROUP", "8")

    node_a, lib_a, loc_a = _seed_library(tmp_path / "clean", chaos_tree, "gclean")
    _identify(node_a, lib_a, loc_a)
    clean = _snapshot(lib_a)
    node_a.shutdown()

    node_b, lib_b, loc_b = _seed_library(tmp_path / "chaos", chaos_tree, "gchaos")
    faults.install("commit:sqlite_busy:6;hash_dispatch:wedge:once", seed=7)
    jid = _identify(node_b, lib_b, loc_b)
    fired = faults.fired()
    faults.clear()
    chaos = _snapshot(lib_b)
    row = lib_b.db.find_one(JobRow, {"id": jid})
    meta = _decoded(row["metadata"])
    node_b.shutdown()

    # the alias normalized to the canonical seam and the storm happened
    assert fired.get("hash:wedge") == 1, fired
    assert fired.get("commit:sqlite_busy") == 6, fired

    assert row["status"] == JobStatus.COMPLETED_WITH_ERRORS
    assert meta["quarantined_files"] == 0
    assert meta["recovered_batches"] == 1
    assert meta["pipeline_batches"] == 8  # ceil(2000/256)
    assert meta["commit_txns"] <= 8  # grouping actually engaged

    assert chaos[0] == clean[0], "cas_id rows diverge under group-commit chaos"
    assert chaos[1] == clean[1], "object linkage diverges under group-commit chaos"
    assert chaos[2] == clean[2], "CRDT op order diverges under group-commit chaos"


# -- per-item quarantine -------------------------------------------------------


def test_vanished_and_denied_files_quarantine(tmp_path, monkeypatch,
                                              clean_faults):
    """A file deleted mid-scan and an injected EACCES both quarantine: soft
    errors, COMPLETED_WITH_ERRORS, every other file identified."""
    monkeypatch.setattr(fi, "BATCH_SIZE", 16)
    monkeypatch.setenv("SD_PIPELINE", "1")
    rng = random.Random(3)
    tree = tmp_path / "tree"
    tree.mkdir()
    for i in range(40):
        (tree / f"f{i:02d}.dat").write_bytes(rng.randbytes(600 + i))

    node, lib, loc_id = _seed_library(tmp_path / "q", tree, "q")
    (tree / "f07.dat").unlink()  # vanishes AFTER indexing, BEFORE identify
    faults.install("gather:eacces:once")
    jid = _identify(node, lib, loc_id)
    faults.clear()

    row = lib.db.find_one(JobRow, {"id": jid})
    meta = _decoded(row["metadata"])
    n_identified = lib.db.query(
        "SELECT count(*) c FROM file_path WHERE cas_id IS NOT NULL")[0]["c"]
    node.shutdown()

    assert row["status"] == JobStatus.COMPLETED_WITH_ERRORS
    assert meta["quarantined_files"] == 2
    assert (row["errors_text"] or "").count("quarantined") == 2
    assert n_identified == 38  # everything else still identified


# -- pipeline stage supervision ------------------------------------------------


def test_transient_stage_crash_checkpoint_pauses_then_resumes(
        tmp_path, monkeypatch, clean_faults):
    """A transient crash on the prefetch thread drains to a resumable
    checkpoint-pause (not FAILED); resume completes to the same terminal
    state a fault-free run reaches."""
    monkeypatch.setattr(fi, "BATCH_SIZE", 8)
    monkeypatch.setenv("SD_PIPELINE", "1")
    rng = random.Random(5)
    tree = tmp_path / "tree"
    tree.mkdir()
    for i in range(40):
        (tree / f"f{i:02d}.dat").write_bytes(rng.randbytes(500 + i * 7))

    node_a, lib_a, loc_a = _seed_library(tmp_path / "ref", tree, "ref")
    _identify(node_a, lib_a, loc_a)
    reference = _snapshot(lib_a)
    node_a.shutdown()

    node, lib, loc_id = _seed_library(tmp_path / "crash", tree, "crash")
    faults.install("gather:crash:once")
    jid = node.jobs.spawn(lib, [fi.FileIdentifierJob({"location_id": loc_id})])
    assert node.jobs.wait_idle(60)
    faults.clear()

    row = lib.db.find_one(JobRow, {"id": jid})
    assert row["status"] == JobStatus.PAUSED, JobStatus.NAMES[row["status"]]
    assert "checkpoint-paused" in (row["errors_text"] or "")

    assert node.jobs.resume(lib, jid)
    assert node.jobs.wait_idle(120)
    row = lib.db.find_one(JobRow, {"id": jid})
    # the stage-crash soft error survives the resume, so the terminal
    # status is CompletedWithErrors — the DB state must still be identical
    assert row["status"] == JobStatus.COMPLETED_WITH_ERRORS
    resumed = _snapshot(lib)
    node.shutdown()
    assert resumed[0] == reference[0]
    assert resumed[1] == reference[1]
    assert resumed[2] == reference[2], "CRDT op order diverges after " \
                                       "stage-crash pause/resume"


def test_stuck_gather_cannot_strand_a_pausing_job(tmp_path, monkeypatch,
                                                  clean_faults):
    """Drain-timeout escalation: a never-returning gather (hang fault)
    leaks its stage thread, but the pause still lands within two bounded
    join windows and the leak becomes a report soft error."""
    monkeypatch.setattr(fi, "BATCH_SIZE", 8)
    monkeypatch.setenv("SD_PIPELINE", "1")
    monkeypatch.setenv("SD_PIPELINE_DRAIN_S", "0.3")
    rng = random.Random(9)
    tree = tmp_path / "tree"
    tree.mkdir()
    for i in range(24):
        (tree / f"f{i:02d}.dat").write_bytes(rng.randbytes(400 + i))

    node, lib, loc_id = _seed_library(tmp_path / "hang", tree, "hang")
    faults.install("gather:hang:once")
    jid = node.jobs.spawn(lib, [fi.FileIdentifierJob({"location_id": loc_id})])
    time.sleep(0.3)  # let the prefetch thread wedge inside the gather
    assert node.jobs.pause(jid)

    deadline = time.monotonic() + 15
    row = None
    while time.monotonic() < deadline:
        row = lib.db.find_one(JobRow, {"id": jid})
        if row and row["status"] == JobStatus.PAUSED:
            break
        time.sleep(0.05)
    assert row is not None and row["status"] == JobStatus.PAUSED
    assert "leaked" in (row["errors_text"] or "")
    faults.clear()
    node.shutdown()


# -- sharded gather chaos (ISSUE 17) -------------------------------------------


def test_sharded_gather_chaos_byte_identical(tmp_path, chaos_tree, monkeypatch,
                                             clean_faults):
    """The acceptance-gate storm rerun with the gather stage split across 4
    parallel shards: EIO retries, busy commits, and the one-shot hash wedge
    must all be absorbed exactly as in the two-thread topology, and the
    ordered ticket merger must keep rows + CRDT op order byte-identical to
    a fault-free (unsharded) run.

    The EIO trigger is a COUNT (2), not the unsharded gate's probability:
    four shard threads draw from the shared fault RNG in nondeterministic
    interleave, so a probability storm can land 3 low draws on one file's
    retry sequence and quarantine it (breaking byte-identity) on an
    unlucky run. Two count fires can never exhaust GATHER_RETRY's three
    calls, whatever the interleaving — recovery is guaranteed."""
    monkeypatch.setattr(fi, "BATCH_SIZE", 256)
    monkeypatch.setenv("SD_PIPELINE", "1")

    monkeypatch.setenv("SD_SCAN_SHARDS", "1")
    node_a, lib_a, loc_a = _seed_library(tmp_path / "clean", chaos_tree, "sclean")
    _identify(node_a, lib_a, loc_a)
    clean = _snapshot(lib_a)
    node_a.shutdown()

    monkeypatch.setenv("SD_SCAN_SHARDS", "4")
    node_b, lib_b, loc_b = _seed_library(tmp_path / "chaos", chaos_tree, "schaos")
    faults.install("gather:eio:2;commit:sqlite_busy:3;hash:wedge:once",
                   seed=4321)
    jid = _identify(node_b, lib_b, loc_b)
    fired = faults.fired()
    faults.clear()
    chaos = _snapshot(lib_b)
    row = lib_b.db.find_one(JobRow, {"id": jid})
    meta = _decoded(row["metadata"])
    node_b.shutdown()

    assert fired.get("gather:eio") == 2, fired
    assert fired.get("hash:wedge") == 1, fired
    assert fired.get("commit:sqlite_busy") == 3, fired

    assert row["status"] == JobStatus.COMPLETED_WITH_ERRORS
    assert meta["quarantined_files"] == 0
    assert meta["recovered_batches"] == 1
    assert meta["pipeline_batches"] == 8  # ceil(2000/256)
    assert meta["pipeline_shards"] == "4"

    assert chaos[0] == clean[0], "cas_id rows diverge under sharded faults"
    assert chaos[1] == clean[1], "object linkage diverges under sharded faults"
    assert chaos[2] == clean[2], "CRDT op order diverges under sharded faults"


def test_sharded_quarantine_stays_per_item(tmp_path, monkeypatch,
                                           clean_faults):
    """Quarantine granularity survives sharding: with 4 gather shards, a
    vanished file and an injected EACCES each quarantine exactly one item —
    the failing shard slice must not take its page (or its shard's whole
    slice) down with it."""
    monkeypatch.setattr(fi, "BATCH_SIZE", 16)
    monkeypatch.setenv("SD_PIPELINE", "1")
    monkeypatch.setenv("SD_SCAN_SHARDS", "4")
    rng = random.Random(3)
    tree = tmp_path / "tree"
    tree.mkdir()
    for i in range(40):
        (tree / f"f{i:02d}.dat").write_bytes(rng.randbytes(600 + i))

    node, lib, loc_id = _seed_library(tmp_path / "q", tree, "sq")
    (tree / "f07.dat").unlink()  # vanishes AFTER indexing, BEFORE identify
    faults.install("gather:eacces:once")
    jid = _identify(node, lib, loc_id)
    faults.clear()

    row = lib.db.find_one(JobRow, {"id": jid})
    meta = _decoded(row["metadata"])
    n_identified = lib.db.query(
        "SELECT count(*) c FROM file_path WHERE cas_id IS NOT NULL")[0]["c"]
    node.shutdown()

    assert row["status"] == JobStatus.COMPLETED_WITH_ERRORS
    assert meta["quarantined_files"] == 2
    assert (row["errors_text"] or "").count("quarantined") == 2
    assert n_identified == 38  # everything else still identified


def test_sharded_stuck_gather_drain_escalates(tmp_path, monkeypatch,
                                              clean_faults):
    """A never-returning gather now wedges ONE shard worker while its three
    siblings finish their slices; the merger is left waiting on a ticket
    that can never complete. Pause must still land within the bounded
    drain windows, abandoning the wedged worker as a leak soft error."""
    monkeypatch.setattr(fi, "BATCH_SIZE", 8)
    monkeypatch.setenv("SD_PIPELINE", "1")
    monkeypatch.setenv("SD_SCAN_SHARDS", "4")
    monkeypatch.setenv("SD_PIPELINE_DRAIN_S", "0.3")
    rng = random.Random(9)
    tree = tmp_path / "tree"
    tree.mkdir()
    for i in range(24):
        (tree / f"f{i:02d}.dat").write_bytes(rng.randbytes(400 + i))

    node, lib, loc_id = _seed_library(tmp_path / "hang", tree, "shang")
    faults.install("gather:hang:once")
    jid = node.jobs.spawn(lib, [fi.FileIdentifierJob({"location_id": loc_id})])
    time.sleep(0.3)  # let one shard worker wedge inside the gather
    assert node.jobs.pause(jid)

    deadline = time.monotonic() + 15
    row = None
    while time.monotonic() < deadline:
        row = lib.db.find_one(JobRow, {"id": jid})
        if row and row["status"] == JobStatus.PAUSED:
            break
        time.sleep(0.05)
    assert row is not None and row["status"] == JobStatus.PAUSED
    assert "leaked" in (row["errors_text"] or "")
    faults.clear()
    node.shutdown()


# -- pause/cancel during a retry backoff window (satellite) --------------------


def test_pause_during_commit_retry_backoff_unwinds_promptly(
        tmp_path, monkeypatch, clean_faults):
    """With the inner txn retry disabled and a deliberately huge committer
    backoff, a Pause arriving mid-backoff must unwind within poll-interval
    latency — not sleep out the 8s window. The checkpoint then resumes to
    a complete scan once the faults clear."""
    monkeypatch.setattr(fi, "BATCH_SIZE", 8)
    monkeypatch.setenv("SD_PIPELINE", "1")
    monkeypatch.setattr(models_base, "TXN_RETRY",
                        RetryPolicy(attempts=1, budget_s=0.1))
    monkeypatch.setattr(executor_mod, "COMMIT_RETRY",
                        RetryPolicy(attempts=6, base_s=8.0, max_s=8.0,
                                    multiplier=1.0, jitter=0.0,
                                    budget_s=120.0))
    rng = random.Random(11)
    tree = tmp_path / "tree"
    tree.mkdir()
    for i in range(32):
        (tree / f"f{i:02d}.dat").write_bytes(rng.randbytes(500 + i * 3))

    node, lib, loc_id = _seed_library(tmp_path / "bk", tree, "bk")
    faults.install("commit:sqlite_busy:500")
    jid = node.jobs.spawn(lib, [fi.FileIdentifierJob({"location_id": loc_id})])
    time.sleep(1.0)  # first commit has failed by now; committer is backing off
    t0 = time.monotonic()
    assert node.jobs.pause(jid)
    deadline = time.monotonic() + 10
    row = None
    while time.monotonic() < deadline:
        row = lib.db.find_one(JobRow, {"id": jid})
        if row and row["status"] == JobStatus.PAUSED:
            break
        time.sleep(0.02)
    pause_latency = time.monotonic() - t0
    assert row is not None and row["status"] == JobStatus.PAUSED
    # the backoff window is 8s; prompt unwinding means far under that
    assert pause_latency < 3.0, f"pause took {pause_latency:.1f}s " \
                                f"(slept out the backoff?)"

    faults.clear()
    assert node.jobs.resume(lib, jid)
    assert node.jobs.wait_idle(120)
    assert lib.db.find_one(JobRow, {"id": jid})["status"] == JobStatus.COMPLETED
    n = lib.db.query("SELECT count(*) c FROM file_path "
                     "WHERE cas_id IS NOT NULL")[0]["c"]
    node.shutdown()
    assert n == 32


# -- transaction-level busy retry (satellite) ----------------------------------


def test_txn_retry_absorbs_injected_busy(tmp_path, clean_faults):
    db = models_base.Database(tmp_path / "t.db", [])
    db.execute("CREATE TABLE t (x INTEGER)")
    faults.install("commit:sqlite_busy:2")
    with db.transaction():
        db.execute("INSERT INTO t VALUES (1)")
    assert faults.fired()["commit:sqlite_busy"] == 2
    assert db.query("SELECT count(*) c FROM t")[0]["c"] == 1
    db.close()


def test_busy_storm_leaves_crdt_op_order_unchanged(tmp_path, monkeypatch,
                                                   clean_faults):
    """The satellite gate for models/base: an injected-busy storm across
    every transaction of an identify run changes nothing about the CRDT
    op stream."""
    monkeypatch.setattr(fi, "BATCH_SIZE", 8)
    monkeypatch.setenv("SD_PIPELINE", "1")
    # per-page txns: this gate targets the _Txn-level busy retry, so every
    # page must BEGIN/COMMIT through the seam (group commit would coalesce
    # the run into one txn and starve the probabilistic storm of hits)
    monkeypatch.setenv("SD_COMMIT_GROUP", "1")
    rng = random.Random(21)
    tree = tmp_path / "tree"
    tree.mkdir()
    for i in range(48):
        (tree / f"f{i:02d}.dat").write_bytes(rng.randbytes(700 + i * 5))

    node_a, lib_a, loc_a = _seed_library(tmp_path / "ref", tree, "ref")
    _identify(node_a, lib_a, loc_a)
    reference = _snapshot(lib_a)
    node_a.shutdown()

    node_b, lib_b, loc_b = _seed_library(tmp_path / "busy", tree, "busy")
    faults.install("commit:sqlite_busy:0.4", seed=99)
    _identify(node_b, lib_b, loc_b)
    assert faults.fired().get("commit:sqlite_busy", 0) > 0
    faults.clear()
    busy = _snapshot(lib_b)
    node_b.shutdown()
    assert busy[2] == reference[2], "CRDT op order changed under busy storm"
    assert busy[0] == reference[0] and busy[1] == reference[1]


# -- cold resume (satellite) ---------------------------------------------------


def test_cold_resume_failure_is_failed_and_notified(tmp_data_dir):
    """A corrupt checkpoint blob must persist FAILED with errors_text and
    emit a library notification — never a silent CANCELED."""
    node = Node(tmp_data_dir, probe_accelerator=False, watch_locations=False)
    lib = node.libraries.create("cr")
    report = JobReport.new("file_identifier")
    report.status = JobStatus.RUNNING  # a crashed run
    report.data = b"\x00 not a checkpoint"
    report.create(lib.db)

    assert node.jobs.cold_resume(lib) == 0
    row = lib.db.find_one(JobRow, {"id": report.id})
    assert row["status"] == JobStatus.FAILED
    assert "cold resume failed" in (row["errors_text"] or "")
    notes = lib.db.find(Notification)
    kinds = [(n["data"] or {}).get("kind") for n in notes]
    node.shutdown()
    assert "job_cold_resume_failed" in kinds


# -- sync ingest seam ----------------------------------------------------------


def test_sync_apply_crash_falls_back_to_careful_pass(tmp_path, clean_faults):
    """A one-shot crash inside op materialization aborts the optimistic
    single-savepoint pass; the careful per-op rerun still converges."""
    node_a = Node(tmp_path / "a", probe_accelerator=False, watch_locations=False)
    node_b = Node(tmp_path / "b", probe_accelerator=False, watch_locations=False)
    lib_a = node_a.libraries.create("src")
    lib_b = node_b.libraries.create("dst")
    lib_a.sync.emit_messages = True
    lib_a.add_remote_instance(lib_b.instance())
    lib_b.add_remote_instance(lib_a.instance())
    for i in range(20):
        pub = f"tag-{i:02d}"
        lib_a.sync.write_ops(
            [lib_a.sync.shared_create(Tag, pub, {"name": f"t{i}"})],
            lambda db, p=pub, j=i: db.insert(Tag, {"pub_id": p,
                                                   "name": f"t{j}"}))

    faults.install("sync_apply:crash:once")
    ingester = Ingester(lib_b)
    applied = 0
    while True:
        ops, has_more = lib_a.sync.get_ops(lib_b.sync.timestamps(), 100)
        applied += ingester.receive(ops)
        if not has_more:
            break
    assert faults.fired()["sync_apply:crash"] == 1
    faults.clear()
    assert applied == 20
    names = sorted(r["name"] for r in lib_b.db.find(Tag))
    node_a.shutdown()
    node_b.shutdown()
    assert names == sorted(f"t{i}" for i in range(20))


# -- hasher degradation ladder -------------------------------------------------


def test_hybrid_degrade_flips_verdict():
    h = hasher_mod.HybridHasher()
    assert h.router.unprobed
    h.router.seed(10.0, 99.0)
    assert h.router.current == "device" and not h.router.unprobed
    h.degrade_device("unit")
    assert h.router.dev_bps == 0.0 and h.router.cpu_bps == 10.0
    assert h.router.current == "cpu" and h.router.degraded
    # a degraded router holds a verdict: the next page does not re-probe
    assert not h.router.unprobed


def test_bounded_call_does_not_count_compile_time(monkeypatch):
    """A cold compile is set-up, not a slow device: time the worker spends
    compiling leaves the deadline untouched; time outside it counts."""
    import threading

    compiling = threading.Event()
    compiling.set()
    monkeypatch.setattr(hasher_mod, "_compiling",
                        lambda _t: compiling.is_set())

    def slow_compile_then_run():
        time.sleep(0.6)  # "compiling": well past the 0.2 s deadline
        compiling.clear()
        time.sleep(0.05)
        return "digests"

    assert hasher_mod._bounded_call(slow_compile_then_run, 0.2, "t",
                                    poll_s=0.05) == ("ok", "digests")
    # no compile in sight: the same deadline times the call out
    assert hasher_mod._bounded_call(lambda: time.sleep(0.6), 0.2, "t",
                                    poll_s=0.05) == ("timeout", None)


def test_router_reprobes_device_after_bounded_cpu_batches():
    """Satellite gate: a degraded route must NOT pin CPU for the whole
    scan — after REPROBE_AFTER cpu-routed batches the router asks for a
    bounded device probe, and a measured device success clears the pin."""
    r = hasher_mod.BackendRouter()
    r.seed(100.0, 500.0)
    r.degrade("transient wedge")
    assert r.current == "cpu" and r.degraded
    probes = 0
    for _ in range(r.REPROBE_AFTER - 1):
        main, probe = r.route()
        assert main == "cpu"
        probes += probe is not None
    assert probes == 0  # pinned, no device touch inside the bound
    main, probe = r.route()
    assert (main, probe) == ("cpu", "device")  # the bounded re-probe
    # the offer REPEATS until a probe actually runs — a batch with no
    # routable messages must not burn the token
    assert r.route() == ("cpu", "device")
    # a failed/timed-out probe (degrade) restarts the bound
    r.degrade("probe timed out")
    assert r.route() == ("cpu", None)
    # a measured device success clears the pin and the rate comparison
    # takes back over (hysteresis decides the flip)
    r.observe("device", 10_000_000, 1.0)
    assert not r.degraded


def test_router_hysteresis_damps_flapping():
    """The route only flips when the other engine's EWMA beats the
    incumbent by HYSTERESIS× — jittery near-equal rates must not flap."""
    r = hasher_mod.BackendRouter()
    r.seed(100.0, 120.0)  # device wins the seed (ratio < hysteresis)
    assert r.current == "device"
    flips = r.flips
    # cpu drifts slightly ahead, but inside the hysteresis band: no flip
    r.observe("cpu", 130, 1.0)
    assert r.route()[0] == "device" and r.flips == flips
    # cpu rate decisively beats device × hysteresis: one flip, then stable
    for _ in range(4):
        r.observe("cpu", 1000, 1.0)
    assert r.route()[0] == "cpu"
    assert r.flips == flips + 1
    assert r.route()[0] == "cpu"
    assert r.flips == flips + 1


# -- the primitives ------------------------------------------------------------


def test_fault_spec_grammar_and_determinism():
    plan1 = FaultPlan("gather:eio:0.25;hash:wedge:once;commit:sqlite_busy:2",
                      seed=42)
    plan2 = FaultPlan("gather:eio:0.25;hash:wedge:once;commit:sqlite_busy:2",
                      seed=42)

    def firing_pattern(plan):
        hits = []
        for i in range(200):
            try:
                plan.check("gather", key=str(i))
                hits.append(0)
            except OSError:
                hits.append(1)
        return hits

    a, b = firing_pattern(plan1), firing_pattern(plan2)
    assert a == b, "same seed + same sequence must fire identically"
    assert 20 < sum(a) < 80  # p=0.25 over 200 draws

    with pytest.raises(DeviceWedgeError):
        plan1.check("hash")
    plan1.check("hash")  # `once` consumed
    for _ in range(2):
        with pytest.raises(Exception):
            plan1.check("commit")
    plan1.check("commit")  # count exhausted
    assert plan1.fired()["hash:wedge"] == 1

    for bad in ("gather", "gather:nope", "g:eio:0", "g:eio:1.5",
                "g:eio:-1", "g:eio:soon", ""):
        with pytest.raises(FaultSpecError):
            FaultPlan(bad)


def test_at_most_one_rule_fires_per_seam_hit():
    """Co-armed rules must not drain their once/count budgets behind the
    rule that actually surfaced: each kind fires on its own hit."""
    plan = FaultPlan("gather:eio:once;gather:enoent:once")
    with pytest.raises(OSError) as e1:
        plan.check("gather")
    assert e1.value.errno == 5  # EIO first, ENOENT budget untouched
    with pytest.raises(FileNotFoundError):
        plan.check("gather")
    plan.check("gather")  # both consumed
    assert plan.fired() == {"gather:eio": 1, "gather:enoent": 1}


def test_inject_is_a_noop_when_disarmed(clean_faults):
    assert faults.active() is None
    faults.inject("gather")
    faults.inject("whatever", key="x")
    assert faults.fired() == {}


def test_retry_call_backoff_budget_and_classification():
    sleeps = []
    attempts = {"n": 0}

    def flaky():
        attempts["n"] += 1
        if attempts["n"] < 3:
            raise OSError(5, "I/O error")  # EIO
        return 42

    policy = RetryPolicy(attempts=5, base_s=0.2, max_s=1.0, jitter=0.0,
                         budget_s=30.0)
    assert retry_call(flaky, policy=policy, classify=is_transient,
                      sleep=sleeps.append, rng=random.Random(0)) == 42
    assert attempts["n"] == 3
    assert abs(sum(sleeps[:4]) - 0.2) < 1e-9  # first delay, in poll quanta

    # non-transient: no retry
    attempts["n"] = 0

    def fatal():
        attempts["n"] += 1
        raise ValueError("bug")

    with pytest.raises(ValueError):
        retry_call(fatal, policy=policy, sleep=sleeps.append)
    assert attempts["n"] == 1

    # attempts exhausted: the last transient re-raises
    attempts["n"] = 0

    def always_busy():
        attempts["n"] += 1
        raise OSError(5, "I/O error")

    with pytest.raises(OSError):
        retry_call(always_busy, policy=RetryPolicy(attempts=3, base_s=0.0,
                                                   jitter=0.0, budget_s=9.0),
                   sleep=sleeps.append)
    assert attempts["n"] == 3


def test_retry_cancel_check_unwinds_immediately():
    class Unwind(Exception):
        pass

    state = {"calls": 0}

    def cancel_check():
        state["calls"] += 1
        if state["calls"] >= 2:
            raise Unwind()

    slept = []

    def busy():
        raise OSError(5, "I/O error")

    with pytest.raises(Unwind):
        retry_call(busy,
                   policy=RetryPolicy(attempts=10, base_s=60.0, jitter=0.0,
                                      budget_s=600.0),
                   cancel_check=cancel_check, sleep=slept.append)
    # unwound after ~one poll quantum of a 60s backoff, not the whole window
    assert sum(slept) < 1.0
