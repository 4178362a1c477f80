"""Process-kill torture harness (ISSUE 9 tentpole).

Parent-side API (used by tests/test_crash_recovery.py and ``bench.py
--crash``): spawn a REAL node in its own OS process running a scan /
sync-ingest / backup workload, let an armed ``kill`` fault SIGKILL it at
a seeded, seam-driven point (mid-group-commit, mid-gather, mid-sync-
window, mid-backup), then restart the same data dir and gate that

- the library DB passes the boot integrity check (``PRAGMA quick_check``
  after SQLite's WAL recovery — recovery.py),
- interrupted jobs cold-resume from their durable checkpoint, and
- the final state is byte-identical (structural snapshot: rows + CRDT op
  order) to an uninterrupted reference run of the same workload.

Child protocol: ``python tests/crash_harness.py <mode> <data_dir>
<json-args>``. The child writes its result JSON to ``args["out"]``
(stdout carries the node's log stream); a killed child simply dies with
``-SIGKILL`` and leaves whatever the kernel left — that debris is the
test subject.

Everything is deterministic: fixed library ids, fixed file_path pub_ids
(sorted insert order), a seeded fixture tree, seeded op streams, and
``skipN``-triggered kills — the same matrix entry dies at the same seam
hit every run.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]

#: deterministic library ids (uuid-shaped only for path hygiene)
SCAN_LIB_ID = "c0a5c0de-0000-4000-8000-00000000aaaa"
SYNC_LIB_ID = "c0a5c0de-0000-4000-8000-00000000bbbb"
BK_LIB_ID = "c0a5c0de-0000-4000-8000-00000000cccc"

#: scan workload shape: small files, many pages, several group commits
SCAN_FILES = 200
SCAN_BATCH = 24
COMMIT_GROUP = 4

#: sync workload shape
SYNC_OPS = 1800
SYNC_WINDOW = 150

#: the kill matrix (shared by tests/test_crash_recovery.py and ``bench.py
#: --crash``): ≥6 seeded kill points across scan / sync / backup
#: workloads; skipN pins each to an exact seam hit (deterministic
#: workload ⇒ deterministic death point)
SCAN_KILLS = ("gather:kill:skip30", "hash:kill:skip4", "commit:kill:skip3")
SYNC_KILLS = ("sync_apply:kill:skip100", "sync_apply:kill:skip700")
#: backup:kill:skip1 dies at the write-adjacent seam (tar already built —
#: `once` would fire at the entry seam, before any work);
#: artifact_write:kill:once dies INSIDE the atomic-write discipline, with
#: the temp durable but the destination name not yet created
BACKUP_KILLS = ("backup:kill:skip1", "artifact_write:kill:once")
#: ISSUE 17: the sharded-prefetch kill point. SD_SCAN_SHARDS=4 forces the
#: split → shard → merge prefetch topology in BOTH the crash run and the
#: restart, and ``gather:kill:skip1`` dies on an early slice INSIDE a
#: gather shard worker thread — the restart must cold-resume from the
#: durable checkpoint and converge byte-identical to the UNSHARDED
#: uninterrupted reference (the ordered-merger equivalence claim, under
#: SIGKILL)
SHARDED_SCAN_KILL = "gather:kill:skip1"
SHARDED_SCAN_ENV = {"SD_SCAN_SHARDS": "4"}
#: ISSUE 18: the manifest-commit kill point. SD_CHUNK_MANIFESTS=1 turns the
#: chunk-manifest stage on in BOTH the crash run and the restart, and the
#: ``manifest_commit`` seam dies INSIDE the identify transaction just
#: before the chunk_manifest rows land — skip1 guarantees at least one
#: durable group precedes the death, so the restart proves identify rows
#: and manifest rows are one atomic unit (never a half: an object with
#: cas_id but torn manifest rows cannot survive the SIGKILL)
MANIFEST_SCAN_KILL = "manifest_commit:kill:skip1"
MANIFEST_SCAN_ENV = {"SD_CHUNK_MANIFESTS": "1", "SD_CDC_KERNEL": "numpy"}


# ---------------------------------------------------------------------------
# fixtures (parent side)
# ---------------------------------------------------------------------------


def make_tree(root: Path, n_files: int = SCAN_FILES, seed: int = 11) -> Path:
    """Deterministic scan tree: mixed sizes incl. duplicates + empties."""
    import random

    rng = random.Random(seed)
    root.mkdir(parents=True, exist_ok=True)
    dup = rng.randbytes(3000)
    for i in range(n_files):
        sub = root / f"d{i % 4}"
        sub.mkdir(exist_ok=True)
        if i % 23 == 0:
            body = b""
        elif i % 11 == 0:
            body = dup
        elif i % 17 == 0:
            body = rng.randbytes(120_000 + i)  # sampled-class
        else:
            body = rng.randbytes(800 + (i * 37) % 4000)
        (sub / f"f{i:04d}.dat").write_bytes(body)
    return root


def gen_ops_file(path: Path, n_ops: int = SYNC_OPS, seed: int = 5) -> Path:
    """Deterministic CRDT op stream from 3 virtual peer instances: tag
    creates + per-field updates, HLC-stamped within the drift bound."""
    import random

    rng = random.Random(seed)
    base = time.time() - 300.0  # inside MAX_DRIFT_SECONDS
    instances = [f"crash-inst-{k}" for k in range(3)]
    ops = []
    for i in range(n_ops):
        inst = instances[i % len(instances)]
        ts_unix = base + i * 0.01
        sec = int(ts_unix)
        frac = int((ts_unix - sec) * (1 << 32))
        ts = (sec << 32) | (frac & 0xFFFFFFFF)
        tag = f"crash-tag-{rng.randrange(max(2, n_ops // 4)):05d}"
        if rng.random() < 0.5:
            typ = {"_t": "shared", "model": "tag", "record_id": tag,
                   "kind": "c", "data": {"name": f"t{i}"}}
        else:
            typ = {"_t": "shared", "model": "tag", "record_id": tag,
                   "kind": "u:name", "data": f"n{i}"}
        ops.append({"instance": inst, "timestamp": ts,
                    "id": f"crash-op-{i:06d}", "typ": typ})
    path.write_text("\n".join(json.dumps(op) for op in ops) + "\n")
    return path


# ---------------------------------------------------------------------------
# child runner (parent side)
# ---------------------------------------------------------------------------


def child_env() -> dict:
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu", "SD_NO_WATCHER": "1", "SD_P2P_DISABLED": "1",
        "SD_NO_ACCEL_PROBE": "1", "SD_COMMIT_GROUP": str(COMMIT_GROUP),
    })
    env.pop("SD_FAULTS", None)  # kills are armed in-process, post-seed
    return env


def run_child(mode: str, data_dir: Path, args: dict, expect_kill: bool =
              False, timeout: float = 180.0,
              extra_env: dict | None = None) -> tuple[int, dict | None]:
    """Run one child; returns (returncode, result-dict-or-None). With
    ``expect_kill`` the caller asserts rc == -SIGKILL itself."""
    out_path = data_dir.parent / f"{data_dir.name}.{mode}.result.json"
    out_path.unlink(missing_ok=True)
    env = child_env()
    if extra_env:
        env.update(extra_env)
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).resolve()), mode, str(data_dir),
         json.dumps({**args, "out": str(out_path)})],
        env=env, capture_output=True, text=True, timeout=timeout)
    result = None
    if out_path.exists():
        result = json.loads(out_path.read_text())
    if not expect_kill and proc.returncode != 0:
        raise AssertionError(
            f"crash-harness child {mode} rc={proc.returncode}\n"
            f"--- stdout ---\n{proc.stdout[-4000:]}\n"
            f"--- stderr ---\n{proc.stderr[-4000:]}")
    return proc.returncode, result


def run_kill_point(base: Path, mode: str, faults_spec: str,
                   workload_args: dict,
                   extra_env: dict | None = None) -> dict:
    """One matrix entry: crash run (must die by SIGKILL) + restart run
    (must recover). Returns the restart result plus recovery accounting;
    the caller compares ``result["snapshot"]`` against its reference.
    ``extra_env`` reaches both runs (the sharded kill point pins
    SD_SCAN_SHARDS in the crash AND the restart)."""
    data_dir = base / f"{mode}-{faults_spec.replace(':', '_')}"
    rc, _ = run_child(mode, data_dir, {**workload_args,
                                       "faults": faults_spec},
                      expect_kill=True, extra_env=extra_env)
    assert rc == -signal.SIGKILL, \
        f"kill point {mode}/{faults_spec}: child exited rc={rc}, " \
        f"expected SIGKILL (did the seam fire?)"
    t0 = time.perf_counter()
    rc2, result = run_child(mode, data_dir, workload_args,
                            extra_env=extra_env)
    assert rc2 == 0 and result is not None
    result["recovery_s"] = round(time.perf_counter() - t0, 3)
    result["kill_point"] = f"{mode}:{faults_spec}"
    return result


# ---------------------------------------------------------------------------
# child side
# ---------------------------------------------------------------------------


def snapshot_library(db) -> dict:
    """Structural snapshot: per-path cas ids, object membership (random
    object pub_ids normalized to their sorted member path-set), and the
    CRDT op order (same normalization as tests/test_pipeline._snapshot,
    JSON-safe so parent-side comparison is a dict equality)."""
    members: dict[str, list[str]] = {}
    kind_of: dict[str, int] = {}
    path_cas: dict[str, object] = {}
    for r in db.query(
            "SELECT fp.pub_id pid, fp.cas_id cas, o.pub_id opub, o.kind kind "
            "FROM file_path fp LEFT JOIN object o ON fp.object_id = o.id "
            "WHERE fp.is_dir = 0 ORDER BY fp.id"):
        path_cas[r["pid"]] = r["cas"]
        if r["opub"] is not None:
            members.setdefault(r["opub"], []).append(r["pid"])
            kind_of[r["opub"]] = r["kind"]

    def map_obj(opub):
        return ["object", sorted(members.get(opub, [])),
                kind_of.get(opub)]

    path_obj = {}
    for r in db.query(
            "SELECT fp.pub_id pid, o.pub_id opub FROM file_path fp "
            "JOIN object o ON fp.object_id = o.id"):
        path_obj[r["pid"]] = map_obj(r["opub"])

    ops = []
    for r in db.query(
            "SELECT model, record_id, kind, data FROM shared_operation "
            "ORDER BY rowid"):
        record = r["record_id"]
        data = json.loads(r["data"]) if r["data"] else None
        if r["model"] == "object":
            record = map_obj(record)
            if r["kind"] == "c" and isinstance(data, dict):
                data = {k: ("<ts>" if k == "date_created" else v)
                        for k, v in data.items()}
        if isinstance(data, dict) and "__ref__" in data:
            table, pub = data["__ref__"]
            data = {"__ref__": [table, map_obj(pub) if table == "object"
                                else pub]}
        ops.append([r["model"], record, r["kind"], repr(data)])

    # chunk manifests (ISSUE 18), keyed by pinned file_path pub_id so the
    # random object pub_ids never leak into the comparison; empty when the
    # run had SD_CHUNK_MANIFESTS off (the table always exists)
    manifests: dict[str, list] = {}
    for r in db.query(
            "SELECT fp.pub_id pid, cm.seq, cm.chunk_hash, cm.length "
            "FROM chunk_manifest cm JOIN object o ON cm.object_id = o.id "
            "JOIN file_path fp ON fp.object_id = o.id "
            "ORDER BY fp.pub_id, cm.seq"):
        manifests.setdefault(r["pid"], []).append(
            [r["seq"], r["chunk_hash"], r["length"]])
    return {"path_cas": path_cas, "path_obj": path_obj, "ops": ops,
            "manifests": manifests}


def oplog_rows(db) -> list:
    """The sync workload's byte-identity surface: the full op-log joined
    to origin instance pub_ids, in insert order."""
    return [list(r) for r in db.query(
        "SELECT so.id, so.timestamp, so.model, so.record_id, so.kind, "
        "so.data, i.pub_id FROM shared_operation so "
        "JOIN instance i ON so.instance_id = i.id ORDER BY so.rowid")]


def _peek_checkpoint(db_path: Path) -> dict:
    """Pre-boot look at the interrupted job rows (the child does this
    BEFORE Node() cold-resumes them)."""
    import sqlite3

    if not db_path.exists():
        return {}
    try:
        conn = sqlite3.connect(db_path, timeout=5.0)
        try:
            rows = conn.execute(
                "SELECT id, name, status, data FROM job").fetchall()
        finally:
            conn.close()
    except sqlite3.Error:
        return {}
    out = {}
    for jid, name, status, data in rows:
        step = None
        steps = None
        if data:
            try:
                blob = data.decode() if isinstance(data, bytes) else data
                state = json.loads(blob)
                step = state.get("step_number")
                steps = len(state.get("steps") or [])
            except (ValueError, AttributeError):
                pass
        out[jid] = {"name": name, "status": status,
                    "checkpoint_step": step, "steps_total": steps}
    return out


def _boot_report(node, lib) -> dict:
    from spacedrive_tpu import telemetry

    return {
        "quick_check_ok": lib.db.quick_check() == [],
        "integrity_ok": telemetry.value(
            "sd_boot_integrity_checks_total", outcome="ok"),
        "integrity_corrupt": telemetry.value(
            "sd_boot_integrity_checks_total", outcome="corrupt"),
        "wal_recovered": telemetry.value(
            "sd_boot_integrity_wal_recovered_total"),
        "cold_resumed": telemetry.value(
            "sd_recovery_cold_resumed_jobs_total"),
    }


def _seed_scan_library(node, lib_id: str, tree: str) -> "object":
    from spacedrive_tpu.models import FilePath, Location

    lib = node.libraries.create("crash-scan", lib_id=lib_id)
    loc_id = lib.db.insert(Location, {
        "pub_id": "loc-crash", "name": "crash", "path": tree,
        "date_created": "2026-01-01T00:00:00+00:00",
        "instance_id": lib.instance_id, "hasher": "cpu",
    })
    tree_path = Path(tree)
    rows = []
    for i, f in enumerate(sorted(tree_path.rglob("*.dat"))):
        rel = f.relative_to(tree_path)
        rows.append({
            "pub_id": f"fp-{i:04d}", "location_id": loc_id,
            "materialized_path": (f"/{rel.parent}/"
                                  if str(rel.parent) != "." else "/"),
            "name": f.stem, "extension": f.suffix.lstrip("."), "is_dir": 0,
            "size_in_bytes": f.stat().st_size,
            "date_created": "2026-01-01T00:00:00+00:00",
        })
    lib.db.insert_many(FilePath, rows)
    return lib, loc_id


def _child_scan(data_dir: Path, args: dict) -> dict:
    from spacedrive_tpu import faults
    from spacedrive_tpu.config import BackendFeature
    from spacedrive_tpu.node import Node
    from spacedrive_tpu.objects import file_identifier as fi

    lib_id = args.get("lib_id", SCAN_LIB_ID)
    fi.BATCH_SIZE = int(args.get("batch_size", SCAN_BATCH))
    pre = _peek_checkpoint(data_dir / "libraries" / f"{lib_id}.db")
    t0 = time.perf_counter()
    node = Node(data_dir, probe_accelerator=False, watch_locations=False)
    # sync emission must be a PERSISTED node feature (not a live-object
    # flag): the restart run's cold-resumed job starts committing during
    # Node() construction, long before this function could re-set a flag
    if BackendFeature.SYNC_EMIT_MESSAGES not in \
            node.config.get()["features"]:
        node.config.toggle_feature(BackendFeature.SYNC_EMIT_MESSAGES)
    fresh = lib_id not in {l.id for l in node.libraries.list()}
    if fresh:
        lib, loc_id = _seed_scan_library(node, lib_id, args["tree"])
        if args.get("faults"):
            faults.install(args["faults"], seed=0)
        node.jobs.spawn(lib, [fi.FileIdentifierJob(
            {"location_id": loc_id})])
    else:
        lib = node.libraries.get(lib_id)
        if args.get("faults"):
            faults.install(args["faults"], seed=0)
    # a restart run has nothing to spawn: cold resume already re-ingested
    # the interrupted job during Node() construction
    assert node.jobs.wait_idle(150), "scan did not finish"
    result = {
        "boot": _boot_report(node, lib),
        "pre_jobs": pre,
        "jobs": _peek_checkpoint(
            data_dir / "libraries" / f"{lib_id}.db"),
        "snapshot": snapshot_library(lib.db),
        "total_s": round(time.perf_counter() - t0, 3),
    }
    node.shutdown()
    return result


def _child_sync(data_dir: Path, args: dict) -> dict:
    from spacedrive_tpu import faults
    from spacedrive_tpu.node import Node
    from spacedrive_tpu.sync.ingest import Ingester

    lib_id = args.get("lib_id", SYNC_LIB_ID)
    window = int(args.get("window", SYNC_WINDOW))
    wire_ops = [json.loads(line) for line in
                Path(args["ops_file"]).read_text().splitlines()
                if line.strip()]
    wire_ops.sort(key=lambda op: (op["timestamp"], op["id"]))
    t0 = time.perf_counter()
    node = Node(data_dir, probe_accelerator=False, watch_locations=False)
    if lib_id not in {l.id for l in node.libraries.list()}:
        lib = node.libraries.create("crash-sync", lib_id=lib_id)
    else:
        lib = node.libraries.get(lib_id)
    boot = _boot_report(node, lib)
    if args.get("faults"):
        faults.install(args["faults"], seed=0)
    ingester = Ingester(lib)
    # floor-driven replay, exactly what a re-serving peer does: windows
    # are the ops above each instance's durable clock floor, in
    # (timestamp, id) order — a kill mid-window rolls that window back
    # and the un-advanced floors re-serve it on restart
    initial_pending = None
    while True:
        clocks = lib.sync.timestamps()
        pending = [op for op in wire_ops
                   if op["timestamp"] > clocks.get(op["instance"], 0)]
        if initial_pending is None:
            # on a restart this is the resume burden: every op the durable
            # floors do not yet cover (rolled-back + never-served)
            initial_pending = len(pending)
        if not pending:
            break
        ingester.receive(pending[:window])
        if not ingester.last_floor_advanced:
            raise RuntimeError("sync ingest made no progress")
    result = {
        "boot": boot,
        "initial_pending": initial_pending,
        "oplog": oplog_rows(lib.db),
        "total_s": round(time.perf_counter() - t0, 3),
    }
    node.shutdown()
    return result


def _tag_rows(db) -> list:
    return [list(r) for r in db.query(
        "SELECT pub_id, name FROM tag ORDER BY pub_id")]


def _seed_tags(lib, count: int, prefix: str) -> None:
    from spacedrive_tpu.models import Tag

    lib.db.insert_many(Tag, [
        {"pub_id": f"{prefix}-{i:04d}", "name": f"{prefix}{i}",
         "date_created": "2026-01-01T00:00:00+00:00"}
        for i in range(count)])


def _child_backup(data_dir: Path, args: dict) -> dict:
    """Backup workload, self-contained: a tag-seeded library (created on
    the first run), one do_backup (the kill target), and optional
    ``post_rows`` inserted AFTER the backup so a later restore test can
    distinguish live state from backup content."""
    from spacedrive_tpu import backups, faults
    from spacedrive_tpu.node import Node

    lib_id = args.get("lib_id", BK_LIB_ID)
    t0 = time.perf_counter()
    node = Node(data_dir, probe_accelerator=False, watch_locations=False)
    if lib_id not in {l.id for l in node.libraries.list()}:
        lib = node.libraries.create("crash-backup", lib_id=lib_id)
        _seed_tags(lib, int(args.get("rows", 400)), "bk")
    else:
        lib = node.libraries.get(lib_id)
    boot = _boot_report(node, lib)
    if args.get("faults"):
        faults.install(args["faults"], seed=0)
    backup_id = backups.do_backup(node, lib_id)
    if args.get("post_rows"):
        _seed_tags(lib, int(args["post_rows"]), "post")
    validity = {}
    for entry in (node.data_dir / "backups").glob("*.bkp"):
        try:
            backups.validate_backup(entry)
            validity[entry.name] = True
        except ValueError:
            validity[entry.name] = False
    result = {
        "boot": boot,
        "backup_id": backup_id,
        "backup_path": str(node.data_dir / "backups" / f"{backup_id}.bkp"),
        "backups": [b["id"] for b in backups.list_backups(node)],
        "validity": validity,
        "snapshot": {"tags": _tag_rows(lib.db)},
        "total_s": round(time.perf_counter() - t0, 3),
    }
    node.shutdown()
    return result


def _child_restore(data_dir: Path, args: dict) -> dict:
    """Restore workload against the backup-mode library: restore the named
    backup (kill seam inside restore_files — before any rename — proves
    the old library survives a mid-restore death)."""
    from spacedrive_tpu import backups, faults
    from spacedrive_tpu.node import Node

    lib_id = args.get("lib_id", BK_LIB_ID)
    t0 = time.perf_counter()
    node = Node(data_dir, probe_accelerator=False, watch_locations=False)
    boot = _boot_report(node, node.libraries.get(lib_id))
    if args.get("faults"):
        faults.install(args["faults"], seed=0)
    backups.do_restore(node, args["backup_path"])
    lib = node.libraries.get(lib_id)
    result = {
        "boot": boot,
        "snapshot": {"tags": _tag_rows(lib.db)},
        "total_s": round(time.perf_counter() - t0, 3),
    }
    node.shutdown()
    return result


#: the serve-worker kill point (ISSUE 11 satellite): ``skipN`` pins the
#: SIGKILL to the (N+1)th request a given pool worker serves — the seam
#: lives in the worker request loop, so the fault plan armed in the node
#: process is inherited across the fork and every respawned worker dies
#: again after another N requests (a standing worker-death storm)
SERVE_KILL = "serve_worker:kill:skip5"
SERVE_REQUESTS = 30
SERVE_WORKERS = 2


def _child_serve(data_dir: Path, args: dict) -> dict:
    """Serve-worker SIGKILL drill: a reader pool serves a fixed request
    sequence while the armed ``serve_worker:kill`` seam SIGKILLs workers
    mid-load AND a real identify scan runs in the node process. The
    CHILD process must survive (only workers die): every response must
    be byte-identical to the in-process result, the pool must end
    recovered, and the scan must complete untouched."""
    from spacedrive_tpu import faults
    from spacedrive_tpu.node import Node
    from spacedrive_tpu.objects import file_identifier as fi
    from spacedrive_tpu.server.pool import ReaderPool

    lib_id = args.get("lib_id", SCAN_LIB_ID)
    fi.BATCH_SIZE = int(args.get("batch_size", SCAN_BATCH))
    os.environ["SD_SERVE_HEALTH_S"] = "0.3"
    t0 = time.perf_counter()
    node = Node(data_dir, probe_accelerator=False, watch_locations=False)
    lib, loc_id = _seed_scan_library(node, lib_id, args["tree"])
    if args.get("faults"):
        faults.install(args["faults"], seed=0)
    pool = ReaderPool(node, workers=int(args.get("workers",
                                                 SERVE_WORKERS))).start()
    node.reader_pool = pool
    node.jobs.spawn(lib, [fi.FileIdentifierJob({"location_id": loc_id})])
    mismatches = 0
    request_errors = []
    n_requests = int(args.get("requests", SERVE_REQUESTS))
    for i in range(n_requests):
        arg = {"take": 10, "skip": (i % 5) * 10}
        try:
            via_pool = node.router.resolve("search.paths", arg, lib_id)
            try:
                pool.set_enabled(False)
                in_proc = node.router.resolve("search.paths", arg, lib_id)
            finally:
                # a raise here must not leave the pool bypassed for the
                # rest of the drill — the kill seam would stop firing
                pool.set_enabled(True)
            # compare shape-stable columns: the scan is live, so cas_id
            # columns legitimately change between the two reads
            key = [(it["pub_id"], it["name"]) for it in via_pool["items"]]
            ref = [(it["pub_id"], it["name"]) for it in in_proc["items"]]
            if key != ref:
                mismatches += 1
        except Exception as e:
            request_errors.append(repr(e))
    assert node.jobs.wait_idle(150), "scan did not finish under worker kills"
    # the LAST request may have killed its worker microseconds ago —
    # "recovers within the health-check interval" is the contract, so
    # give the supervisor a few intervals before reading final strength
    deadline = time.perf_counter() + 3.0
    status = pool.status()
    while status["alive"] < status["workers"] \
            and time.perf_counter() < deadline:
        time.sleep(0.05)
        status = pool.status()
    identified = lib.db.query(
        "SELECT COUNT(*) c FROM file_path WHERE cas_id IS NOT NULL")[0]["c"]
    total = lib.db.query(
        "SELECT COUNT(*) c FROM file_path WHERE is_dir = 0")[0]["c"]
    result = {
        "requests": n_requests,
        "request_errors": request_errors,
        "mismatches": mismatches,
        "worker_restarts": status["restarts"],
        "failovers": status["failovers"],
        "pool_alive": status["alive"],
        "pool_workers": status["workers"],
        "scan_identified": identified,
        "scan_total": total,
        "snapshot": snapshot_library(lib.db),
        "total_s": round(time.perf_counter() - t0, 3),
    }
    pool.stop()
    node.reader_pool = None
    node.shutdown()
    return result


#: the replica-serve kill point (ISSUE 19 satellite): the child IS a
#: replica node serving watermark-gated queries through serve_query's
#: in-process path, where the ``replica_serve`` seam firing ``kill`` is
#: the WHOLE replica node dying mid-query (over real p2p the client's
#: ladder eats the dropped connection; here the parent eats -SIGKILL)
REPLICA_LIB_ID = "c0a5c0de-0000-4000-8000-00000000dddd"
REPLICA_KILL = "replica_serve:kill:skip3"
REPLICA_SERVES = 8


def _child_replica(data_dir: Path, args: dict) -> dict:
    """Replica-node SIGKILL drill: mirror a deterministic op stream (the
    client's writes), then serve a fixed watermark-gated query sequence
    in-process while the armed ``replica_serve:kill`` seam dies mid-
    query. The restart must boot clean (WAL recovery), be re-eligible
    straight from its durable floors (no re-mirror needed — every
    applied window committed with the floors that cover it), and serve
    the exact bytes the library's in-process handler encodes."""
    from spacedrive_tpu import faults
    from spacedrive_tpu.node import Node
    from spacedrive_tpu.server.replica import (covers, encode_reply,
                                               serve_query)
    from spacedrive_tpu.sync.ingest import Ingester

    lib_id = args.get("lib_id", REPLICA_LIB_ID)
    window = int(args.get("window", SYNC_WINDOW))
    wire_ops = [json.loads(line) for line in
                Path(args["ops_file"]).read_text().splitlines()
                if line.strip()]
    wire_ops.sort(key=lambda op: (op["timestamp"], op["id"]))
    # the client's last-write watermark: every origin floor in the stream
    require: dict[str, int] = {}
    for op in wire_ops:
        if op["timestamp"] > require.get(op["instance"], 0):
            require[op["instance"]] = op["timestamp"]
    t0 = time.perf_counter()
    node = Node(data_dir, probe_accelerator=False, watch_locations=False)
    if lib_id not in {l.id for l in node.libraries.list()}:
        lib = node.libraries.create("crash-replica", lib_id=lib_id)
    else:
        lib = node.libraries.get(lib_id)
    boot = _boot_report(node, lib)
    payload = {"library_id": lib_id, "key": "tags.list", "arg": None,
               "require": require}
    # eligibility straight off the durable floors, BEFORE any mirroring:
    # a fresh replica must refuse (not_eligible, never a partial page); a
    # restarted one must already cover — its floors committed with the
    # windows that advanced them
    pre = serve_query(node, dict(payload), peer="crash-client")
    eligible_at_boot = bool(pre.get("ok"))
    ingester = Ingester(lib, peer="crash-client")
    while True:
        clocks = lib.sync.timestamps()
        pending = [op for op in wire_ops
                   if op["timestamp"] > clocks.get(op["instance"], 0)]
        if not pending:
            break
        ingester.receive(pending[:window])
        if not ingester.last_floor_advanced:
            raise RuntimeError("replica mirror made no progress")
    if args.get("faults"):
        faults.install(args["faults"], seed=0)
    serves_ok = []
    for _ in range(int(args.get("serves", REPLICA_SERVES))):
        reply = serve_query(node, dict(payload), peer="crash-client")
        serves_ok.append(bool(reply.get("ok")))
    proc = node.router.procedures["tags.list"]
    reference = encode_reply(proc.fn(node, lib, None))
    final = serve_query(node, dict(payload), peer="crash-client")
    result = {
        "boot": boot,
        "eligible_at_boot": eligible_at_boot,
        "covers": covers(lib.sync.timestamps(), require),
        "serves_ok": serves_ok,
        "identical": bool(final.get("ok"))
        and final.get("raw") == reference,
        "tag_count": lib.db.query(
            "SELECT count(*) AS c FROM tag")[0]["c"],
        "oplog": oplog_rows(lib.db),
        "total_s": round(time.perf_counter() - t0, 3),
    }
    node.shutdown()
    return result


def _child_inspect(data_dir: Path, args: dict) -> dict:
    """Boot + report only (no workload): how the matrix asserts that a
    crashed-and-not-yet-recovered dir still boots clean, and how the
    restore-kill test reads the surviving library."""
    from spacedrive_tpu.node import Node

    lib_id = args["lib_id"]
    t0 = time.perf_counter()
    node = Node(data_dir, probe_accelerator=False, watch_locations=False)
    lib = node.libraries.get(lib_id)
    assert node.jobs.wait_idle(150)
    result = {
        "boot": _boot_report(node, lib),
        "snapshot": {"tags": _tag_rows(lib.db)},
        "total_s": round(time.perf_counter() - t0, 3),
    }
    node.shutdown()
    return result


CHILD_MODES = {
    "scan": _child_scan,
    "sync": _child_sync,
    "backup": _child_backup,
    "restore": _child_restore,
    "serve": _child_serve,
    "replica": _child_replica,
    "inspect": _child_inspect,
}


def _child_main() -> int:
    mode, data_dir, raw_args = sys.argv[1], Path(sys.argv[2]), sys.argv[3]
    sys.path.insert(0, str(REPO_ROOT))
    args = json.loads(raw_args)
    result = CHILD_MODES[mode](data_dir, args)
    out = args.get("out")
    if out:
        Path(out).write_text(json.dumps(result))
    else:
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(_child_main())
