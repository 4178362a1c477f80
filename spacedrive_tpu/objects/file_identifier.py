"""FileIdentifierJob: assign cas_ids and dedup into Objects.

Semantics from core/src/object/file_identifier/{mod,file_identifier_job}.rs:
orphans are file_paths with no object (not directories); each step takes the
next cursor-paginated chunk (id > cursor, file_identifier_job.rs:245-268),
computes cas_ids (empty files get none, mod.rs:80-88), writes them, links
paths to existing objects sharing the cas_id, and batch-creates objects for
the rest (:136-335). ObjectKind comes from the extension registry.

TPU-first deviation: the chunk is the device batch. The reference hashes 100
files per step with per-file tokio tasks; here a step gathers sampled messages
for BATCH_SIZE files and hashes them in one fused device call via the
location's hasher backend. Within-batch duplicates collapse to one object
(the reference creates one object per path and converges on later scans).

Each step is split into the three streaming-pipeline stages
(pipeline/executor.py): ``pipeline_page`` (cursor SELECT + sample-message
gather, read-only), ``pipeline_process`` (the hash batch), and
``pipeline_commit`` (the transaction + CRDT ops + cursor advance). The
sequential path runs the same three callables back-to-back, so pipelined and
sequential runs produce byte-identical DB state and op order
(tests/test_pipeline.py). The committer also warm-starts media processing:
prefixes whose identified rows carry thumbnailable extensions are handed to
LocationsActor.media_warm_start, which spawns media-lane jobs that overlap
the rest of the identify run instead of waiting for it to finish.
"""

from __future__ import annotations

import logging
import os
import uuid
from typing import Any

from .. import faults, telemetry
from ..jobs import EarlyFinish, JobError, StatefulJob, StepResult, WorkerContext
from ..recovery import is_disk_full, note_disk_full
from ..models import FilePath, Location, Object, ObjectMinhash, utc_now
from ..sync.crdt import ref
from .cas import read_sampled_batch_fast as read_sampled_batch
from .hasher import get_hasher
# imported unconditionally so the sd_chunk_* telemetry families exist on
# /metrics (and in observability.md's drift gate) even with manifests off
from . import manifest as chunk_manifest

_QUARANTINED = telemetry.counter(
    "sd_quarantined_files_total",
    "per-item failures quarantined by the identifier")
_RECOVERED = telemetry.counter(
    "sd_recovered_batches_total",
    "hash batches re-dispatched on the CPU ladder after a device failure")
_SCAN_RATE = telemetry.gauge(
    "sd_scan_files_per_sec",
    "files/s of the most recent completed identify pass")

_THUMBABLE_EXTS: list = []


def _thumbable_exts() -> set[str]:
    """Memoized thumbnailable-extension set (media/processor.py) — consulted
    once per committed batch on the scan hot path."""
    if not _THUMBABLE_EXTS:
        from .media.processor import _thumbable_extensions

        _THUMBABLE_EXTS.append(_thumbable_extensions())
    return _THUMBABLE_EXTS[0]


def ref_obj(pub_id: str):
    """object FK crossing the sync wire as a pub_id reference (crdt.py)."""
    return ref(Object.TABLE, pub_id)

logger = logging.getLogger(__name__)

#: files per step = device batch size (reference CHUNK_SIZE=100 is a CPU
#: tuning; the TPU kernel amortizes over thousands of lanes)
BATCH_SIZE = 1024

#: adaptive page-size clamps (ISSUE 17): pages shrink toward finer
#: pipelining when the hash stage dominates and grow to amortize per-page
#: fixed costs when gather or commit does
ADAPT_MIN_BATCH = 256
ADAPT_MAX_BATCH = 4096

_BATCH_GAUGE = telemetry.gauge(
    "sd_scan_batch_size",
    "files per scan page after adaptive sizing (the fixed BATCH_SIZE "
    "when adaptation is pinned off)")


def _env_batch_pin() -> int | None:
    """Explicit page-size pin (``SD_SCAN_BATCH``) — turns adaptation off
    and sizes every page to exactly this many files."""
    raw = os.environ.get("SD_SCAN_BATCH", "").strip()
    if raw:
        try:
            return max(1, int(raw))
        except ValueError:
            return None
    return None


def _adaptive_batching() -> bool:
    """Adaptive page sizing is live only at the stock configuration: a
    monkeypatched ``BATCH_SIZE`` (tests pin page boundaries), an explicit
    ``SD_SCAN_BATCH``, or ``SD_SCAN_ADAPT=0`` all mean FIXED pages —
    pipelined page boundaries then match the sequential schedule exactly,
    which is what the byte-identity matrices assert."""
    if BATCH_SIZE != 1024 or _env_batch_pin() is not None:
        return False
    return os.environ.get("SD_SCAN_ADAPT", "1").lower() not in (
        "0", "false", "off")


def _page_limit(scratch: dict) -> int:
    """Files in the next page. With adaptation live, sizes from the
    executor's measured stage balance (``scratch['stage_shares']``,
    target: no stage above 60% of the pipeline wall): a dominant hash
    stage shrinks pages (finer overlap, smaller device batches feed the
    double-buffer sooner), a dominant gather or commit stage grows them
    (amortize the per-page SELECT / txn / uring-round fixed costs), and a
    balanced pipeline drifts back toward the static default. The scratch
    dict is pipeline-local and only the prefetch/split thread touches it."""
    pin = _env_batch_pin()
    if pin is not None:
        return pin
    if not _adaptive_batching():
        return BATCH_SIZE
    cur = int(scratch.get("batch_size") or BATCH_SIZE)
    shares = scratch.get("stage_shares")
    if shares:
        dominant = max(shares, key=shares.get)
        if shares[dominant] > 0.6:
            if dominant == "hash":
                cur = max(cur * 3 // 4, ADAPT_MIN_BATCH)
            else:
                cur = min(cur * 3 // 2, ADAPT_MAX_BATCH)
        else:
            cur += (BATCH_SIZE - cur) // 4
    scratch["batch_size"] = cur
    _BATCH_GAUGE.set(cur)
    return cur


def _orphan_where(location_id: int, sub_path: str | None) -> tuple[str, list]:
    sql = ('object_id IS NULL AND is_dir = 0 AND location_id = ? AND name != ""')
    params: list[Any] = [location_id]
    if sub_path:
        sql += " AND materialized_path LIKE ?"
        params.append(f"/{sub_path.strip('/')}/%")
    return sql, params


class FileIdentifierJob(StatefulJob):
    NAME = "file_identifier"
    IS_BATCHED = True

    def init(self, ctx: WorkerContext):
        db = ctx.library.db
        location_id = self.init_args["location_id"]
        location = db.find_one(Location, {"id": location_id})
        if location is None:
            raise JobError(f"location {location_id} not found")
        where, params = _orphan_where(location_id, self.init_args.get("sub_path"))
        count = db.query(f"SELECT COUNT(*) AS n FROM file_path WHERE {where}", params)[0]["n"]
        if count == 0:
            raise EarlyFinish("Found no orphan file paths to process")
        logger.info("Found %d orphan file paths", count)
        # plan steps from the EFFECTIVE page size: an SD_SCAN_BATCH pin
        # below the default would otherwise exhaust init's step budget
        # with orphans left over (the executor only grows the step ledger
        # for adaptive runs, and pinned runs are exact by definition)
        page = _env_batch_pin() or BATCH_SIZE
        steps = [{"kind": "identify"} for _ in range(-(-count // page))]
        data = {"location_id": location_id, "location_path": location["path"],
                # hybrid probes both engines on the first full page and
                # routes each batch to the faster, so a production scan
                # never takes a known-losing path on hosts where transfers
                # are slow
                "hasher": location.get("hasher") or "hybrid", "cursor": 0,
                "sub_path": self.init_args.get("sub_path"),
                "preview_media":
                    location.get("generate_preview_media") is not False}
        return data, steps, {"total_orphan_paths": count, "created_objects": 0,
                             "linked_objects": 0, "hash_time": 0.0,
                             "quarantined_files": 0, "recovered_batches": 0,
                             "chunked_files": 0, "chunk_quarantined": 0}

    def pipeline_spec(self):
        from ..pipeline import PipelineSpec

        return PipelineSpec(page=self.pipeline_page,
                            process=self.pipeline_process,
                            commit=self.pipeline_commit,
                            split=self.pipeline_page_split,
                            shard=self.pipeline_page_shard,
                            merge=self.pipeline_page_merge,
                            adaptive=_adaptive_batching())

    def execute_step(self, ctx: WorkerContext, data: dict, step: dict,
                     step_number: int) -> StepResult:
        # the sequential path IS the pipeline, stages run back-to-back —
        # one implementation, two schedules
        scratch = {"cursor": data["cursor"]}
        batch = self.pipeline_page(ctx, data, scratch)
        if batch is None:
            return StepResult()
        return self.pipeline_commit(ctx, data,
                                    self.pipeline_process(ctx, data, batch))

    # -- stage 1: prefetch (DB reads + file I/O only) ------------------------
    def pipeline_page(self, ctx: WorkerContext, data: dict,
                      scratch: dict) -> dict | None:
        db = ctx.library.db
        cursor = scratch.get("cursor", data["cursor"])
        where, params = _orphan_where(data["location_id"], data.get("sub_path"))
        # only the columns this step consumes, undecoded: size_in_bytes and
        # is_dir are ints, date_created stays an ISO string (Model.encode
        # passes strings through on re-insert) — a SELECT * + full
        # decode_row costs ~15% of the whole identify pass at 100k files.
        # The speculative cursor rides in ``scratch``: rows at id <= cursor
        # are untouched by later commits, so speculative pages see exactly
        # the row sets the sequential loop would
        with telemetry.span(getattr(ctx, "trace", None), "identifier.select"):
            rows = [dict(r) for r in db.query(
                f"SELECT id, pub_id, name, extension, materialized_path, "
                f"is_dir, size_in_bytes, date_created FROM file_path "
                f"WHERE {where} AND id > ? ORDER BY id LIMIT ?",
                params + [cursor, _page_limit(scratch)],
            )]
        if not rows:
            return None
        scratch["cursor"] = rows[-1]["id"]
        hashable, empty, messages, gather_s = \
            self._gather_rows(ctx, data, rows)
        return {"cursor": rows[-1]["id"], "hashable": hashable, "empty": empty,
                "messages": messages, "gather_s": gather_s}

    def _gather_rows(self, ctx: WorkerContext, data: dict,
                     rows: list[dict]) -> tuple[list, list, list, float]:
        """SELECT'd page (or page-slice) rows → ``(hashable, empty,
        messages, gather_s)``: the size split, the fused sample gather and
        the magic-head attach — shared verbatim by the whole-page and
        sharded-slice prefetch paths, so a merged page is byte-identical
        to a sequential one by construction."""
        hashable, empty = [], []
        for row in rows:
            if (row["size_in_bytes"] or 0) > 0:
                hashable.append(row)
            else:
                empty.append(row)  # "We can't do shit with empty files"

        location_path = data["location_path"]
        # ad-hoc timing goes through spans (telemetry-discipline): the
        # gather duration lands in the report via the span, nests under
        # pipeline.page (or the shard's pipeline.gather) in the job trace,
        # and still measures when telemetry is off (bare-timer degradation)
        paths = [_abs_path(location_path, r) for r in hashable]
        with telemetry.span(getattr(ctx, "trace", None), "identifier.gather",
                            files=len(hashable)) as gather_sp:
            messages = read_sampled_batch(
                paths, [r["size_in_bytes"] for r in hashable])
            gather_sp.set(bytes=sum(len(m) for m in messages
                                    if not isinstance(m, Exception)))
            if chunk_manifest.manifests_enabled():
                # manifest payloads ride the same gather (small files reuse
                # the cas message body byte-for-byte): attached per row, so
                # shard-merge concatenation carries them automatically
                chunk_manifest.pipeline_chunk_gather(paths, hashable, messages)
        # the cas message is size_le_8 ‖ header ‖ … — its head IS the file's
        # first bytes, so magic-byte kind resolution rides the gather for
        # free instead of re-opening every file on the commit thread (the
        # single hottest commit cost at 100k files: one open+read per object)
        from .magic import HEADER_LEN

        for row, msg in zip(hashable, messages):
            row["_kind_head"] = (None if isinstance(msg, Exception)
                                 else bytes(msg[8:8 + HEADER_LEN]))
        for row in empty:
            row["_kind_head"] = b""  # what _read_head returns for empty files
        return hashable, empty, messages, gather_sp.duration_s

    # -- stage 1, sharded (ISSUE 17): split → parallel slices → merge --------
    def pipeline_page_split(self, ctx: WorkerContext, data: dict,
                            scratch: dict) -> dict | None:
        """Split-coordinator half of the page stage: one id-only cursor
        SELECT, chopped into contiguous id-range slices (one per gather
        shard). Contiguity in strict id order is the byte-identity
        argument: the slices' row sets concatenate back into exactly the
        row set — in exactly the order — the unsharded SELECT returns for
        the same cursor window, and commits only ever touch rows at
        ``id <=`` an already-committed cursor, so slice SELECTs re-running
        the predicate later cannot see different rows."""
        db = ctx.library.db
        cursor = scratch.get("cursor", data["cursor"])
        where, params = _orphan_where(data["location_id"],
                                      data.get("sub_path"))
        with telemetry.span(getattr(ctx, "trace", None), "identifier.select"):
            ids = [r["id"] for r in db.query(
                f"SELECT id FROM file_path WHERE {where} AND id > ? "
                f"ORDER BY id LIMIT ?",
                params + [cursor, _page_limit(scratch)])]
        if not ids:
            return None
        scratch["cursor"] = ids[-1]
        shards = max(1, int(scratch.get("shards") or 1))
        per = -(-len(ids) // shards)
        parts = [{"lo": ids[lo], "hi": ids[min(lo + per, len(ids)) - 1]}
                 for lo in range(0, len(ids), per)]
        return {"cursor": ids[-1], "parts": parts}

    def pipeline_page_shard(self, ctx: WorkerContext, data: dict,
                            part: dict) -> dict:
        """One slice's row SELECT + sample gather — the same read-only
        contract as ``pipeline_page``, safe to run concurrently with the
        other slices (reads serialize on the shared reader connection;
        the fused native gather releases the GIL for the whole slice)."""
        db = ctx.library.db
        where, params = _orphan_where(data["location_id"],
                                      data.get("sub_path"))
        with telemetry.span(getattr(ctx, "trace", None), "identifier.select"):
            rows = [dict(r) for r in db.query(
                f"SELECT id, pub_id, name, extension, materialized_path, "
                f"is_dir, size_in_bytes, date_created FROM file_path "
                f"WHERE {where} AND id >= ? AND id <= ? ORDER BY id",
                params + [part["lo"], part["hi"]])]
        hashable, empty, messages, gather_s = \
            self._gather_rows(ctx, data, rows)
        return {"hashable": hashable, "empty": empty, "messages": messages,
                "gather_s": gather_s}

    def pipeline_page_merge(self, ctx: WorkerContext, data: dict,
                            header: dict, results: list[dict]) -> dict:
        """Reassemble the slice results (slice order == id order) into
        exactly the payload ``pipeline_page`` returns. Per-list
        concatenation preserves the hashable↔messages alignment because
        each slice's lists are aligned and slices are disjoint id ranges
        in page order. ``gather_s`` is the MAX slice gather — the page's
        gather *wall*, the number shard parallelism is supposed to
        shrink (the per-slice sum would hide the win)."""
        hashable: list = []
        empty: list = []
        messages: list = []
        gather_s = 0.0
        for res in results:
            hashable.extend(res["hashable"])
            empty.extend(res["empty"])
            messages.extend(res["messages"])
            gather_s = max(gather_s, res["gather_s"])
        return {"cursor": header["cursor"], "hashable": hashable,
                "empty": empty, "messages": messages, "gather_s": gather_s}

    # -- stage 2: dispatch (device/CPU compute) ------------------------------
    def pipeline_process(self, ctx: WorkerContext, data: dict,
                         batch: dict) -> dict:
        hasher = get_hasher(data.get("hasher"), node=ctx.node)
        hashable = batch["hashable"]
        #: MinHash signatures a device backend computed from the rows it
        #: hashed, by index into ``hashable`` (the dedup detector reads them)
        signatures: dict = {}
        with telemetry.span(getattr(ctx, "trace", None), "identifier.hash",
                            files=len(hashable)) as hash_sp:
            try:
                faults.inject("hash")
                if getattr(hasher, "USES_DEVICE", False):
                    cas_results = hasher.hash_gathered(batch["messages"],
                                                       signatures)
                else:
                    cas_results = hasher.hash_gathered(batch["messages"])
            except Exception as e:  # noqa: BLE001 — degradation ladder below
                # mid-batch hasher failure (device wedge, dying backend):
                # this batch re-dispatches on the native CPU path over the
                # already-gathered messages (byte-identical cas_ids), the
                # hybrid verdict flips so later batches skip the dead
                # engine, and the pipeline keeps moving. A CPU-path failure
                # here raises through to stage supervision — there is no
                # rung below the oracle.
                logger.exception("hash dispatch failed mid-batch; "
                                 "re-dispatching batch on the native CPU "
                                 "path")
                degrade = getattr(hasher, "degrade_device", None)
                if degrade is not None:
                    degrade(repr(e))
                cas_results = get_hasher("cpu").hash_gathered(
                    batch["messages"])
                batch["recovered_error"] = repr(e)
                _RECOVERED.inc()
        batch["cas_results"] = cas_results
        batch["signatures"] = signatures
        batch["hash_s"] = hash_sp.duration_s
        batch["messages"] = None  # the gather buffers are dead weight now
        if chunk_manifest.manifests_enabled():
            # the manifest stage rides the same dispatch thread behind its
            # own router; failures degrade/quarantine inside, never raise
            chunk_manifest.pipeline_chunk_process(
                batch["hashable"], trace=getattr(ctx, "trace", None))
        return batch

    # -- stage 3: commit (the only stage that writes) ------------------------
    def pipeline_commit(self, ctx: WorkerContext, data: dict,
                        batch: dict) -> StepResult:
        db = ctx.library.db
        location_path = data["location_path"]
        hashable, empty = batch["hashable"], batch["empty"]
        errors: list[str] = []

        # per-item quarantine: vanished/permission-denied/truncated files
        # (post-retry) are excluded from this batch's writes and recorded as
        # soft errors — the scan completes COMPLETED_WITH_ERRORS instead of
        # dying, and the next scan retries them as still-orphan paths
        identified: list[tuple[dict, str]] = []
        quarantined = 0
        for row, cas in zip(hashable, batch["cas_results"]):
            if isinstance(cas, Exception):
                errors.append(
                    f"quarantined {_abs_path(location_path, row)}: {cas!r}")
                quarantined += 1
                if is_disk_full(cas):
                    # ENOSPC during the gather (a full disk can fail reads
                    # through mmap'd page allocation and vanished temp
                    # space): degrade per-item like every other quarantine,
                    # but light up the one disk-full series operators watch
                    note_disk_full("gather")
            else:
                identified.append((row, cas))
        if quarantined:
            _QUARANTINED.inc(quarantined)
        chunk_errors: list[str] = []
        if chunk_manifest.manifests_enabled():
            # per-item manifest quarantine: the file still identifies, only
            # its manifest is skipped (next scan rebuilds it)
            chunk_errors = chunk_manifest.quarantine_errors(
                hashable, location_path)
            errors.extend(chunk_errors)
        if batch.get("recovered_error"):
            errors.append(f"hash batch recovered on native CPU path after: "
                          f"{batch['recovered_error']}")

        sync = getattr(ctx.library, "sync", None)
        emit = sync is not None and getattr(sync, "emit_messages", False)
        ops = []  # CRDT ops logged atomically with the writes (write_ops semantics)

        with db.transaction():
            # 1. write cas_ids (one executemany: this loop runs for every
            # file in the location)
            db.executemany_noted(
                "UPDATE file_path SET cas_id = ? WHERE id = ?",
                [(cas, row["id"]) for row, cas in identified],
                "file_path", (row["id"] for row, _cas in identified))
            if emit:
                for row, cas in identified:
                    ops.append(sync.shared_update(FilePath, row["pub_id"], "cas_id", cas))

            # 2. link to existing objects owning these cas_ids
            cas_ids = sorted({cas for _, cas in identified})
            existing: dict[str, tuple[int, str]] = {}
            for chunk_start in range(0, len(cas_ids), 500):
                chunk = cas_ids[chunk_start : chunk_start + 500]
                marks = ",".join("?" for _ in chunk)
                for r in db.query(
                    f"SELECT fp.cas_id AS cas_id, o.id AS oid, o.pub_id AS opub "
                    f"FROM file_path fp JOIN object o ON fp.object_id = o.id "
                    f"WHERE fp.cas_id IN ({marks})", chunk):
                    existing.setdefault(r["cas_id"], (r["oid"], r["opub"]))

            linked = 0
            link_rows: list[tuple[int, int]] = []  # (object_id, file_path_id)
            need_object: dict[str, list[dict]] = {}
            for row, cas in identified:
                if cas in existing:
                    oid, opub = existing[cas]
                    link_rows.append((oid, row["id"]))
                    if emit:
                        ops.append(sync.shared_update(
                            FilePath, row["pub_id"], "object_id", ref_obj(opub)))
                    linked += 1
                else:
                    need_object.setdefault(cas, []).append(row)

            # 3. create one object per unique new cas_id (+ one per empty
            # file) — one executemany then one pub_id->id readback instead
            # of a round-trip per object (this loop also runs for every
            # file in the location)
            creations: list[tuple[dict, list[dict]]] = \
                [(members[0], members) for members in need_object.values()] \
                + [(row, [row]) for row in empty]
            created = len(creations)
            if creations:
                obj_rows = [self._object_row(rep, data["location_path"])
                            for rep, _members in creations]
                db.insert_many(Object, obj_rows)
                oid_of: dict[str, int] = {}
                for start in range(0, len(obj_rows), 500):
                    chunk = obj_rows[start : start + 500]
                    marks = ",".join("?" * len(chunk))
                    for r in db.query(
                            f"SELECT id, pub_id FROM object "
                            f"WHERE pub_id IN ({marks})",
                            [c["pub_id"] for c in chunk]):
                        oid_of[r["pub_id"]] = r["id"]
                for obj, (_rep, members) in zip(obj_rows, creations):
                    oid, opub = oid_of[obj["pub_id"]], obj["pub_id"]
                    if emit:
                        ops.append(sync.shared_create(Object, opub, {
                            "kind": obj["kind"],
                            "date_created": utc_now().isoformat(),
                        }))
                    for row in members:
                        link_rows.append((oid, row["id"]))
                        if emit:
                            ops.append(sync.shared_update(
                                FilePath, row["pub_id"], "object_id",
                                ref_obj(opub)))
            db.executemany_noted(
                "UPDATE file_path SET object_id = ? WHERE id = ?",
                link_rows, "file_path", (fp_id for _oid, fp_id in link_rows))
            oid_by_fp = {fp_id: oid for oid, fp_id in link_rows}

            # 4. one MinHash signature per sampled object; an object that
            # already has one keeps it (equal cas_id, equal signature)
            sig_rows = [{"object_id": oid_by_fp[hashable[k]["id"]],
                         "signature": sig.astype("<u4").tobytes()}
                        for k, sig in (batch.get("signatures") or {}).items()
                        if hashable[k]["id"] in oid_by_fp]
            db.insert_many(ObjectMinhash, sig_rows, or_ignore=True)

            # 5. persist chunk manifests (opt-in) in the SAME transaction —
            # a crash between the identify writes and the manifest rows can
            # never surface (the kill matrix pins a SIGKILL here)
            chunked = 0
            if chunk_manifest.manifests_enabled():
                faults.inject("manifest_commit")
                items: list[tuple[int, list]] = []
                seen_oids: set[int] = set()
                for row, _cas in identified:
                    m = row.get("_chunk_manifest")
                    oid = oid_by_fp.get(row["id"])
                    if m is None or oid is None or oid in seen_oids:
                        continue  # within-batch cas-duplicates: one copy wins
                    seen_oids.add(oid)
                    items.append((oid, m))
                chunked = chunk_manifest.commit_manifest_rows(db, items)
            if emit and ops:
                sync.log_ops(ops)
        # the checkpoint cursor advances ONLY here, after the transaction
        # committed — a pause/crash resumes at the last committed batch
        data["cursor"] = batch["cursor"]

        # everything below is BEST-EFFORT tail work: the batch is durable,
        # so nothing past this point may raise — the committer's retry
        # (pipeline/executor.COMMIT_RETRY) assumes an exception out of
        # pipeline_commit means the transaction did NOT land, and a re-run
        # here would re-log every CRDT op of the batch
        if emit and ops:
            try:
                sync.created()
            except Exception:
                logger.exception("sync.created broadcast failed (peers "
                                 "will pull on their next round)")
        try:
            self._media_warm_start(ctx, data, identified)
            ctx.progress(message=f"identified {len(identified)} files "
                                 f"({created} new objects, {linked} linked)")
        except Exception:
            logger.exception("post-commit warm-start/progress failed "
                             "(batch is committed; continuing)")
        return StepResult(metadata={"created_objects": created,
                                    "linked_objects": linked,
                                    "hash_time": batch["hash_s"],
                                    "gather_s": batch["gather_s"],
                                    "quarantined_files": quarantined,
                                    "recovered_batches":
                                        1 if batch.get("recovered_error")
                                        else 0,
                                    "chunked_files": chunked,
                                    "chunk_quarantined": len(chunk_errors)},
                          errors=errors)

    def _media_warm_start(self, ctx: WorkerContext, data: dict,
                          identified: list[tuple[dict, str]]) -> None:
        """Hand freshly identified thumbnailable prefixes to the locations
        actor so media-lane jobs start while this job is still hashing the
        rest of the location. Best-effort: the chained whole-location media
        job still sweeps up stragglers (existing thumbnails are skipped)."""
        node = getattr(ctx, "node", None)
        actor = getattr(node, "locations", None)
        if actor is None or not data.get("preview_media", True):
            return
        exts = _thumbable_exts()
        prefixes = set()
        for row, _cas in identified:
            if (row.get("extension") or "").lower() in exts:
                mp = (row.get("materialized_path") or "/").strip("/")
                if mp:
                    prefixes.add(mp.split("/")[0])
        if prefixes:
            actor.media_warm_start(ctx.library, data["location_id"], prefixes)

    def _object_row(self, row: dict, location_path: str | None) -> dict:
        from .magic import resolve_kind

        # magic-byte disambiguation for conflicting/unknown extensions
        # (file_identifier/mod.rs:75 → magic.rs); the head bytes came with
        # the gather (``_kind_head``) so this never touches the disk — the
        # path fallback only fires for rows that skipped the page stage
        kind = resolve_kind(
            row.get("extension"),
            _abs_path(location_path, row) if location_path else None,
            bool(row.get("is_dir")),
            head=row.get("_kind_head"))
        return {"pub_id": str(uuid.uuid4()), "kind": kind,
                "date_created": row.get("date_created") or utc_now()}

    def finalize(self, ctx: WorkerContext, data: dict, run_metadata: dict):
        ctx.library.emit("invalidate_query", {"key": "search.paths"})
        ctx.library.emit("invalidate_query", {"key": "search.objects"})
        # the operator's headline number: identify throughput of the pass
        # that just finished (elapsed read off the job's root span).
        # Un-resumed passes only: a CROSS-PROCESS resume starts a fresh
        # trace whose elapsed covers just the final run, and dividing the
        # checkpoint-accumulated file total by it would inflate the gauge
        # (an in-process resume continues the original trace, but the gate
        # keys on the checkpoint either way — conservative, never bogus)
        trace = getattr(ctx, "trace", None)
        total = run_metadata.get("total_orphan_paths") or 0
        dyn = getattr(getattr(ctx, "_worker", None), "dyn_job", None)
        resumed = dyn is not None and getattr(dyn, "was_resumed", False)
        if trace is not None and total and not resumed:
            elapsed = trace.elapsed_s()
            if elapsed > 0:
                _SCAN_RATE.set(round(total / elapsed, 1))
        if trace is not None:
            buckets = _bucket_tallies(trace)
            if buckets:
                run_metadata["hash_buckets"] = buckets
        logger.info("file_identifier finished: %s", run_metadata)
        return run_metadata


def _bucket_tallies(trace) -> dict[str, dict[str, int]]:
    """``{cap: {calls, files, lanes, payload_bytes}}`` summed over this
    job's ``hash.bucket`` spans: its own device bucket calls, apart from
    what other jobs and the H_HASH service add to the process-wide
    ``sd_hash_bucket_*`` counters."""
    out: dict[str, dict[str, int]] = {}
    for rec in trace.records():
        if rec["name"] != "hash.bucket":
            continue
        attrs = rec.get("attrs") or {}
        tally = out.setdefault(str(attrs["cap"]), dict.fromkeys(
            ("calls", "files", "lanes", "payload_bytes"), 0))
        tally["calls"] += 1
        for key in ("files", "lanes", "payload_bytes"):
            tally[key] += attrs[key]
    return out


def _abs_path(location_path: str, row: dict) -> str:
    name = row["name"] or ""
    ext = row["extension"] or ""
    full = f"{name}.{ext}" if ext and not row["is_dir"] else name
    return f"{location_path}{row['materialized_path']}{full}"


def shallow_identify(library, location_id: int, sub_path: str = "") -> dict[str, Any]:
    """Non-job single-directory identify (file_identifier/shallow.rs) used by
    the watcher path."""

    class _ShallowCtx:
        def __init__(self, lib):
            self.library = lib
            self.node = lib.node

        def progress(self, *a, **k):
            pass

        def check_commands(self, *a):
            pass

    job = FileIdentifierJob({"location_id": location_id, "sub_path": sub_path or None})
    ctx = _ShallowCtx(library)
    try:
        data, steps, meta = job.init(ctx)  # type: ignore[arg-type]
    except EarlyFinish:
        return {"identified": 0}
    for i, step in enumerate(steps):
        job.execute_step(ctx, data, step, i)  # type: ignore[arg-type]
    return {"identified": meta.get("total_orphan_paths", 0)}
