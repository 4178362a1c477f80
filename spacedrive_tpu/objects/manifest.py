"""Opt-in chunk-manifest stage of FileIdentifierJob (``SD_CHUNK_MANIFESTS=1``).

The identifier's sharded gather already has every file's head bytes in
flight; with manifests on, each page additionally carries whole-file chunk
payloads (small files reuse the cas message body byte-for-byte — zero extra
I/O; larger files re-read once, capped at ``SD_CHUNK_MAX_BYTES``), the
process stage chunks them with the ops/cdc.py gear kernel behind a
:class:`~.hasher.BackendRouter` instance (EWMA device-vs-native-CPU per
batch, same hysteresis/exploration/degrade ladder as the hash router, its
own ``sd_chunk_router_*`` families), and the commit stage persists the
``chunk_manifest`` table inside the identifier's existing transaction —
RowJournal-noted, so the device query engine and sync both see manifest
churn.

Stage discipline mirrors the identifier exactly: the gather and process
helpers here are read-only/compute-only (sdlint's pipeline-ordering and
commit-discipline passes know these names), per-item failures quarantine
instead of killing the batch (``chunk`` fault seam: eio retries under the
same transient policy as the cas gather, so a transient storm yields
byte-identical manifests; persistent failures quarantine per item), and a
device wedge mid-dispatch degrades to the numpy rung over the same
payloads — byte-identical chunk ids by the cdc module's cross-rung
guarantee.
"""

from __future__ import annotations

import logging
import os
import time

from .. import faults, telemetry
from ..models import ChunkManifest
from ..ops import cdc
from ..utils.retry import RetryPolicy, is_transient_io, retry_call
from .hasher import BackendRouter

logger = logging.getLogger(__name__)

# -- telemetry: declared at import time (file_identifier imports this
# module unconditionally) so every family below renders on /metrics with
# zero samples and the observability.md drift gate holds both directions
_CHUNK_FILES = telemetry.counter(
    "sd_chunk_files_total", "files chunked into manifests")
_CHUNK_CHUNKS = telemetry.counter(
    "sd_chunk_chunks_total", "content-defined chunks produced")
_CHUNK_BYTES = telemetry.counter(
    "sd_chunk_bytes_total", "payload bytes run through the CDC kernel")
_CHUNK_QUARANTINED = telemetry.counter(
    "sd_chunk_quarantined_total",
    "per-item manifest failures quarantined (file still identifies)")
_CHUNK_SKIPPED = telemetry.counter(
    "sd_chunk_skipped_total",
    "files skipped by the manifest stage (payload over SD_CHUNK_MAX_BYTES)")
_CHUNK_ROUTER_BPS = telemetry.gauge(
    "sd_chunk_router_bytes_per_sec",
    "EWMA transfer-inclusive CDC payload bytes/s per engine (router input)",
    labels=("backend",))
_CHUNK_ROUTER_FLIPS = telemetry.counter(
    "sd_chunk_router_flips_total",
    "engine flips by the per-batch chunk router (hysteresis-damped)")
_CHUNK_ROUTER_BATCHES = telemetry.counter(
    "sd_chunk_router_batches_total",
    "chunk (sub-)batches the router dispatched per engine",
    labels=("backend",))

#: the chunk stage's own router instance — same logic as the hash router,
#: separate EWMAs (CDC arithmetic intensity is nothing like BLAKE3's)
router = BackendRouter(flips_counter=_CHUNK_ROUTER_FLIPS,
                       batches_counter=_CHUNK_ROUTER_BATCHES,
                       bps_gauge=_CHUNK_ROUTER_BPS,
                       event_prefix="chunk_router")

#: transient payload-read retries (same shape as cas.GATHER_RETRY): an
#: injected/organic EIO storm retries clean, so manifests under chaos stay
#: byte-identical to the fault-free run
PAYLOAD_RETRY = RetryPolicy(attempts=3, base_s=0.01, max_s=0.1, budget_s=1.0)

#: files above the whole-payload cap skip manifests (sd_chunk_skipped_total)
DEFAULT_MAX_BYTES = 4 * 1024 * 1024

#: the cas message is size_le_8 ‖ content for files at or under this
#: (cas.MINIMUM_FILE_SIZE) — their payload is the message body, free
_SMALL = 102400


def manifests_enabled() -> bool:
    return os.environ.get("SD_CHUNK_MANIFESTS", "").strip().lower() in (
        "1", "true", "on", "yes")


def payload_cap() -> int:
    raw = os.environ.get("SD_CHUNK_MAX_BYTES", "").strip()
    if raw:
        try:
            return max(1, int(raw))
        except ValueError:
            pass
    return DEFAULT_MAX_BYTES


# -- stage 1 half: payload gather (rides _gather_rows, read-only) -----------


def _read_payload(path: str, msg: "bytes | Exception", size: int) -> bytes:
    """One file's whole-content chunk payload. The ``chunk`` fault seam sits
    here — inside the retry, like the cas gather's — so ``chunk:eio:p``
    storms retry clean and ``chunk:kill`` dies at the exact read."""
    faults.inject("chunk", key=path)
    if size <= _SMALL and not isinstance(msg, Exception):
        return bytes(msg[8:])
    with open(path, "rb") as fh:
        return fh.read(size)


def pipeline_chunk_gather(paths: list[str], rows: list[dict],
                          messages: list) -> None:
    """Attach ``row['_chunk_payload']`` to every hashable row: the payload
    bytes, ``None`` (cas gather already failed the file, or it is over the
    cap — skipped, not quarantined), or the post-retry Exception (per-item
    quarantine at commit). Read-only: payloads ride the row dicts through
    shard-merge concatenation untouched."""
    cap = payload_cap()
    for path, row, msg in zip(paths, rows, messages):
        if isinstance(msg, Exception):
            row["_chunk_payload"] = None  # quarantined by the cas path
            continue
        size = row["size_in_bytes"] or 0
        if size > cap:
            row["_chunk_payload"] = None
            _CHUNK_SKIPPED.inc()
            continue
        try:
            row["_chunk_payload"] = retry_call(
                lambda p=path, m=msg, s=size: _read_payload(p, m, s),
                policy=PAYLOAD_RETRY, classify=is_transient_io,
                label="chunk-gather")
        except Exception as e:  # noqa: BLE001 — per-item quarantine
            row["_chunk_payload"] = e


# -- stage 2 half: chunk + id behind the router (compute-only) --------------


def _chunk_slice(payloads: list[bytes], engine: str) -> list[list[tuple[str, int]]]:
    """Chunk one engine's slice: boundaries + per-chunk BLAKE3 ids. The
    ``cpu`` engine is the vectorized numpy rung; ``device`` resolves
    ``SD_CDC_KERNEL`` (xla default, pallas opt-in). Byte-identical either
    way — that is the cdc module's contract, so routing is pure economics."""
    kernel = "numpy" if engine == "cpu" else cdc.resolve_kernel(None)
    chunks = cdc.chunk_batch(payloads, kernel=kernel)
    ids = cdc.chunk_ids(payloads, chunks, kernel=kernel)
    return [[(cid, ln) for cid, (_off, ln) in zip(fid, fch)]
            for fid, fch in zip(ids, chunks)]


def _dispatch(payloads: list[bytes], engine: str) -> list[list[tuple[str, int]]]:
    faults.inject("chunk", key=f"dispatch:{engine}")
    t0 = time.perf_counter()
    out = _chunk_slice(payloads, engine)
    router.observe(engine, sum(len(p) for p in payloads),
                   time.perf_counter() - t0)
    return out


def pipeline_chunk_process(rows: list[dict], trace=None) -> None:
    """Chunk every gathered payload in the page, routed per batch. Device
    failures (wedge, dying backend) re-dispatch the slice on the numpy rung
    over the same payloads and degrade the router — same ladder as the
    hasher, with byte-identical output by construction. Results land as
    ``row['_chunk_manifest']`` (ordered ``(chunk_id, length)`` pairs);
    failures become ``row['_chunk_payload']`` Exceptions for the committer's
    quarantine loop."""
    work = [r for r in rows if isinstance(r.get("_chunk_payload"), bytes)]
    if not work:
        return
    payloads = [r["_chunk_payload"] for r in work]
    nbytes = sum(len(p) for p in payloads)
    with telemetry.span(trace, "identifier.chunk", files=len(work),
                        bytes=nbytes):
        main, probe = router.route()
        split = 0
        results: list[list[tuple[str, int]]] = []
        if probe is not None and len(work) > 1:
            split = min(router.PROBE_SLICE, len(work) // 2 or 1)
            try:
                results.extend(_dispatch(payloads[:split], probe))
            except Exception as e:  # noqa: BLE001 — probe slice redoes on numpy
                if probe == "device":
                    router.degrade(repr(e))
                results.extend(_chunk_slice(payloads[:split], "cpu"))
        try:
            results.extend(_dispatch(payloads[split:], main))
        except Exception as e:  # noqa: BLE001 — degradation ladder
            logger.exception("chunk dispatch failed mid-batch; re-dispatching "
                             "on the numpy rung")
            if main == "device":
                router.degrade(repr(e))
            results.extend(_chunk_slice(payloads[split:], "cpu"))
    for row, manifest in zip(work, results):
        row["_chunk_manifest"] = manifest
        row["_chunk_payload"] = None  # the payload bytes are dead weight now
    _CHUNK_FILES.inc(len(work))
    _CHUNK_CHUNKS.inc(sum(len(m) for m in results))
    _CHUNK_BYTES.inc(nbytes)


# -- stage 3 half: persist (called INSIDE the identifier's transaction) -----


def commit_manifest_rows(db, items: list[tuple[int, list[tuple[str, int]]]]) -> int:
    """Overwrite-then-insert the batch's manifests. ``items`` is
    ``(object_id, manifest)`` — already deduped by object (within-batch
    cas-duplicates carry identical manifests, one copy wins). Both the
    delete and the insert are RowJournal-noted; the caller owns the
    transaction."""
    rows = []
    for oid, manifest in items:
        db.delete(ChunkManifest, {"object_id": oid})
        for seq, (chunk_hash, length) in enumerate(manifest):
            rows.append({"object_id": oid, "seq": seq,
                         "chunk_hash": chunk_hash, "length": length})
    if rows:
        db.insert_many(ChunkManifest, rows)
    return len(items)


def quarantine_errors(rows: list[dict], location_path: str) -> list[str]:
    """Post-process quarantine sweep: rows whose payload ended as an
    Exception lose only their manifest — the file still identified. Returns
    the soft-error strings for the step result."""
    from .file_identifier import _abs_path

    errs = []
    n = 0
    for row in rows:
        p = row.get("_chunk_payload")
        if isinstance(p, Exception):
            errs.append(f"chunk manifest quarantined "
                        f"{_abs_path(location_path, row)}: {p!r}")
            row["_chunk_payload"] = None
            n += 1
    if n:
        _CHUNK_QUARANTINED.inc(n)
    return errs
