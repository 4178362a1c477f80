"""Near-duplicate detection over indexed files (the MinHash op as a feature).

The reference collapses only EXACT duplicates (same cas_id → one Object).
This module finds *near* duplicates — edited photos, re-encoded media,
truncated copies — by running the TPU MinHash pipeline (ops/minhash.py) over
a location's sampled content, and the all-pairs sweep returns similarity
groups. Each file's signature is its object's ``object_minhash`` row, which
the identify page computed on the device from the rows it hashed; a file
with none (hashed on the CPU, synced in, identified before the table) is
gathered here — native gather reads its cas sample rows, the same bytes the
identifier hashed — and the device computes its signature.
"""

from __future__ import annotations

import logging
from typing import TYPE_CHECKING, Any

import numpy as np

from .. import telemetry
from ..jobs import EarlyFinish, StatefulJob, StepResult, WorkerContext
from ..models import FilePath
from .cas import MINIMUM_FILE_SIZE
from .hasher import SAMPLED_CHUNKS

if TYPE_CHECKING:
    from ..library import Library

logger = logging.getLogger(__name__)

SAMPLED_STRIDE = SAMPLED_CHUNKS * 1024  # 58368


#: above this row count the all-pairs device sweep gives way to LSH
#: banding (candidate buckets + exact verification) — O(N·BANDS) instead
#: of O(N²K)
ALL_PAIRS_LIMIT = 8192

#: signature batch per device pass (gather + minhash)
SIG_BATCH = 8192

_SIGNATURES = telemetry.counter(
    "sd_dedup_signatures_total",
    "MinHash signatures the dedup detector compared, by where each came "
    "from: stored by the identify page, or gathered and computed here",
    labels=("source",))


def _paths_of(db, rows_db) -> tuple[list[str], list[int]]:
    from .fs import location_path_of

    paths, sizes = [], []
    roots: dict[int, Any] = {}
    for r in rows_db:
        loc = r["location_id"]
        if loc not in roots:
            roots[loc] = location_path_of(db, loc)
        rel = (r["materialized_path"] or "/").lstrip("/")
        name = r["name"] + (f".{r['extension']}" if r["extension"] else "")
        paths.append(str(roots[loc] / rel / name))
        sizes.append(r["size_in_bytes"])
    return paths, sizes


def _signatures(paths: list[str], sizes: list[int],
                errors: list[str]) -> tuple[np.ndarray, np.ndarray]:
    """(n, K) uint32 MinHash signatures + lengths, computed in SIG_BATCH
    device passes so corpus size never explodes host/device memory. Each
    pass is a ``dedup.gather`` span (the sample reads) and a
    ``dedup.minhash`` span (H2D, kernel, readback)."""
    import jax

    from ..ops.minhash import K, minhash_rows

    trace = telemetry.current_trace()
    n = len(paths)
    sigs = np.zeros((n, K), np.uint32)
    lengths = np.zeros(n, np.int32)
    for start in range(0, n, SIG_BATCH):
        stop = min(n, start + SIG_BATCH)
        cnt = stop - start
        with telemetry.span(trace, "dedup.gather", files=cnt):
            buf = np.zeros((cnt, SAMPLED_STRIDE), np.uint8)
            lens = np.zeros(cnt, np.int32)
            try:
                from ..native import cas_native

                cas_native.gather_batch(paths[start:stop], sizes[start:stop],
                                        buf, lens)
            except Exception:
                from .cas import read_sampled_batch

                msgs = read_sampled_batch(paths[start:stop],
                                          sizes[start:stop])
                for i, m in enumerate(msgs):
                    if isinstance(m, Exception):
                        errors.append(f"{paths[start + i]}: {m}")
                        continue
                    buf[i, : len(m)] = np.frombuffer(m, np.uint8)
                    lens[i] = len(m)
        with telemetry.span(trace, "dedup.minhash", files=cnt):
            sigs[start:stop] = np.asarray(minhash_rows(
                jax.device_put(buf.view(np.uint32).reshape(
                    cnt, SAMPLED_STRIDE // 4)),
                jax.device_put(lens)))
        lengths[start:stop] = lens
    errors += [paths[i] for i in range(n) if lengths[i] == 0]
    return sigs, lengths


def find_near_duplicates(library: "Library", location_id: int | None = None,
                         threshold: float = 0.8, limit: int = ALL_PAIRS_LIMIT,
                         method: str = "auto") -> dict[str, Any]:
    """Similarity groups among sampled-size files. Returns {groups:
    [[file_path rows...]], pairs (each row's best match), pair_count
    (every verified pair), scanned, method, errors, signatures: {reused
    (stored by the identify page), gathered (read and computed here)}}.

    ``method``: ``all_pairs`` (device O(N²K) sweep), ``banded`` (LSH
    candidate buckets + exact verify, corpus-scale), or ``auto`` (all-pairs
    up to ALL_PAIRS_LIMIT rows, banded beyond)."""
    from ..utils.jax_guard import ensure_jax_safe

    ensure_jax_safe()  # the one device gate (analysis jax-wedge pass)
    from ..ops.minhash import K

    # per-pass spans under the caller's open span (the detector job's
    # job.step); a bare timer outside a job
    trace = telemetry.current_trace()
    db = library.db
    where = "fp.is_dir = 0 AND fp.size_in_bytes > ?"
    params: list[Any] = [MINIMUM_FILE_SIZE]
    if location_id is not None:
        where += " AND fp.location_id = ?"
        params.append(location_id)
    with telemetry.span(trace, "dedup.select"):
        rows_db, stored = [], []
        for r in db.query(
                f"SELECT fp.*, om.signature AS _minhash FROM file_path fp "
                f"LEFT JOIN object_minhash om ON om.object_id = fp.object_id "
                f"WHERE {where} ORDER BY fp.id LIMIT ?", params + [limit]):
            row = FilePath.decode_row(r)
            stored.append(row.pop("_minhash"))
            rows_db.append(row)
        reused = [i for i, b in enumerate(stored) if b is not None]
        gather = [i for i, b in enumerate(stored) if b is None]
        paths, sizes = _paths_of(db, [rows_db[i] for i in gather])
    n = len(rows_db)
    counts = {"reused": len(reused), "gathered": len(gather)}
    if n < 2:
        return {"groups": [], "pairs": [], "pair_count": 0, "scanned": n,
                "errors": [], "method": "none", "signatures": counts}
    if method == "auto":
        method = "all_pairs" if n <= ALL_PAIRS_LIMIT else "banded"

    errors: list[str] = []
    sigs = np.zeros((n, K), np.uint32)
    valid = np.ones(n, bool)
    if reused:
        sigs[reused] = np.frombuffer(
            b"".join(stored[i] for i in reused), "<u4").reshape(-1, K)
    if gather:
        sigs[gather], lengths = _signatures(paths, sizes, errors)
        valid[gather] = lengths > 0
    _SIGNATURES.inc(counts["reused"], source="identify")
    _SIGNATURES.inc(counts["gathered"], source="gather")
    thr_k = max(1, int(threshold * K))

    if method == "banded":
        if threshold < 0.7:
            # BANDS/BAND_ROWS are tuned for the 0.8 default; candidate
            # recall degrades at low thresholds (≈0.64 at s=0.5) — say so
            # instead of silently under-reporting vs the all-pairs path
            errors.append(
                f"banded LSH recall degrades below threshold 0.7 "
                f"(requested {threshold}); pairs near the threshold may "
                "be missed — force method='all_pairs' for exhaustive "
                "comparison")
        raw_pairs = _banded_pairs(sigs, valid, thr_k, errors)
        pair_count = len(raw_pairs)
    else:
        raw_pairs, pair_count = _all_pairs(sigs, valid, thr_k)

    with telemetry.span(trace, "dedup.verify"):
        return {**_group(rows_db, raw_pairs, pair_count, errors, method),
                "signatures": counts}


def _group(rows_db: list[dict], raw_pairs: list[tuple[int, int, int]],
           pair_count: int, errors: list[str], method: str) -> dict[str, Any]:
    """Verified pairs → the result: union-find groups and each row's best
    match."""
    from ..ops.minhash import K

    n = len(rows_db)
    # union-find grouping from verified pairs
    parent = list(range(n))

    def find(x: int) -> int:
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for i, j, _m in raw_pairs:
        parent[find(j)] = find(i)

    # collapse cliques to spanning pairs: each row keeps only its best
    # match, so a 200-file family emits ≤199 rows, not 19,900 (the banded
    # verifier returns full cliques)
    best: dict[int, tuple[int, int]] = {}
    for i, j, m in raw_pairs:
        for x, y in ((i, j), (j, i)):
            if m > best.get(x, (0, -1))[0]:
                best[x] = (m, y)
    edges: dict[tuple[int, int], int] = {}
    for x, (m, y) in best.items():
        key = (x, y) if x < y else (y, x)
        if m > edges.get(key, 0):
            edges[key] = m
    pairs = [{"a": rows_db[i], "b": rows_db[j], "similarity": float(m) / K}
             for (i, j), m in sorted(edges.items())]

    members: dict[int, list[int]] = {}
    linked = {i for i, _j, _m in raw_pairs} | {j for _i, j, _m in raw_pairs}
    for i in linked:
        members.setdefault(find(i), []).append(i)
    out_groups = [[rows_db[i] for i in sorted(ids)]
                  for ids in members.values() if len(ids) > 1]
    return {"groups": out_groups, "pairs": pairs,
            "pair_count": pair_count, "scanned": n, "errors": errors,
            "method": method}


def _all_pairs(sigs: np.ndarray, valid_rows: np.ndarray,
               thr_k: int) -> tuple[list[tuple[int, int, int]], int]:
    """Device all-pairs sweep → each flagged row's best earlier match as
    verified (i, j, matches), and the sweep's count of all similar pairs.
    Spans: ``dedup.sweep`` (pad, kernel, readback), then ``dedup.verify``
    (the host best-match loop)."""
    import jax

    from ..ops.minhash import pad_for_blocks, similar_pairs_count

    trace = telemetry.current_trace()
    n = sigs.shape[0]
    with telemetry.span(trace, "dedup.sweep", files=n):
        sigs_p, valid = pad_for_blocks(sigs)
        valid[:n] &= valid_rows
        total, dup = similar_pairs_count(jax.device_put(sigs_p),
                                         jax.device_put(valid), thr_k)
        total, dup = int(total), np.asarray(dup)[:n]
    out: list[tuple[int, int, int]] = []
    with telemetry.span(trace, "dedup.verify"):
        for i in range(n):
            if not dup[i]:
                continue
            eq = (sigs[i][None, :] == sigs[:i]).sum(axis=1)
            eq[~valid_rows[:i]] = 0
            j = int(np.argmax(eq))
            if eq[j] >= thr_k:
                out.append((j, i, int(eq[j])))
    return out, total


def _banded_pairs(sigs: np.ndarray, valid_rows: np.ndarray, thr_k: int,
                  errors: list[str]) -> list[tuple[int, int, int]]:
    """LSH banding: bucket by band keys, exact-verify candidates."""
    from ..ops.minhash import (band_keys, banded_candidate_pairs,
                               verify_pairs)

    keys = band_keys(sigs)
    cand, oversized = banded_candidate_pairs(keys, valid_rows)
    if oversized:
        errors.append(
            f"{oversized} oversized LSH buckets collapsed to "
            "representative pairing (members compared against one "
            "representative instead of all-pairs)")
    return verify_pairs(sigs, cand, thr_k)


def persisted_near_duplicate_groups(db, location_id: int | None = None,
                                    limit: int = 1000) -> dict[str, Any]:
    """Similarity groups from the PERSISTED ``near_duplicate`` pairs the
    chained :class:`DedupDetectorJob` wrote — pure ``library.db`` reads
    (no filesystem, no device), so the ``search.nearDuplicates`` handler
    serving it is pool- and replica-eligible (ISSUE 19 serve rung).

    Same result shape as :func:`find_near_duplicates` minus the live
    probe fields: ``{groups: [[file_path rows]], pairs, scanned, method:
    "persisted", errors: []}`` with ``scanned`` counting the pair rows
    considered. Ordering is fully deterministic (similarity DESC then
    pair id; members by id; groups by smallest member id) — replica
    byte-identity asserts on it."""
    where, params = "1=1", []
    if location_id is not None:
        where = "(fa.location_id = ? OR fb.location_id = ?)"
        params = [location_id, location_id]
    limit = max(0, min(int(limit), 5000))
    pair_rows = db.query(
        f"SELECT nd.id, nd.file_path_a_id AS a, nd.file_path_b_id AS b, "
        f"nd.similarity FROM near_duplicate nd "
        f"JOIN file_path fa ON nd.file_path_a_id = fa.id "
        f"JOIN file_path fb ON nd.file_path_b_id = fb.id "
        f"WHERE {where} ORDER BY nd.similarity DESC, nd.id LIMIT ?",
        params + [limit])

    parent: dict[int, int] = {}

    def find(x: int) -> int:
        parent.setdefault(x, x)
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    pairs = []
    for r in pair_rows:
        parent[find(int(r["b"]))] = find(int(r["a"]))
        pairs.append({"a": int(r["a"]), "b": int(r["b"]),
                      "similarity": r["similarity"]})
    ids = sorted(parent)
    rows_by_id: dict[int, dict] = {}
    if ids:
        marks = ",".join("?" for _ in ids)
        rows_by_id = {r["id"]: FilePath.decode_row(r) for r in db.query(
            f"SELECT * FROM file_path WHERE id IN ({marks})", ids)}
    members: dict[int, list[int]] = {}
    for i in ids:
        members.setdefault(find(i), []).append(i)
    groups = [[rows_by_id[i] for i in sorted(group) if i in rows_by_id]
              for _root, group in sorted(
                  members.items(), key=lambda kv: min(kv[1]))
              if len(group) > 1]
    return {"groups": [g for g in groups if len(g) > 1], "pairs": pairs,
            "scanned": len(pair_rows), "method": "persisted", "errors": []}


class DedupDetectorJob(StatefulJob):
    """Chained detector persisting near-dup pairs into `near_duplicate`
    (this framework's 4th pipeline stage after indexer → identifier →
    media; the reference has no analogue — it only collapses exact
    cas_id matches). ≤ ALL_PAIRS_LIMIT files use the device all-pairs
    sweep; bigger locations switch to LSH banding (candidate buckets +
    exact verification) up to DEVICE_LIMIT, beyond which the window is
    truncated loudly (no silent caps)."""

    NAME = "dedup_detector"
    IS_BATCHED = True

    #: rows per detection pass (signatures stream through the device in
    #: SIG_BATCH batches; banding keeps candidate generation linear)
    DEVICE_LIMIT = 131072

    def init(self, ctx: WorkerContext):
        db = ctx.library.db
        location_id = self.init_args["location_id"]
        count = db.query(
            "SELECT COUNT(*) n FROM file_path WHERE is_dir = 0 "
            "AND location_id = ? AND size_in_bytes > ?",
            [location_id, MINIMUM_FILE_SIZE])[0]["n"]
        if count < 2:
            raise EarlyFinish("not enough sampled-size files for dedup")
        if count > self.DEVICE_LIMIT:
            logger.warning(
                "dedup_detector: location %s has %d eligible files; only the "
                "first %d are compared this pass", location_id, count,
                self.DEVICE_LIMIT)
        data = {"location_id": location_id,
                "threshold": float(self.init_args.get("threshold", 0.8))}
        return data, [{"kind": "detect"}], {"pairs_found": 0, "scanned": 0}

    def execute_step(self, ctx: WorkerContext, data, step, step_number):
        from ..models import NearDuplicate, utc_now

        db = ctx.library.db
        result = find_near_duplicates(
            ctx.library, data["location_id"], threshold=data["threshold"],
            limit=self.DEVICE_LIMIT)
        rows = []
        for pair in result["pairs"]:
            a, b = pair["a"]["id"], pair["b"]["id"]
            rows.append({"file_path_a_id": min(a, b),
                         "file_path_b_id": max(a, b),
                         "similarity": pair["similarity"],
                         "date_detected": utc_now()})
        with telemetry.span(getattr(ctx, "trace", None), "dedup.persist",
                            pairs=len(rows)), db.transaction():
            # rescan refreshes the location's pair set
            db.query(
                "DELETE FROM near_duplicate WHERE file_path_a_id IN "
                "(SELECT id FROM file_path WHERE location_id = ?)",
                [data["location_id"]])
            if rows:
                db.insert_many(NearDuplicate, rows, or_ignore=True)
        ctx.progress(message=f"{len(rows)} near-duplicate pairs")
        return StepResult(metadata={"pairs_found": len(rows),
                                    "scanned": result["scanned"],
                                    "signatures": result["signatures"]},
                          errors=[str(e) for e in result["errors"]])

    def finalize(self, ctx: WorkerContext, data, run_metadata):
        ctx.library.emit("invalidate_query", {"key": "search.duplicates"})
        return run_metadata
