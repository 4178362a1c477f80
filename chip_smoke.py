#!/usr/bin/env python
"""One pass of the main path on a TPU, through the entry points a user calls.

    python chip_smoke.py            # one chip: the run the driver makes
    python chip_smoke.py --chips 4  # the tpu-sharded scan + identify_step
                                    # on a four-chip mesh, and its reference

Phases (one chip): build BASELINE config 2's mixed tree (100,000 files)
plus seeded photos and planted duplicates; boot a ``Node``, scan the tree
as a ``hasher="tpu"`` location (indexer → file_identifier → media_processor
→ dedup_detector) and check every ``cas_id`` against the native CPU hasher
and a sample against the pure oracle; scan it again as ``hasher="hybrid"``;
sweep the sampled-class files for near-duplicates on the device and check
the pair count against the CPU reference; serve a few rspc requests over
HTTP and check the answers against the database.

Each phase prints one JSON line (wall time, compile/set-up time, counts).
Any failure exits non-zero. With no TPU, it exits non-zero before any
phase and prints no result. The last stdout line is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Everything runs in this one process; nothing it starts touches JAX.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import sys
import time
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent
#: BASELINE config 2: the 100k-file mixed tree
N_FILES = 100_000
N_IMAGES = 256
N_DUP_PAIRS = 8
SEED = 1234

# -- compile accounting --------------------------------------------------------

_COMPILE_S = [0.0]


def _count_compiles() -> None:
    import jax

    def listener(event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILE_S[0] += duration

    jax.monitoring.register_event_duration_secs_listener(listener)


class Phase:
    """Times one phase; ``emit`` prints its line with the compile seconds
    that fell inside it."""

    def __init__(self, name: str) -> None:
        self.name = name
        self.t0 = time.perf_counter()
        self.c0 = _COMPILE_S[0]
        self.setup_s = 0.0

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t0

    def emit(self, **counts) -> dict:
        line = {"phase": self.name,
                "wall_s": time.perf_counter() - self.t0,
                "setup_s": self.setup_s,
                "compile_s": _COMPILE_S[0] - self.c0, **counts}
        print(json.dumps(line, default=str), flush=True)
        return line


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# -- fixture -------------------------------------------------------------------


def build_fixture(work: Path, n_files: int = N_FILES,
                  n_images: int = N_IMAGES, n_dup_pairs: int = N_DUP_PAIRS,
                  seed: int = SEED) -> dict:
    """The mixed tree (bench._ensure_scan_fixture) plus ``n_images`` seeded
    JPEG/PNG photos at 1024×768 and ``n_dup_pairs`` byte copies of tree
    files (half small, half sampled-class). Returns what the checks need."""
    import numpy as np
    from PIL import Image

    import bench  # the repo root: this script's directory

    tree = work / "tree"
    bench._ensure_scan_fixture(n_files, root=tree)
    photos = tree / "photos"
    photos.mkdir()
    rng = np.random.default_rng(seed)
    images = []
    for k in range(n_images):
        # smooth seeded content: a coarse random grid upsampled bilinearly
        coarse = rng.integers(0, 256, (12, 16, 3), dtype=np.uint8)
        img = Image.fromarray(coarse).resize((1024, 768), Image.BILINEAR)
        name = f"img{k:04d}.{'jpg' if k % 2 == 0 else 'png'}"
        img.save(photos / name)
        images.append(f"photos/{name}")
    dups_dir = tree / "dups"
    dups_dir.mkdir()
    pick = random.Random(seed)
    data = sorted(tree.glob("d*/*.dat"))
    small = [p for p in data if p.stat().st_size <= 100 * 1024]
    sampled = [p for p in data if p.stat().st_size > 100 * 1024]
    n_sampled = min(n_dup_pairs // 2, len(sampled))  # none below 4,000
    chosen = (pick.sample(small, n_dup_pairs - n_sampled)
              + pick.sample(sampled, n_sampled))
    pairs = []
    for src in chosen:
        copy = dups_dir / f"{src.stem}_copy.dat"
        shutil.copyfile(src, copy)
        pairs.append((str(src.relative_to(tree)), str(copy.relative_to(tree))))
    return {"root": tree, "files": n_files + n_images + len(pairs),
            "images": images, "dup_pairs": pairs}


# -- scan ----------------------------------------------------------------------


def _file_rows(lib, location_id: int) -> list[dict]:
    return lib.db.query(
        "SELECT id, materialized_path, name, extension, cas_id, object_id, "
        "size_in_bytes FROM file_path WHERE location_id = ? AND is_dir = 0",
        [location_id])


def _rel(row: dict) -> str:
    name = row["name"] + (f".{row['extension']}" if row["extension"] else "")
    return (row["materialized_path"] or "/").lstrip("/") + name


def scan_phase(node, fixture: dict, hasher: str, timeout_s: float = 900.0,
               oracle_sample: int = 200) -> dict:
    """Scan the tree as a ``hasher`` location in a fresh library and check
    the result. Returns the phase line."""
    from spacedrive_tpu import telemetry
    from spacedrive_tpu.jobs import JobStatus
    from spacedrive_tpu.locations import create_location, scan_location
    from spacedrive_tpu.objects.cas import generate_cas_id
    from spacedrive_tpu.objects.hasher import get_hasher
    from spacedrive_tpu.objects.media.thumbnail import (_DEVICE_VERDICT,
                                                        thumbnail_path)

    ph = Phase(f"scan[{hasher}]")
    root = fixture["root"]
    lib = node.libraries.create(f"smoke-{hasher}")
    loc = create_location(lib, str(root), hasher=hasher)
    hashed0 = telemetry.value("sd_hash_files_total", backend=hasher)
    ph.setup_done()
    t0 = time.perf_counter()
    scan_location(lib, loc["id"])
    _check(node.jobs.wait_idle(timeout_s), f"scan not idle in {timeout_s}s")
    scan_s = time.perf_counter() - t0

    rows = _file_rows(lib, loc["id"])
    _check(len(rows) == fixture["files"],
           f"indexed {len(rows)} of {fixture['files']} files")
    missing = [_rel(r) for r in rows if not r["cas_id"]]
    _check(not missing, f"{len(missing)} files without cas_id: {missing[:5]}")
    jobs = lib.db.query("SELECT name, status, metadata, errors_text FROM job")
    for job in jobs:
        _check(job["status"] == JobStatus.COMPLETED,
               f"{job['name']} ended {JobStatus.NAMES.get(job['status'])}: "
               f"{(job['errors_text'] or '')[:400]}")
    ident = [j for j in jobs if j["name"] == "file_identifier"]
    _check(len(ident) == 1, f"{len(ident)} file_identifier jobs")
    meta = json.loads(ident[0]["metadata"] or "{}")
    _check(meta.get("recovered_batches", 0) == 0
           and meta.get("quarantined_files", 0) == 0,
           f"identify recovered/quarantined: {meta}")
    hashed = telemetry.value("sd_hash_files_total", backend=hasher) - hashed0
    _check(hashed == len(rows),
           f"sd_hash_files_total{{backend={hasher!r}}} moved by {hashed}, "
           f"{len(rows)} files identified")

    paths = [str(root / _rel(r)) for r in rows]
    sizes = [r["size_in_bytes"] for r in rows]
    t1 = time.perf_counter()
    native = get_hasher("cpu").hash_batch(paths, sizes)
    cpu_s = time.perf_counter() - t1
    diff = [p for p, r, c in zip(paths, rows, native) if r["cas_id"] != c]
    _check(not diff, f"{len(diff)} cas_ids differ from the native CPU "
                     f"hasher: {diff[:5]}")
    pick = random.Random(SEED)
    for i in pick.sample(range(len(rows)), min(oracle_sample, len(rows))):
        _check(rows[i]["cas_id"] == generate_cas_id(paths[i]),
               f"{paths[i]}: cas_id differs from the pure oracle")

    by_rel = {_rel(r): r for r in rows}
    for a, b in fixture["dup_pairs"]:
        _check(by_rel[a]["object_id"] == by_rel[b]["object_id"],
               f"planted duplicates {a}, {b} do not share one object")
    objects = lib.db.query(
        "SELECT count(DISTINCT object_id) n FROM file_path "
        "WHERE location_id = ? AND is_dir = 0", [loc["id"]])[0]["n"]
    _check(objects == len(rows) - len(fixture["dup_pairs"]),
           f"{objects} objects for {len(rows)} files")
    no_thumb = [rel for rel in fixture["images"] if not thumbnail_path(
        node.data_dir, by_rel[rel]["cas_id"]).exists()]
    _check(not no_thumb, f"{len(no_thumb)} images without a WebP "
                         f"thumbnail: {no_thumb[:5]}")

    route = None
    if hasher == "hybrid":
        r = get_hasher("hybrid").router
        route = {"current": r.current, "degraded": r.degraded,
                 "flips": r.flips, "cpu_Bps": r.cpu_bps, "device_Bps": r.dev_bps}
    line = ph.emit(files=len(rows), objects=objects, scan_s=scan_s,
                   smoke_files_per_s=len(rows) / scan_s,
                   hashed_by_backend=hashed, native_parity_s=cpu_s,
                   identify_batches=meta.get("pipeline_batches"),
                   thumbnails=len(fixture["images"]),
                   thumbnail_device_verdict=_DEVICE_VERDICT["value"],
                   hybrid_route=route)
    line["library"], line["location_id"] = lib, loc["id"]
    return line


# -- dedup ---------------------------------------------------------------------


def _minhash_np(rows32, lengths):
    """numpy MinHash, the reference for ops/minhash.minhash_rows."""
    import numpy as np

    from spacedrive_tpu.ops import minhash as mh

    def mix(x):
        x = x ^ (x >> np.uint32(16))
        x = x * np.uint32(0x7FEB352D)
        x = x ^ (x >> np.uint32(15))
        x = x * np.uint32(0x846CA68B)
        return x ^ (x >> np.uint32(16))

    lo, hi = rows32[:, 0::2], rows32[:, 1::2]
    n_sh = np.maximum(1, lengths.astype(np.int64) // 8)
    valid = np.arange(lo.shape[1])[None, :] < n_sh[:, None]
    sigs = np.empty((rows32.shape[0], mh.K), np.uint32)
    with np.errstate(over="ignore"):
        for k in range(mh.K):
            h = mix(lo * mh._A[k] + hi * mh._B[k] + mh._C[k])
            sigs[:, k] = np.where(valid, h, np.uint32(0xFFFFFFFF)).min(axis=1)
    return sigs


def dedup_phase(lib, location_id: int, fixture: dict,
                threshold: float = 0.8, sig_sample: int = 32) -> dict:
    """``find_near_duplicates(method="all_pairs")`` on the device; its
    verified pair count must equal the CPU sweep over the same signatures,
    and a sample of the device signatures must equal numpy MinHash."""
    import numpy as np

    from spacedrive_tpu.models import FilePath
    from spacedrive_tpu.objects import dedup
    from spacedrive_tpu.objects.cas import MINIMUM_FILE_SIZE, read_sampled_batch
    from spacedrive_tpu.ops import minhash as mh

    ph = Phase("dedup")
    res = dedup.find_near_duplicates(lib, location_id, threshold=threshold,
                                     method="all_pairs")
    dedup_s = time.perf_counter() - ph.t0
    _check(not res["errors"], f"dedup errors: {res['errors'][:3]}")

    rows_db = [FilePath.decode_row(r) for r in lib.db.query(
        "SELECT * FROM file_path WHERE is_dir = 0 AND size_in_bytes > ? "
        "AND location_id = ? ORDER BY id LIMIT ?",
        [MINIMUM_FILE_SIZE, location_id, dedup.ALL_PAIRS_LIMIT])]
    paths, sizes = dedup._paths_of(lib.db, rows_db)
    errors: list[str] = []
    sigs, lengths = dedup._signatures(paths, sizes, errors)
    _check(not errors, f"signature errors: {errors[:3]}")
    sigs_p, valid = mh.pad_for_blocks(sigs)
    valid[: len(sigs)] &= lengths > 0
    t0 = time.perf_counter()
    ref_total, _dup = mh.similar_pairs_count_cpu(
        sigs_p, valid, max(1, int(threshold * mh.K)))
    ref_s = time.perf_counter() - t0
    _check(res["pair_count"] == ref_total,
           f"device sweep found {res['pair_count']} pairs, CPU {ref_total}")

    pick = random.Random(SEED).sample(range(len(paths)),
                                      min(sig_sample, len(paths)))
    msgs = read_sampled_batch([paths[i] for i in pick], [sizes[i] for i in pick])
    buf = np.zeros((len(pick), dedup.SAMPLED_STRIDE), np.uint8)
    for j, m in enumerate(msgs):
        buf[j, : len(m)] = np.frombuffer(m, np.uint8)
    ref_sigs = _minhash_np(buf.view(np.uint32), np.array(
        [len(m) for m in msgs], np.int32))
    _check((ref_sigs == sigs[pick]).all(),
           "device MinHash signatures differ from numpy MinHash")

    group_of = {r["id"]: g for g, members in enumerate(res["groups"])
                for r in members}
    by_rel = {_rel(r): r["id"] for r in rows_db}
    for a, b in fixture["dup_pairs"]:
        if a in by_rel:  # sampled-class pair: identical sigs, one group
            _check(group_of.get(by_rel[a]) is not None
                   and group_of.get(by_rel[a]) == group_of.get(by_rel[b]),
                   f"planted duplicates {a}, {b} not grouped")
    return ph.emit(scanned=res["scanned"], pairs=res["pair_count"],
                   groups=len(res["groups"]), cpu_pairs=ref_total,
                   dedup_s=dedup_s, cpu_reference_s=ref_s,
                   sigs_checked=len(pick))


# -- serve ---------------------------------------------------------------------


def _rspc(base: str, proc: str, arg, library_id: str):
    body = json.dumps({"arg": arg, "library_id": library_id}).encode()
    req = urllib.request.Request(f"{base}/rspc/{proc}", data=body,
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        out = json.loads(resp.read())
    return out["result"] if isinstance(out, dict) and "result" in out else out


def serve_phase(node, lib, location_id: int) -> dict:
    """``Server(node, port=0)`` answers /health, three rspc queries and
    /metrics; each answer is checked against the database."""
    from spacedrive_tpu.server.shell import Server

    ph = Phase("serve")
    srv = Server(node, port=0)
    srv.start()
    try:
        ph.setup_done()
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(f"{base}/health", timeout=30) as r:
            _check(r.read().strip() == b"OK", "/health did not answer OK")

        row = lib.db.query(
            "SELECT id, name, object_id FROM file_path WHERE location_id = ? "
            "AND is_dir = 0 ORDER BY id DESC LIMIT 1", [location_id])[0]
        found = _rspc(base, "search.paths",
                      {"location_id": location_id, "search": row["name"],
                       "take": 500}, lib.id)
        want = {r["id"] for r in lib.db.query(
            "SELECT id FROM file_path WHERE location_id = ? AND name LIKE ?",
            [location_id, f"%{row['name']}%"])}
        _check({i["id"] for i in found["items"]} == want,
               f"search.paths {row['name']!r}: ids differ from SQL")

        got = _rspc(base, "files.get", {"file_path_id": row["id"]}, lib.id)
        _check(got["object"]["id"] == row["object_id"],
               "files.get: object differs from the file_path row")
        want_paths = {r["id"] for r in lib.db.query(
            "SELECT id FROM file_path WHERE object_id = ?", [row["object_id"]])}
        _check({p["id"] for p in got["file_paths"]} == want_paths,
               "files.get: file_paths differ from SQL")

        count = _rspc(base, "search.pathsCount",
                      {"location_id": location_id}, lib.id)
        want_n = lib.db.query("SELECT count(*) n FROM file_path "
                              "WHERE location_id = ?", [location_id])[0]["n"]
        _check(count == want_n, f"search.pathsCount {count} != {want_n}")

        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
            metrics = r.read().decode()
        _check("sd_hash_files_total" in metrics, "/metrics lacks sd_hash_*")

        pool = node.reader_pool.status() if node.reader_pool else None
        readers = []
        if node.reader_pool is not None:
            for w in node.reader_pool._slots:
                if w is not None:
                    readers.append(_threads(w.proc.pid))
    finally:
        srv.stop()
    return ph.emit(requests=6, pool_alive=pool and pool["alive"],
                   reader_threads=readers)


def _threads(pid: int) -> int | None:
    try:
        for line in Path(f"/proc/{pid}/status").read_text().splitlines():
            if line.startswith("Threads:"):
                return int(line.split()[1])
    except OSError:
        return None
    return None


def device_check_after_fork() -> None:
    """The node still owns the chip after the reader pool forked."""
    import jax
    import jax.numpy as jnp

    x = jnp.arange(1 << 20, dtype=jnp.uint32)
    _check(int(jax.block_until_ready((x * 3).sum())) ==
           int((3 * (((1 << 20) - 1) * (1 << 20) // 2)) % (1 << 32)),
           "device arithmetic after the serve phase is wrong")


# -- four chips ----------------------------------------------------------------


def sharded_phase(node, fixture: dict, n_devices: int) -> dict:
    """The tpu-sharded scan over an ``n_devices`` mesh (checked against the
    native CPU hasher inside scan_phase), then identify_step on the mesh
    with planted duplicate lanes, checked against the pure oracle."""
    import jax
    import numpy as np

    from spacedrive_tpu.objects.blake3_ref import blake3
    from spacedrive_tpu.objects.cas import cas_message_from_bytes
    from spacedrive_tpu.ops.blake3_jax import digests_to_hex, pack_messages
    from spacedrive_tpu.parallel.mesh import (identify_step, make_mesh,
                                              pad_batch_for_mesh)

    scan = scan_phase(node, fixture, "tpu-sharded")
    ph = Phase("identify_step")
    mesh = make_mesh(n_devices)
    lanes = pad_batch_for_mesh(64 * n_devices, mesh)
    rng = np.random.default_rng(SEED)
    msgs = [cas_message_from_bytes(rng.integers(
        0, 256, int(rng.integers(1, 16 * 1024 - 9)), np.uint8).tobytes())
        for _ in range(lanes)]
    shard = lanes // n_devices
    for k in range(1, n_devices):  # repeat a lane of an earlier chip's shard
        msgs[k * shard + 3] = msgs[(k - 1) * shard + 5]
    words, lengths = pack_messages(msgs, 16)
    digests, dup = identify_step(mesh)(words, lengths)
    jax.block_until_ready((digests, dup))
    hexes = digests_to_hex(np.asarray(digests))
    bad = [i for i, m in enumerate(msgs) if hexes[i] != blake3(m).hex()]
    _check(not bad, f"identify_step digests differ from the oracle: {bad[:5]}")
    seen: set[bytes] = set()
    want = []
    for m in msgs:
        want.append(m in seen)
        seen.add(m)
    _check(list(np.asarray(dup)) == want,
           "identify_step dup flags differ from first-occurrence order")
    # the CPU backend keeps no memory stats; a TPU always does
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()]
    _check(jax.default_backend() != "tpu"
           or all(b and b > 0 for b in in_use[:n_devices]),
           f"some devices hold nothing: {in_use}")
    line = ph.emit(lanes=lanes, duplicates=int(np.asarray(dup).sum()),
                   mesh=dict(mesh.shape), bytes_in_use=in_use)
    return {"scan": scan, "identify_step": line}


# -- main ----------------------------------------------------------------------


def require_tpu(count: int) -> dict:
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU ({info}); the main "
                         "path is not run on another platform")
    if len(devices) < count:
        raise SystemExit(f"chip_smoke: --chips {count} but JAX sees "
                         f"{len(devices)} devices")
    return info


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    try:
        import spacedrive_tpu  # noqa: F401
    except ImportError as e:
        raise SystemExit(f"chip_smoke: the repository is not here ({e})")
    from spacedrive_tpu.utils.jax_guard import configure_compile_cache

    info = require_tpu(args.chips)
    print(json.dumps({"phase": "device", **info,
                      "compile_cache": configure_compile_cache()}), flush=True)
    _count_compiles()

    os.environ.setdefault("SD_SERVE_WORKERS", "2")
    work = REPO / ".bench_cache" / "chip_smoke"
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    node = None
    try:
        ph = Phase("fixture")
        fixture = build_fixture(work)
        ph.emit(files=fixture["files"], images=len(fixture["images"]),
                dup_pairs=len(fixture["dup_pairs"]))

        from spacedrive_tpu.config import BackendFeature
        from spacedrive_tpu.node import Node

        ph = Phase("boot")
        node = Node(work / "data", watch_locations=False)
        node.config.toggle_feature(BackendFeature.TPU_THUMBNAILS)
        ph.emit(accelerator=node.config.get()["accelerator"])

        if args.chips == 4:
            sharded_phase(node, fixture, 4)
        else:
            scan = scan_phase(node, fixture, "tpu")
            scan_phase(node, fixture, "hybrid")
            dedup_phase(scan["library"], scan["location_id"], fixture)
            serve_phase(node, scan["library"], scan["location_id"])
            device_check_after_fork()
    finally:
        if node is not None:
            node.shutdown()
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
