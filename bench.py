#!/usr/bin/env python
"""Headline benchmark. Prints ONE JSON line {metric, value, unit, vs_baseline}.

Default mode (``combined``): the dedup headline plus the north-star identify
record in ``extra``.

``SD_BENCH_MODE=dedup``: MinHash near-duplicate detection — BASELINE.json
config 4. Signatures for N objects (the ones the identify pass computes
on-device for free, ops/minhash.py) are swept all-pairs on the TPU vs the
identical blocked-numpy algorithm on CPU; pair sets must match exactly
before timing counts. This is the TPU-native capability the reference lacks
entirely (its dedup is exact-cas_id only).

``SD_BENCH_MODE=identify``: the file_identifier cas_id path (north-star
files/sec, BASELINE configs 1-3) — the production HybridHasher vs the
native-CPU baseline, identical cas_ids enforced. The hybrid probes both
engines and routes each batch to the faster one.

A mode that measures the device exits non-zero when JAX's backend is not
a TPU: it never measures the CPU under a device metric's name. The
CPU-only modes (fleet, crash, serve, load, sync, check-history) name the
platform they ran on.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from pathlib import Path

MODE = os.environ.get("SD_BENCH_MODE", "combined")
#: ``--fleet``: the synthetic-device-fleet soak (ISSUE 8) — N in-process
#: peers pushing CRDT sessions through admission control + partitioned
#: ingest lanes at ONE node; emits the fleet record to BENCH_fleet.json
#: so the trajectory file exists for future PRs
if "--fleet" in sys.argv[1:]:
    MODE = "fleet"
#: ``--wan <profile>`` (ISSUE 13, implies ``--fleet``): run the fleet
#: soak across a modeled WAN — ``lan`` / ``wan`` / ``flaky-wan`` topology
#: matrices from faults/net.py PROFILES (the same matrices the
#: tests/test_wan.py soak gates arm), with the accept-layer throttle +
#: auto-ban armed and one scripted BUSY-ignoring flooder on flaky-wan.
#: Headline: converged ops/s + heal-to-lag-zero seconds, to
#: BENCH_fleet_wan.json and BENCH_history.jsonl.
WAN_PROFILE = None
if "--wan" in sys.argv[1:]:
    MODE = "fleet"
    _wan_i = sys.argv.index("--wan")
    WAN_PROFILE = (sys.argv[_wan_i + 1]
                   if len(sys.argv) > _wan_i + 1
                   and not sys.argv[_wan_i + 1].startswith("-")
                   else "flaky-wan")
#: ``--crash``: the process-kill torture matrix (ISSUE 9) — SIGKILL real
#: node subprocesses at seeded seam hits, restart, and measure recovery;
#: emits the record to BENCH_crash.json
if "--crash" in sys.argv[1:]:
    MODE = "crash"
#: ``--serve``: the concurrent read-path load bench (ISSUE 10) — client
#: threads driving search + directory listing + thumbnail/range fetches
#: over real HTTP against a mounted router DURING an active pipelined
#: scan; per-procedure p50/p95/p99 from the sd_rspc_* histograms, to
#: BENCH_serve.json. With ``--wan <profile>`` (ISSUE 19) it becomes the
#: distributed replica serve gate instead: an N-peer fleet with two
#: armed replicas serves pool-marked queries over the modeled WAN
#: through flaky-wan's two partition waves — tail SLOs held, zero
#: pre-watermark rows, every failover accounted, byte-identity at the
#: quiescent point; record to BENCH_serve_wan.json
if "--serve" in sys.argv[1:]:
    MODE = "serve"
#: ``--search``: the device query engine bench (ISSUE 15) — a synthetic
#: SD_BENCH_SEARCH_N-object corpus (default 1M) served through the real
#: router with the columnar/JAX engine vs the SQLite path, byte-identical
#: orderings asserted across the whole query matrix; emits the record to
#: BENCH_search.json
if "--search" in sys.argv[1:]:
    MODE = "search"
#: ``--scan``: the shard/batch sweep (ISSUE 17) — one identify scan per
#: (SD_SCAN_SHARDS, BATCH_SIZE) grid cell over the cached tree, per-cell
#: files/s + gather_share; best cell is the headline, full grid to
#: BENCH_scan_sweep.json
if "--scan" in sys.argv[1:]:
    MODE = "scan_sweep"
#: ``--chunk``: the content-defined chunking bench (ISSUE 18) — CDC MB/s
#: per rung (numpy / XLA / Pallas) vs the naive pure-Python Gear oracle
#: (boundaries byte-identical, every rung >=3x the oracle), the dedup
#: ratio manifests surface on an edited-copies corpus, and the delta
#: bytes-on-wire headline from the NetModel ledger; record to
#: BENCH_chunk.json
if "--chunk" in sys.argv[1:]:
    MODE = "chunk"
#: ``--load``: the open-loop multi-tenant load harness (ISSUE 20) —
#: seeded Poisson arrival schedules (arrivals never wait for
#: completions) over a Zipf tenant mix of SD_LOAD_TENANTS libraries,
#: dispatched in-process through the real router with the dispatch
#: admission budget + reader-pool autosizer + SLO burn-rate engine all
#: live. Emits the latency-vs-offered-load curve (p50/p99/p99.9 + shed
#: rate per step), the detected knee as the headline, and the
#: flash-crowd acceptance gates (burn alert fires AND resolves, the
#: flooding tenant absorbs the sheds, quiet tenants stay fast, the
#: autosizer grows then shrinks) to BENCH_load.json
if "--load" in sys.argv[1:]:
    MODE = "load"
#: ``--check-history``: the regression sentinel (ISSUE 20) — compare
#: each (mode, metric)'s latest BENCH_history.jsonl value against the
#: trailing median of its predecessors and print a verdict table;
#: always exits 0 (a sentinel, not a gate — combined mode runs it
#: warn-only at the end of every full bench)
if "--check-history" in sys.argv[1:]:
    MODE = "check_history"
REPEATS = int(os.environ.get("SD_BENCH_REPEATS", "3"))
#: ``--faults`` (or SD_BENCH_FAULTS=1): bench_scan adds a chaos pass under
#: an injected fault storm and reports recovery overhead alongside
#: throughput (recovered_batches / quarantined_files / retry_total_s)
CHAOS_MODE = "--faults" in sys.argv[1:] or bool(os.environ.get("SD_BENCH_FAULTS"))
if CHAOS_MODE:  # combined mode runs bench_scan in a child — it must inherit
    os.environ.setdefault("SD_BENCH_FAULTS", "1")


def time_best(fn, repeats: int):
    best, out = float("inf"), None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def bench_dedup() -> dict:
    import jax
    import numpy as np

    from spacedrive_tpu.ops import minhash as mh

    n = int(os.environ.get("SD_BENCH_OBJECTS", "8192"))
    k = mh.K
    rng = np.random.default_rng(42)

    # synthetic object corpus: families of 4 near-duplicates (2%/4%/6%
    # content drift) — the shape of a photo library with edited copies
    w = 2048  # u32 words of sampled content per object
    base = rng.integers(0, 2**32, (n // 4, w), dtype=np.uint32)
    rows = np.repeat(base, 4, axis=0).copy()
    for m in range(1, 4):
        sel = rng.random((n // 4, w)) < (m * 0.02)
        rows[m::4][sel] = rng.integers(0, 2**32, int(sel.sum()), dtype=np.uint32)
    lengths = np.full(n, w * 4, np.int32)

    sigs = np.asarray(mh.minhash_rows(jax.device_put(rows),
                                      jax.device_put(lengths)))
    sigs_p, valid = mh.pad_for_blocks(sigs)
    thr = int(0.5 * k)

    cpu_t, cpu_res = time_best(
        lambda: mh.similar_pairs_count_cpu(sigs_p, valid, thr), 1)
    d_sigs, d_valid = jax.device_put(sigs_p), jax.device_put(valid)

    def tpu_run():
        total, dup = mh.similar_pairs_count(d_sigs, d_valid, thr)
        return int(np.asarray(total)), np.asarray(dup)

    tpu_run()  # compile
    tpu_t, tpu_res = time_best(tpu_run, REPEATS)

    if cpu_res[0] != tpu_res[0] or not (cpu_res[1] == tpu_res[1]).all():
        print(f"FATAL: dedup mismatch cpu={cpu_res[0]} tpu={tpu_res[0]}",
              file=sys.stderr)
        sys.exit(1)

    comparisons = (n * (n - 1) / 2) * k
    print(f"info: {n} objects, {cpu_res[0]} near-dup pairs; "
          f"cpu {cpu_t:.2f}s tpu {tpu_t:.3f}s", file=sys.stderr)
    return {
        "metric": f"minhash_dedup_comparisons_per_sec[{n}obj,K={k}]",
        "value": round(comparisons / tpu_t / 1e9, 2),
        "unit": "Gcomparisons/sec",
        "vs_baseline": round(cpu_t / tpu_t, 2),
    }


def bench_device_kernel() -> dict:
    """Device-RESIDENT BLAKE3 kernel throughput: rows already on device,
    timing = kernel + (8,B)-digest readback only. This isolates the kernel
    from the host→device path so the two regimes of the identify pipeline
    are separately evidenced, against the native C++ BLAKE3 hashing the
    SAME buffers host-resident (one core; the reference's identify path
    is likewise one worker, file_identifier/mod.rs:36,107-134). Every
    timed run ends in an ``np.asarray`` host round-trip.
    """
    import jax
    import numpy as np

    from spacedrive_tpu.native import cas_native
    from spacedrive_tpu.ops import roofline
    from spacedrive_tpu.ops.blake3_jax import (BLOCKS_PER_CHUNK, CHUNK_LEN,
                                               blake3_batch_rows,
                                               digests_to_hex, resolve_kernel)

    # 8192 lanes amortize the fixed dispatch overhead: smaller batches
    # measure the dispatch, not the kernel
    B = int(os.environ.get("SD_BENCH_DEVICE_LANES", "8192"))
    sampled_bytes = 57_352          # 8 size-prefix + 8KiB + 4x10KiB + 8KiB
    C = -(-sampled_bytes // CHUNK_LEN)            # 57 chunks
    W = C * BLOCKS_PER_CHUNK * 16                 # row words
    rng = np.random.default_rng(42)
    rows = rng.integers(0, 2**32, (B, W), dtype=np.uint32)
    # zero the padding tail beyond each message length, as the gather does
    tail_words = sampled_bytes // 4
    rows[:, tail_words:] = 0
    lengths = np.full(B, sampled_bytes, np.int32)

    # host-resident native baseline over identical bytes (single core)
    msgs = [rows[i].tobytes()[:sampled_bytes] for i in range(B)]
    host_t, host_hex = time_best(
        lambda: [cas_native.blake3_hex(m) for m in msgs], 1)

    d_rows, d_lengths = jax.device_put(rows), jax.device_put(lengths)

    def run():
        return np.asarray(blake3_batch_rows(d_rows, d_lengths))

    out = run()  # compile + correctness gate vs the native oracle
    if digests_to_hex(out) != host_hex:
        print("FATAL: device kernel digest mismatch", file=sys.stderr)
        sys.exit(1)
    dev_t, _ = time_best(run, REPEATS)

    # transfer-included number for the same batch (H2D + kernel + readback)
    def run_with_transfer():
        return np.asarray(blake3_batch_rows(jax.device_put(rows),
                                            jax.device_put(lengths)))

    xfer_t, _ = time_best(run_with_transfer, 1)

    # live H2D link rate (16 MiB incompressible + honest barrier): the
    # number that decides which engine the hybrid router SHOULD pick —
    # recorded so the routing decision is auditable per run (see
    # docs/architecture/tpu-backend.md, "The host→device ceiling")
    probe = rng.integers(0, 256, 16 * 1024 * 1024, dtype=np.uint8)
    barrier = jax.jit(lambda x: x[:8].astype(jax.numpy.uint32).sum())
    np.asarray(barrier(jax.device_put(probe)))  # compile off the clock
    h2d_t, _ = time_best(
        lambda: np.asarray(barrier(jax.device_put(probe))), 2)
    h2d_mbps = probe.nbytes / 1e6 / h2d_t

    gb = B * sampled_bytes / 1e9
    # roofline/MFU accounting (ops/roofline.py): achieved payload bytes/s ×
    # 12.5 u32 ops/byte against the chip's peak u32 ops/s — kernel progress
    # expressed against hardware peak, not just the 1-core CPU baseline
    kernel = resolve_kernel()
    peak = roofline.peak(jax.devices()[0])  # unknown TPU kind: raises
    mfu = gb * 1e9 / dev_t * roofline.OPS_PER_BYTE / peak.u32_ops
    print(f"info: device-resident kernel[{kernel}] {B} lanes x "
          f"{sampled_bytes}B: device {dev_t:.3f}s ({gb / dev_t:.2f} GB/s, "
          f"{B / dev_t:.0f} files-equiv/s, MFU {mfu:.1%}) | "
          f"+transfer {xfer_t:.3f}s "
          f"({gb / xfer_t:.2f} GB/s) | host 1-core native {host_t:.3f}s "
          f"({gb / host_t:.2f} GB/s) | h2d link {h2d_mbps:.0f} MB/s",
          file=sys.stderr)
    return {
        "metric": f"blake3_device_resident_GBps[{B}x56KiB]",
        "value": round(gb / dev_t, 2),
        "unit": "GB/sec",
        "vs_baseline": round(host_t / dev_t, 2),
        "kernel": kernel,
        "mfu": round(mfu, 4),
        "ops_per_byte": roofline.OPS_PER_BYTE,
        "peak_u32_ops_per_sec": peak.u32_ops,
        "files_equiv_per_sec": round(B / dev_t, 1),
        "transfer_included_GBps": round(gb / xfer_t, 2),
        "host_native_GBps": round(gb / host_t, 2),
        "h2d_MBps": round(h2d_mbps, 1),
    }


def bench_identify() -> dict:
    """North-star config 1-3: file_identifier files/sec vs the native-CPU
    baseline, using the production HybridHasher (adaptive engine routing:
    the probe sends sampled work to whichever engine measured faster)."""
    import numpy as np

    from spacedrive_tpu.objects.cas import read_sampled_batch
    from spacedrive_tpu.objects.hasher import CpuHasher, HybridHasher

    n_files = int(os.environ.get("SD_BENCH_FILES", "2048"))
    file_size = int(os.environ.get("SD_BENCH_FILE_SIZE", str(192 * 1024)))
    tmp = tempfile.TemporaryDirectory(prefix="sd_bench_")
    rng = np.random.default_rng(42)
    pool = rng.integers(0, 256, file_size + n_files, dtype=np.uint8).tobytes()
    paths, sizes = [], []
    for i in range(n_files):
        p = Path(tmp.name) / f"{i:06d}.bin"
        p.write_bytes(pool[i : i + file_size])
        paths.append(str(p))
        sizes.append(file_size)

    cpu = CpuHasher()
    cpu_t, cpu_ids = time_best(lambda: cpu.hash_batch(paths, sizes), REPEATS)
    hy = HybridHasher()

    def hybrid():  # the gather is timed with the hash, as cpu's fused path
        return hy.hash_gathered(read_sampled_batch(paths, sizes))

    hybrid()  # warmup: compiles kernels + runs the probe
    hy_t, hy_ids = time_best(hybrid, REPEATS)
    if cpu_ids != hy_ids:
        print("FATAL: cas_id mismatch", file=sys.stderr)
        sys.exit(1)
    print(f"info: identify {n_files} files, cpu {cpu_t:.3f}s "
          f"hybrid {hy_t:.3f}s", file=sys.stderr)
    return {
        "metric": f"file_identifier_files_per_sec[{n_files}x{file_size >> 10}KiB]",
        "value": round(n_files / hy_t, 1),
        "unit": "files/sec",
        "vs_baseline": round(cpu_t / hy_t, 3),
    }


def bench_thumbs() -> dict:
    """Batched device thumbnail resize (SURVEY §3.2's second hot CPU loop)
    vs the scalar PIL path, resize step isolated (decode/encode cost is
    identical either way). Both regimes reported like the BLAKE3 bench:
    device-resident kernel rate and transfer-included."""
    import jax
    import numpy as np

    from PIL import Image

    from spacedrive_tpu.ops.resize_jax import resize_batch, target_dims

    n = int(os.environ.get("SD_BENCH_THUMBS", "48"))
    # post-host-reduce shape (thumbnail.MAX_INPUT_EDGE): what the device
    # actually sees; smooth gradient data because PIL's BILINEAR antialiases
    # downscales (box support) while the kernel is a true 4-tap bilinear —
    # on photographic content they agree, on white noise they cannot
    h_in, w_in = 768, 1024
    yy, xx = np.mgrid[0:h_in, 0:w_in]
    base = np.stack([yy * 255.0 / h_in, xx * 255.0 / w_in,
                     (yy + xx) * 255.0 / (h_in + w_in)], -1)
    rng = np.random.default_rng(7)
    phase = rng.uniform(0, 40, (n, 1, 1, 3))
    batch = np.clip(base[None] + phase, 0, 255).astype(np.uint8)
    src = np.tile(np.int32([h_in, w_in]), (n, 1))
    th, tw = target_dims(w_in, h_in)
    tgt = np.tile(np.int32([th, tw]), (n, 1))

    # scalar PIL baseline (bilinear, same filter class as the kernel)
    imgs = [Image.fromarray(batch[i]) for i in range(n)]
    pil_t, _ = time_best(
        lambda: [np.asarray(im.resize((tw, th), Image.BILINEAR))
                 for im in imgs], REPEATS)

    import jax.numpy as jnp

    d_batch = jax.device_put(batch)
    d_src, d_tgt = jax.device_put(src), jax.device_put(tgt)

    @jax.jit
    def kernel_sum(b, s, t):
        # on-device checksum with a 48-word readback: the timing is the
        # KERNEL, not the D2H of 37MB of pixels
        return resize_batch(b, s, t).astype(jnp.uint32).sum(axis=(1, 2, 3))

    def run_kernel():
        return np.asarray(kernel_sum(d_batch, d_src, d_tgt))

    def run_full():
        return np.asarray(resize_batch(d_batch, d_src, d_tgt))

    out = run_full()  # compile both; correctness gate vs PIL
    ref = np.asarray(imgs[0].resize((tw, th), Image.BILINEAR), dtype=np.float32)
    got = out[0, :th, :tw].astype(np.float32)
    # error bound vs PIL, per channel plus the worst single pixel — a bare
    # batch-mean can hide a localized divergence (one bad tile averages
    # away); the bound is what preview-media.md documents and gates
    err = np.abs(ref - got)
    mae_per_channel = [float(x) for x in err.mean(axis=(0, 1))]
    max_abs_err = float(err.max())
    mae = float(err.mean())
    if mae > 4.0 or max_abs_err > 48.0:
        # mean gate: filters differ slightly at edges; max gate: no single
        # pixel may diverge by more than ~19% of full scale (see
        # docs/architecture/preview-media.md, "Filter choice and tolerance").
        # raise (not sys.exit): combined mode treats thumbs as additive
        # evidence and must still print the headline record
        raise RuntimeError(f"device resize diverges from PIL "
                           f"(MAE {mae:.1f}, max {max_abs_err:.0f})")
    run_kernel()
    kern_t, _ = time_best(run_kernel, REPEATS)
    full_t, _ = time_best(run_full, 1)

    def run_with_transfer():
        return np.asarray(resize_batch(jax.device_put(batch), d_src, d_tgt))

    xfer_t, _ = time_best(run_with_transfer, 1)

    # the ROUTED path — what the media processor actually runs: get_hasher-
    # style hybrid routing (thumbnail.resize_images) picks the device kernel
    # only when it measures faster than PIL; on CPU fallback that means the
    # PIL path, so production never takes the losing jax resize (0.11× in
    # BENCH_r05). The headline is the routed rate; the raw kernel stays as
    # an extra field for device-rig comparisons.
    from spacedrive_tpu.objects.media.thumbnail import resize_images

    arrays = [batch[i] for i in range(n)]
    resize_images(arrays)  # route decision (and any device warmup) off-clock
    routed_t, _ = time_best(lambda: resize_images(arrays), REPEATS)

    mpx = n * h_in * w_in / 1e6
    print(f"info: thumbs {n}x{w_in}x{h_in}: routed {routed_t:.3f}s "
          f"({n / routed_t:.1f} img/s) | kernel {kern_t:.3f}s "
          f"({n / kern_t:.1f} img/s, {mpx / kern_t:.0f} MPx/s) | "
          f"+readback {full_t:.3f}s | +transfer {xfer_t:.3f}s | "
          f"PIL {pil_t:.3f}s ({n / pil_t:.1f} img/s) | "
          f"MAE/chan vs PIL {['%.2f' % c for c in mae_per_channel]} "
          f"max |err| {max_abs_err:.1f}", file=sys.stderr)
    return {
        "metric": f"thumbnail_resize_images_per_sec[{n}x{w_in}x{h_in}]",
        "value": round(n / routed_t, 1),
        "unit": "images/sec",
        "vs_baseline": round(pil_t / routed_t, 2),
        "device_kernel_images_per_sec": round(n / kern_t, 1),
        "readback_included_images_per_sec": round(n / full_t, 1),
        "transfer_included_images_per_sec": round(n / xfer_t, 1),
        "pil_images_per_sec": round(n / pil_t, 1),
        "mae_vs_pil_per_channel": [round(c, 3) for c in mae_per_channel],
        "max_abs_err_vs_pil": round(max_abs_err, 1),
    }


def _peak_rss_mb() -> float:
    """This process's own peak RSS. /proc VmHWM, not getrusage: on this
    kernel ru_maxrss is INHERITED across fork+exec, so a subprocess bench
    would report the parent's high-water mark."""
    try:
        with open("/proc/self/status") as fh:
            for line in fh:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) / 1024
    except OSError:
        pass
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024


def bench_dedup_1m() -> dict:
    """BASELINE config 4 at its stated scale: the LSH-banded near-duplicate
    pass over >=1M objects. Signatures are computed by the real device
    MinHash kernel over synthetic sampled-content rows (families of 4 with
    2/4/6% drift, the shape of a photo library with edited copies),
    streamed in device batches; banding + exact verification then run at
    full scale with bounded memory. Recall is scored against the exact
    signature-threshold answer on a sampled subset. vs_baseline projects
    the all-pairs device sweep (the config's 'all-pairs psum reduction')
    at its measured rate over the same N — the quadratic cost LSH exists
    to avoid."""
    import jax
    import numpy as np

    from spacedrive_tpu.ops import minhash as mh

    n = int(os.environ.get("SD_BENCH_DEDUP_1M_OBJECTS", "1000000"))
    n -= n % 4  # families of 4
    w = 64  # u32 words of sampled content per object (256 B)
    rng = np.random.default_rng(99)
    base = rng.integers(0, 2**32, (n // 4, w), dtype=np.uint32)
    rows = np.repeat(base, 4, axis=0)
    del base
    for m in range(1, 4):
        sel = rng.random((n // 4, w)) < (m * 0.02)
        rows[m::4][sel] = rng.integers(0, 2**32, int(sel.sum()), dtype=np.uint32)

    # device MinHash in streamed batches (the identify pass computes these
    # for free in production; here they're timed explicitly)
    t0 = time.perf_counter()
    sig_chunks = []
    step = 65536
    lengths = np.full(step, w * 4, np.int32)
    for start in range(0, n, step):
        chunk = rows[start : start + step]
        real = len(chunk)
        if real < step:  # pad the tail: one compiled shape for every batch
            chunk = np.vstack([chunk, np.zeros((step - real, w), np.uint32)])
        sig_chunks.append(np.asarray(mh.minhash_rows(
            jax.device_put(chunk), jax.device_put(lengths)))[:real])
    sigs = np.concatenate(sig_chunks)
    del sig_chunks, rows  # ~256 MB at 1M objects: dead weight for the LSH pass
    sig_t = time.perf_counter() - t0

    thr_k = int(0.5 * mh.K)
    t0 = time.perf_counter()
    keys = mh.band_keys(sigs)
    cand, oversized = mh.banded_candidate_pairs(keys, np.ones(n, bool))
    verified = mh.verify_pairs(sigs, cand, thr_k)
    lsh_t = time.perf_counter() - t0

    # recall vs the exact answer on a sampled subset (contiguous slice so
    # whole families fall inside it)
    s0, s1 = 0, int(os.environ.get("SD_BENCH_DEDUP_1M_SAMPLE", "4000"))
    sub = sigs[s0:s1]
    exact = set()
    for r0 in range(0, s1 - s0, 256):  # row-blocked: the 3D broadcast would
        blk = sub[r0 : r0 + 256]       # cost ~1 GB and pollute peak-RSS
        eq = (blk[:, None, :] == sub[None, :, :]).sum(axis=2)
        for bi, j in zip(*np.nonzero(eq >= thr_k)):
            i = r0 + int(bi)
            if i < j:
                exact.add((i, int(j)))
    got = {(i, j) for i, j, _m in verified if s0 <= i < s1 and s0 <= j < s1}
    recall = 1.0 if not exact else len(exact & got) / len(exact)

    # projected all-pairs cost at the device sweep's measured rate
    dev_rate = float(os.environ.get("SD_BENCH_DEDUP_GCMPS", "15")) * 1e9
    allpairs_t = (n * (n - 1) / 2) * mh.K / dev_rate
    peak_rss_mb = _peak_rss_mb()

    print(f"info: dedup {n} objects: signatures {sig_t:.1f}s | "
          f"LSH pass {lsh_t:.1f}s ({n / lsh_t:,.0f} obj/s, "
          f"{len(cand):,} candidates, {len(verified):,} verified pairs, "
          f"{len(verified) / lsh_t:,.0f} pairs/s) | recall {recall:.4f} "
          f"on {s1} sampled | all-pairs projected {allpairs_t:,.0f}s | "
          f"peak RSS {peak_rss_mb:.0f} MB", file=sys.stderr)
    return {
        "metric": f"minhash_dedup_1M[{n}obj,LSH {mh.BANDS}x{mh.BAND_ROWS}]",
        "value": round(n / lsh_t, 1),
        "unit": "objects/sec",
        "vs_baseline": round(allpairs_t / lsh_t, 1),
        "signature_time_s": round(sig_t, 1),
        "lsh_pass_s": round(lsh_t, 1),
        "candidate_pairs": int(len(cand)),
        "verified_pairs": int(len(verified)),
        "verified_pairs_per_sec": round(len(verified) / lsh_t, 1),
        "recall_sampled": round(recall, 4),
        "oversized_buckets": int(oversized),
        "peak_rss_mb": round(peak_rss_mb, 1),
    }


def _ensure_scan_fixture(n_files: int, root: Path | None = None) -> Path:
    """Build (once) and cache a mixed n-file tree: ~85% small text-class
    files (0.4–4 KiB, whole-file cas messages), 10% mid (40 KiB), 5%
    sampled-class (150 KiB > MINIMUM_FILE_SIZE). 200 directories. Matches
    BASELINE config 2's '100k-file mixed tree' shape without media decode
    noise (extensions stay data-class so the media stage runs but has no
    thumbnail work — its cost is measured, its codec noise is not).
    ``root`` builds the same tree afresh there instead of in the cache."""
    import numpy as np

    if root is None:
        root = Path(__file__).parent / ".bench_cache" / f"scan_{n_files}_v2"
    marker = root / ".complete"
    if marker.exists():
        return root
    import shutil

    shutil.rmtree(root, ignore_errors=True)
    root.mkdir(parents=True)
    rng = np.random.default_rng(1234)
    pool = rng.integers(0, 256, 256 * 1024 + n_files, dtype=np.uint8).tobytes()
    n_dirs = 200
    dirs = []
    for d in range(n_dirs):
        p = root / f"d{d:03d}"
        p.mkdir()
        dirs.append(p)
    for i in range(n_files):
        # slot keyed to the file's index WITHIN its directory (i % n_dirs
        # picks the dir), so every directory carries the full size mix —
        # i % 20 would alias with the dir assignment and concentrate each
        # size class into dedicated directories
        slot = (i // n_dirs) % 20
        if slot >= 19:
            size = 150 * 1024
        elif slot >= 17:
            size = 40 * 1024
        else:
            size = 400 + (i * 37) % 3600
        # unique leading offset → distinct contents (no dedup collapse)
        (dirs[i % n_dirs] / f"f{i:06d}.dat").write_bytes(pool[i : i + size])
    marker.write_bytes(b"ok")
    return root


def bench_scan() -> dict:
    """BASELINE configs 1-2: full end-to-end scan_location throughput
    (walk → index → identify → media) over the cached 100k-file mixed tree,
    production hybrid hasher vs the cpu backend, fresh library each run.
    Peak RSS recorded (the jobs run in this process)."""
    import shutil

    from spacedrive_tpu.locations import create_location
    from spacedrive_tpu.locations.indexer_job import IndexerJob
    from spacedrive_tpu.node import Node
    from spacedrive_tpu.objects.file_identifier import FileIdentifierJob
    from spacedrive_tpu.objects.media.processor import MediaProcessorJob

    n_files = int(os.environ.get("SD_BENCH_SCAN_FILES", "100000"))
    fixture = _ensure_scan_fixture(n_files)

    # warm the process-wide hybrid engine off the clock: its one-time probe
    # (XLA kernel compile + link measurement) is per-process, not per-scan,
    # and would otherwise dominate the timed window
    from spacedrive_tpu.objects.cas import read_sampled_batch
    from spacedrive_tpu.objects.hasher import get_hasher

    warm: list[tuple[str, int]] = []
    for p in sorted(fixture.rglob("*.dat")):
        size = p.stat().st_size
        if size > 100 * 1024:  # sampled-class: what the probe measures
            warm.append((str(p), size))
        if len(warm) >= 24:
            break
    get_hasher("hybrid").hash_gathered(read_sampled_batch(
        [p for p, _ in warm], [s for _, s in warm]))

    # pre-read the tree so both timed passes see the same (warm) page
    # cache — otherwise whichever hasher runs first pays the cold IO and
    # the comparison wobbles with fixture-cache state
    for p in fixture.rglob("*.dat"):
        with open(p, "rb") as fh:
            while fh.read(1 << 20):
                pass

    from spacedrive_tpu import telemetry as _tm

    def _router_batches() -> dict[str, float]:
        return {lbl["backend"]: v for lbl, v in
                _tm.series_values("sd_hash_router_batches_total")}

    def one_scan(hasher: str, expect_all: bool = True) -> tuple[float, dict]:
        tmp = Path(tempfile.mkdtemp(prefix=f"sd_scan_{hasher}_"))
        # per-batch router accounting for THIS scan (registry deltas):
        # flips and per-engine routed batch counts ride back on the stages
        # dict next to the job's own metadata keys
        flips0 = _tm.value("sd_hash_router_flips_total")
        rb0 = _router_batches()
        try:
            node = Node(tmp, probe_accelerator=False, watch_locations=False)
            # the GC actors' periodic ticks (30s/60s) would land inside one
            # engine's window and not the other's — this measures the scan
            # pipeline, not actor scheduling luck
            node.thumbnail_remover.stop()
            lib = node.libraries.create(f"scan-{hasher}")
            lib.orphan_remover.stop()
            loc = create_location(lib, str(fixture), hasher=hasher)
            args = {"location_id": loc["id"]}
            t0 = time.perf_counter()
            node.jobs.spawn(lib, [IndexerJob(dict(args)),
                                  FileIdentifierJob(dict(args)),
                                  MediaProcessorJob(dict(args))],
                            action="scan_location")
            assert node.jobs.wait_idle(3600)
            dt = time.perf_counter() - t0
            n_indexed = lib.db.query(
                "SELECT count(*) c FROM file_path WHERE is_dir=0")[0]["c"]
            n_identified = lib.db.query(
                "SELECT count(*) c FROM file_path WHERE cas_id IS NOT NULL")[0]["c"]
            assert n_indexed == n_files, (n_indexed, n_files)
            # the chaos pass quarantines what its fault storm kills — those
            # files legitimately stay unidentified
            if expect_all:
                assert n_identified == n_files, (n_identified, n_files)
            # identify stage breakdown (pipeline/executor.py timing keys) so
            # the next PR can see where the pipeline stalls
            row = lib.db.query(
                "SELECT metadata FROM job WHERE name='file_identifier' "
                "ORDER BY date_created DESC LIMIT 1")
            stages = json.loads(row[0]["metadata"]) if row and row[0]["metadata"] else {}
            stages["router_flips"] = int(
                _tm.value("sd_hash_router_flips_total") - flips0)
            stages["router_batches"] = {
                k: int(v - rb0.get(k, 0)) for k, v in _router_batches().items()
                if v - rb0.get(k, 0) > 0}
            node.shutdown()
            return dt, stages
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    # alternate engine order and keep each engine's best: one-shot timings
    # on a shared host wobble ±15%
    cpu_t, _ = one_scan("cpu")
    hyb_t, hyb_stages = one_scan("hybrid")
    hyb2_t, hyb2_stages = one_scan("hybrid")
    if hyb2_t < hyb_t:
        hyb_t, hyb_stages = hyb2_t, hyb2_stages
    cpu2_t, _ = one_scan("cpu")
    times = {"cpu": min(cpu_t, cpu2_t), "hybrid": hyb_t}

    # the telemetry A/B below runs two more FULL telemetry-on hybrid scans
    # late in the process — on this container the process warms up
    # monotonically, so those are often the least-biased samples. Fold the
    # best ON run back into the headline (same "keep each engine's best"
    # doctrine as the alternation above; the ON side is the production
    # config the headline claims to measure).
    telemetry_overhead, on_best_t, on_best_stages = \
        _bench_telemetry_overhead(one_scan, n_files, times["hybrid"])
    if on_best_stages is not None and on_best_t < times["hybrid"]:
        times["hybrid"], hyb_stages = on_best_t, on_best_stages
        # the cpu engine gets its own late sample so the vs_baseline
        # comparison draws both engines from the same sampling windows
        cpu3_t, _ = one_scan("cpu")
        times["cpu"] = min(times["cpu"], cpu3_t)

    page_s = hyb_stages.get("pipeline_page_s", 0.0)
    hash_s = hyb_stages.get("pipeline_hash_s", 0.0)
    commit_s = hyb_stages.get("pipeline_commit_s", 0.0)
    wall_s = hyb_stages.get("pipeline_wall_s", 0.0)
    gather_s = hyb_stages.get("gather_s", 0.0)
    # the scan-ceiling tracker (ISSUE 17): fraction of the page stage spent
    # in the file-IO gather — the sharded prefetch exists to shrink this
    gather_share = round(gather_s / page_s, 3) if page_s else 0.0
    # 1.0 = the identify wall clock collapsed to its slowest stage (perfect
    # overlap); 0.0 = stages ran back-to-back like the sequential loop
    serial = page_s + hash_s + commit_s
    ideal = max(page_s, hash_s, commit_s)
    overlap = ((serial - wall_s) / (serial - ideal)
               if wall_s and serial > ideal else 0.0)
    overlap = max(0.0, min(1.0, overlap))

    peak_rss_mb = _peak_rss_mb()
    rate = n_files / times["hybrid"]
    # the new-knob visibility satellite: group-commit coalescing and the
    # per-batch router's decisions, read from the chosen hybrid scan
    batches = int(hyb_stages.get("pipeline_batches", 0))
    txns = int(hyb_stages.get("commit_txns", 0))
    txn_pages = round(batches / txns, 2) if txns else 0.0
    router_flips = int(hyb_stages.get("router_flips", 0))
    router_batches = hyb_stages.get("router_batches", {})
    print(f"info: scan {n_files} files e2e: cpu {times['cpu']:.1f}s | "
          f"hybrid {times['hybrid']:.1f}s ({rate:,.0f} files/s) | "
          f"identify page {page_s:.1f}s (gather {gather_s:.1f}s, "
          f"share {gather_share:.2f}) "
          f"hash {hash_s:.1f}s commit {commit_s:.1f}s wall {wall_s:.1f}s "
          f"(overlap {overlap:.2f}) | {batches} pages in {txns} txns "
          f"({txn_pages}/txn) | router flips {router_flips} "
          f"batches {router_batches} | peak RSS {peak_rss_mb:.0f} MB",
          file=sys.stderr)
    chaos = _bench_scan_chaos(one_scan, n_files, times["hybrid"]) \
        if CHAOS_MODE else None
    record = {
        "metric": f"scan_e2e_files_per_sec[{n_files}files]",
        "value": round(rate, 1),
        "unit": "files/sec",
        "vs_baseline": round(times["cpu"] / times["hybrid"], 3),
        "cpu_files_per_sec": round(n_files / times["cpu"], 1),
        "page_s": round(page_s, 2),
        "gather_s": round(gather_s, 2),
        "gather_share": gather_share,
        "scan_shards": hyb_stages.get("pipeline_shards", "1"),
        "hash_s": round(hash_s, 2),
        "commit_s": round(commit_s, 2),
        "identify_wall_s": round(wall_s, 2),
        "overlap_efficiency": round(overlap, 3),
        "group_commit_txns": txns,
        "commit_txn_pages": txn_pages,
        "router_flips": router_flips,
        "router_batches": router_batches,
        "peak_rss_mb": round(peak_rss_mb, 1),
        "telemetry_overhead": telemetry_overhead,
        "lock_overhead": _bench_lock_overhead(),
    }
    if chaos is not None:
        record["chaos"] = chaos
    return record


def bench_scan_sweep() -> dict:
    """``--scan`` (ISSUE 17): the shard/batch grid. One identify run per
    (SD_SCAN_SHARDS, BATCH_SIZE) cell over the cached tree — indexing runs
    once per cell off the clock, the timed window is the file_identifier
    job alone, so the cells isolate exactly what the knobs move. Per-cell
    files/s + gather_share (gather_s / page_s); the best cell is the
    headline and the full grid lands in BENCH_scan_sweep.json."""
    import shutil

    from spacedrive_tpu.locations import create_location
    from spacedrive_tpu.locations.indexer_job import IndexerJob
    from spacedrive_tpu.node import Node
    from spacedrive_tpu.objects.file_identifier import FileIdentifierJob

    n_files = int(os.environ.get("SD_BENCH_SCAN_FILES", "20000"))
    fixture = _ensure_scan_fixture(n_files)

    # same off-the-clock warmups as bench_scan: the hybrid engine's
    # one-time probe, then the tree into the page cache so every cell
    # sees identical (warm) IO
    from spacedrive_tpu.objects.cas import read_sampled_batch
    from spacedrive_tpu.objects.hasher import get_hasher

    warm: list[tuple[str, int]] = []
    for p in sorted(fixture.rglob("*.dat")):
        size = p.stat().st_size
        if size > 100 * 1024:
            warm.append((str(p), size))
        if len(warm) >= 24:
            break
    get_hasher("hybrid").hash_gathered(read_sampled_batch(
        [p for p, _ in warm], [s for _, s in warm]))
    for p in fixture.rglob("*.dat"):
        with open(p, "rb") as fh:
            while fh.read(1 << 20):
                pass

    def one_cell(shards: int, batch: int) -> dict:
        tmp = Path(tempfile.mkdtemp(prefix="sd_scan_sweep_"))
        try:
            node = Node(tmp, probe_accelerator=False, watch_locations=False)
            node.thumbnail_remover.stop()
            lib = node.libraries.create(f"sweep-{shards}x{batch}")
            lib.orphan_remover.stop()
            loc = create_location(lib, str(fixture), hasher="hybrid")
            args = {"location_id": loc["id"]}
            # indexing is identical across cells — run it off the clock
            node.jobs.spawn(lib, [IndexerJob(dict(args))])
            assert node.jobs.wait_idle(3600)
            t0 = time.perf_counter()
            node.jobs.spawn(lib, [FileIdentifierJob(dict(args))])
            assert node.jobs.wait_idle(3600)
            dt = time.perf_counter() - t0
            n_identified = lib.db.query(
                "SELECT count(*) c FROM file_path "
                "WHERE cas_id IS NOT NULL")[0]["c"]
            assert n_identified == n_files, (n_identified, n_files)
            row = lib.db.query(
                "SELECT metadata FROM job WHERE name='file_identifier' "
                "ORDER BY date_created DESC LIMIT 1")
            stages = (json.loads(row[0]["metadata"])
                      if row and row[0]["metadata"] else {})
            node.shutdown()
            page_s = stages.get("pipeline_page_s", 0.0)
            gather_s = stages.get("gather_s", 0.0)
            return {
                "shards": shards,
                "batch": batch,
                "files_per_sec": round(n_files / dt, 1),
                "identify_s": round(dt, 2),
                "gather_share": (round(gather_s / page_s, 3)
                                 if page_s else 0.0),
                "gather_s": round(gather_s, 2),
                "page_s": round(page_s, 2),
                "hash_s": round(stages.get("pipeline_hash_s", 0.0), 2),
                "commit_s": round(stages.get("pipeline_commit_s", 0.0), 2),
                "wall_s": round(stages.get("pipeline_wall_s", 0.0), 2),
            }
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    shards_grid = [int(s) for s in os.environ.get(
        "SD_BENCH_SWEEP_SHARDS", "1,2,4").split(",") if s.strip()]
    batch_grid = [int(b) for b in os.environ.get(
        "SD_BENCH_SWEEP_BATCH", "512,1024,2048").split(",") if b.strip()]
    saved = {k: os.environ.get(k)
             for k in ("SD_SCAN_SHARDS", "SD_SCAN_BATCH")}
    cells = []
    try:
        for shards in shards_grid:
            for batch in batch_grid:
                os.environ["SD_SCAN_SHARDS"] = str(shards)
                os.environ["SD_SCAN_BATCH"] = str(batch)
                cell = one_cell(shards, batch)
                cells.append(cell)
                print(f"info: sweep shards={shards} batch={batch}: "
                      f"{cell['files_per_sec']:,.0f} files/s, "
                      f"gather_share {cell['gather_share']:.2f}",
                      file=sys.stderr)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    best = max(cells, key=lambda c: c["files_per_sec"])
    record = {
        "metric": (f"scan_sweep_files_per_sec[{n_files}files,"
                   f"shards={best['shards']},batch={best['batch']}]"),
        "value": best["files_per_sec"],
        "unit": "files/sec",
        "gather_share": best["gather_share"],
        "best": {"shards": best["shards"], "batch": best["batch"]},
        "grid": cells,
    }
    out = Path(__file__).resolve().parent / "BENCH_scan_sweep.json"
    out.write_text(json.dumps(record, indent=1) + "\n")
    print(f"info: sweep best shards={best['shards']} "
          f"batch={best['batch']}: {best['files_per_sec']:,.0f} files/s "
          f"-> {out.name}", file=sys.stderr)
    return record


def _bench_lock_overhead() -> dict:
    """Same-session A/B for the named-lock migration (ISSUE 14 gate:
    with SD_LOCK_SANITIZER unset, SdLock must stay ≥0.95× a bare
    threading.Lock — it IS one by construction, the factories return
    the raw primitive, and this keeps that claim measured instead of
    assumed). Interleaved raw→sd→raw like the telemetry A/B, best of
    each side, on a contention-free acquire/release loop (the disabled
    path has no contention story to tell — that is the sanitizer's)."""
    import threading

    from spacedrive_tpu.utils.locks import SdLock, sanitizer_enabled

    if sanitizer_enabled():
        # the A/B measures the DISABLED fast path; under an exported
        # SD_LOCK_SANITIZER=1 the comparison would be sanitizer cost,
        # not wrapper cost — skip rather than gate on the wrong number
        print("info: lock overhead A/B skipped (SD_LOCK_SANITIZER set)",
              file=sys.stderr)
        return {"skipped": "SD_LOCK_SANITIZER set"}
    n = 200_000

    def loop(lock) -> float:
        acquire, release = lock.acquire, lock.release
        t0 = time.perf_counter()
        for _ in range(n):
            acquire()
            release()
        return time.perf_counter() - t0

    # three alternating rounds, best of each side: the loop runs ~20ms,
    # and on the 2-shared-core container a single scheduler preemption is
    # a 10-30% swing — with IDENTICAL objects on both sides the noise is
    # symmetric, so best-of-N converges on the true (≈1.0×) ratio
    raw_t = sd_t = float("inf")
    for _ in range(3):
        raw_t = min(raw_t, loop(threading.Lock()))
        sd_t = min(sd_t, loop(SdLock("bench.probe")))
    out = {
        "acquire_release_per_sec_raw": round(n / raw_t, 0),
        "acquire_release_per_sec_sd": round(n / sd_t, 0),
        # >1.0 = the named lock was faster (noise); the 0.95 acceptance
        # floor reads this ratio directly
        "sd_vs_raw": round(raw_t / sd_t, 3),
    }
    print(f"info: lock overhead A/B (sanitizer off): SdLock "
          f"{out['acquire_release_per_sec_sd']:,.0f}/s vs raw "
          f"{out['acquire_release_per_sec_raw']:,.0f}/s "
          f"(sd/raw {out['sd_vs_raw']:.3f}x)", file=sys.stderr)
    _append_history({
        "metric": "lock_overhead_sd_vs_raw",
        "value": out["sd_vs_raw"],
        "unit": "ratio",
    })
    return out


def _bench_telemetry_overhead(one_scan, n_files: int,
                              on_hybrid_s: float) -> tuple:
    """Same-session A/B for the always-on instrumentation (ISSUE 5 gate:
    telemetry-on must stay ≥0.95× the off files/s, i.e. inside the
    container's noise band). Single scans on this shared-core container
    wobble ±15% with occasional 2× outliers AND speed up monotonically
    as the process warms, so the A/B interleaves off→on→off (the extra
    ON run sits between the OFF pair, cancelling the warm-up trend) and
    keeps each side's best — one unlucky run must not masquerade as
    instrumentation overhead. A real per-batch record cost still shows
    up: it shifts both OFF runs relative to every ON run."""
    from spacedrive_tpu import telemetry

    was_enabled = telemetry.enabled()
    try:
        telemetry.set_enabled(False)
        off_t, _ = one_scan("hybrid")
        telemetry.set_enabled(True)
        on2_t, on2_stages = one_scan("hybrid")
        # the headline scan joins the ON side only if it actually ran with
        # the recorder on — an operator benching with SD_TELEMETRY=off must
        # not have an off-measurement win as the "on" sample (that would
        # make the 0.95x gate vacuous)
        on_hybrid_s = min(on_hybrid_s, on2_t) if was_enabled else on2_t
        telemetry.set_enabled(False)
        off2_t, _ = one_scan("hybrid")
        off_t = min(off_t, off2_t)
    finally:
        telemetry.set_enabled(was_enabled)
    overhead = {
        "files_per_sec_on": round(n_files / on_hybrid_s, 1),
        "files_per_sec_off": round(n_files / off_t, 1),
        # >1.0 = on was faster (noise); the 0.95 acceptance floor reads
        # this ratio directly
        "on_vs_off": round(off_t / on_hybrid_s, 3),
    }
    print(f"info: telemetry overhead A/B: on "
          f"{overhead['files_per_sec_on']:,.0f} files/s vs off "
          f"{overhead['files_per_sec_off']:,.0f} files/s "
          f"(on/off {overhead['on_vs_off']:.3f}x)", file=sys.stderr)
    # the extra ON run is a headline candidate (only when the recorder was
    # actually on — off-config stages must never pose as the headline)
    return overhead, on2_t, (on2_stages if was_enabled else None)


#: chaos mode (``--faults`` / SD_BENCH_FAULTS=1): one extra scan under an
#: injected fault storm so fault-recovery overhead is a tracked number in
#: BENCH files, not a hope. SD_BENCH_FAULTS_SPEC overrides the storm.
DEFAULT_CHAOS_SPEC = "gather:eio:0.002;commit:sqlite_busy:0.02;hash:wedge:once"


def _bench_scan_chaos(one_scan, n_files: int, clean_hybrid_s: float) -> dict:
    """Chaos pass accounting reads the unified telemetry registry
    (sd_retry_* / sd_faults_fired_total deltas across the run) — the
    PR 4 module-global retry stats dict is gone."""
    from spacedrive_tpu import faults, telemetry

    def fired_by_rule() -> dict[str, float]:
        return {f"{lbl['seam']}:{lbl['kind']}": v for lbl, v in
                telemetry.series_values("sd_faults_fired_total") if v}

    spec = os.environ.get("SD_BENCH_FAULTS_SPEC", DEFAULT_CHAOS_SPEC)
    # the accounting below reads registry deltas, so the recorder must be
    # ON for the chaos window even when the operator benches with
    # SD_TELEMETRY=off (zeros would silently report the storm as inert)
    was_enabled = telemetry.enabled()
    telemetry.set_enabled(True)
    before_backoff = telemetry.value("sd_retry_backoff_seconds_total")
    before_retries = telemetry.value("sd_retry_attempts_total")
    before_fired = fired_by_rule()
    faults.install(spec)
    try:
        chaos_t, stages = one_scan("hybrid", expect_all=False)
    finally:
        faults.clear()
        telemetry.set_enabled(was_enabled)
    retry_total_s = (telemetry.value("sd_retry_backoff_seconds_total")
                     - before_backoff)
    fired = {rule: int(v - before_fired.get(rule, 0))
             for rule, v in fired_by_rule().items()
             if v > before_fired.get(rule, 0)}
    chaos = {
        "spec": spec,
        "files_per_sec": round(n_files / chaos_t, 1),
        "vs_clean": round(clean_hybrid_s / chaos_t, 3),
        "recovered_batches": int(stages.get("recovered_batches", 0)),
        "quarantined_files": int(stages.get("quarantined_files", 0)),
        "retry_total_s": round(retry_total_s, 3),
        "retries": int(telemetry.value("sd_retry_attempts_total")
                       - before_retries),
        "faults_fired": fired,
    }
    print(f"info: chaos scan [{spec}]: {chaos['files_per_sec']:,.0f} files/s "
          f"({chaos['vs_clean']:.2f}x clean) | recovered_batches "
          f"{chaos['recovered_batches']} | quarantined "
          f"{chaos['quarantined_files']} | retry_total "
          f"{chaos['retry_total_s']:.3f}s | fired {fired}", file=sys.stderr)
    return chaos


def _apply_delay_totals(telemetry) -> tuple[int, float]:
    """(count, sum) across every peer series of the apply-delay
    histogram — bench deltas bracket one pull run."""
    fam = telemetry.snapshot()["metrics"].get(
        "sd_sync_apply_delay_seconds", {})
    count = sum(s.get("count", 0) for s in fam.get("series", []))
    total = sum(s.get("sum", 0.0) for s in fam.get("series", []))
    return count, total


def bench_sync() -> dict:
    """Two-node CRDT sync throughput (BASELINE config 5's replication
    half): emit N shared ops on instance A, pull+ingest them on B through
    the real manager/ingester with the production 1000-op pull windows
    (batch prefetch + optimistic single-savepoint pass). vs_baseline =
    speedup over REFERENCE-FAITHFUL ingestion: per-op arbitration queries
    and per-op savepoints (the shape of ingest.rs:114-186's
    receive_crdt_operation) at the reference test's 100-op pull window
    (core/crates/sync tests/lib.rs:140) — i.e. production pipeline vs the
    reference design on identical hardware and data."""
    import shutil

    from spacedrive_tpu.models import Tag
    from spacedrive_tpu.node import Node
    from spacedrive_tpu.sync.ingest import Ingester

    n_ops = int(os.environ.get("SD_BENCH_SYNC_OPS", "30000"))
    tmp = Path(tempfile.mkdtemp(prefix="sd_bench_sync_"))
    try:
        node_a = Node(tmp / "a", probe_accelerator=False, watch_locations=False)
        node_b = Node(tmp / "b", probe_accelerator=False, watch_locations=False)
        lib_a = node_a.libraries.create("bench")
        lib_b = node_b.libraries.create("bench-mirror")
        lib_a.sync.emit_messages = True
        lib_a.add_remote_instance(lib_b.instance())
        lib_b.add_remote_instance(lib_a.instance())

        t0 = time.perf_counter()
        for start in range(0, n_ops, 200):
            ops, rows = [], []
            for i in range(start, min(n_ops, start + 200)):
                pub = f"bench-tag-{i}"
                ops.append(lib_a.sync.shared_create(
                    Tag, pub, {"name": f"t{i}"}))
                rows.append({"pub_id": pub, "name": f"t{i}"})
            lib_a.sync.write_ops(
                ops, lambda db, rows=rows: [db.insert(Tag, r) for r in rows])
        emit_t = time.perf_counter() - t0

        def pull_all(batch: int, reference_mode: bool,
                     use_session: bool = False) -> float:
            # fresh floor each run: reset B's view by ingesting into a
            # throwaway mirror library
            import contextlib

            from spacedrive_tpu.sync.ingest import SESSION_FLUSH_OPS

            mirror = node_b.libraries.create(
                f"m-{batch}-{reference_mode}-{use_session}")
            mirror.add_remote_instance(lib_a.instance())
            ingester = Ingester(mirror, reference_mode=reference_mode)
            t = time.perf_counter()
            total = 0
            has_more = True
            while has_more:
                # session mode groups windows under one durable transaction
                # (the Actor's production shape) so small pull windows don't
                # pay a WAL commit each
                scope = (ingester.session() if use_session
                         else contextlib.nullcontext())
                pulled = 0
                with scope:
                    while True:
                        ops, has_more = lib_a.sync.get_ops(
                            mirror.sync.timestamps(), batch)
                        total += ingester.receive(ops)
                        pulled += len(ops)
                        if not has_more or (use_session
                                            and pulled >= SESSION_FLUSH_OPS):
                            break
            dt = time.perf_counter() - t
            assert total >= n_ops, (total, n_ops)
            return dt

        from spacedrive_tpu import telemetry

        ref_t = pull_all(100, True)     # reference design: per-op, 100-op window
        delay_before = _apply_delay_totals(telemetry)
        prod_t = pull_all(1000, False)  # production: prefetched optimistic pass
        delay_after = _apply_delay_totals(telemetry)
        # small windows through the session path: the 3× batch=100 tax
        # (BENCH_r05: 3.50s vs 1.17s) is per-window commit overhead, not
        # arbitration — grouped flushes should land near the batch=1000 rate
        small_t = pull_all(100, False, use_session=True)
        rate = n_ops / prod_t
        print(f"info: sync {n_ops} shared ops: emit {emit_t:.2f}s | "
              f"ingest batch=1000 {prod_t:.2f}s ({rate:,.0f} ops/s) | "
              f"batch=100 session {small_t:.2f}s ({n_ops / small_t:,.0f} ops/s)"
              f" | reference batch=100 {ref_t:.2f}s", file=sys.stderr)
        node_a.shutdown()
        node_b.shutdown()
        record = {
            "metric": f"sync_ingest_ops_per_sec[{n_ops}ops,2node]",
            "value": round(rate, 1),
            "unit": "ops/sec",
            "vs_baseline": round(ref_t / prod_t, 2),
            "small_window_session_ops_per_sec": round(n_ops / small_t, 1),
            "emit_ops_per_sec": round(n_ops / emit_t, 1),
        }
        # mesh observability ride-along: mean op_created->op_applied delay
        # of the production pull (registry delta over
        # sd_sync_apply_delay_seconds — emit-to-ingest distance on one
        # host, the convergence-lag instrument the fleet soak will read)
        d_count = delay_after[0] - delay_before[0]
        if d_count > 0:
            record["apply_delay_mean_s"] = round(
                (delay_after[1] - delay_before[1]) / d_count, 6)
        return record
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _bench_sanitizer_soak(lanes: int) -> dict:
    """One fleet storm with the lock sanitizer LIVE (ISSUE 14): every
    migrated lock created under ``SD_LOCK_SANITIZER=1`` carries held
    stacks, feeds the global order graph, and records contention
    telemetry — the storm converging with ZERO violations is the
    dynamic deadlock gate, and its wall time is the recorded price of
    running a soak in sanitizer mode."""
    import shutil

    from spacedrive_tpu.utils import locks as sd_locks

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tests.fleet_harness import Fleet

    peers = int(os.environ.get("SD_BENCH_SANITIZER_PEERS", "4"))
    ops_per_peer = int(os.environ.get("SD_BENCH_SANITIZER_OPS", "1000"))
    tmp = Path(tempfile.mkdtemp(prefix="sd_bench_san_"))
    sd_locks.reset_sanitizer()
    prior_env = os.environ.get("SD_LOCK_SANITIZER")
    os.environ["SD_LOCK_SANITIZER"] = "1"
    try:
        t0 = time.perf_counter()
        fleet = Fleet(tmp, peers=peers, lanes=lanes, pipeline=2)
        try:
            res = fleet.run_storm(ops_per_peer=ops_per_peer, batch=250,
                                  emit_chunks=2)
            fleet.drain()
        finally:
            fleet.shutdown()
        wall_s = time.perf_counter() - t0
        bad = sd_locks.violations()
        out = {
            "peers": peers,
            "ops_per_peer": ops_per_peer,
            "wall_s": round(wall_s, 3),
            "ops_per_sec_total": res["ops_per_sec_total"],
            "errors": res["errors"],
            "violations": bad,   # the gate: MUST stay []
        }
        print(f"info: sanitizer-on soak: {peers} peers x {ops_per_peer} "
              f"ops in {wall_s:.2f}s ({res['ops_per_sec_total']:,.0f} "
              f"ops/s), {len(bad)} violations", file=sys.stderr)
        _append_history({
            "metric": f"fleet_sanitizer_soak_wall_s[{peers}peers,"
                      f"{ops_per_peer}ops,{lanes}lanes]",
            "value": round(wall_s, 3),
            "unit": "s",
        })
        return out
    finally:
        # restore, never pop: an operator who exported the sanitizer for
        # the whole run must not have it silently stripped mid-process
        if prior_env is None:
            os.environ.pop("SD_LOCK_SANITIZER", None)
        else:
            os.environ["SD_LOCK_SANITIZER"] = prior_env
        sd_locks.reset_sanitizer()
        shutil.rmtree(tmp, ignore_errors=True)


def bench_fleet() -> dict:
    """Fleet survival headline (ISSUE 8): N synthetic peers hammering one
    node through the real admission budget + partitioned ingest lanes
    (tests/fleet_harness.py, wire-less session mirror), with remote hash
    batches and rspc query traffic alongside. Emits
    ``fleet{peers, ops_per_sec_total, p99_apply_delay_s, shed_ops,
    peak_rss_mb, max_peer_lag_ops}`` and writes the record to
    BENCH_fleet.json — the trajectory file future fleet PRs measure
    against.

    With ``--wan <profile>`` (ISSUE 13) the same storm crosses a modeled
    WAN: the faults/net.py topology matrix named by the profile (shared
    with tests/test_wan.py), relation-heavy workloads, pipelined lane
    submissions, the accept-layer throttle + auto-ban, and — on
    flaky-wan — one scripted BUSY-ignoring flooder. Adds the
    heal-to-lag-zero headline (seconds from the last scheduled partition
    heal until every peer's lag gauge read 0) and the ban ledger; writes
    BENCH_fleet_wan.json instead so the wire-perfect trajectory file
    stays comparable run-over-run."""
    import shutil

    from spacedrive_tpu import telemetry
    from spacedrive_tpu.faults import net

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tests.fleet_harness import WAN_RETRY, Fleet

    wan = WAN_PROFILE
    peers = int(os.environ.get("SD_BENCH_FLEET_PEERS",
                               "64" if wan else "8"))
    ops_per_peer = int(os.environ.get("SD_BENCH_FLEET_OPS",
                                      "96" if wan else "5000"))
    lanes = int(os.environ.get("SD_BENCH_FLEET_LANES", "4"))
    telemetry.set_enabled(True)
    tmp = Path(tempfile.mkdtemp(prefix="sd_bench_fleet_"))
    model = None
    try:
        if wan:
            from spacedrive_tpu.p2p.throttle import AutoBan, SessionThrottle

            model = net.install(net.profile_plan(wan),
                                seed=int(os.environ.get("SD_NET_SEED", "13")))
            fleet = Fleet(tmp, peers=peers, lanes=lanes,
                          flooder=(wan == "flaky-wan"), pipeline=2,
                          throttle=SessionThrottle(rate=20.0, burst=12.0),
                          ban=AutoBan(strikes=6, window_s=5.0, ban_s=2.0,
                                      max_ban_s=8.0),
                          retry=WAN_RETRY)
        else:
            fleet = Fleet(tmp, peers=peers, lanes=lanes)

        def _lane_ops() -> dict[str, float]:
            return {lbl.get("lane", "?"): v for lbl, v in
                    telemetry.series_values("sd_sync_ingest_lane_ops_total")}

        lane_ops0 = _lane_ops()
        try:
            res = fleet.run_storm(ops_per_peer=ops_per_peer, batch=500,
                                  emit_chunks=4 if wan else 2,
                                  hash_traffic=True, query_traffic=True,
                                  rich=bool(wan),
                                  # paced WAN bursts span the partition
                                  # schedule on any machine speed
                                  burst_gap_s=2.6 if wan else 0.0)
            storm_end = time.monotonic()
            drain_s = fleet.drain()
            heal_to_lag_zero_s = None
            if model is not None and model.last_heal_s() > 0:
                # lag hit 0 when the drain finished; the last heal was
                # last_heal_s after the storm-relative epoch (profiles
                # without partition windows have no heal to anchor on)
                heal_wall = (storm_end - res["elapsed_s"]
                             + model.last_heal_s())
                heal_to_lag_zero_s = round(
                    max(0.0, storm_end + drain_s - heal_wall), 3)
            converged_target = len(
                fleet.target_lib.db.query(
                    "SELECT id FROM shared_operation")) \
                + len(fleet.target_lib.db.query(
                    "SELECT id FROM relation_operation")) \
                == peers * ops_per_peer
        finally:
            fleet.shutdown()
        # lane-occupancy skew (ISSUE 17 satellite): max/mean of the per-lane
        # applied-ops deltas over this storm — 1.0 is a perfectly balanced
        # hash partition, rising values mean hot lanes are serializing the
        # ingest that the lanes exist to parallelize
        lane_deltas = [v - lane_ops0.get(k, 0.0)
                       for k, v in _lane_ops().items()]
        lane_deltas = [d for d in lane_deltas if d > 0]
        lane_skew = (round(max(lane_deltas)
                           / (sum(lane_deltas) / len(lane_deltas)), 3)
                     if lane_deltas else 0.0)
        record = {
            "metric": (f"fleet_ops_per_sec[{peers}peers,"
                       f"{ops_per_peer}ops,{lanes}lanes"
                       + (f",wan={wan}" if wan else "") + "]"),
            "value": res["ops_per_sec_total"],
            "unit": "ops/sec",
            "fleet": {
                "peers": peers,
                "ops_per_sec_total": res["ops_per_sec_total"],
                "p99_apply_delay_s": res["p99_apply_delay_s"],
                "shed_ops": res["shed_ops"],
                "peak_rss_mb": res["peak_rss_mb"],
                "max_peer_lag_ops": res["max_peer_lag_ops"],
            },
            "lanes": lanes,
            "lane_skew": lane_skew,
            "ops_total": res["ops_total"],
            "elapsed_s": res["elapsed_s"],
            "shed_windows": res["shed_windows"],
            "sessions": res["sessions"],
            "hash_batches": res["hash_batches"],
            "max_admission_ops": res["max_admission_ops"],
            "max_lane_depth": res["max_lane_depth"],
            "rss_growth_mb": res["rss_growth_mb"],
            "errors": res["errors"],
            "converged": converged_target,
        }
        if wan:
            record["wan"] = {
                "profile": wan,
                "plan": net.profile_plan(wan),
                "heal_to_lag_zero_s": heal_to_lag_zero_s,
                "net": res["net"],
                "ban": res["ban"],
                "ban_ledger": res["ban_ledger"],
                "flooder": res["flooder"],
                "max_banned_peers": res["max_banned_peers"],
                "pipeline": 2,
            }
        if not wan:
            # ISSUE 14: the soak as a deadlock detector — a second,
            # smaller storm with SD_LOCK_SANITIZER=1 so every migrated
            # lock created from here on is sanitized (held stacks, order
            # graph, contention telemetry). The WAN variant skips it: the
            # installed net model would fold modeled latency into the
            # wall time and the number would stop meaning "sanitizer".
            record["sanitizer_soak"] = _bench_sanitizer_soak(lanes)
        out = Path(__file__).resolve().parent / (
            "BENCH_fleet_wan.json" if wan else "BENCH_fleet.json")
        out.write_text(json.dumps(record, indent=1) + "\n")
        print(f"info: fleet {peers} peers x {ops_per_peer} ops, {lanes} "
              f"lanes{f', wan={wan}' if wan else ''}: "
              f"{res['ops_per_sec_total']:,.0f} ops/s total, "
              f"{res['shed_ops']} ops shed, lane skew {lane_skew:.2f}, "
              f"peak RSS {res['peak_rss_mb']:.0f}MB -> {out.name}",
              file=sys.stderr)
        if lane_skew:
            # second fleet headline (standing invariant: every bench mode
            # appends its headlines): lane-occupancy balance trajectory
            _append_history({
                "metric": f"fleet_lane_skew[{peers}peers,{lanes}lanes"
                          + (f",wan={wan}" if wan else "") + "]",
                "value": lane_skew,
                "unit": "max/mean",
            })
        if wan and heal_to_lag_zero_s is not None:
            # the second WAN headline rides the history too (standing
            # invariant: every bench mode appends its headlines)
            _append_history({
                "metric": f"fleet_heal_to_lag_zero_s[{peers}peers,"
                          f"wan={wan}]",
                "value": heal_to_lag_zero_s,
                "unit": "s",
            })
            print(f"info: heal-to-lag-zero {heal_to_lag_zero_s:.2f}s, "
                  f"bans {len(res['ban_ledger'])} ledger entries",
                  file=sys.stderr)
        return record
    finally:
        if model is not None:
            net.clear()
        shutil.rmtree(tmp, ignore_errors=True)


def _rspc_histogram_deltas(telemetry, before: dict) -> dict:
    """Per-procedure (bucket_counts, sum, count) deltas of
    sd_rspc_request_seconds since ``before`` (same helper's output) —
    the serve bench's quantiles are computed over ITS window, not the
    process lifetime."""
    from spacedrive_tpu.telemetry.requests import REQUEST_BUCKETS

    fam = telemetry.histogram("sd_rspc_request_seconds",
                              labels=("proc",), buckets=REQUEST_BUCKETS)
    out = {}
    for labels, series in fam.series_items():
        counts, total, n = series.read()
        b_counts, b_total, b_n = before.get(
            labels["proc"], ([0] * len(counts), 0.0, 0))
        out[labels["proc"]] = (
            [c - b for c, b in zip(counts, b_counts)],
            total - b_total, n - b_n)
    return out


def bench_serve() -> dict:
    """Serving-tier load bench (ISSUE 10): N client threads drive
    concurrent ``search.paths`` (substring search + directory listings)
    + ``search.pathsCount`` + ranged file fetches + thumbnail misses
    over real HTTP against the shell WHILE a pipelined identify scan
    runs. Per-procedure p50/p95/p99 and error rates are read from the
    new ``sd_rspc_*`` histograms (window deltas); a post-scan fixed
    window A/Bs telemetry on vs off (the 0.95× overhead gate, extended
    to the read path). Writes BENCH_serve.json."""
    import random
    import shutil
    import threading
    import urllib.error
    import urllib.request

    from spacedrive_tpu import telemetry
    from spacedrive_tpu.locations import create_location
    from spacedrive_tpu.locations.indexer_job import IndexerJob
    from spacedrive_tpu.node import Node
    from spacedrive_tpu.objects.file_identifier import FileIdentifierJob
    from spacedrive_tpu.objects.media.processor import MediaProcessorJob
    from spacedrive_tpu.server.shell import Server
    from spacedrive_tpu.telemetry.registry import estimate_quantiles
    from spacedrive_tpu.telemetry.requests import REQUEST_BUCKETS

    n_files = int(os.environ.get("SD_BENCH_SERVE_FILES", "20000"))
    clients = int(os.environ.get("SD_BENCH_SERVE_CLIENTS", "8"))
    ab_window_s = float(os.environ.get("SD_BENCH_SERVE_AB_S", "8"))
    fixture = _ensure_scan_fixture(n_files)
    telemetry.set_enabled(True)
    tmp = Path(tempfile.mkdtemp(prefix="sd_bench_serve_"))
    server = None
    node = None
    try:
        node = Node(tmp, probe_accelerator=False, watch_locations=False)
        node.thumbnail_remover.stop()
        lib = node.libraries.create("serve")
        lib.orphan_remover.stop()
        loc = create_location(lib, str(fixture), hasher="cpu")
        args = {"location_id": loc["id"]}
        # index first: the read path needs rows to serve; identify+media
        # run DURING the traffic window below (the north-star scenario)
        node.jobs.spawn(lib, [IndexerJob(dict(args))],
                        action="scan_location")
        assert node.jobs.wait_idle(3600)
        fp_ids = [r["id"] for r in lib.db.query(
            "SELECT id FROM file_path WHERE is_dir=0 ORDER BY id LIMIT 512")]
        dirs = [r["materialized_path"] for r in lib.db.query(
            "SELECT DISTINCT materialized_path FROM file_path "
            "WHERE is_dir=0 LIMIT 64")]
        server = Server(node, port=0)
        server.start()
        base = f"http://127.0.0.1:{server.port}"

        def rspc(key: str, arg: dict) -> None:
            body = json.dumps({"library_id": lib.id, "arg": arg}).encode()
            req = urllib.request.Request(
                f"{base}/rspc/{key}", data=body,
                headers={"content-type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                resp.read()

        def one_request(rng: random.Random, counts: dict) -> None:
            roll = rng.random()
            try:
                if roll < 0.35:       # substring search
                    rspc("search.paths",
                         {"search": f"f{rng.randrange(n_files):06d}"[:5],
                          "take": 64})
                    counts["search"] += 1
                elif roll < 0.60:     # directory listing (explorer browse)
                    rspc("search.paths",
                         {"materialized_path": rng.choice(dirs),
                          "dirs_first": True, "take": 200})
                    counts["listing"] += 1
                elif roll < 0.70:     # count badge
                    rspc("search.pathsCount", {"location_id": loc["id"]})
                    counts["count"] += 1
                elif roll < 0.95:     # ranged file fetch (custom_uri)
                    fp = rng.choice(fp_ids)
                    req = urllib.request.Request(
                        f"{base}/spacedrive/file/{lib.id}/{loc['id']}/{fp}",
                        headers={"range": "bytes=0-4095"})
                    with urllib.request.urlopen(req, timeout=30) as resp:
                        resp.read()
                    counts["file_range"] += 1
                else:                 # thumbnail miss path (no media in
                    cas = "0" * 32    # the fixture; exercises the 404 arm)
                    try:
                        urllib.request.urlopen(
                            f"{base}/spacedrive/thumbnail/{cas[:2]}/"
                            f"{cas}.webp", timeout=30).read()
                    except urllib.error.HTTPError:
                        pass
                    counts["thumbnail"] += 1
            except Exception:
                counts["client_errors"] += 1

        def traffic(stop_when, seed: int) -> dict:
            counts = {k: 0 for k in ("search", "listing", "count",
                                     "file_range", "thumbnail",
                                     "client_errors")}
            rng = random.Random(seed)
            while not stop_when():
                one_request(rng, counts)
            return counts

        window_nonce = [0]

        def run_window(stop_when) -> tuple[dict, float]:
            totals = {k: 0 for k in ("search", "listing", "count",
                                     "file_range", "thumbnail",
                                     "client_errors")}
            results: list[dict] = []
            # distinct request streams per window: a replayed seed would
            # let the serve-pool page cache (ISSUE 11) answer the whole
            # window from memory, and every A/B would then measure cache
            # warm-up drift instead of the thing it toggles
            window_nonce[0] += 1
            nonce = window_nonce[0] * 10_000

            def worker(i: int) -> None:
                results.append(traffic(stop_when, seed=nonce + i))

            threads = [threading.Thread(target=worker, args=(i,),
                                        daemon=True)
                       for i in range(clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            for r in results:
                for k, v in r.items():
                    totals[k] += v
            return totals, dt

        # -- the measured window: traffic while the scan is LIVE ----------
        hist_before = _rspc_histogram_deltas(telemetry, {})
        req_before = {(lbl["proc"], lbl["outcome"]): v for lbl, v in
                      telemetry.series_values("sd_rspc_requests_total")}
        node.jobs.spawn(lib, [FileIdentifierJob(dict(args)),
                              MediaProcessorJob(dict(args))],
                        action="scan_location")
        scan_t0 = time.perf_counter()
        totals, window_dt = run_window(
            lambda: not node.jobs.is_active()
            and time.perf_counter() - scan_t0 > 1.0)
        assert node.jobs.wait_idle(3600)
        scan_dt = time.perf_counter() - scan_t0
        n_identified = lib.db.query(
            "SELECT count(*) c FROM file_path WHERE cas_id IS NOT NULL"
        )[0]["c"]
        assert n_identified == n_files, (n_identified, n_files)

        procs = {}
        for proc, (counts, total, n) in _rspc_histogram_deltas(
                telemetry, hist_before).items():
            if n <= 0:
                continue
            q = estimate_quantiles(tuple(REQUEST_BUCKETS), counts)
            errors = sum(
                v - req_before.get((lbl["proc"], lbl["outcome"]), 0)
                for lbl, v in
                telemetry.series_values("sd_rspc_requests_total")
                if lbl["proc"] == proc and lbl["outcome"] != "ok")
            procs[proc] = {
                "count": int(n),
                "p50_ms": round(q[0.5] * 1000, 2),
                "p95_ms": round(q[0.95] * 1000, 2),
                "p99_ms": round(q[0.99] * 1000, 2),
                "mean_ms": round(total / n * 1000, 2),
                "errors": int(errors),
                "error_rate": round(errors / n, 4),
            }
        requests_total = sum(totals.values()) - totals["client_errors"]
        rps_during_scan = requests_total / window_dt if window_dt else 0.0

        # -- same-session A/B on the quiet node: telemetry+profiler on
        # vs off over a fixed window (the read-path overhead gate) -------
        def timed_window() -> float:
            deadline = time.perf_counter() + ab_window_s
            totals_ab, dt = run_window(
                lambda: time.perf_counter() > deadline)
            n_ok = sum(totals_ab.values()) - totals_ab["client_errors"]
            return n_ok / dt if dt else 0.0

        # untimed warmup: reach steady state (page caches, OS buffers,
        # thread pools) BEFORE any A/B window — otherwise monotonic
        # warm-up drift systematically advantages whichever side runs
        # later, regardless of what the A/B toggles
        timed_window()

        # interleaved on→off→on→off, best of each PAIR — both sides get
        # two samples (like the scan bench's A/B), so one unlucky window
        # on either side can't skew the 0.95× gate. The telemetry A/B
        # runs on the IN-PROCESS path (pool bypassed): each A/B toggles
        # exactly one variable on a stable substrate — with the pool in
        # the loop, per-window page-cache hit-mix variance (±40% on this
        # container) would drown the few-percent telemetry cost it
        # exists to bound
        pool = node.reader_pool
        if pool is not None:
            pool.set_enabled(False)
        rps_on = timed_window()
        telemetry.set_enabled(False)
        rps_off = timed_window()
        telemetry.set_enabled(True)
        rps_on = max(rps_on, timed_window())
        telemetry.set_enabled(False)
        rps_off = max(rps_off, timed_window())
        telemetry.set_enabled(True)
        if pool is not None:
            pool.set_enabled(True)
        overhead = {
            "rps_on": round(rps_on, 1),
            "rps_off": round(rps_off, 1),
            "on_vs_off": round(rps_on / rps_off, 3) if rps_off else 0.0,
        }

        # -- pool-vs-in-process A/B (ISSUE 11): same session, same quiet
        # node — the pool bypass toggles per window, so both sides see
        # identical caches/pages. SD_SERVE_WORKERS=0 keeps the whole
        # bench on the degraded in-process path (pool_ab = None then).
        pool_ab = None
        if pool is not None:
            rps_pool = timed_window()
            pool.set_enabled(False)
            rps_inproc = timed_window()
            pool.set_enabled(True)
            rps_pool = max(rps_pool, timed_window())
            pool.set_enabled(False)
            rps_inproc = max(rps_inproc, timed_window())
            pool.set_enabled(True)
            pool_ab = {
                "rps_pool": round(rps_pool, 1),
                "rps_inproc": round(rps_inproc, 1),
                "pool_vs_inproc": (round(rps_pool / rps_inproc, 3)
                                   if rps_inproc else 0.0),
            }

        record = {
            "metric": (f"serve_requests_per_sec[{clients}clients,"
                       f"{n_files}files,during-scan]"),
            "value": round(rps_during_scan, 1),
            "unit": "requests/sec",
            "scan_files_per_sec": round(n_files / scan_dt, 1),
            "window_s": round(window_dt, 2),
            "clients": clients,
            "mix": totals,
            "procedures": procs,
            "serve_overhead": overhead,
            "serve_pool_ab": pool_ab,
            "serve_pool": pool.status() if pool is not None else None,
        }
        from spacedrive_tpu.telemetry import requests as rq

        record["slow_requests"] = len(rq.slow_requests())
        out = Path(__file__).resolve().parent / "BENCH_serve.json"
        out.write_text(json.dumps(record, indent=1) + "\n")
        if pool_ab is not None:
            # the degraded-mode headline rides the history too, so the
            # trajectory shows BOTH serving modes run-over-run
            _append_history({
                "metric": (f"serve_requests_per_sec[{clients}clients,"
                           f"{n_files}files,inprocess-quiet]"),
                "value": pool_ab["rps_inproc"],
                "unit": "requests/sec",
            })
        print(f"info: serve {clients} clients over {window_dt:.1f}s "
              f"during a live scan: {rps_during_scan:,.0f} req/s "
              f"({requests_total} requests, "
              f"{totals['client_errors']} client errors) | scan held "
              f"{n_files / scan_dt:,.0f} files/s | A/B on/off "
              f"{overhead['on_vs_off']:.3f}x | pool/inproc "
              f"{pool_ab['pool_vs_inproc'] if pool_ab else 'n/a'}x "
              f"-> {out.name}",
              file=sys.stderr)
        for proc, p in sorted(procs.items()):
            print(f"info:   {proc}: n={p['count']} p50 {p['p50_ms']}ms "
                  f"p95 {p['p95_ms']}ms p99 {p['p99_ms']}ms err "
                  f"{p['error_rate']:.2%}", file=sys.stderr)
        return record
    finally:
        if server is not None:
            server.stop()
        if node is not None:
            node.shutdown()
        shutil.rmtree(tmp, ignore_errors=True)


def bench_serve_wan() -> dict:
    """Distributed replica serve bench (ISSUE 19), ``--serve --wan
    <profile>``: an N-peer fleet with two armed read replicas serves
    pool-marked queries over the modeled WAN WHILE the ingest storm
    runs. On flaky-wan the profile's two partition waves each cut one
    replica from the mesh mid-storm, so the strict ladder
    replica → local pool → in-process has to degrade and recover twice.
    Gates: the serve probes hold their tail SLO through both waves,
    zero pre-watermark (stale) rows ever leave a replica, every
    degradation is accounted by reason in ``sd_replica_failovers_total``,
    and at the quiescent point every replica serves the full id-free
    query matrix byte-identically to the target's in-process path.
    Headline: serve-probe p99 ms; record to BENCH_serve_wan.json."""
    import shutil

    from spacedrive_tpu import telemetry
    from spacedrive_tpu.faults import net
    from spacedrive_tpu.server.pool import ReaderPool
    from spacedrive_tpu.telemetry.registry import estimate_quantiles
    from spacedrive_tpu.telemetry.requests import REQUEST_BUCKETS

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tests.fleet_harness import WAN_RETRY, Fleet, replica_counters

    wan = WAN_PROFILE
    peers = int(os.environ.get("SD_BENCH_SERVE_PEERS", "12"))
    ops_per_peer = int(os.environ.get("SD_BENCH_SERVE_OPS", "400"))
    lanes = int(os.environ.get("SD_BENCH_SERVE_LANES", "2"))
    slo_p99_s = float(os.environ.get("SD_BENCH_SERVE_SLO_P99_S", "5.0"))
    telemetry.set_enabled(True)
    tmp = Path(tempfile.mkdtemp(prefix="sd_bench_serve_wan_"))
    model = net.install(net.profile_plan(wan),
                        seed=int(os.environ.get("SD_NET_SEED", "13")))
    fleet = None
    pools: list = []
    try:
        fleet = Fleet(tmp, peers=peers, lanes=lanes, retry=WAN_RETRY)
        # one replica on each side of flaky-wan's partition schedule:
        # fleet-peer-00 sits in the first wave (fleet-peer-0*), the
        # second replica in the second wave (fleet-peer-1*) when the
        # fleet is big enough to have one — each wave then cuts exactly
        # one replica while the other keeps serving
        rep_indices = sorted({0, 10 if peers > 10 else peers - 1})
        replicas = fleet.arm_replicas(indices=rep_indices, max_attempts=2)
        for peer in replicas:
            peer.node.reader_pool = ReaderPool(peer.node, workers=1).start()
            pools.append(peer.node.reader_pool)
        fleet.target.reader_pool = ReaderPool(fleet.target,
                                              workers=1).start()
        pools.append(fleet.target.reader_pool)

        res = fleet.run_storm(ops_per_peer=ops_per_peer, batch=200,
                              emit_chunks=4, serve_traffic=True,
                              rich=True,
                              # paced bursts span flaky-wan's partition
                              # schedule (last heal at 7.0s) on any
                              # machine speed
                              burst_gap_s=2.6)
        storm_end = time.monotonic()
        drain_s = fleet.drain()
        heal_to_lag_zero_s = None
        if model.last_heal_s() > 0:
            heal_wall = (storm_end - res["elapsed_s"]
                         + model.last_heal_s())
            heal_to_lag_zero_s = round(
                max(0.0, storm_end + drain_s - heal_wall), 3)
        net_status = res["net"]
        net.clear()
        fleet.stop_replica_mirror(drain=True)
        ledger = replica_counters()
        identity = fleet.replica_identity_report()

        # -- gates (the bench IS the acceptance harness) ------------------
        assert res["errors"] == [], res["errors"]
        st = fleet.serve_stats
        assert st["queries"] > 20, st
        # the zero-pre-watermark claim: count-monotonicity probes never
        # saw a stale row, and no probe errored
        assert st["stale"] == 0, st["errors"][:5]
        assert st["errors"] == [], st["errors"][:5]
        # the replica rung served real traffic, and every degradation
        # the ladder took is accounted by reason
        assert ledger["dispatch"].get("ok", 0) > 0, ledger
        assert set(ledger["failover"]) <= {"busy", "error",
                                           "not_eligible", "no_peers"}
        assert set(ledger["serve"]) <= {"ok", "not_eligible", "busy",
                                        "error"}
        if model.last_heal_s() > 0:
            # the waves really cut links, and the ladder degraded at
            # least once while they were open
            assert telemetry.value("sd_net_link_messages_total",
                                   verdict="cut") > 0
            assert sum(ledger["failover"].values()) > 0, ledger
        # quiescent byte-identity: every replica x id-free pool query
        # serves the exact bytes the target's handler encodes
        assert identity and all(identity.values()), identity

        # -- tail SLOs: the serve probes (full ladder, partitions and
        # all) and the replica round-trip histogram ----------------------
        lats = sorted(st["latencies_s"])

        def q(p: float) -> float:
            return (lats[min(len(lats) - 1, int(p * len(lats)))]
                    if lats else 0.0)

        probe = {"count": len(lats),
                 "p50_ms": round(q(0.50) * 1000, 2),
                 "p95_ms": round(q(0.95) * 1000, 2),
                 "p99_ms": round(q(0.99) * 1000, 2)}
        assert q(0.99) <= slo_p99_s, (probe, slo_p99_s)

        fam = telemetry.histogram("sd_replica_request_seconds",
                                  labels=("peer",),
                                  buckets=REQUEST_BUCKETS)
        agg: list[float] | None = None
        rtt_total, rtt_n = 0.0, 0
        for _lbls, series in fam.series_items():
            counts, total, n = series.read()
            agg = (list(counts) if agg is None
                   else [a + c for a, c in zip(agg, counts)])
            rtt_total += total
            rtt_n += int(n)
        replica_rtt = None
        if agg is not None and rtt_n > 0:
            rq = estimate_quantiles(tuple(REQUEST_BUCKETS), agg)
            replica_rtt = {"count": rtt_n,
                           "p50_ms": round(rq[0.5] * 1000, 2),
                           "p95_ms": round(rq[0.95] * 1000, 2),
                           "p99_ms": round(rq[0.99] * 1000, 2),
                           "mean_ms": round(rtt_total / rtt_n * 1000, 2)}

        dispatched = sum(ledger["dispatch"].values())
        ok_share = (round(ledger["dispatch"].get("ok", 0.0)
                          / dispatched, 3) if dispatched else 0.0)
        record = {
            "metric": (f"serve_replica_probe_p99_ms[{peers}peers,"
                       f"{len(replicas)}replicas,wan={wan}]"),
            "value": probe["p99_ms"],
            "unit": "ms",
            "serve_probe": probe,
            "replica_rtt": replica_rtt,
            "replica_ledger": ledger,
            "replica_ok_share": ok_share,
            "router": fleet.target.replica_router.status(),
            "identity": identity,
            "stale": st["stale"],
            "queries": st["queries"],
            "wan": {
                "profile": wan,
                "plan": net.profile_plan(wan),
                "heal_to_lag_zero_s": heal_to_lag_zero_s,
                "net": net_status,
            },
            "fleet": {
                "peers": peers,
                "replicas": [p.identity for p in replicas],
                "lanes": lanes,
                "ops_per_peer": ops_per_peer,
                "ops_per_sec_total": res["ops_per_sec_total"],
                "p99_apply_delay_s": res["p99_apply_delay_s"],
                "max_peer_lag_ops": res["max_peer_lag_ops"],
                "peak_rss_mb": res["peak_rss_mb"],
            },
        }
        out = Path(__file__).resolve().parent / "BENCH_serve_wan.json"
        out.write_text(json.dumps(record, indent=1) + "\n")
        # second headline (standing invariant: every bench mode appends
        # its headlines): how much of the serve load the replica rung
        # actually carried through the chaos
        _append_history({
            "metric": (f"serve_replica_ok_share[{peers}peers,"
                       f"{len(replicas)}replicas,wan={wan}]"),
            "value": ok_share,
            "unit": "ratio",
        })
        print(f"info: serve-wan {peers} peers / {len(replicas)} replicas "
              f"over wan={wan}: {st['queries']} probes, 0 stale, "
              f"probe p99 {probe['p99_ms']}ms, replica ok-share "
              f"{ok_share:.0%}, failovers "
              f"{ {k: int(v) for k, v in ledger['failover'].items()} }, "
              f"heal-to-lag-zero "
              f"{heal_to_lag_zero_s if heal_to_lag_zero_s is not None else 'n/a'}s "
              f"-> {out.name}", file=sys.stderr)
        return record
    finally:
        net.clear()
        for pool in pools:
            pool.stop()
        if fleet is not None:
            fleet.shutdown()
        shutil.rmtree(tmp, ignore_errors=True)


def bench_search() -> dict:
    """Device query engine headline (ISSUE 15): a synthetic corpus
    (SD_BENCH_SEARCH_N objects, default 1M) served through the REAL
    router twice per query — engine armed (columnar index scored by the
    JAX/Pallas kernels, routed per query by the search BackendRouter)
    vs the SQLite path — with byte-identical results asserted for every
    query in the matrix (substring / prefix-dir / extension / filters /
    date / size / cursor + offset pagination). Headline: engine
    queries/s vs SQLite queries/s. Writes BENCH_search.json."""
    import shutil
    import statistics

    from spacedrive_tpu import telemetry
    from spacedrive_tpu.models import FilePath, Instance, Location, Object
    from spacedrive_tpu.node import Node

    n_rows = int(os.environ.get("SD_BENCH_SEARCH_N", "1000000"))
    repeats = int(os.environ.get("SD_BENCH_SEARCH_REPEATS", "5"))
    os.environ["SD_SEARCH_ENGINE"] = "device"
    telemetry.set_enabled(True)
    tmp = Path(tempfile.mkdtemp(prefix="sd_bench_search_"))
    node = None
    try:
        node = Node(tmp, probe_accelerator=False, watch_locations=False)
        node.thumbnail_remover.stop()
        lib = node.libraries.create("search-bench")
        lib.orphan_remover.stop()
        db = lib.db
        loc_id = db.insert(Location, {
            "pub_id": "loc-bench", "name": "bench", "path": "/bench",
            "instance_id": lib.instance_id})

        # -- corpus: word-salad names over a directory tree, ~1% objects
        # carrying kind/favorite, deterministic (seeded) ----------------
        import random

        rng = random.Random(15)
        words = ["report", "photo", "invoice", "backup", "video", "track",
                 "draft", "final", "holiday", "scan", "render", "notes",
                 "meeting", "budget", "design", "export", "raw", "edit"]
        exts = ["pdf", "jpg", "png", "mov", "mp4", "txt", "doc", "zip",
                "flac", "dng", None]
        dirs = ["/"] + [f"/{a}/{b}/" for a in words[:8] for b in words[8:]]
        t_corpus = time.perf_counter()
        n_objects = max(1, n_rows // 100)
        db.executemany(
            "INSERT INTO object (pub_id, kind, favorite) VALUES (?, ?, ?)",
            [(f"ob-{i}", i % 8, int(i % 5 == 0))
             for i in range(n_objects)])
        first_obj = db.query("SELECT MIN(id) m FROM object")[0]["m"]
        chunk: list[tuple] = []
        for i in range(n_rows):
            name = (f"{rng.choice(words)}-{rng.choice(words)}"
                    f"-{i:07d}.{rng.choice(exts[:-1])}")
            chunk.append((
                f"fp-{i:07d}", loc_id, rng.choice(dirs), name,
                rng.choice(exts), 0,
                rng.choice((None, 0, 0, 0, 1)),
                rng.randrange(1, 1 << 30),
                first_obj + (i % n_objects) if i % 2 else None,
                f"2026-{1 + i % 12:02d}-{1 + i % 28:02d}T"
                f"{i % 24:02d}:{i % 60:02d}:00+00:00"))
            if len(chunk) >= 20000:
                db.executemany(
                    "INSERT INTO file_path (pub_id, location_id, "
                    "materialized_path, name, extension, is_dir, hidden, "
                    "size_in_bytes, object_id, date_created) VALUES "
                    "(?,?,?,?,?,?,?,?,?,?)", chunk)
                chunk = []
        if chunk:
            db.executemany(
                "INSERT INTO file_path (pub_id, location_id, "
                "materialized_path, name, extension, is_dir, hidden, "
                "size_in_bytes, object_id, date_created) VALUES "
                "(?,?,?,?,?,?,?,?,?,?)", chunk)
        corpus_s = time.perf_counter() - t_corpus

        engine = node.search_engine
        assert engine is not None, "SD_SEARCH_ENGINE gate did not arm"
        node.emit("db.commit", None, lib.id)
        t_build = time.perf_counter()
        engine.refresh_now(lib)
        build_s = time.perf_counter() - t_build
        status = engine.status()["libraries"][lib.id]
        assert status["fresh"], status

        matrix = [
            ("substring_rare", "search.paths",
             {"search": "holiday-budget-00", "take": 100}),
            ("substring_word", "search.pathsCount", {"search": "invoice"}),
            ("substring_cold", "search.paths",
             {"search": "zq-never-written", "take": 100}),
            ("prefix_dir", "search.paths",
             {"materialized_path": dirs[3], "search": "design",
              "take": 200}),
            ("extension", "search.pathsCount",
             {"extensions": ["flac", ".DNG"]}),
            ("filters_kind_fav", "search.pathsCount",
             {"kinds": [2, 3], "favorite": True}),
            ("date_range", "search.pathsCount",
             {"date_range": ["2026-06-01T00:00:00+00:00",
                             "2026-06-30T23:59:59+00:00"],
              "search": "render"}),
            ("size_range", "search.pathsCount",
             {"size_range": [1 << 28, None], "search": "raw-"}),
            ("paginate_cursor", "search.paths",
             {"search": "photo-track", "take": 50}),
            ("paginate_offset", "search.paths",
             {"search": "meeting", "take": 50, "skip": 100}),
        ]

        def run(key, arg):
            t0 = time.perf_counter()
            out = node.router.resolve(key, arg, lib.id)
            return time.perf_counter() - t0, out

        per_query: dict[str, dict] = {}
        lat_engine: list[float] = []
        lat_sqlite: list[float] = []
        # untimed warmup: the first engine pass per predicate shape pays
        # jit tracing/compilation — steady-state is what the headline
        # measures (the compile cost is once-per-process, amortized over
        # the serving lifetime; index/corpus build costs ARE reported)
        for _label, key, arg in matrix:
            engine.set_enabled(True)
            run(key, arg)
            engine.set_enabled(False)
            run(key, arg)
        for label, key, arg in matrix:
            engine.set_enabled(True)
            engine_lat, engine_out = [], None
            for _ in range(repeats):
                dt, engine_out = run(key, arg)
                engine_lat.append(dt)
            cursor = (engine_out or {}).get("cursor") \
                if isinstance(engine_out, dict) else None
            engine.set_enabled(False)
            sqlite_lat, sqlite_out = [], None
            for _ in range(repeats):
                dt, sqlite_out = run(key, arg)
                sqlite_lat.append(dt)
            # byte-identity is the gate, not a spot check
            assert json.dumps(engine_out, sort_keys=True, default=str) \
                == json.dumps(sqlite_out, sort_keys=True, default=str), label
            if cursor is not None:
                page_arg = {**arg, "cursor": cursor}
                page_arg.pop("skip", None)
                engine.set_enabled(True)
                _, p_dev = run(key, page_arg)
                engine.set_enabled(False)
                _, p_sql = run(key, page_arg)
                assert json.dumps(p_dev, sort_keys=True, default=str) \
                    == json.dumps(p_sql, sort_keys=True, default=str), label
            engine.set_enabled(True)
            lat_engine.extend(engine_lat)
            lat_sqlite.extend(sqlite_lat)
            per_query[label] = {
                "engine_ms": round(min(engine_lat) * 1000, 2),
                "sqlite_ms": round(min(sqlite_lat) * 1000, 2),
                "speedup": round(min(sqlite_lat) / max(min(engine_lat),
                                                       1e-9), 2),
            }

        def p99(lat):
            # nearest-rank: ceil(0.99 n) — int(0.99 n) - 1 understates
            # the tail at these sample sizes (n=50 → 48th, ~p96)
            import math

            return sorted(lat)[min(len(lat) - 1,
                                   max(0, math.ceil(0.99 * len(lat)) - 1))]

        engine_qps = len(lat_engine) / max(sum(lat_engine), 1e-9)
        sqlite_qps = len(lat_sqlite) / max(sum(lat_sqlite), 1e-9)
        served = engine.status()["served"]
        record = {
            "metric": "search_engine_queries_per_sec",
            "value": round(engine_qps, 2),
            "unit": "q/s",
            "corpus_rows": n_rows,
            "corpus_build_s": round(corpus_s, 1),
            "index_build_s": round(build_s, 2),
            "index_rows": status["rows"],
            "index_bytes": status["bytes"],
            "sqlite_queries_per_sec": round(sqlite_qps, 2),
            "speedup_vs_sqlite": round(engine_qps / max(sqlite_qps, 1e-9),
                                       2),
            "p99_engine_ms": round(p99(lat_engine) * 1000, 2),
            "p99_sqlite_ms": round(p99(lat_sqlite) * 1000, 2),
            "p50_engine_ms": round(
                statistics.median(lat_engine) * 1000, 2),
            "p50_sqlite_ms": round(
                statistics.median(lat_sqlite) * 1000, 2),
            "byte_identical_matrix": True,
            "router_backend": engine.status()["backend"],
            "kernel": engine.status()["kernel"],
            "served": served,
            "per_query": per_query,
        }
        out_path = Path(__file__).resolve().parent / "BENCH_search.json"
        out_path.write_text(json.dumps(record, indent=2))
        # second headline: the honest relative number (standing
        # invariant: every bench mode appends its headlines)
        _history_extra("search_speedup_vs_sqlite",
                       record["speedup_vs_sqlite"], "x")
        return record
    finally:
        if node is not None:
            node.shutdown()
        shutil.rmtree(tmp, ignore_errors=True)


def _bench_analysis_wall() -> None:
    """Time one whole-tree sdlint run and append the ``analysis_wall_s``
    headline: lint cost is a gate like every other — when the
    whole-program passes (ISSUE 16) slow down, the pre-commit hook's
    wall budget is the first thing to rot, and this history line is how
    the drift is seen before the hook starts failing."""
    if os.environ.get("SD_BENCH_NO_ANALYSIS"):
        return  # combined-mode children: the parent owns the headline
    if MODE == "check_history":
        return  # the read-only sentinel must stay sub-second
    try:
        from spacedrive_tpu.analysis.engine import (build_manager,
                                                    default_root)

        t0 = time.perf_counter()
        findings = build_manager(default_root(), None).check_tree()
        wall = round(time.perf_counter() - t0, 3)
        _history_extra("analysis_wall_s", wall, "s")
        print(f"info: sdlint whole tree {wall}s "
              f"({len(findings)} finding(s))", file=sys.stderr)
    except Exception as e:
        print(f"warn: analysis wall bench skipped: {e}", file=sys.stderr)


def _history_extra(metric: str, value, unit: str) -> None:
    try:
        from spacedrive_tpu.utils.atomic import append_line

        append_line(
            Path(__file__).resolve().parent / "BENCH_history.jsonl",
            json.dumps({"unix": round(time.time(), 1), "rev": _git_rev(),
                        "mode": MODE, "metric": metric, "value": value,
                        "unit": unit}))
    except Exception as e:
        print(f"warn: BENCH_history.jsonl append failed: {e}",
              file=sys.stderr)


def bench_load() -> dict:
    """Open-loop multi-tenant load bench (ISSUE 20): the serving tier
    under an arrival *schedule* instead of a client loop. A closed-loop
    driver slows its own offered rate exactly when the server saturates
    (k workers can never have more than k requests outstanding), which
    hides queue growth; the open-loop harness keeps offering load, so
    saturation lands where operators will see it in production — the
    latency distribution and the shed rate.

    Three phases against one live node (admission budget + reader-pool
    autosizer + SLO engine armed, ``serve_worker:stall`` giving every
    pool-served query an honest service cost):

    1. *flash crowd* — one tenant floods 10x the base rate for a few
       seconds. Gates: the burn-rate alert fires AND resolves, the
       flooding tenant absorbs ~all of the sheds, quiet tenants' p99
       stays near steady state, and the autosizer grows then shrinks.
    2. *curve* — stepped Poisson rates over the Zipf tenant mix; the
       knee (last step with p99 <= 3x base and shed rate <= 1%) is the
       headline ``load_knee_rps``.
    3. *A/B* — closed-loop throughput telemetry-on vs -off (the 0.95x
       overhead gate extended to the admission + SLO + tenant-family
       instrumentation this issue added).

    Scenario grammar via ``SD_LOAD_SCENARIO``: steady | diurnal |
    flash-crowd | cold-cache | mid-scan | partitioned-replica (unset
    runs the full acceptance: flash + steady curve + A/B)."""
    import random
    import shutil
    import threading

    # serving-tier knobs must be pinned BEFORE the node boots: the pool
    # reads worker/autosizer config at construction, the admission
    # budget at Node.__init__, the SLO engine at alerts start. Every
    # setdefault stays operator-overridable.
    stall_s = float(os.environ.get("SD_LOAD_STALL_S", "0.012"))
    os.environ.setdefault("SD_FAULT_STALL_S", str(stall_s))
    os.environ.setdefault("SD_SERVE_WORKERS", "3")
    os.environ.setdefault("SD_SERVE_WORKERS_MIN", "3")
    os.environ.setdefault("SD_SERVE_WORKERS_MAX", "6")
    os.environ.setdefault("SD_SERVE_HEALTH_S", "0.1")
    os.environ.setdefault("SD_SERVE_AUTOSIZE_COOLDOWN_S", "0.5")
    # the admission budget keeps in-flight near pool capacity, so queue
    # waits under overload are tens of ms, not the 2 s shell default —
    # the autosizer thresholds must sit inside that regime to see them
    os.environ.setdefault("SD_SERVE_GROW_WAIT_S", "0.012")
    os.environ.setdefault("SD_SERVE_SHRINK_WAIT_S", "0.0015")
    os.environ.setdefault("SD_SERVE_QUEUE_WAIT_S", "0.25")
    # the budget counts queued + in-service: 8 against a 3-worker floor
    # leaves a ~5-deep queue under a flood, so tail-of-line waits cross
    # the SLO threshold (the burn alert must see real badness before
    # admission flattens it) while the FIFO checkout keeps the worst
    # wait ~2 service times — inside the quiet-tenant fairness promise
    os.environ.setdefault("SD_RSPC_BUDGET", "8")
    os.environ.setdefault("SD_SLO_INTERVAL_S", "0.2")

    n_tenants = int(os.environ.get("SD_LOAD_TENANTS", "200"))
    rates = [float(r) for r in os.environ.get(
        "SD_LOAD_RATES", "25,50,100,200,400").split(",")]
    step_s = float(os.environ.get("SD_LOAD_STEP_S", "3"))
    scenario = os.environ.get("SD_LOAD_SCENARIO", "")
    seed = int(os.environ.get("SD_LOAD_SEED", "0"))
    flash_base_hz = float(os.environ.get("SD_LOAD_FLASH_BASE", "30"))
    flash_crowd_hz = float(os.environ.get("SD_LOAD_FLASH_CROWD", "600"))

    tmp = Path(tempfile.mkdtemp(prefix="sd_bench_load_"))
    # bench-local SLO objective tuned to the stall cost: steady-state
    # pool-served latency is ~stall + dispatch overhead, overload pushes
    # queued requests past ~2x stall — that is the "bad" the burn-rate
    # windows integrate. The threshold snaps UP to a histogram bucket
    # boundary (SLO good counts come from cumulative buckets, so a
    # between-boundaries threshold silently rounds down). Sub-minute
    # windows so firing AND resolution both happen inside one bench run.
    from spacedrive_tpu.telemetry.requests import REQUEST_BUCKETS

    threshold_s = min((b for b in REQUEST_BUCKETS if b >= 1.8 * stall_s),
                      default=REQUEST_BUCKETS[-1])
    slo_path = tmp / "slo_objectives.json"
    slo_path.write_text(json.dumps([{
        "name": "load-fast", "threshold_s": threshold_s, "target": 0.9,
        "window_s": 60.0, "fast_windows": [1.0, 3.0],
        "slow_windows": [2.0, 6.0], "fast_burn": 2.0, "slow_burn": 1.5,
        "severity": "page",
        "description": "bench: pool-served reads under ~2x service cost",
    }]))
    os.environ.setdefault("SD_SLO_OBJECTIVES", str(slo_path))

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tests.load_harness import (ClosedLoopRunner, OpenLoopRunner,
                                    flash_crowd_arrivals, diurnal_arrivals,
                                    percentile, poisson_arrivals, summarize)

    from spacedrive_tpu import faults, telemetry
    from spacedrive_tpu.api.router import ApiError, BusyError
    from spacedrive_tpu.node import Node
    from spacedrive_tpu.server.shell import Server
    from spacedrive_tpu.telemetry import slo as _slo

    # honest per-request service cost: every pool-served query sleeps
    # SD_FAULT_STALL_S inside the worker (the plan is inherited across
    # the fork), so pool capacity = workers / stall and the 25..400
    # req/s ramp genuinely saturates it
    faults.install("serve_worker:stall", seed=seed)
    telemetry.set_enabled(True)
    node = None
    server = None
    flight: list[dict] = []
    flight_lock = threading.Lock()

    def _hook(rec: dict) -> None:
        # the shed flood churns the 256-deep event ring faster than the
        # bench reads it — capture the gating kinds at the source
        if rec.get("name") in ("slo.burn", "pool.resize"):
            with flight_lock:
                flight.append(dict(rec))

    def _shed_by_tenant() -> dict[str, float]:
        return {lbl.get("tenant", ""): v for lbl, v in
                telemetry.series_values("sd_rspc_shed_total")}

    try:
        node = Node(tmp / "node", probe_accelerator=False,
                    watch_locations=False)
        node.thumbnail_remover.stop()
        libs = [node.libraries.create(f"tenant-{i:03d}")
                for i in range(n_tenants)]
        for lib in libs:
            lib.orphan_remover.stop()
        lib_ids = [lib.id for lib in libs]
        # the shell owns the reader pool; dispatch stays in-process so a
        # shed is a caught BusyError, not HTTP parsing
        server = Server(node, port=0)
        server.start()
        telemetry.add_event_hook(_hook)

        def submit(lib_id: str) -> str:
            try:
                node.router.resolve("search.pathsCount", {},
                                    library_id=lib_id)
                return "ok"
            except BusyError:
                return "shed"
            except ApiError:
                return "error"

        runner = OpenLoopRunner(submit, lib_ids, seed=seed)
        rng = random.Random(seed)

        # -- warmup + steady baseline ---------------------------------
        runner.run(poisson_arrivals(20.0, 1.0, rng), drain_s=3.0)
        steady = summarize(runner.run(
            poisson_arrivals(flash_base_hz, step_s, rng), drain_s=4.0))
        steady_p99 = steady["p99_s"] or 1e-9

        # -- flash crowd ----------------------------------------------
        flash = None
        if scenario in ("", "flash-crowd"):
            flood_id = lib_ids[0]
            flood_label = _slo.tenant_label(flood_id)
            shed_before = _shed_by_tenant()
            base = [(t, None) for t in poisson_arrivals(
                flash_base_hz, 17.0, rng)]
            crowd = [(3.0 + t, flood_id) for t in poisson_arrivals(
                flash_crowd_hz, 5.0, rng)]
            schedule = sorted(base + crowd)
            tenants_for = [t for _, t in schedule]
            records = runner.run(
                [s for s, _ in schedule], drain_s=6.0,
                tenant_for=lambda i: (tenants_for[i]
                                      if tenants_for[i] is not None
                                      else runner.picker.pick()))
            # resolution needs post-crowd good traffic inside the slow
            # burn windows; keep a trickle until the alert resolves
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                with flight_lock:
                    burn_states = [e.get("state") for e in flight
                                   if e.get("name") == "slo.burn"]
                if "resolved" in burn_states and "firing" in burn_states:
                    break
                runner.run(poisson_arrivals(flash_base_hz, 1.0, rng),
                           drain_s=2.0)
            shed_delta = {
                t: v - shed_before.get(t, 0.0)
                for t, v in _shed_by_tenant().items()
                if v - shed_before.get(t, 0.0) > 0}
            total_shed = sum(shed_delta.values())
            quiet = [r.latency_s for r in records
                     if r.outcome == "ok" and r.tenant != flood_id]
            with flight_lock:
                burn_events = [e for e in flight
                               if e.get("name") == "slo.burn"]
                resizes = [e for e in flight
                           if e.get("name") == "pool.resize"]
            flash = {
                "flood_tenant": flood_label,
                "offered": len(records),
                "summary": summarize(records),
                "quiet_p99_s": round(percentile(quiet, 0.99), 6),
                "steady_p99_s": round(steady_p99, 6),
                "quiet_within_2x_steady":
                    percentile(quiet, 0.99) <= 2.0 * steady_p99,
                "burn_fired": any(e.get("state") == "firing"
                                  for e in burn_events),
                "burn_resolved": any(e.get("state") == "resolved"
                                     for e in burn_events),
                "shed_total": int(total_shed),
                "flood_shed_share": round(
                    shed_delta.get(flood_label, 0.0) / total_shed, 4)
                    if total_shed else None,
                "pool_grew": any(e.get("direction") == "grow"
                                 for e in resizes),
                "pool_shrank": any(e.get("direction") == "shrink"
                                   for e in resizes),
            }
            # let the autosizer settle back before the curve phase
            time.sleep(1.0)

        # -- scenario arms riding the curve ---------------------------
        scan_lib = None
        if scenario == "mid-scan":
            from spacedrive_tpu.locations import create_location
            from spacedrive_tpu.locations.indexer_job import IndexerJob
            from spacedrive_tpu.objects.file_identifier import (
                FileIdentifierJob)

            fixture = _ensure_scan_fixture(
                int(os.environ.get("SD_LOAD_SCAN_FILES", "2000")))
            scan_lib = node.libraries.create("load-scan")
            scan_lib.orphan_remover.stop()
            loc = create_location(scan_lib, str(fixture), hasher="cpu")
            node.jobs.spawn(
                scan_lib,
                [IndexerJob({"location_id": loc["id"]}),
                 FileIdentifierJob({"location_id": loc["id"]})],
                action="scan_location")
        if scenario == "partitioned-replica":
            from spacedrive_tpu.faults import net as _net

            _net.install(_net.profile_plan(
                os.environ.get("SD_NET_PLAN", "flaky-wan")))

        # -- the latency-vs-offered-load curve ------------------------
        curve = []
        for rate in rates:
            if scenario == "cold-cache":
                # bump every tenant's watermark so the pool page cache
                # re-misses each step (the post-write regime, not the
                # hot-cache best case)
                for lib in libs:
                    lib.emit("db.commit", {"source": "bench.load"})
            arrivals = (diurnal_arrivals(rate * 2.0, step_s, rng,
                                         period_s=step_s)
                        if scenario == "diurnal"
                        else poisson_arrivals(rate, step_s, rng))
            step = summarize(runner.run(arrivals, drain_s=4.0))
            step["rate_hz"] = rate
            curve.append(step)
            print(f"info: load step {rate:g}/s -> p50 "
                  f"{step['p50_s'] * 1000:.1f}ms p99 "
                  f"{step['p99_s'] * 1000:.1f}ms shed "
                  f"{step['shed_rate']:.1%}", file=sys.stderr)
            time.sleep(0.3)
        if scan_lib is not None:
            node.jobs.wait_idle(600)
        if scenario == "partitioned-replica":
            from spacedrive_tpu.faults import net as _net

            _net.clear()

        # knee vs the steady-phase baseline, not curve[0]: the first step
        # pays the autosizer's cold grow (the pool shrank during the
        # settle gap) and its p99 is not the uncongested floor
        base_p99 = steady_p99
        knee = None
        for step in curve:
            if (step["p99_s"] <= 3.0 * base_p99
                    and step["shed_rate"] <= 0.01):
                knee = step["rate_hz"]
            else:
                break

        # -- telemetry overhead A/B (closed-loop: fixed concurrency, so
        # the two sides offer identical pressure and the ratio isolates
        # the instrumentation) ----------------------------------------
        ab_s = float(os.environ.get("SD_LOAD_AB_S", "2.0"))
        # freeze the autosizer for the A/B: with telemetry off the
        # queue-wait histogram goes dark, the sizing signal reads empty,
        # and the pool would shrink under exactly one side — the ratio
        # must compare instrumentation cost on an identical pool
        if node.reader_pool is not None:
            node.reader_pool.autosize_cooldown_s = float("inf")
        closed = ClosedLoopRunner(submit, lib_ids, seed=seed,
                                  concurrency=4)
        closed.run(ab_s)  # warmup to steady caches before either side

        def _closed_rps() -> float:
            return len([r for r in closed.run(ab_s)
                        if r.outcome == "ok"]) / ab_s

        # interleaved on/off pairs, best of each side: one unlucky
        # window (GC pause, autosizer tick) must not decide the gate
        on_rps, off_rps = [], []
        for _ in range(2):
            on_rps.append(_closed_rps())
            telemetry.set_enabled(False)
            off_rps.append(_closed_rps())
            telemetry.set_enabled(True)
        ab_ratio = (round(max(on_rps) / max(off_rps), 4)
                    if max(off_rps, default=0.0) else None)

        slo_status = node.slo.status() if getattr(node, "slo", None) else []
        admission = (node.dispatch_budget.status()
                     if getattr(node, "dispatch_budget", None) else None)
        pool_status = (node.reader_pool.status()
                       if getattr(node, "reader_pool", None) else None)
        record = {
            "metric": "load_knee_rps",
            "value": knee if knee is not None else 0.0,
            "unit": "req/s",
            "scenario": scenario or "full",
            "tenants": n_tenants,
            "stall_s": stall_s,
            "step_s": step_s,
            "steady": steady,
            "curve": curve,
            "flash": flash,
            "telemetry_ab_ratio": ab_ratio,
            "slo": slo_status,
            "dispatch_admission": admission,
            "pool": pool_status,
        }
        out_path = Path(__file__).resolve().parent / "BENCH_load.json"
        out_path.write_text(json.dumps(record, indent=2))
        if flash is not None:
            _history_extra("load_flood_shed_share",
                           flash["flood_shed_share"], "ratio")
        if ab_ratio is not None:
            _history_extra("load_telemetry_ab", ab_ratio, "x")
        return record
    finally:
        telemetry.remove_event_hook(_hook)
        faults.clear()
        if server is not None:
            server.stop()
        if node is not None:
            node.shutdown()
        shutil.rmtree(tmp, ignore_errors=True)


def _history_verdicts(history_path: Path | None = None
                      ) -> list[dict]:
    """The regression-sentinel core: for each (mode, metric) series in
    BENCH_history.jsonl with >= 4 numeric samples, compare the latest
    value against the median of its trailing window (up to 8
    predecessors). Outside a generous +/-40% band -> flagged. The wide
    band is deliberate: history rows span fixture-size changes and host
    noise — the sentinel exists to catch
    step-function regressions the PR author did not notice, not to
    relitigate every 10% wobble."""
    import statistics

    path = history_path or (Path(__file__).resolve().parent
                            / "BENCH_history.jsonl")
    if not path.exists():
        return []
    series: dict[tuple[str, str], list[float]] = {}
    for line in path.read_text().splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            row = json.loads(line)
        except ValueError:
            continue
        mode, metric = row.get("mode"), row.get("metric")
        value = row.get("value")
        if not mode or not metric or not isinstance(value, (int, float)):
            continue
        series.setdefault((str(mode), str(metric)), []).append(float(value))
    rows = []
    for (mode, metric), values in sorted(series.items()):
        latest = values[-1]
        prior = values[:-1][-8:]
        if len(prior) < 3:
            rows.append({"mode": mode, "metric": metric, "latest": latest,
                         "median": None, "ratio": None, "verdict": "n/a",
                         "samples": len(values)})
            continue
        med = statistics.median(prior)
        ratio = latest / med if med else None
        verdict = ("ok" if ratio is not None and 0.6 <= ratio <= 1.4
                   else "drift")
        rows.append({"mode": mode, "metric": metric, "latest": latest,
                     "median": round(med, 6),
                     "ratio": round(ratio, 4) if ratio is not None else None,
                     "verdict": verdict, "samples": len(values)})
    return rows


def _print_history_verdicts(rows: list[dict]) -> None:
    if not rows:
        print("check-history: no BENCH_history.jsonl series to check",
              file=sys.stderr)
        return
    w_mode = max(len(r["mode"]) for r in rows)
    w_metric = max(len(r["metric"]) for r in rows)
    print(f"{'mode':<{w_mode}}  {'metric':<{w_metric}}  "
          f"{'latest':>12}  {'median':>12}  {'ratio':>7}  verdict",
          file=sys.stderr)
    for r in rows:
        med = "-" if r["median"] is None else f"{r['median']:>12.4g}"
        ratio = "-" if r["ratio"] is None else f"{r['ratio']:>7.3f}"
        print(f"{r['mode']:<{w_mode}}  {r['metric']:<{w_metric}}  "
              f"{r['latest']:>12.4g}  {med:>12}  {ratio:>7}  "
              f"{r['verdict']}", file=sys.stderr)


def bench_check_history() -> dict:
    """``--check-history`` (ISSUE 20): the perf-trajectory sentinel.
    Prints the per-(mode, metric) verdict table and emits a
    ``history_drift`` record counting out-of-band series. Always a
    sentinel, never a gate: the exit code stays 0 — a human (or the PR
    description) decides whether a flagged drift is a regression or an
    intentional change riding a fixture/knob edit."""
    rows = _history_verdicts()
    _print_history_verdicts(rows)
    flagged = [r for r in rows if r["verdict"] == "drift"]
    return {
        "metric": "history_drift",
        "value": len(flagged),
        "unit": "series",
        "checked": len(rows),
        "flagged": flagged,
    }


def bench_crash() -> dict:
    """Crash-recovery headline (ISSUE 9): the seeded kill matrix from
    tests/crash_harness.py — spawn a real node subprocess per workload,
    SIGKILL it at a seam-driven point (mid-group-commit, mid-gather,
    mid-sync-window, mid-backup), restart the same data dir, and measure
    recovery. Emits ``crash{kills_survived, mean_recovery_s,
    mean_pages_lost}`` plus the per-kill ledger and writes the record to
    BENCH_crash.json. ``pages_lost`` is the work the restart re-ran
    because the kill rolled it back short of a durable checkpoint: scan =
    reference pages minus the checkpoint page the restart booted from;
    sync = windows re-served off the durable clock floors; backup = the
    one atomic archive write (nothing partial ever survives)."""
    import math
    import shutil

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tests import crash_harness as ch

    tmp = Path(tempfile.mkdtemp(prefix="sd_bench_crash_"))
    kills = []
    try:
        tree = ch.make_tree(tmp / "tree")
        ops = ch.gen_ops_file(tmp / "ops.jsonl")
        scan_args = {"tree": str(tree)}
        sync_args = {"ops_file": str(ops)}
        _rc, scan_ref = ch.run_child("scan", tmp / "scan-ref", scan_args)
        _rc, sync_ref = ch.run_child("sync", tmp / "sync-ref", sync_args)
        _rc, bk_ref = ch.run_child("backup", tmp / "bk-ref", {})
        ref_pages = math.ceil(ch.SCAN_FILES / ch.SCAN_BATCH)

        for spec in ch.SCAN_KILLS:
            res = ch.run_kill_point(tmp, "scan", spec, scan_args)
            durable = max((j["checkpoint_step"] or 0
                           for j in res["pre_jobs"].values()), default=0)
            kills.append({
                "kill_point": res["kill_point"],
                "recovery_s": res["recovery_s"],
                "pages_lost": ref_pages - durable,
                "identical": res["snapshot"] == scan_ref["snapshot"],
            })
        for spec in ch.SYNC_KILLS:
            res = ch.run_kill_point(tmp, "sync", spec, sync_args)
            kills.append({
                "kill_point": res["kill_point"],
                "recovery_s": res["recovery_s"],
                "pages_lost": math.ceil(
                    (res["initial_pending"] or 0) / ch.SYNC_WINDOW),
                "identical": res["oplog"] == sync_ref["oplog"],
            })
        for spec in ch.BACKUP_KILLS:
            res = ch.run_kill_point(tmp, "backup", spec, {})
            kills.append({
                "kill_point": res["kill_point"],
                "recovery_s": res["recovery_s"],
                "pages_lost": 1,
                "identical": res["snapshot"] == bk_ref["snapshot"],
            })

        survived = sum(1 for k in kills if k["identical"])
        mean_recovery = sum(k["recovery_s"] for k in kills) / len(kills)
        mean_pages = sum(k["pages_lost"] for k in kills) / len(kills)
        record = {
            "metric": f"crash_kill_matrix[{len(kills)}kills]",
            "value": survived,
            "unit": "kills survived byte-identically",
            "crash": {
                "kills_survived": survived,
                "kills_total": len(kills),
                "mean_recovery_s": round(mean_recovery, 3),
                "mean_pages_lost": round(mean_pages, 2),
            },
            "commit_group": ch.COMMIT_GROUP,
            "scan_pages_total": ref_pages,
            "sync_windows_total": math.ceil(ch.SYNC_OPS / ch.SYNC_WINDOW),
            "kills": kills,
        }
        out = Path(__file__).resolve().parent / "BENCH_crash.json"
        out.write_text(json.dumps(record, indent=1) + "\n")
        print(f"info: crash matrix {survived}/{len(kills)} kills survived "
              f"byte-identically, mean recovery {mean_recovery:.2f}s, mean "
              f"pages lost to rollback {mean_pages:.1f} -> {out.name}",
              file=sys.stderr)
        return record
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_chunk() -> dict:
    """``--chunk`` (ISSUE 18): the content-defined chunking suite.

    Three numbers: (1) CDC throughput per rung over a mixed corpus, with
    every rung's boundaries asserted byte-identical to the pure-Python
    Gear oracle before its timing counts and every rung required to clear
    3x the oracle's MB/s; (2) the dedup ratio chunk manifests surface on
    a synthetic edited-copies corpus (families of 4 with small in-place
    edits — the shape the chunkDuplicates consumer ranks); (3) the delta
    bytes-on-wire headline: a 50%-shared file sent through the REAL
    p2p/delta.py protocol over the in-memory wire harness, bytes measured
    from the NetModel per-link ledger. The chunk router's sd_chunk_router_*
    families must come out live. Record to BENCH_chunk.json."""
    import asyncio
    import shutil

    import numpy as np

    from spacedrive_tpu import telemetry
    from spacedrive_tpu.faults import net
    from spacedrive_tpu.objects import manifest
    from spacedrive_tpu.ops import cdc

    telemetry.set_enabled(True)
    rng = np.random.default_rng(42)

    def blob(n: int) -> bytes:
        return rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()

    # -- per-rung throughput vs the oracle --------------------------------
    n_files = int(os.environ.get("SD_BENCH_CHUNK_FILES", "24"))
    corpus = [blob(256 * 1024) for _ in range(n_files)]
    total_mb = sum(len(d) for d in corpus) / 1e6
    # the oracle is a per-byte Python loop (~1 MB/s): rate it on a slice
    oracle_slice = corpus[:2]
    oracle_mb = sum(len(d) for d in oracle_slice) / 1e6
    oracle_t, oracle_chunks = time_best(
        lambda: [cdc.chunk_ref(d) for d in oracle_slice], 1)
    oracle_mbps = oracle_mb / oracle_t

    # on a host without the device backend the Pallas rung runs in
    # interpret mode — a per-instruction CPU emulation of the TPU kernel
    # (slower than the oracle by design). It stays in the suite as a
    # correctness rung timed on the small slice, but the 3x-oracle floor
    # applies only to rungs executing natively on this host; a real TPU
    # rig gates all three.
    from spacedrive_tpu.ops.blake3_pallas import interpret_mode

    emulated = {"pallas"} if interpret_mode() else set()
    rates: dict[str, float] = {}
    for kernel in cdc.KERNELS:
        if cdc.chunk_batch(oracle_slice, kernel=kernel) != oracle_chunks:
            print(f"FATAL: {kernel} boundaries diverge from the oracle",
                  file=sys.stderr)
            sys.exit(1)
        if kernel in emulated:
            t, _ = time_best(
                lambda k=kernel: cdc.chunk_batch(oracle_slice, kernel=k), 1)
            rates[kernel] = round(oracle_mb / t, 2)
            continue
        cdc.chunk_batch(corpus, kernel=kernel)  # compile/warm off the clock
        t, _ = time_best(
            lambda k=kernel: cdc.chunk_batch(corpus, kernel=k), REPEATS)
        rates[kernel] = round(total_mb / t, 1)
    vs_oracle = {k: round(v / oracle_mbps, 2) for k, v in rates.items()}
    gated = {k: v for k, v in vs_oracle.items() if k not in emulated}
    if min(gated.values()) < 3.0:
        print(f"FATAL: a rung failed the 3x-oracle floor: {gated} "
              f"(oracle {oracle_mbps:.2f} MB/s)", file=sys.stderr)
        sys.exit(1)
    best_kernel = max(gated, key=lambda k: rates[k])

    # -- dedup ratio on an edited-copies corpus ----------------------------
    families = int(os.environ.get("SD_BENCH_CHUNK_FAMILIES", "8"))
    dedup_corpus: list[bytes] = []
    for _ in range(families):
        base = blob(192 * 1024)
        dedup_corpus.append(base)
        for _m in range(3):  # 3 edited copies: one 4 KiB in-place edit each
            edited = bytearray(base)
            off = int(rng.integers(0, len(base) - 4096))
            edited[off : off + 4096] = blob(4096)
            dedup_corpus.append(bytes(edited))
    uniq: dict[str, int] = {}
    for d in dedup_corpus:
        for cid, ln in cdc.build_manifest(d, kernel=best_kernel):
            uniq[cid] = ln
    dedup_total = sum(len(d) for d in dedup_corpus)
    dedup_unique = sum(uniq.values())
    dedup_ratio = dedup_total / dedup_unique

    # -- router liveness: one routed dispatch, families must be live -------
    manifest.router.reset()
    rows = [{"_chunk_payload": d} for d in corpus[:4]]
    manifest.pipeline_chunk_process(rows)
    routed = {lbl["backend"]: int(v) for lbl, v in
              telemetry.series_values("sd_chunk_router_batches_total") if v}
    snap = telemetry.snapshot()["metrics"]
    for fam in ("sd_chunk_router_bytes_per_sec",
                "sd_chunk_router_batches_total",
                "sd_chunk_router_flips_total"):
        if fam not in snap:
            print(f"FATAL: {fam} missing from the registry", file=sys.stderr)
            sys.exit(1)
    if not routed:
        print("FATAL: the chunk router dispatched no batches",
              file=sys.stderr)
        sys.exit(1)

    # -- delta bytes-on-wire through the real protocol ---------------------
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tests.test_delta_transfer import make_blob, run_delta

    net.clear()
    model = net.install("*>*:bw=256MBps", seed=7)
    tmp = Path(tempfile.mkdtemp(prefix="sd_bench_delta_"))
    try:
        shared = make_blob(1, 512 * 1024)
        base_file = shared + make_blob(2, 512 * 1024)
        fresh = shared + make_blob(3, 512 * 1024)  # 1 MiB, ~50% shared
        t0 = time.perf_counter()
        asyncio.run(run_delta(tmp, fresh, base_data=base_file))
        delta_t = time.perf_counter() - t0
        wire = sum(v for k, v in model.bytes_by_link().items()
                   if k.startswith("sender>"))
    finally:
        net.clear()
        shutil.rmtree(tmp, ignore_errors=True)
    wire_frac = wire / len(fresh)
    if not 0 < wire_frac < 0.6:
        print(f"FATAL: delta shipped {wire_frac:.2f}x of the file bytes "
              f"(gate: < 0.6 with 50% shared)", file=sys.stderr)
        sys.exit(1)

    print(f"info: cdc {total_mb:.1f} MB corpus: oracle {oracle_mbps:.2f} "
          f"MB/s | " +
          " | ".join(f"{k} {rates[k]:,.2f} MB/s ({vs_oracle[k]:,.1f}x"
                     + (", interpret" if k in emulated else "") + ")"
                     for k in cdc.KERNELS) +
          f" | dedup ratio {dedup_ratio:.2f}x over "
          f"{dedup_total >> 20} MiB | delta wire "
          f"{wire:,} B / {len(fresh):,} B ({wire_frac:.2f}x) in "
          f"{delta_t:.2f}s | router batches {routed}", file=sys.stderr)
    record = {
        "metric": f"cdc_chunk_MBps[{best_kernel},{n_files}x256KiB]",
        "value": rates[best_kernel],
        "unit": "MB/sec",
        "vs_baseline": vs_oracle[best_kernel],
        "oracle_MBps": round(oracle_mbps, 2),
        "kernel_MBps": rates,
        "kernel_vs_oracle": vs_oracle,
        "emulated_rungs": sorted(emulated),
        "dedup_ratio": round(dedup_ratio, 3),
        "dedup_corpus_bytes": dedup_total,
        "dedup_unique_bytes": dedup_unique,
        "delta_wire_bytes": int(wire),
        "delta_file_bytes": len(fresh),
        "delta_wire_fraction": round(wire_frac, 3),
        "delta_transfer_s": round(delta_t, 3),
        "router_batches": routed,
    }
    out = Path(__file__).resolve().parent / "BENCH_chunk.json"
    out.write_text(json.dumps(record, indent=1) + "\n")
    return record


#: modes that do no device work: they run wherever they are started and
#: name the platform in their record
CPU_ONLY_MODES = ("fleet", "crash", "serve", "load", "sync", "check_history")


def _require_tpu() -> dict:
    """The device a measuring mode runs on; exits non-zero when JAX's
    backend is not a TPU, so no CPU number lands under a device metric."""
    from spacedrive_tpu.utils.jax_guard import device_info

    info = device_info()
    if info["platform"] != "tpu":
        print(f"FAILED PRECONDITION: mode {MODE!r} measures the device, and "
              f"JAX found no TPU ({info}).", file=sys.stderr)
        sys.exit(2)
    return info


def main() -> int:
    if MODE in CPU_ONLY_MODES:
        device = {"platform": "cpu", "note": f"{MODE}: no device work"}
    else:
        from spacedrive_tpu.utils.jax_guard import configure_compile_cache

        configure_compile_cache()
        device = _require_tpu()
    if MODE == "dedup":
        record = bench_dedup()
    elif MODE == "identify":
        record = bench_identify()
    elif MODE == "device_kernel":
        record = bench_device_kernel()
    elif MODE == "thumbs":
        record = bench_thumbs()
    elif MODE == "scan":
        record = bench_scan()
    elif MODE == "scan_sweep":
        record = bench_scan_sweep()
    elif MODE == "sync":
        record = bench_sync()
    elif MODE == "fleet":
        record = bench_fleet()
    elif MODE == "crash":
        record = bench_crash()
    elif MODE == "serve":
        record = bench_serve_wan() if WAN_PROFILE else bench_serve()
    elif MODE == "load":
        record = bench_load()
    elif MODE == "check_history":
        record = bench_check_history()
    elif MODE == "search":
        record = bench_search()
    elif MODE == "dedup_1m":
        record = bench_dedup_1m()
    elif MODE == "chunk":
        record = bench_chunk()
    else:  # combined (default): dedup headline + north-star identify record
        # + the device-resident kernel evidence (both identify regimes)
        # + the batched thumbnail-resize experiment, all in THIS process:
        # it holds the chip, so no child could use it
        record = bench_dedup()
        record["extra"] = [bench_identify(), bench_device_kernel()]
        for extra in (bench_thumbs, bench_sync, bench_scan, bench_dedup_1m):
            try:
                record["extra"].append(extra())
            except Exception as e:  # additive evidence, not gating
                print(f"warn: {extra.__name__} skipped: {e}", file=sys.stderr)
        # regression sentinel, warn-only (satellite of ISSUE 20): the
        # combined run ends with the trajectory verdict table so drift
        # is visible in every full bench log without gating it
        try:
            _print_history_verdicts(_history_verdicts())
        except Exception as e:
            print(f"warn: check-history skipped: {e}", file=sys.stderr)
    record["device"] = device
    _bench_analysis_wall()
    _append_history(record)
    print(json.dumps(record))
    return 0


def _git_rev() -> str:
    import subprocess

    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _append_history(record: dict) -> None:
    """Every bench run (all modes) appends its headline to
    BENCH_history.jsonl — git rev + mode + metric/value — so the perf
    trajectory is recorded run-over-run instead of living only in the
    latest BENCH_*.json snapshot. Concurrent runs are safe: one
    O_APPEND write per line (utils/atomic.append_line)."""
    try:
        from spacedrive_tpu.utils.atomic import append_line

        entry = {
            "unix": round(time.time(), 1),
            "rev": _git_rev(),
            "mode": MODE,
            "metric": record.get("metric"),
            "value": record.get("value"),
            "unit": record.get("unit"),
        }
        if record.get("vs_baseline") is not None:
            entry["vs_baseline"] = record["vs_baseline"]
        if record.get("device"):
            entry["device"] = record["device"]
        if record.get("gather_share") is not None:
            # scan-ceiling trajectory (ISSUE 17): gather_s / page_s rides
            # every scan headline so the shard payoff is visible run-over-run
            entry["gather_share"] = record["gather_share"]
        append_line(Path(__file__).resolve().parent / "BENCH_history.jsonl",
                    json.dumps(entry))
    except Exception as e:  # the headline must print even if history fails
        print(f"warn: BENCH_history.jsonl append failed: {e}",
              file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
