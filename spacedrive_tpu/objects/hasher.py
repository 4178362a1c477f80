"""Hasher backends: the seam that makes identity hashing pluggable.

The reference hard-codes scalar CPU BLAKE3 inside FileMetadata::new
(file_identifier/mod.rs:80-88). Here the cas_id computation is a backend
behind the per-location ``hasher`` config ("cpu" | "tpu", BASELINE.json's
`hasher = "tpu"` flag) so the identifier job, dedup and sync stay
hasher-agnostic.

The TPU backend batches sampled messages into shape buckets:
- the fixed 57,352-byte sampled bucket (every file > 100KiB) — the hot path,
  one compiled kernel shape;
- a handful of small-file chunk-capacity buckets (1/4/16/32/64/101 chunks) to
  bound zero-padding waste while keeping the compiled-shape count constant.

The device compression kernel under every batched path here (row pipeline,
small-file buckets, sharded variants) is selected by ``SD_BLAKE3_KERNEL=
xla|pallas`` — resolved per call inside ops/blake3_jax's entry points, so
the hashers need no plumbing and a process switches kernels without
re-instantiating backends (each choice jit-caches separately).

Per-file IO errors come back as Exception entries; callers route them into
job errors instead of aborting the batch.
"""

from __future__ import annotations

import functools
import logging
import threading
import time
from pathlib import Path
from typing import Callable, Protocol

from .. import telemetry
from .cas import (MINIMUM_FILE_SIZE, SAMPLED_MESSAGE_LEN, generate_cas_id,
                  read_sampled_batch)

logger = logging.getLogger(__name__)

#: chunk capacities for small-file buckets (1 chunk = 1024 B); 101 covers the
#: largest whole-file message (100KiB + 8B size prefix)
SMALL_BUCKETS = (1, 4, 16, 32, 64, 101)
SAMPLED_CHUNKS = (SAMPLED_MESSAGE_LEN + 1023) // 1024  # 57
#: words of a sampled message's row the MinHash signature reads: the
#: 57-chunk row the dedup detector gathers into (objects/dedup.py)
SIGN_WORDS = SAMPLED_CHUNKS * 256
#: every device call of a bucket hashes a fixed number of rows: at most
#: BUCKET_MAX_ROWS, and at most BUCKET_CALL_CHUNKS chunks (32 MiB) of rows a
#: call. One shape per bucket, whatever the page size or the calls before
#: it; a bucket with more messages takes several calls (``bucket_rows``)
BUCKET_MAX_ROWS = 1024
BUCKET_CALL_CHUNKS = 32 * 1024


# -- dispatch telemetry --------------------------------------------------------
# Per-batch accounting on the unified registry: batches/files/payload-bytes
# per backend, plus the live files-per-sec / bytes-per-sec gauges of the
# last batch. The decorators guard with a thread-local "outermost" flag so
# composed backends (hybrid → cpu/tpu, remote → hybrid fallback) count
# each batch exactly once, attributed to the entry-point backend.

_HASH_BATCHES = telemetry.counter(
    "sd_hash_batches_total", "hash batches dispatched per backend",
    labels=("backend",))
_HASH_FILES = telemetry.counter(
    "sd_hash_files_total", "files hashed per backend", labels=("backend",))
_HASH_BYTES = telemetry.counter(
    "sd_hash_bytes_total", "cas-message payload bytes hashed per backend",
    labels=("backend",))
_HASH_SECONDS = telemetry.histogram(
    "sd_hash_batch_seconds", "hash batch latency per backend",
    labels=("backend",))
_HASH_RATE = telemetry.gauge(
    "sd_hash_files_per_sec", "files/s of the last hash batch")
_HASH_BPS = telemetry.gauge(
    "sd_hash_bytes_per_sec", "payload bytes/s of the last hash batch")

# -- per-batch router telemetry ------------------------------------------------
# The hybrid router's decision inputs and outcomes: live transfer-inclusive
# bytes/s per engine (EWMA over full dispatch wall time, H2D included),
# engine flips, and per-engine routed-batch counts. These are the series
# the bench's BENCH_r06 knobs (`router_flips`, per-backend batch counts)
# and the tpu-backend.md router docs read.
_ROUTER_BPS = telemetry.gauge(
    "sd_hash_router_bytes_per_sec",
    "EWMA transfer-inclusive payload bytes/s per engine (router input)",
    labels=("backend",))
_ROUTER_FLIPS = telemetry.counter(
    "sd_hash_router_flips_total",
    "engine flips by the per-batch hash router (hysteresis-damped)")
_ROUTER_BATCHES = telemetry.counter(
    "sd_hash_router_batches_total",
    "hash (sub-)batches the hybrid router dispatched per engine",
    labels=("backend",))

# -- small-file bucket telemetry -----------------------------------------------
# Per chunk-capacity bucket of the whole-file path (``_bucketed_hash``):
# device calls, messages, rows (the empty lanes included) and payload bytes,
# so the rows' padding share is lanes x cap x 1024 against payload.
_BUCKET_CALLS = telemetry.counter(
    "sd_hash_bucket_calls_total",
    "device hash calls per chunk-capacity bucket", labels=("cap",))
_BUCKET_FILES = telemetry.counter(
    "sd_hash_bucket_files_total",
    "cas messages hashed per chunk-capacity bucket", labels=("cap",))
_BUCKET_LANES = telemetry.counter(
    "sd_hash_bucket_lanes_total",
    "rows hashed per chunk-capacity bucket, padding lanes included",
    labels=("cap",))
_BUCKET_PAYLOAD = telemetry.counter(
    "sd_hash_bucket_payload_bytes_total",
    "cas-message payload bytes per chunk-capacity bucket", labels=("cap",))

class _OutermostGuard:
    """Process-wide outermost-call tracker (not thread-local: the
    hybrid's work-stealing branch runs the leaf backends on helper
    THREADS, and those sub-batches must still attribute to the one
    hybrid batch). Concurrent independent batches undercount to one —
    acceptable: the jobs manager runs one identify at a time per lane."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._depth = 0

    def enter(self) -> bool:
        with self._lock:
            self._depth += 1
            return self._depth == 1

    def leave(self) -> None:
        with self._lock:
            self._depth -= 1


_HASH_OUTERMOST = _OutermostGuard()


def _is_sampled(message: bytes) -> bool:
    """A cas message in the sampled layout: its 8-byte size prefix names a
    file above MINIMUM_FILE_SIZE (a whole file of SAMPLED_MESSAGE_LEN - 8
    bytes has a message of the same length, and is not one)."""
    return int.from_bytes(message[:8], "little") > MINIMUM_FILE_SIZE


def _message_len(size: int) -> int:
    """Bytes of the cas message actually hashed for a file of ``size``
    (sampled layout caps at SAMPLED_MESSAGE_LEN; whole-file below)."""
    if size > MINIMUM_FILE_SIZE:
        return SAMPLED_MESSAGE_LEN
    return size + 8  # size-prefix + whole file


def _record_hash(backend: str, files: int, nbytes: int, seconds: float) -> None:
    _HASH_BATCHES.inc(backend=backend)
    _HASH_FILES.inc(files, backend=backend)
    _HASH_BYTES.inc(nbytes, backend=backend)
    _HASH_SECONDS.observe(seconds, backend=backend)
    if seconds > 0:
        _HASH_RATE.set(round(files / seconds, 1))
        _HASH_BPS.set(round(nbytes / seconds, 1))


def _instrumented(bytes_of: Callable[[tuple], int]):
    """Wrap a ``hash_batch``/``hash_gathered`` method with outermost-only
    per-batch accounting; ``bytes_of(args)`` computes the payload size."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, *args, **kwargs):
            # keyword invocations stay valid against the HasherBackend
            # protocol; they just skip the accounting (bytes_of reads
            # positional slots — every production call site is positional)
            if not telemetry.enabled() or kwargs:
                return fn(self, *args, **kwargs)
            outermost = _HASH_OUTERMOST.enter()
            t0 = time.perf_counter()
            try:
                result = fn(self, *args)
            finally:
                _HASH_OUTERMOST.leave()
            # record only COMPLETED batches: an aborted batch (device
            # wedge mid-call) hashed nothing — counting it would inflate
            # files/bytes and let the CPU re-dispatch double-count
            if outermost:
                _record_hash(self.name, len(args[0]), bytes_of(args),
                             time.perf_counter() - t0)
            return result
        return wrapper
    return deco


def _paths_bytes(args: tuple) -> int:
    _paths, sizes = args
    return sum(_message_len(s) for s in sizes)


def _messages_bytes(args: tuple) -> int:
    messages = args[0]
    return sum(len(m) for m in messages if not isinstance(m, Exception))


_count_hash_batch = _instrumented(_paths_bytes)
_count_hash_gathered = _instrumented(_messages_bytes)


class HasherBackend(Protocol):
    name: str
    #: True when hash_batch may touch the jax device backend — get_hasher
    #: passes the device gate before instantiating such backends
    USES_DEVICE: bool

    def hash_batch(self, paths: list[str | Path],
                   sizes: list[int]) -> list[str | Exception]: ...

    def hash_gathered(self, messages: list[bytes | Exception],
                      signatures: dict | None = None) -> list[str | Exception]:
        """cas_ids of pre-gathered cas messages. A device backend also puts
        into ``signatures`` ``{message index: (K,) uint32}`` the MinHash
        signature of each sampled message it hashed on the device; the
        identify page passes ``signatures`` to device backends alone, so a
        host backend may take the messages only."""


class CpuHasher:
    """Scalar reference path; byte-exact oracle (objects/cas.py). The native
    C++ helper slots in here when present (native/)."""

    name = "cpu"

    def __init__(self) -> None:
        self._fast = _load_native_hasher()

    @_count_hash_batch
    def hash_batch(self, paths: list[str | Path], sizes: list[int]) -> list[str | Exception]:
        if self._fast is not None:
            return self._fast(paths, sizes)
        out: list[str | Exception] = []
        for path, size in zip(paths, sizes):
            try:
                out.append(generate_cas_id(path, size))
            except (OSError, EOFError) as e:
                out.append(e)
        return out

    @_count_hash_gathered
    def hash_gathered(self,
                      messages: list[bytes | Exception]) -> list[str | Exception]:
        """Hash pre-gathered cas messages (the pipelined prefetcher already
        did the file I/O): native C++ BLAKE3 batch, python oracle fallback.
        Exception entries (gather failures) pass through in place."""
        return _hash_gathered_messages(messages, _native_hex_batch())


#: files per device sub-batch in the pipelined sampled path
PIPELINE_BATCH = 2048


class TpuHasher:
    """Batched JAX/TPU path.

    Large (sampled) files take the fused pipeline: the native C++ gather
    reads each file's sample message straight into a row of the device-layout
    byte matrix (no per-file Python work), the (block,word,chunk,batch)
    permutation happens on device, and sub-batches are double-buffered so the
    next gather overlaps the previous batch's transfer+compute (async jax
    dispatch). Small files go through the bucketed whole-file path.
    """

    name = "tpu"
    USES_DEVICE = True

    @_count_hash_batch
    def hash_batch(self, paths: list[str | Path], sizes: list[int]) -> list[str | Exception]:
        from .cas import MINIMUM_FILE_SIZE

        out: list[str | Exception] = [None] * len(paths)  # type: ignore[list-item]
        sampled = [i for i, s in enumerate(sizes) if s > MINIMUM_FILE_SIZE]
        small = [i for i, s in enumerate(sizes) if s <= MINIMUM_FILE_SIZE]
        if sampled:
            self._hash_sampled(paths, sizes, sampled, out)
        if small:
            self._hash_small(paths, sizes, small, out)
        return out

    # -- sampled (fixed-shape) pipeline ------------------------------------
    def _hash_sampled(self, paths, sizes, indices: list[int], out: list) -> None:
        """Fused gather→hash with DOUBLE-BUFFERED H2D: while batch k's
        kernel executes on device, batch k+1's host gather runs and its
        rows are already staged device-side (``_stage_rows`` → async
        ``jax.device_put``), and batch k-1's digests come back. Three
        batches in flight — transfer is never serialized behind compute,
        which is exactly where the one-shot r05 device path lost
        (0.13 GB/s resident vs 0.07 GB/s transfer-inclusive)."""
        try:
            from ..native import cas_native
        except Exception:
            # no native toolchain: the Python gather into the buckets
            self._hash_small(paths, sizes, indices, out)
            return

        import numpy as np

        from ..ops.blake3_jax import _pad_to_tier, digests_to_hex

        stride = SAMPLED_CHUNKS * 1024
        pending = None  # (device result, host lengths, batch indices)
        # per sub-batch spans under the caller's open span (the dispatch
        # thread's pipeline.hash): pack = native gather into the rows,
        # put = H2D enqueue, wait = kernel enqueue + digest readback
        trace = telemetry.current_trace()

        def stage(idxs):
            """Host gather + device staging for one sub-batch (enqueued
            H2D overlaps whatever kernel is currently running)."""
            tier = self._pad_lanes(_pad_to_tier(len(idxs)))
            with telemetry.span(trace, "hash.pack", files=len(idxs)):
                rows = np.zeros((tier, stride), np.uint8)
                lengths = np.zeros(tier, np.int32)
                cas_native.gather_batch([paths[i] for i in idxs],
                                        [sizes[i] for i in idxs], rows,
                                        lengths)
                rows32 = rows.view(np.uint32).reshape(tier, stride // 4)
            with telemetry.span(trace, "hash.put"):
                dev_rows, dev_lengths = self._stage_rows(rows32, lengths)
            return (dev_rows, dev_lengths, lengths, idxs)

        def collect(item):
            dev, lengths, idxs = item
            with telemetry.span(trace, "hash.wait"):
                digests = np.asarray(dev)
            hexes = digests_to_hex(digests)
            for j, i in enumerate(idxs):
                if lengths[j] == 0:
                    out[i] = OSError(f"cas gather failed for {paths[i]}")
                else:
                    out[i] = hexes[j][:16]

        chunks = [indices[s : s + PIPELINE_BATCH]
                  for s in range(0, len(indices), PIPELINE_BATCH)]
        staged = stage(chunks[0])
        for nxt in chunks[1:] + [None]:
            dev_rows, dev_lengths, lengths, idxs = staged
            # enqueue batch k's kernel (async jax dispatch) ...
            with telemetry.span(trace, "hash.wait"):
                dev = self._device_hash_rows(dev_rows, dev_lengths)
            # ... then gather + H2D-stage batch k+1 while it runs ...
            staged = stage(nxt) if nxt is not None else None
            # ... and only now block on batch k-1's D2H digest readback
            if pending is not None:
                collect(pending)
            pending = (dev, lengths, idxs)
        if pending is not None:
            collect(pending)

    # -- small files (variable size, bucketed) -----------------------------
    def _hash_small(self, paths, sizes, indices: list[int], out: list) -> None:
        messages = read_sampled_batch([paths[i] for i in indices],
                                      [sizes[i] for i in indices])
        ok = [j for j, m in enumerate(messages) if not isinstance(m, Exception)]
        for j, m in enumerate(messages):
            if isinstance(m, Exception):
                out[indices[j]] = m
        ids = _bucketed_hash([messages[j] for j in ok], self._hash_bucket)
        for j, cid in zip(ok, ids):
            out[indices[j]] = cid

    @_count_hash_gathered
    def hash_gathered(self, messages: list[bytes | Exception],
                      signatures: dict | None = None) -> list[str | Exception]:
        """Pre-gathered messages through the device bucket path (sampled
        57-chunk messages land in the 64-chunk bucket; same digests as the
        fused row pipeline, the message is identical either way).
        ``signatures``, when given, receives ``{message index: (K,)
        uint32}`` for each sampled message (:func:`_is_sampled`)."""
        ok = [j for j, m in enumerate(messages) if not isinstance(m, Exception)]
        signed: dict = {}
        out = _hash_gathered_messages(
            messages, lambda msgs: _bucketed_hash(msgs, self._hash_bucket,
                                                  signed))
        if signatures is not None:
            signatures.update((ok[k], sig) for k, sig in signed.items())
        return out

    # hooks the sharded variant overrides
    def _hash_bucket(self, msgs: list[bytes], cap: int,
                     lanes: int) -> tuple[list[str], dict]:
        """``(hex digests, {row: (K,) uint32 signature})`` of one bucket
        call. A call that holds a sampled message also computes, from the
        same device rows, the MinHash signature of each row's first
        SIGN_WORDS words, and keeps the sampled rows' ones; any other call
        runs the digest program alone and signs nothing."""
        from ..ops.blake3_jax import blake3_batch_hex, hash_sign_rows_hex

        sampled = [k for k, m in enumerate(msgs) if _is_sampled(m)]
        if not sampled:
            return blake3_batch_hex(msgs, max_chunks=cap, lanes=lanes), {}
        hexes, sigs = hash_sign_rows_hex(msgs, cap, lanes, SIGN_WORDS)
        return hexes, {k: sigs[k] for k in sampled}

    def _pad_lanes(self, n: int) -> int:
        return n

    def _stage_rows(self, rows32, lengths):
        """Begin the H2D transfer for a gathered sub-batch (async enqueue;
        completion overlaps the in-flight kernel). The sharded variant
        keeps rows on host — the mesh decides placement per shard."""
        from ..utils.jax_guard import ensure_jax_safe

        ensure_jax_safe()  # the device gate; backends pass it in get_hasher
        import jax

        return jax.device_put(rows32), jax.device_put(lengths)

    def _device_hash_rows(self, rows32, lengths):
        import jax.numpy as jnp

        from ..ops.blake3_jax import blake3_batch_rows

        # donate: each staged row buffer is used exactly once (stage() in
        # _hash_sampled allocates fresh per sub-batch)
        return blake3_batch_rows(jnp.asarray(rows32), jnp.asarray(lengths),
                                 donate=True)


#: frames of JAX's tracing/lowering/compiling code: a worker standing in
#: one of these is compiling a new shape, not waiting on the device
_COMPILE_FILES = ("jax/_src/compiler.py", "jax/_src/interpreters/mlir.py",
                  "jax/_src/interpreters/partial_eval.py")


def _compiling(thread: threading.Thread) -> bool:
    import sys

    frame = sys._current_frames().get(thread.ident)
    while frame is not None:
        if frame.f_code.co_filename.endswith(_COMPILE_FILES):
            return True
        frame = frame.f_back
    return False


def _bounded_call(fn, deadline_s: float, name: str, poll_s: float = 0.25):
    """Run ``fn`` on a bounded daemon worker so no per-batch dispatch may
    park the scan. Time the worker spends compiling a new shape does not
    count against ``deadline_s``: a cold first compile is set-up, not a
    slow device. Returns ``("ok", value)``, ``("error", exc)``, or
    ``("timeout", None)`` (the leaked worker is a daemon; its result is
    discarded)."""
    box: list = []

    def _run() -> None:
        try:
            box.append(("ok", fn()))
        except BaseException as e:  # noqa: BLE001 — scored by the caller
            box.append(("error", e))

    worker = threading.Thread(target=_run, daemon=True, name=name)
    worker.start()
    budget = deadline_s
    while worker.is_alive() and budget > 0:
        step = min(poll_s, budget)
        worker.join(timeout=step)
        if not _compiling(worker):
            budget -= step
    if not box:
        return ("timeout", None)
    return box[0]


class BackendRouter:
    """Per-batch cpu-vs-device routing from LIVE transfer-inclusive rates.

    The one-shot probe verdict answered "which engine wins right now?" once
    per process — wrong whenever transfer conditions drift mid-scan (host
    contention, page-cache state, a recovering device). The router instead
    keeps an EWMA of each engine's *transfer-inclusive* payload bytes/s —
    measured around the full dispatch (host staging + H2D + kernel + D2H),
    exactly the number "GPUs as Storage System Accelerators" says decides
    offload — and re-picks per batch:

    - **hysteresis**: the losing engine must beat the incumbent's EWMA by
      ``HYSTERESIS``× to flip, so jittery rates don't flap the route;
    - **exploration**: every ``EXPLORE_EVERY`` batches a small capped
      sub-slice runs on the losing engine to keep its EWMA live (a rate
      nobody measures can never win back the route);
    - **degraded re-probe**: after a mid-batch device failure pins the
      route to CPU, a bounded device probe re-runs after ``REPROBE_AFTER``
      CPU-routed batches — a transient failure must not pin CPU for the
      whole scan.

    Decisions and inputs are published on the unified registry
    (``sd_hash_router_*``).
    """

    HYSTERESIS = 1.25
    EWMA_ALPHA = 0.3
    EXPLORE_EVERY = 32
    REPROBE_AFTER = 64
    #: messages per exploration/re-probe sub-slice — bounds the cost of
    #: measuring the losing engine to a sliver of one batch
    PROBE_SLICE = 128

    def __init__(self, flips_counter=None, batches_counter=None,
                 bps_gauge=None, event_prefix: str = "hash_router") -> None:
        # metric handles default to the hash families; other subsystems
        # (the device search engine, ISSUE 15) reuse the routing logic
        # with their own sd_* families and event names
        self._flips_counter = flips_counter if flips_counter is not None \
            else _ROUTER_FLIPS
        self._batches_counter = batches_counter \
            if batches_counter is not None else _ROUTER_BATCHES
        self._bps_gauge = bps_gauge if bps_gauge is not None else _ROUTER_BPS
        self._event_prefix = event_prefix
        self._lock = threading.Lock()
        self.cpu_bps: float | None = None
        self.dev_bps: float | None = None
        self.current = "cpu"
        self.degraded = False
        self.flips = 0
        self._streak = 0
        self._cpu_since_degrade = 0

    def seed(self, cpu_bps: float, dev_bps: float) -> None:
        """Initialize from the one-time fused probe (both engines measured
        on real work); the EWMAs take over from here."""
        with self._lock:
            self.cpu_bps = cpu_bps
            self.dev_bps = dev_bps
            self.current = "device" if dev_bps > cpu_bps else "cpu"
            self.degraded = False
            self._streak = 0

    def reset(self) -> None:
        """Forget everything (test/bench isolation): the next batch
        re-probes from scratch."""
        with self._lock:
            self.cpu_bps = self.dev_bps = None
            self.current = "cpu"
            self.degraded = False
            self._streak = 0
            self._cpu_since_degrade = 0

    def degrade(self, reason: str = "") -> None:
        """A device dispatch died mid-batch: pin the route to CPU until a
        bounded re-probe clears it."""
        with self._lock:
            self.degraded = True
            self.dev_bps = 0.0
            self._cpu_since_degrade = 0
            if self.current != "cpu":
                self._flip_locked("cpu")
        telemetry.event(f"{self._event_prefix}_degraded", reason=reason)

    def _flip_locked(self, to: str) -> None:
        self.current = to
        self.flips += 1
        self._streak = 0
        self._flips_counter.inc()
        # flight-recorder edge: router flips are exactly what an operator
        # tails a live node for (telemetry.watch / SSE)
        telemetry.event(f"{self._event_prefix}_flip", to=to,
                        cpu_bps=round(self.cpu_bps or 0.0),
                        device_bps=round(self.dev_bps or 0.0))
        logger.info("hash router: engine flipped to %s "
                    "(cpu %.2f MB/s, device %.2f MB/s)", to,
                    (self.cpu_bps or 0.0) / 1e6, (self.dev_bps or 0.0) / 1e6)

    def route(self) -> tuple[str, str | None]:
        """Pick engines for one batch: ``(main, probe)`` where ``probe``
        (None most batches) asks the caller to run a capped sub-slice on
        the named engine to refresh its live rate."""
        with self._lock:
            if self.degraded:
                self._cpu_since_degrade += 1
                if self._cpu_since_degrade >= self.REPROBE_AFTER:
                    # the counter is NOT reset here: a batch that cannot
                    # carry the probe (no routable messages) must not burn
                    # the token — the offer repeats until a probe actually
                    # runs, and then degrade() (failed/timed-out probe) or
                    # observe("device") (success) restarts the bound
                    return ("cpu", "device")
                return ("cpu", None)
            cur_bps = self.dev_bps if self.current == "device" else self.cpu_bps
            other = "cpu" if self.current == "device" else "device"
            other_bps = self.dev_bps if other == "device" else self.cpu_bps
            if (cur_bps is not None and other_bps is not None
                    and other_bps > cur_bps * self.HYSTERESIS):
                self._flip_locked(other)
                return (self.current, None)
            self._streak += 1
            if self._streak >= self.EXPLORE_EVERY:
                self._streak = 0
                return (self.current, other)
            return (self.current, None)

    def observe(self, engine: str, nbytes: int, seconds: float) -> None:
        """Fold one measured dispatch into the engine's EWMA. A measured
        device success also clears the degraded pin — the engine is
        demonstrably alive, so the rate comparison takes back over."""
        bps = nbytes / max(seconds, 1e-9)
        with self._lock:
            prev = self.dev_bps if engine == "device" else self.cpu_bps
            ewma = bps if not prev else \
                self.EWMA_ALPHA * bps + (1.0 - self.EWMA_ALPHA) * prev
            if engine == "device":
                self.dev_bps = ewma
                if self.degraded:
                    self.degraded = False
                    logger.info("hash router: device re-probe succeeded "
                                "(%.2f MB/s) — degraded pin cleared",
                                bps / 1e6)
            else:
                self.cpu_bps = ewma
        self._batches_counter.inc(backend=engine)
        self._bps_gauge.set(round(ewma, 1), backend=engine)


class HybridHasher:
    """Adaptive heterogeneous executor over the native-CPU and TPU engines.

    On first use it probes each engine's solo throughput on real work (the
    results are kept, not discarded); the probe SEEDS a
    :class:`BackendRouter` that then re-picks the engine PER BATCH from
    live transfer-inclusive rates (EWMA, hysteresis-damped, with periodic
    exploration of the losing engine and a bounded re-probe out of the
    degraded pin). On the fused path, when the device holds the route,
    sampled chunks are work-stolen from one queue with a tail guard so the
    slower engine's last chunk never dominates the makespan. On rigs where
    the device loses, ALL sampled work routes native, so hybrid throughput
    equals the best available engine by construction instead of losing to
    contention.

    The reference has a single engine (CPU join_all, file_identifier/
    mod.rs:107-134); this seam is where a local-PCIe TPU host gets its
    speedup without any config change."""

    name = "hybrid"
    USES_DEVICE = True

    #: steal unit: small enough that the slower engine's last chunk can't
    #: dominate the makespan, large enough to amortize a device dispatch
    CHUNK = 128
    #: files used for the one-time engine rate probe
    PROBE = 64

    def __init__(self) -> None:
        self._tpu = TpuHasher()
        self._cpu = CpuHasher()
        self._cpu_rate: float | None = None
        self._device_rate: float | None = None
        #: per-batch engine router (live transfer-inclusive rates + EWMA
        #: hysteresis); seeded by the one-time fused probe below
        self.router = BackendRouter()

    def degrade_device(self, reason: str = "") -> None:
        """Pin the route to native CPU after a mid-batch device failure.
        The pin is NOT forever: the router re-probes the device on a
        bounded sub-slice after ``REPROBE_AFTER`` CPU-routed batches."""
        self._cpu_rate = self._cpu_rate or 1.0
        self._device_rate = 0.0
        self.router.degrade(reason)
        logger.warning("hybrid hasher degraded to native CPU%s",
                       f": {reason}" if reason else "")

    def _cpu_into(self, paths, sizes, idxs: list[int], out: list) -> None:
        """Native-CPU hash ``idxs`` and scatter results into ``out``."""
        res = self._cpu.hash_batch([paths[i] for i in idxs],
                                   [sizes[i] for i in idxs])
        for i, r in zip(idxs, res):
            out[i] = r

    #: floor rate for the bounded device deadline: a dispatch slower than
    #: this is indistinguishable from a wedge and gets abandoned
    DEVICE_FLOOR_BPS = 512 * 1024

    def _device_deadline_s(self, nbytes: int, probe: bool) -> float:
        """Deadline for a bounded device dispatch. Probe/exploration slices
        get a TIGHT bound derived from the CPU's live rate — the probe only
        exists to ask "could the device win?", and a device that cannot
        hash the slice within ~4× the CPU's time for the same bytes has
        already answered no; waiting out a generous wedge deadline would
        stall the scan ~40s per exploration on collapsed-transfer rigs.
        Main-route dispatches (the device actually won) keep the generous
        wedge-detection bound."""
        if probe:
            cpu_bps = self.router.cpu_bps or 0.0
            if cpu_bps > 0:
                return min(15.0, max(2.0, 4.0 * nbytes / cpu_bps))
            return 15.0
        return max(60.0, nbytes / self.DEVICE_FLOOR_BPS)

    def _dispatch_gathered(self, engine: str, idxs: list[int], messages,
                           out: list, signatures: dict | None,
                           probe: bool = False) -> None:
        """Run one routed sub-batch: measure the transfer-inclusive rate
        into the router's EWMA; a device failure/timeout finishes the
        sub-batch natively (byte-identical digests, no signatures) and
        degrades the pin."""
        sub = [messages[i] for i in idxs]
        nbytes = sum(len(m) for m in sub)
        t0 = time.perf_counter()
        if engine == "device":
            signed: dict = {}
            status, res = _bounded_call(
                lambda: self._tpu.hash_gathered(sub, signed),
                self._device_deadline_s(nbytes, probe),
                "hybrid-device-dispatch")
            if status == "ok":
                self.router.observe("device", nbytes,
                                    time.perf_counter() - t0)
                if signatures is not None:
                    signatures.update((idxs[k], sig)
                                      for k, sig in signed.items())
            else:
                why = repr(res) if status == "error" else \
                    "deadline exceeded (wedged device?)"
                logger.warning("hybrid device dispatch failed mid-batch "
                               "(%s); re-dispatching on native CPU", why)
                self.degrade_device(why)
                res = self._cpu.hash_gathered(sub)
        else:
            res = self._cpu.hash_gathered(sub)
            self.router.observe("cpu", nbytes, time.perf_counter() - t0)
        for i, r in zip(idxs, res):
            out[i] = r

    @_count_hash_gathered
    def hash_gathered(self, messages: list[bytes | Exception],
                      signatures: dict | None = None) -> list[str | Exception]:
        """Gathered-message route: PER-BATCH engine choice by the router
        (live transfer-inclusive rates, hysteresis, bounded re-probe). An
        unprobed process routes native — the safe default on wire-limited
        rigs (the pipelined identifier runs its first batch through
        ``hash_batch`` precisely so the probe seeds the router). With no
        native lib there is nothing to race — mirror hash_batch's routing
        to the device path, never the python oracle. ``signatures`` takes
        what the device slice signed (:meth:`TpuHasher.hash_gathered`);
        the CPU slice signs nothing."""
        if self._cpu._fast is None:
            return self._tpu.hash_gathered(messages, signatures)
        if self._cpu_rate is None or self._device_rate is None:
            return self._cpu.hash_gathered(messages)
        # mirror hash_batch's small/sampled split — short messages stay on
        # native CPU (IO-bound work whose varied lengths would fan the
        # device path across many bucket shapes); sampled-class messages
        # are the routable payload
        big = [i for i, m in enumerate(messages)
               if not isinstance(m, Exception) and len(m) >= SAMPLED_MESSAGE_LEN]
        if not big:
            return self._cpu.hash_gathered(messages)
        main, probe = self.router.route()
        big_set = set(big)
        rest = [i for i in range(len(messages)) if i not in big_set]
        out: list[str | Exception] = [None] * len(messages)  # type: ignore[list-item]
        if probe is not None and probe != main and len(big) > 1:
            # capped sub-slice on the losing engine keeps its EWMA live
            # (and is the degraded path's bounded device re-probe) — under
            # the TIGHT probe deadline, so a collapsed/wedged device costs
            # seconds, not a generous wedge-detection window
            cut = min(self.router.PROBE_SLICE, len(big) // 2)
            if cut > 0:
                self._dispatch_gathered(probe, big[:cut], messages, out,
                                        signatures, probe=True)
                big = big[cut:]
        if big:
            self._dispatch_gathered(main, big, messages, out, signatures)
        if rest:
            res = self._cpu.hash_gathered([messages[i] for i in rest])
            for i, r in zip(rest, res):
                out[i] = r
        return out

    def _probe_rates(self, paths, sizes, sampled: list[int],
                     out: list) -> list[int] | None:
        """Measure both engines on leading slices of the real workload;
        returns the still-unhashed indices — or None when the batch is too
        small to measure anything (rates stay unset so a real batch
        re-probes; the process-wide hasher must not pin itself to
        placeholder rates off a tiny first batch)."""
        import time as _time

        k = min(self.PROBE, len(sampled) // 2)
        if k < 8:
            return None
        cpu_part, dev_part, rest = sampled[:k], sampled[k:2 * k], sampled[2 * k:]
        t0 = _time.perf_counter()
        self._cpu_into(paths, sizes, cpu_part, out)
        cpu_rate = k / max(1e-9, _time.perf_counter() - t0)
        t0 = _time.perf_counter()
        # the device probe gets a hard deadline: a probe that never
        # returns would stall every scan — _bounded_call runs it on a
        # bounded daemon worker (the one wedge-handling policy; compile
        # time does not count against it)
        status, err = _bounded_call(
            lambda: self._tpu._hash_sampled(paths, sizes, dev_part, out),
            max(60.0, k * 0.5), "hybrid-device-probe")
        if status == "ok":
            device_rate = k / max(1e-9, _time.perf_counter() - t0)
        else:
            # timeout, or a dying device — either way it must not leave
            # half-set rates (permanently broken comparisons): score it
            # dead and finish on CPU (same values, benign overwrite)
            logger.warning(
                "hybrid probe: device engine %s; routing everything to "
                "native CPU",
                "unresponsive after deadline" if status == "timeout"
                else f"failed ({err!r})")
            self._cpu_into(paths, sizes, dev_part, out)
            device_rate = 0.0
        # set both rates atomically only once both probes concluded, and
        # seed the per-batch router's EWMAs (probe files/s × the sampled
        # message size = transfer-inclusive bytes/s on the probe slices)
        self._cpu_rate, self._device_rate = cpu_rate, device_rate
        self.router.seed(cpu_rate * SAMPLED_MESSAGE_LEN,
                         device_rate * SAMPLED_MESSAGE_LEN)
        logger.info("hybrid probe: cpu %.0f files/s, device %.0f files/s — %s",
                    self._cpu_rate, self._device_rate,
                    "engaging device" if self._device_rate > self._cpu_rate
                    else "routing to native CPU")
        return rest

    @_count_hash_batch
    def hash_batch(self, paths: list[str | Path], sizes: list[int]) -> list[str | Exception]:
        import queue as _q
        import threading

        from .cas import MINIMUM_FILE_SIZE

        n = len(paths)
        out: list[str | Exception] = [None] * n  # type: ignore[list-item]
        sampled = [i for i, s in enumerate(sizes) if s > MINIMUM_FILE_SIZE]
        small = [i for i, s in enumerate(sizes) if s <= MINIMUM_FILE_SIZE]
        if small:  # small files: native CPU batch (IO-bound, not worth device)
            self._cpu_into(paths, sizes, small, out)

        if not sampled:
            return out
        if self._cpu._fast is None:  # no native lib: nothing to race
            self._tpu._hash_sampled(paths, sizes, sampled, out)
            return out

        if self._cpu_rate is None:
            rest = self._probe_rates(paths, sizes, sampled, out)
            if rest is None:  # too small to probe — CPU for THIS batch only
                self._cpu_into(paths, sizes, sampled, out)
                return out
            sampled = rest
            if not sampled:
                return out

        if self._device_rate <= self._cpu_rate:
            self._cpu_into(paths, sizes, sampled, out)
            return out

        work: _q.Queue[list[int]] = _q.Queue()
        for start in range(0, len(sampled), self.CHUNK):
            work.put(sampled[start : start + self.CHUNK])

        # this branch only runs when the device won the probe, so the CPU is
        # the slower engine here — the tail guard (slower engine never takes
        # one of the last chunks, or its chunk latency becomes the makespan)
        # belongs on the CPU worker
        def cpu_worker():
            while True:
                if work.qsize() < 2:
                    return
                try:
                    idxs = work.get_nowait()
                except _q.Empty:
                    return
                self._cpu_into(paths, sizes, idxs, out)

        def tpu_worker():
            while True:
                try:
                    idxs = work.get_nowait()
                except _q.Empty:
                    return
                try:
                    self._tpu._hash_sampled(paths, sizes, idxs, out)
                except Exception:
                    # device died mid-batch: return the chunk to the queue
                    # and stop stealing — the drain below finishes natively
                    logger.exception("hybrid device worker failed mid-batch")
                    work.put(idxs)
                    return

        threads = [threading.Thread(target=cpu_worker, daemon=True),
                   threading.Thread(target=tpu_worker, daemon=True)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # drain of last resort: anything still queued (device died, CPU
        # stopped at the tail guard) is hashed natively so every index gets
        # a result — the list[str | Exception] contract allows no Nones
        while True:
            try:
                idxs = work.get_nowait()
            except _q.Empty:
                break
            self._cpu_into(paths, sizes, idxs, out)
        return out


class ShardedHasher(TpuHasher):
    """Multi-device variant: batch axis sharded over a data-parallel mesh
    (parallel/mesh.py) for both the row pipeline and the small-file buckets;
    lane counts pad to a multiple of the mesh's data-axis size."""

    name = "tpu-sharded"

    def __init__(self) -> None:
        from ..parallel.mesh import make_mesh

        self._mesh = make_mesh()

    def _pad_lanes(self, n: int) -> int:
        from ..parallel.mesh import pad_batch_for_mesh

        return pad_batch_for_mesh(n, self._mesh)

    def _stage_rows(self, rows32, lengths):
        # host-side staging: the sharded row hasher shards the batch axis
        # itself; a premature single-device put would just be resharded
        return rows32, lengths

    def _device_hash_rows(self, rows32, lengths):
        import jax.numpy as jnp

        from ..parallel.mesh import sharded_row_hasher

        return sharded_row_hasher(self._mesh)(jnp.asarray(rows32),
                                              jnp.asarray(lengths))

    def _hash_bucket(self, msgs: list[bytes], cap: int,
                     lanes: int) -> tuple[list[str], dict]:
        """The mesh signs nothing: the dedup detector gathers its files."""
        from ..ops.blake3_jax import hash_rows_hex
        from ..parallel.mesh import sharded_row_hasher

        return hash_rows_hex(msgs, cap, self._pad_lanes(lanes),
                             sharded_row_hasher(self._mesh)), {}


_BACKENDS: dict[str, Callable[[], HasherBackend]] = {
    "cpu": CpuHasher,
    "tpu": TpuHasher,
    "tpu-sharded": ShardedHasher,
    "hybrid": HybridHasher,
}

_instances: dict[str, HasherBackend] = {}


def get_hasher(name: str | None, node=None) -> HasherBackend:
    """Resolve a backend by location config; unknown/absent → tpu if JAX sees
    an accelerator, else the native cpu path. ``remote`` binds to the node's
    p2p mesh and is never cached (it must not outlive the node)."""
    if name == "remote":
        if node is not None:
            return RemoteHasher(node)
        logger.warning("remote hasher needs a node context; using local")
        name = "hybrid"
    if name not in _BACKENDS:
        if name is not None:
            logger.warning("unknown hasher backend %r, falling back to default", name)
        name = "tpu" if _accelerator_available() else "cpu"
    if getattr(_BACKENDS[name], "USES_DEVICE", False):
        # device-touching backends (incl. ones added via register_backend)
        # pass the one device gate; a "tpu" location on a host without an
        # accelerator says so instead of hashing on XLA:CPU unannounced
        from ..utils.jax_guard import ensure_jax_safe

        if not ensure_jax_safe() and name not in _instances:
            logger.warning("hasher %r runs on the CPU backend: JAX found "
                           "no accelerator", name)
    if name not in _instances:
        _instances[name] = _BACKENDS[name]()
    return _instances[name]


def _native_hex_batch():
    """The C++ ``blake3_hex_batch`` entry point, or None (probe memoized —
    a failed import involves a g++ attempt and must not re-run per batch)."""
    if not _NATIVE_HEX:
        try:
            from ..native import cas_native

            _NATIVE_HEX.append(cas_native.blake3_hex_batch)
        except Exception:
            _NATIVE_HEX.append(None)
    return _NATIVE_HEX[0]


_NATIVE_HEX: list = []


def _hash_gathered_messages(messages: list[bytes | Exception],
                            hex_batch) -> list[str | Exception]:
    """Shared gathered-message driver: Exception entries pass through in
    place, ok messages go through ``hex_batch(list[bytes]) -> list[hex]``
    (or the python oracle when it is None); cas_ids are the 16-hex prefix."""
    out: list[str | Exception] = [None] * len(messages)  # type: ignore[list-item]
    ok = [j for j, m in enumerate(messages) if not isinstance(m, Exception)]
    for j, m in enumerate(messages):
        if isinstance(m, Exception):
            out[j] = m
    if not ok:
        return out
    if hex_batch is not None:
        hexes = hex_batch([messages[j] for j in ok])
    else:
        from .blake3_ref import blake3

        hexes = [blake3(messages[j]).hex() for j in ok]
    for j, h in zip(ok, hexes):
        out[j] = h[:16]
    return out


def bucket_rows(cap: int) -> int:
    """Rows of every device call of the ``cap``-chunk bucket: the most, up
    to BUCKET_MAX_ROWS and by halves, whose rows hold at most
    BUCKET_CALL_CHUNKS chunks (1,024 rows for caps 1 to 32, 512 for 64,
    256 for 101)."""
    rows = BUCKET_MAX_ROWS
    while rows > 1 and rows * cap > BUCKET_CALL_CHUNKS:
        rows //= 2
    return rows


def _bucketed_hash(messages: list[bytes], hash_bucket,
                   signatures: dict | None = None) -> list[str]:
    """Bucket variable-size cas messages by chunk count and hash each
    bucket through ``hash_bucket(msgs, cap, lanes) -> (hex digests,
    {row: signature})``, in calls of
    ``bucket_rows(cap)`` rows (a bucket with more messages takes several
    calls); returns 16-hex cas_ids in input order. The one bucketing
    scheme shared by the local small-file path and the H_HASH service.
    Each call is a ``hash.bucket`` span under the calling
    thread's trace, and is counted by cap (the ``sd_hash_bucket_*``
    family) once it returns. The MinHash signatures the calls return
    (:meth:`TpuHasher._hash_bucket`) go into ``signatures``, ``{message
    index: (K,) uint32}``, when it is given."""
    out: list[str | None] = [None] * len(messages)
    buckets: dict[int, list[int]] = {}
    for j, msg in enumerate(messages):
        chunks = max(1, (len(msg) + 1023) // 1024)
        cap = next((b for b in SMALL_BUCKETS if b >= chunks), chunks)
        buckets.setdefault(cap, []).append(j)
    trace = telemetry.current_trace()
    for cap, bucket in sorted(buckets.items()):
        lanes = bucket_rows(cap)
        label = str(cap)
        for at in range(0, len(bucket), lanes):
            js = bucket[at : at + lanes]
            msgs = [messages[j] for j in js]
            payload = sum(map(len, msgs))
            with telemetry.span(trace, "hash.bucket", cap=cap,
                                files=len(msgs), lanes=lanes,
                                payload_bytes=payload):
                hexes, sigs = hash_bucket(msgs, cap, lanes)
            if signatures is not None:
                signatures.update((js[k], sig) for k, sig in sigs.items())
            _BUCKET_CALLS.inc(cap=label)
            _BUCKET_FILES.inc(len(msgs), cap=label)
            _BUCKET_LANES.inc(lanes, cap=label)
            _BUCKET_PAYLOAD.inc(payload, cap=label)
            for j, h in zip(js, hexes):
                out[j] = h[:16]
    return out  # type: ignore[return-value]


def hash_messages(messages: list[bytes]) -> list[str]:
    """cas_ids for pre-gathered cas messages — the compute side of the
    shared-hasher service (H_HASH): device-bucketed when an accelerator is
    present, else native C++ BLAKE3, else the Python oracle. Its peers keep
    no MinHash signatures, so its device calls compute none."""
    if _accelerator_available():
        from ..ops.blake3_jax import blake3_batch_hex

        return _bucketed_hash(messages, lambda msgs, cap, lanes: (
            blake3_batch_hex(msgs, max_chunks=cap, lanes=lanes), {}))
    try:
        from ..native import cas_native

        return [h[:16] for h in cas_native.blake3_hex_batch(messages)]
    except Exception:
        from .blake3_ref import blake3

        return [blake3(m).hex()[:16] for m in messages]


class RemoteHasher:
    """Route hashing to a paired node that advertises an accelerator — the
    shared TPU hasher service of BASELINE config 5. Files are sampled
    LOCALLY (read_sampled_batch: the 56 KiB budget per file, cas.rs
    layout); only the cas messages travel, so the peer sees samples, never
    whole files, and only if it shares a library with us (the server
    enforces membership). Any remote failure falls back to the local
    hybrid engine for the remainder of the batch."""

    name = "remote"

    #: per-wire-request caps — bound peer memory, stay WELL under the mux's
    #: 64 MiB per-substream buffer, and keep a lost connection from wasting
    #: more than one sub-batch of work
    WIRE_BATCH = 1024
    WIRE_BATCH_BYTES = 32 * 1024 * 1024

    def __init__(self, node) -> None:
        self._node = node

    def _pick_peer(self) -> str | None:
        """A connected peer that (a) advertises an accelerator and (b)
        shares a library with us — the server refuses non-members, so
        offering it a batch would waste the whole upload."""
        p2p = getattr(self._node, "p2p", None)
        if p2p is None:
            return None
        members: set[str] = set()
        for library in self._node.libraries.list():
            members |= p2p.nlm.member_nodes(library)
        for peer in p2p.peer_list():
            accel = peer.get("accelerator") or {}
            if (peer.get("connected") and accel.get("devices")
                    and peer["identity"] in members):
                return peer["identity"]
        return None

    def _wire_batches(self, todo: list[int], messages) -> list[list[int]]:
        """Split by count AND cumulative bytes."""
        batches: list[list[int]] = []
        cur: list[int] = []
        cur_bytes = 0
        for i in todo:
            n = len(messages[i])
            if cur and (len(cur) >= self.WIRE_BATCH
                        or cur_bytes + n > self.WIRE_BATCH_BYTES):
                batches.append(cur)
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += n
        if cur:
            batches.append(cur)
        return batches

    @_count_hash_batch
    def hash_batch(self, paths: list[str | Path],
                   sizes: list[int]) -> list[str | Exception]:
        return self.hash_gathered(read_sampled_batch(paths, sizes))

    @_count_hash_gathered
    def hash_gathered(self,
                      messages: list[bytes | Exception]) -> list[str | Exception]:
        """The natural fit for the pipelined gather: this backend always
        worked on cas messages (only samples travel, never whole files)."""
        out: list[str | Exception | None] = [None] * len(messages)
        todo: list[int] = []
        for i, msg in enumerate(messages):
            if isinstance(msg, Exception):
                out[i] = msg
            else:
                todo.append(i)

        peer_id = self._pick_peer()
        failed: list[int] = []
        if peer_id is None:
            failed = todo
        else:
            from ..telemetry import mesh

            p2p = self._node.p2p
            # trace propagation: captured HERE (the pipeline hash thread,
            # which holds the job trace's open span) — the p2p loop the
            # coroutine runs on has no span context of its own
            ctx = mesh.outbound_context(
                origin=str(self._node.config.get().get("id") or ""))
            batches = self._wire_batches(todo, messages)
            for bi, idxs in enumerate(batches):
                try:
                    ids = p2p.run_coro(p2p.request_hash_batch(
                        peer_id, [messages[i] for i in idxs], ctx=ctx),
                        timeout=120)
                    for i, cid in zip(idxs, ids):
                        out[i] = cid
                except Exception as e:
                    logger.warning("remote hash batch via %s failed (%s); "
                                   "hashing locally", peer_id[:8], e)
                    for rest in batches[bi:]:
                        failed.extend(rest)
                    break

        if failed:
            local = get_hasher("hybrid")
            results = local.hash_gathered([messages[i] for i in failed])
            for i, r in zip(failed, results):
                out[i] = r
        return out  # type: ignore[return-value]


def register_backend(name: str, factory: Callable[[], HasherBackend]) -> None:
    _BACKENDS[name] = factory


def _accelerator_available() -> bool:
    """True only for a real accelerator (JAX's default backend)."""
    from ..utils.jax_guard import ensure_jax_safe

    return ensure_jax_safe()


def _load_native_hasher():
    """ctypes binding to the C++ blake3 helper (native/); None until built."""
    try:
        from ..native import cas_native

        return cas_native.hash_batch
    except Exception:
        return None
