"""Hasher backends: the seam that makes identity hashing pluggable.

The reference hard-codes scalar CPU BLAKE3 inside FileMetadata::new
(file_identifier/mod.rs:80-88). Here the cas_id computation is a backend
behind the per-location ``hasher`` config ("cpu" | "tpu", BASELINE.json's
`hasher = "tpu"` flag) so the identifier job, dedup and sync stay
hasher-agnostic.

Every backend hashes gathered cas messages (``hash_gathered``): reading
files into messages is the gather's job (``cas.read_sampled_batch``, the
identify page), and no backend reads a file. ``CpuHasher.hash_batch``, the
native fused path-reading reference, is the one exception.

The TPU backend has one device path: it buckets messages by chunk count
(1/4/16/32/64/101 chunks; every sampled 57,352-byte message of a file
> 100KiB lands in the 64-chunk bucket), a fixed number of rows a call, so
the compiled-shape count stays constant. The hybrid probes both engines on
a page's sampled messages and leaves the verdict to its ``BackendRouter``.

The device compression kernel under every bucket call (single-device and
sharded) is selected by ``SD_BLAKE3_KERNEL=xla|pallas`` — resolved per call
inside ops/blake3_jax's entry points, so the hashers need no plumbing and a
process switches kernels without re-instantiating backends (each choice
jit-caches separately).

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
from .cas import MINIMUM_FILE_SIZE, SAMPLED_MESSAGE_LEN, generate_cas_id

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
    hybrid runs its device slice on a bounded helper THREAD
    (``_bounded_call``), and that sub-batch must still attribute to the
    one hybrid batch). Concurrent independent batches undercount to one —
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
    #: True when hash_gathered may touch the jax device backend —
    #: get_hasher passes the device gate before instantiating such backends
    USES_DEVICE: bool

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


class TpuHasher:
    """Batched JAX/TPU path over gathered cas messages: the bucketed
    device calls of :func:`_bucketed_hash`."""

    name = "tpu"
    USES_DEVICE = True

    @_count_hash_gathered
    def hash_gathered(self, messages: list[bytes | Exception],
                      signatures: dict | None = None) -> list[str | Exception]:
        """Pre-gathered messages through the device bucket path (sampled
        57-chunk messages land in the 64-chunk bucket).
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

    # the hook the sharded variant overrides
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

    @property
    def unprobed(self) -> bool:
        """Neither seeded by a probe nor degraded: no verdict yet (a
        degraded pin is one — the device lost)."""
        return (self.cpu_bps is None and self.dev_bps is None
                and not self.degraded)

    def seed(self, cpu_bps: float, dev_bps: float) -> None:
        """Initialize from the one-time probe (both engines measured on
        real work); the EWMAs take over from here."""
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

    The first page with enough sampled messages probes each engine's solo
    throughput on them (the results are kept, not discarded); the probe
    SEEDS a :class:`BackendRouter`, which holds the verdict and re-picks
    the engine PER BATCH from live transfer-inclusive rates (EWMA,
    hysteresis-damped, with periodic exploration of the losing engine and a
    bounded re-probe out of the degraded pin). On rigs where the device
    loses, ALL sampled work routes native, so hybrid throughput equals the
    best available engine by construction instead of losing to contention.

    The reference has a single engine (CPU join_all, file_identifier/
    mod.rs:107-134); this seam is where a local-PCIe TPU host gets its
    speedup without any config change."""

    name = "hybrid"
    USES_DEVICE = True

    #: sampled messages each engine hashes in the one-time rate probe
    PROBE = 64

    def __init__(self) -> None:
        self._tpu = TpuHasher()
        self._cpu = CpuHasher()
        #: per-batch engine router (live transfer-inclusive rates + EWMA
        #: hysteresis); seeded by the one-time probe (:meth:`_probe`)
        self.router = BackendRouter()

    def degrade_device(self, reason: str = "") -> None:
        """Pin the route to native CPU after a mid-batch device failure.
        The pin is NOT forever: the router re-probes the device on a
        bounded sub-slice after ``REPROBE_AFTER`` CPU-routed batches."""
        self.router.degrade(reason)
        logger.warning("hybrid hasher degraded to native CPU%s",
                       f": {reason}" if reason else "")

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

    def _probe(self, messages, big: list[int], out: list,
               signatures: dict | None) -> list[int] | None:
        """Measure both engines on leading slices of this page's sampled
        messages and seed the router in bytes/s; returns the still-unhashed
        ones — or None when the page holds too few to measure anything (the
        router stays unprobed so a fuller page probes; the process-wide
        hasher must not pin itself to placeholder rates off a tiny page)."""
        k = min(self.PROBE, len(big) // 2)
        if k < 8:
            return None
        cpu_part, dev_part = big[:k], big[k:2 * k]
        cpu_msgs = [messages[i] for i in cpu_part]
        dev_msgs = [messages[i] for i in dev_part]
        t0 = time.perf_counter()
        res = self._cpu.hash_gathered(cpu_msgs)
        cpu_bps = sum(map(len, cpu_msgs)) / max(1e-9, time.perf_counter() - t0)
        for i, r in zip(cpu_part, res):
            out[i] = r
        # the device probe gets a hard deadline: a probe that never
        # returns would stall every scan — _bounded_call runs it on a
        # bounded daemon worker (the one wedge-handling policy; compile
        # time does not count against it)
        signed: dict = {}
        t0 = time.perf_counter()
        status, res = _bounded_call(
            lambda: self._tpu.hash_gathered(dev_msgs, signed),
            max(60.0, k * 0.5), "hybrid-device-probe")
        if status == "ok":
            dev_bps = sum(map(len, dev_msgs)) / max(
                1e-9, time.perf_counter() - t0)
            if signatures is not None:
                signatures.update((dev_part[j], sig)
                                  for j, sig in signed.items())
        else:
            # timeout, or a dying device: score it dead and finish its
            # slice on the CPU (the router is seeded, not degraded)
            logger.warning(
                "hybrid probe: device engine %s; routing everything to "
                "native CPU",
                "unresponsive after deadline" if status == "timeout"
                else f"failed ({res!r})")
            res = self._cpu.hash_gathered(dev_msgs)
            dev_bps = 0.0
        for i, r in zip(dev_part, res):
            out[i] = r
        self.router.seed(cpu_bps, dev_bps)
        logger.info("hybrid probe: cpu %.2f MB/s, device %.2f MB/s — %s",
                    cpu_bps / 1e6, dev_bps / 1e6,
                    "engaging device" if dev_bps > cpu_bps
                    else "routing to native CPU")
        return big[2 * k:]

    @_count_hash_gathered
    def hash_gathered(self, messages: list[bytes | Exception],
                      signatures: dict | None = None) -> list[str | Exception]:
        """PER-BATCH engine choice by the router (live transfer-inclusive
        rates, hysteresis, bounded re-probe). While the router is unprobed,
        the page's sampled messages first carry the probe (:meth:`_probe`);
        a page too small to probe routes native. With no native lib there
        is nothing to race — everything goes to the device path, never the
        python oracle. ``signatures`` takes what the device slice signed
        (:meth:`TpuHasher.hash_gathered`); the CPU slice signs nothing."""
        if self._cpu._fast is None:
            return self._tpu.hash_gathered(messages, signatures)
        # short messages stay on native CPU (IO-bound work whose varied
        # lengths would fan the device path across many bucket shapes);
        # sampled-class messages are the routable payload
        big = [i for i, m in enumerate(messages)
               if not isinstance(m, Exception) and len(m) >= SAMPLED_MESSAGE_LEN]
        if not big:
            return self._cpu.hash_gathered(messages)
        big_set = set(big)
        rest = [i for i in range(len(messages)) if i not in big_set]
        out: list[str | Exception] = [None] * len(messages)  # type: ignore[list-item]
        if self.router.unprobed:
            big = self._probe(messages, big, out, signatures)
            if big is None:
                return self._cpu.hash_gathered(messages)
        if big:
            main, probe = self.router.route()
            if probe is not None and probe != main and len(big) > 1:
                # capped sub-slice on the losing engine keeps its EWMA
                # live (and is the degraded path's bounded device
                # re-probe) — under the TIGHT probe deadline, so a
                # collapsed/wedged device costs seconds, not a generous
                # wedge-detection window
                cut = min(self.router.PROBE_SLICE, len(big) // 2)
                if cut > 0:
                    self._dispatch_gathered(probe, big[:cut], messages, out,
                                            signatures, probe=True)
                    big = big[cut:]
            self._dispatch_gathered(main, big, messages, out, signatures)
        if rest:
            res = self._cpu.hash_gathered([messages[i] for i in rest])
            for i, r in zip(rest, res):
                out[i] = r
        return out


class ShardedHasher(TpuHasher):
    """Multi-device variant: batch axis sharded over a data-parallel mesh
    (parallel/mesh.py) for the bucket calls; lane counts pad to a multiple
    of the mesh's data-axis size."""

    name = "tpu-sharded"

    def __init__(self) -> None:
        from ..parallel.mesh import make_mesh

        self._mesh = make_mesh()

    def _pad_lanes(self, n: int) -> int:
        from ..parallel.mesh import pad_batch_for_mesh

        return pad_batch_for_mesh(n, self._mesh)

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
