"""Batched BLAKE3 for TPU — the cas_id hot-path kernel.

Byte-identical to the pure-Python oracle (objects/blake3_ref.py) and therefore
to the reference's `blake3` crate output (core/src/object/cas.rs). Designed for
XLA/TPU rather than translated from any CPU implementation:

- **Chunk-parallel phase 1.** BLAKE3's serial dependency is only *within* a
  1024-byte chunk (16 chained block compressions); chunks are independent
  leaves of the merkle tree. So the kernel treats ``chunks x batch`` as one
  giant lane grid and runs a single 16-step ``lax.scan`` over block position —
  every step advances every chunk of every message at once on the VPU's 8x128
  lanes. A batch of 4096 sampled files is 57x4096 ≈ 233k parallel lanes.
- **Log-depth merkle phase 2.** The chunk-stack of streaming implementations
  is a CPU artifact. Level-wise adjacent pairing (odd tail promoted unchanged)
  yields exactly BLAKE3's left-heavy tree, so the merge is ceil(log2(C))
  vectorized parent compressions, each over all pairs of all lanes at once.
  Per-lane root detection (`nodes_left == 2`) applies the ROOT flag.
- **Static shapes.** Messages are zero-padded into fixed chunk capacities
  (57 for the fixed 57,352-byte sampled path, small-file buckets otherwise);
  per-lane byte lengths drive block-count/flag masks computed on device.

Everything is uint32 add/xor/rotate — pure VPU work; the rounds/permutation
schedule is unrolled (static), only the lanes are data.

Two interchangeable compression kernels sit under this orchestration,
selected per call (or via ``SD_BLAKE3_KERNEL=pallas|xla``, default xla):

- ``xla``: the graph-compiled :func:`compress` below (rounds as a 7-step
  ``lax.scan`` — small HLO, XLA schedules everything);
- ``pallas``: the hand-tiled register-resident kernel in blake3_pallas.py
  (8×128 u32 lane tiles, rounds unrolled, permutation baked into the
  schedule). Byte-identical outputs — tests prove both against the
  objects/blake3_ref.py oracle, in Pallas interpret mode on CPU.

Multi-device: shard the batch axis with ``jax.sharding``; see parallel/mesh.py.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import telemetry

# spec constants: the oracle is the single source of truth
from ..objects.blake3_ref import (  # noqa: E402
    BLOCK_LEN,
    CHUNK_END,
    CHUNK_LEN,
    CHUNK_START,
    IV,
    MSG_PERMUTATION,
    PARENT,
    ROOT,
)

BLOCKS_PER_CHUNK = CHUNK_LEN // BLOCK_LEN

_u32 = jnp.uint32

logger = logging.getLogger(__name__)

#: the two compression kernels behind the orchestration (module docstring)
KERNELS = ("xla", "pallas")


def resolve_kernel(kernel: str | None = None) -> str:
    """Explicit argument wins; else ``SD_BLAKE3_KERNEL``; else ``xla``.
    Resolved per call (never memoized) so subprocess tests stay hermetic —
    each jit cache entry is keyed by the resolved name."""
    if kernel is None:
        kernel = os.environ.get("SD_BLAKE3_KERNEL", "").strip().lower() or "xla"
    if kernel not in KERNELS:
        logger.warning("unknown SD_BLAKE3_KERNEL=%r; using xla", kernel)
        kernel = "xla"
    return kernel


def _compress_fn(kernel: str):
    """The compression primitive for a resolved kernel name. The pallas
    module imports lazily so xla-only processes never touch it."""
    if kernel == "pallas":
        from .blake3_pallas import compress_pallas

        return compress_pallas
    return compress


def _rotr(x: jax.Array, n: int) -> jax.Array:
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def _g(s, a, b, c, d, mx, my):
    s[a] = s[a] + s[b] + mx
    s[d] = _rotr(s[d] ^ s[a], 16)
    s[c] = s[c] + s[d]
    s[b] = _rotr(s[b] ^ s[c], 12)
    s[a] = s[a] + s[b] + my
    s[d] = _rotr(s[d] ^ s[a], 8)
    s[c] = s[c] + s[d]
    s[b] = _rotr(s[b] ^ s[c], 7)


_PERM = np.array(MSG_PERMUTATION)


def compress(cv, m, counter, block_len, flags):
    """One BLAKE3 compression, broadcast over any lane shape.

    ``cv``: list of 8 arrays; ``m``: list of 16 arrays or a stacked
    ``(16, ...)`` array; ``counter``/``block_len``/``flags``: arrays
    broadcastable to the lane shape (counter high word is 0 — the cas domain
    never exceeds 2^32 chunks). Returns the first 8 output words (chaining
    value / digest head).

    The 7 rounds run as a ``lax.scan`` with the message permutation as a
    static gather — NOT unrolled: rounds are serial anyway so unrolling buys
    no parallelism, and a ~450-op unrolled body sent XLA:CPU's
    post-layout simplification fixed-point into multi-minute compiles.
    """
    zero = jnp.zeros(jnp.broadcast_shapes(cv[0].shape, block_len.shape, flags.shape), _u32)
    s0 = (
        cv[0] + zero, cv[1] + zero, cv[2] + zero, cv[3] + zero,
        cv[4] + zero, cv[5] + zero, cv[6] + zero, cv[7] + zero,
        zero + _u32(IV[0]), zero + _u32(IV[1]), zero + _u32(IV[2]), zero + _u32(IV[3]),
        counter.astype(_u32) + zero, zero,
        block_len.astype(_u32) + zero, flags.astype(_u32) + zero,
    )
    if isinstance(m, (list, tuple)):
        m = jnp.stack([mw + jnp.zeros_like(zero) for mw in m])
    else:
        m = m + jnp.zeros_like(zero)[None]

    def round_body(carry, _):
        s, m = carry
        s = list(s)
        _g(s, 0, 4, 8, 12, m[0], m[1])
        _g(s, 1, 5, 9, 13, m[2], m[3])
        _g(s, 2, 6, 10, 14, m[4], m[5])
        _g(s, 3, 7, 11, 15, m[6], m[7])
        _g(s, 0, 5, 10, 15, m[8], m[9])
        _g(s, 1, 6, 11, 12, m[10], m[11])
        _g(s, 2, 7, 8, 13, m[12], m[13])
        _g(s, 3, 4, 9, 14, m[14], m[15])
        # permuting after the final round too is harmless: m is discarded
        return (tuple(s), m[_PERM]), None

    (s, _), _ = lax.scan(round_body, (s0, m), None, length=7)
    return [s[i] ^ s[i + 8] for i in range(8)]


def _iv_lanes(shape) -> list[jax.Array]:
    return [jnp.full(shape, w, _u32) for w in IV]


def blake3_batch(words: jax.Array, lengths: jax.Array,
                 kernel: str | None = None) -> jax.Array:
    """Hash B zero-padded messages.

    ``words``: (16 blocks, 16 words, C chunks, B) uint32, little-endian packed
    (see :func:`pack_messages`); ``lengths``: (B,) int32 true byte lengths,
    each <= C*1024. Returns (8, B) digest words — 32 bytes LE per lane.
    ``kernel`` picks the compression primitive (:func:`resolve_kernel`).
    """
    return _blake3_batch_impl(words, lengths, kernel=resolve_kernel(kernel))


@functools.partial(jax.jit, static_argnames=("kernel",))
def _blake3_batch_impl(words: jax.Array, lengths: jax.Array, *,
                       kernel: str = "xla") -> jax.Array:
    compress_k = _compress_fn(kernel)
    _, _, C, B = words.shape
    lengths = lengths.astype(jnp.int32)
    n_chunks = jnp.maximum(1, (lengths + (CHUNK_LEN - 1)) // CHUNK_LEN)  # (B,)

    chunk_idx = jnp.arange(C, dtype=jnp.int32)[:, None]  # (C, 1)
    chunk_len = jnp.clip(lengths[None, :] - chunk_idx * CHUNK_LEN, 0, CHUNK_LEN)  # (C, B)
    n_blocks = jnp.maximum(1, (chunk_len + (BLOCK_LEN - 1)) // BLOCK_LEN)  # (C, B)

    # ---- phase 1: all chunk CVs via one 16-step block scan over (C, B) lanes
    def block_body(cv, xs):
        j, m = xs  # j scalar, m (16, C, B)
        block_len = jnp.clip(chunk_len - j * BLOCK_LEN, 0, BLOCK_LEN).astype(_u32)
        flags = (
            jnp.where(j == 0, _u32(CHUNK_START), _u32(0))
            | jnp.where(j == n_blocks - 1, _u32(CHUNK_END), _u32(0))
        )
        out = compress_k(cv, [m[w] for w in range(16)],
                         jnp.broadcast_to(chunk_idx, (C, B)), block_len, flags)
        keep = j < n_blocks  # (C, B)
        return [jnp.where(keep, out[w], cv[w]) for w in range(8)], None

    cvs, _ = lax.scan(block_body, _iv_lanes((C, B)), (jnp.arange(BLOCKS_PER_CHUNK), words))

    # ---- single-chunk lanes: rerun chunk 0 with ROOT on each lane's final block
    single_root = _single_chunk_root(words[:, :, 0, :], lengths, kernel)  # (8, B)

    # ---- phase 2: log-depth merkle merge (adjacent pairing == BLAKE3 tree).
    # One fixed-shape lax.scan over levels — NOT an unrolled width-shrinking
    # loop, which would instantiate a distinct ~450-op compress per level and
    # blow up XLA compile time. Active nodes stay packed in the array prefix;
    # lanes whose remaining count runs out promote their left node (the odd
    # tail of BLAKE3's left-heavy tree); slots past the prefix carry garbage
    # that the masks never read.
    if C > 1:
        Cp = 1 << (C - 1).bit_length()  # pad chunk axis to a power of two
        nodes = jnp.stack([
            jnp.pad(cv, ((0, Cp - C), (0, 0))) if Cp != C else cv for cv in cvs
        ])  # (8, Cp, B)
        half = Cp // 2
        pair_idx = jnp.arange(half, dtype=jnp.int32)[:, None]  # (half, 1)
        zero = jnp.zeros((half, B), _u32)

        def level(carry, _):
            nodes, remaining, root8 = carry
            left = nodes[:, 0 : 2 * half : 2]  # (8, half, B)
            right = nodes[:, 1 : 2 * half : 2]
            has_right = (2 * pair_idx + 1) < remaining[None, :]  # (half, B)
            is_root_pair = (pair_idx == 0) & (remaining[None, :] == 2)
            flags = jnp.where(is_root_pair, _u32(PARENT | ROOT), _u32(PARENT))
            parent = compress_k(
                _iv_lanes((half, B)),
                [left[w] for w in range(8)] + [right[w] for w in range(8)],
                zero, zero + _u32(BLOCK_LEN), flags,
            )
            merged = jnp.stack(
                [jnp.where(has_right, parent[w], left[w, :, :]) for w in range(8)]
            )
            root8 = jnp.stack(
                [jnp.where(is_root_pair[0], parent[w][0], root8[w]) for w in range(8)]
            )
            nodes = jnp.concatenate(
                [merged, jnp.zeros((8, Cp - half, B), _u32)], axis=1
            )
            return (nodes, (remaining + 1) // 2, root8), None

        carry0 = (nodes, n_chunks, jnp.zeros((8, B), _u32))
        (_, _, root8), _ = lax.scan(level, carry0, None, length=Cp.bit_length() - 1)
        digest = [jnp.where(n_chunks == 1, single_root[w], root8[w]) for w in range(8)]
    else:
        digest = single_root
    return jnp.stack(digest)


def _single_chunk_root(words0: jax.Array, lengths: jax.Array,
                       kernel: str = "xla") -> list[jax.Array]:
    """Digest for lanes whose whole message fits one chunk. ``words0``:
    (16, 16, B). One compression per block: non-final blocks chain the CV,
    each lane's final block takes CHUNK_END|ROOT and emits the digest."""
    compress_k = _compress_fn(kernel)
    B = words0.shape[-1]
    chunk_len = jnp.clip(lengths, 0, CHUNK_LEN)
    n_blocks = jnp.maximum(1, (chunk_len + (BLOCK_LEN - 1)) // BLOCK_LEN)  # (B,)
    zero = jnp.zeros((B,), _u32)

    def body(carry, xs):
        cv, digest = carry
        j, m = xs
        is_final = j == n_blocks - 1
        block_len = jnp.clip(chunk_len - j * BLOCK_LEN, 0, BLOCK_LEN).astype(_u32)
        flags = jnp.where(j == 0, _u32(CHUNK_START), _u32(0)) | jnp.where(
            is_final, _u32(CHUNK_END | ROOT), _u32(0)
        )
        out = compress_k(cv, [m[w] for w in range(16)], zero, block_len, flags)
        # chain only through non-final blocks (a non-final block is always full)
        new_cv = [jnp.where(j < n_blocks - 1, out[w], cv[w]) for w in range(8)]
        new_digest = [jnp.where(is_final, out[w], digest[w]) for w in range(8)]
        return (new_cv, new_digest), None

    carry0 = (_iv_lanes((B,)), [zero] * 8)
    (_, digest), _ = lax.scan(body, carry0, (jnp.arange(BLOCKS_PER_CHUNK), words0))
    return digest


def blake3_batch_rows(rows: jax.Array, lengths: jax.Array,
                      kernel: str | None = None,
                      donate: bool = False) -> jax.Array:
    """Row-major entry: ``rows`` is (B, C*256) uint32 — each row one message
    in natural byte order (the layout the native gather writes). The
    (block, word, chunk, batch) permutation the scan wants happens ON DEVICE,
    where a 120MB transpose is ~free, instead of in a host numpy transpose
    that used to dominate the pipeline profile.

    ``donate=True`` (the hasher's bucket calls) DONATES the row
    buffer on a real accelerator: each call's rows are ``device_put``
    once and never touched again, so XLA may reuse the (tier × 57KiB)
    transfer buffer as scratch instead of allocating per batch —
    double-buffered H2D without doubling resident memory. Donation is
    OPT-IN because it invalidates the caller's array: callers that reuse a
    device-resident input across calls (the device micro-bench's repeat
    loop) must keep the default. The CPU backend doesn't implement
    donation (and would warn), so it always takes the plain entry point.
    """
    k = resolve_kernel(kernel)
    if donate and _backend_supports_donation():
        return _blake3_batch_rows_donated(rows, lengths, kernel=k)
    return _blake3_batch_rows_impl(rows, lengths, kernel=k)


@functools.lru_cache(maxsize=1)
def _backend_supports_donation() -> bool:
    try:
        return jax.default_backend() not in ("cpu",)
    except Exception:
        return False


def _rows_hash_body(rows: jax.Array, lengths: jax.Array,
                    kernel: str) -> jax.Array:
    B, W = rows.shape
    C = W // (BLOCKS_PER_CHUNK * 16)
    words = rows.reshape(B, C, BLOCKS_PER_CHUNK, 16).transpose(2, 3, 1, 0)
    return _blake3_batch_impl(words, lengths, kernel=kernel)


@functools.partial(jax.jit, static_argnames=("kernel",))
def _blake3_batch_rows_impl(rows: jax.Array, lengths: jax.Array, *,
                            kernel: str = "xla") -> jax.Array:
    return _rows_hash_body(rows, lengths, kernel)


@functools.partial(jax.jit, static_argnames=("kernel",), donate_argnums=(0,))
def _blake3_batch_rows_donated(rows: jax.Array, lengths: jax.Array, *,
                               kernel: str = "xla") -> jax.Array:
    return _rows_hash_body(rows, lengths, kernel)


# --------------------------------------------------------------------------
# host packing
# --------------------------------------------------------------------------


def pack_rows(messages: list[bytes], max_chunks: int,
              out: np.ndarray | None = None) -> tuple[np.ndarray, np.ndarray]:
    """Zero-pad B messages into (B, max_chunks*256) little-endian uint32
    rows, one message per row in natural byte order (the layout
    :func:`blake3_batch_rows` takes), plus (B,) int32 lengths.

    ``out``: a C-contiguous uint8 buffer of at least B*max_chunks*1024 bytes
    to pack into, whatever it holds (every byte is written); a new one by
    default. Rows are filled by ``memoryview`` slice assignment, which keeps
    the GIL through each copy: a numpy copy releases it, and while the
    scan's gather and commit threads run Python, each retake waits on them."""
    B = len(messages)
    cap = max_chunks * CHUNK_LEN
    lengths = np.fromiter(map(len, messages), np.int32, B)
    over = np.flatnonzero(lengths > cap)
    if over.size:
        i = int(over[0])
        raise ValueError(f"message {i} ({lengths[i]}B) exceeds capacity {cap}B")
    buf = np.empty(B * cap, np.uint8) if out is None else out[: B * cap]
    dst = memoryview(buf)
    zeros = memoryview(bytes(cap))
    for i, n in enumerate(lengths.tolist()):
        o = i * cap
        dst[o : o + n] = messages[i]
        dst[o + n : o + cap] = zeros[n:]
    return buf.view("<u4").reshape(B, cap // 4), lengths


def pack_messages(messages: list[bytes], max_chunks: int) -> tuple[np.ndarray, np.ndarray]:
    """Zero-pad B messages into the (16, 16, max_chunks, B) batch-minor layout
    plus (B,) int32 lengths."""
    B = len(messages)
    cap = max_chunks * CHUNK_LEN
    buf = np.zeros((B, cap), np.uint8)
    lengths = np.empty(B, np.int32)
    for i, msg in enumerate(messages):
        n = len(msg)
        if n > cap:
            raise ValueError(f"message {i} ({n}B) exceeds capacity {cap}B")
        buf[i, :n] = np.frombuffer(msg, np.uint8)
        lengths[i] = n
    words = buf.view("<u4").reshape(B, max_chunks, BLOCKS_PER_CHUNK, 16)
    # (B, C, blocks, words) -> (blocks, words, C, B)
    return np.ascontiguousarray(words.transpose(2, 3, 1, 0)), lengths


def digests_to_hex(digest_words: np.ndarray) -> list[str]:
    """(8, B) uint32 → per-lane 64-char hex digests (cas_id takes [:16])."""
    words = np.asarray(digest_words).astype("<u4")
    b = np.ascontiguousarray(words.T).tobytes()  # B rows of 32 bytes
    return [b[i * 32 : (i + 1) * 32].hex() for i in range(words.shape[1])]


#: batch-size tiers: every call pads its lane count up to a tier so XLA
#: compiles a handful of (chunks, batch) shapes total, never per-call shapes
BATCH_TIERS = (8, 64, 512, 1024, 2048, 4096)


def _pad_to_tier(n: int) -> int:
    for t in BATCH_TIERS:
        if t >= n:
            return t
    return -(-n // BATCH_TIERS[-1]) * BATCH_TIERS[-1]


class _RowBuffer:
    """The host buffer rows are packed into, kept between device hash calls.

    Faulting in a fresh buffer for every call costs more than the copy into
    it: for a 1,024-file page of 64-chunk rows (64 MiB), 65 us a file
    against 4 on a TPU v5e host. One buffer is kept, the largest used; a
    call that finds it taken packs into a new one. A call that fails drops
    its buffer, since the device may still be reading it; one that returns
    has read back its digests, so the device is done with its rows."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._idle: np.ndarray | None = None

    @contextlib.contextmanager
    def take(self, nbytes: int):
        with self._lock:
            buf, self._idle = self._idle, None
        if buf is None or buf.nbytes < nbytes:
            buf = np.empty(nbytes, np.uint8)
        yield buf
        with self._lock:
            if self._idle is None or self._idle.nbytes < buf.nbytes:
                self._idle = buf


_row_buffer = _RowBuffer()


def _run_rows(messages: list[bytes], max_chunks: int, lanes: int, run):
    """Pack B messages into ``lanes`` >= B rows (the padding lanes hold the
    empty message), put them on the device, and read back what
    ``run(rows, lengths)`` computes from them, as numpy. The host only
    copies each message into its row; the (block, word, chunk, batch)
    permutation runs on the device."""
    B = len(messages)
    # one span per stage of the call, under the caller's open span (the
    # identify dispatch thread's pipeline.hash): host packing, the H2D
    # enqueue, then the kernel through the outputs' readback
    trace = telemetry.current_trace()
    with _row_buffer.take(lanes * max_chunks * CHUNK_LEN) as buf:
        with telemetry.span(trace, "hash.pack", files=B):
            padded = messages + [b""] * (lanes - B)
            rows, lengths = pack_rows(padded, max_chunks, out=buf)
        with telemetry.span(trace, "hash.put"):
            rows, lengths = jnp.asarray(rows), jnp.asarray(lengths)
        with telemetry.span(trace, "hash.wait"):
            return jax.device_get(run(rows, lengths))


def hash_rows_hex(messages: list[bytes], max_chunks: int, lanes: int,
                  hash_rows) -> list[str]:
    """Hex digests of B messages, hashed in ``lanes`` rows on the device
    through ``hash_rows(rows, lengths) -> (8, lanes)`` digest words, the
    padding lanes dropped."""
    digests = _run_rows(messages, max_chunks, lanes, hash_rows)
    return digests_to_hex(digests)[: len(messages)]


def hash_sign_rows_hex(messages: list[bytes], max_chunks: int, lanes: int,
                       sign_words: int) -> tuple[list[str], np.ndarray]:
    """:func:`hash_rows_hex`, and the (B, K) MinHash signatures
    (ops/minhash.py) of each message's first ``sign_words`` words, from the
    same device rows. The signature program is enqueued before the BLAKE3
    program that takes the rows as a donation, and programs on a device run
    in the order they were enqueued, so it reads them first."""
    from .minhash import minhash_rows

    def run(rows, lengths):
        sigs = minhash_rows(rows, lengths, words=sign_words)
        return blake3_batch_rows(rows, lengths, donate=True), sigs

    B = len(messages)
    digests, sigs = _run_rows(messages, max_chunks, lanes, run)
    return digests_to_hex(digests)[:B], sigs[:B]


def blake3_batch_hex(messages: list[bytes], max_chunks: int | None = None,
                     kernel: str | None = None,
                     lanes: int | None = None) -> list[str]:
    """Convenience one-shot: pack rows → device hash → hex digests. Pads the
    batch to ``lanes`` rows (empty-message lanes), by default to a size tier,
    to bound compiled-shape count."""
    if not messages:
        return []
    if max_chunks is None:
        need = max(1, max((len(m) + CHUNK_LEN - 1) // CHUNK_LEN for m in messages))
        max_chunks = 1 << (need - 1).bit_length()  # tier to a power of two
    # each call puts its rows anew, so the device copy may be donated
    return hash_rows_hex(messages, max_chunks,
                         lanes or _pad_to_tier(len(messages)),
                         functools.partial(blake3_batch_rows, kernel=kernel,
                                           donate=True))
