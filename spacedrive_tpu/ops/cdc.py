"""Batched Gear content-defined chunking — the rolling-hash half of the
identifier hot path (the BASELINE north star names "rolling-hash + BLAKE3
kernels"; blake3_jax.py shipped the second half, this module ships the first).

Gear CDC (arxiv 2508.05797, 2505.21194) slides a 32-byte window over the
file: ``h_i = ((h_{i-1} << 1) + G[b_i]) mod 2^32`` with a random 256-entry
``G`` table, cutting where ``h & mask == 0``. The left-shift expires every
byte after 32 steps, so the recurrence *is* a windowed sum::

    h_i = sum_{k=0..31} G[b_{i-k}] << k   (mod 2^32)

— position-independent and therefore lane-parallel: no carried state, just
32 shifted adds over a ``(batch, length)`` u32 plane. That is the whole
vectorization story, and it is exactly the shape the repo already routes to
the device for BLAKE3. (Classic serial Gear resets ``h`` at each cut; the
windowed form is the non-resetting variant — still content-defined and
shift-resistant, and the per-byte pure-Python oracle below matches it
exactly, so every rung agrees byte-for-byte.)

Three rungs, selected per call (or ``SD_CDC_KERNEL=numpy|xla|pallas``):

- ``numpy``: the vectorized native-CPU rung (the BackendRouter's "cpu"
  engine) — 32 in-place shifted adds with natural uint32 wraparound;
- ``xla``: the same plane algebra jit-compiled (the router's "device"
  engine on a real accelerator);
- ``pallas``: a hand-tiled kernel — the gear-mapped u32 plane is cut into
  128-column output tiles each carrying a 128-column left halo (built by an
  XLA gather *outside* the kernel: a 256-way data-dependent byte lookup has
  no efficient VPU lowering, so the table lookup stays in XLA and the
  kernel does the pure shift/add/mask arithmetic — a deliberate deviation
  from "table in SMEM"), grid over ``(row tiles, column tiles)``,
  ``(8, 128)``-aligned VMEM blocks, the boundary mask as an SMEM scalar.
  Interpret mode on CPU (blake3_pallas.interpret_mode).

All three rungs emit the identical *candidate bitmap*; one shared host-side
resolver then applies the min/max clamps with a forward scan over candidate
cut positions — so cross-rung byte-identity of the final boundaries holds by
construction, and the tests prove the bitmaps too.

Per-chunk ids reuse blake3_jax.blake3_batch_hex (chunks from every file in a
batch flatten into one device call; max chunk 64 KiB = 64 BLAKE3 chunks).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .blake3_jax import blake3_batch_hex
from .blake3_pallas import interpret_mode

_u32 = jnp.uint32

logger = logging.getLogger(__name__)

#: the three chunking rungs (module docstring)
KERNELS = ("numpy", "xla", "pallas")

#: rolling window width implied by the u32 left-shift recurrence
WINDOW = 32

#: truncated per-chunk BLAKE3 id length (hex chars; 128 bits — chunk ids key
#: cross-file dedup and delta reassembly, so they carry twice the cas_id's 64)
CHUNK_ID_HEX = 32


def resolve_kernel(kernel: str | None = None) -> str:
    """Explicit argument wins; else ``SD_CDC_KERNEL``; else ``xla``.
    Resolved per call (never memoized) so subprocess tests stay hermetic."""
    if kernel is None:
        kernel = os.environ.get("SD_CDC_KERNEL", "").strip().lower() or "xla"
    if kernel not in KERNELS:
        logger.warning("unknown SD_CDC_KERNEL=%r; using xla", kernel)
        kernel = "xla"
    return kernel


def _gear_table() -> np.ndarray:
    """The 256-entry u32 gear table, derived entry-by-entry from SHA-256 of a
    versioned label — deterministic across platforms and library versions
    (an RNG stream would tie chunk ids to a numpy version)."""
    out = np.empty(256, np.uint32)
    for i in range(256):
        d = hashlib.sha256(b"sd-cdc-gear-v1:%d" % i).digest()
        out[i] = int.from_bytes(d[:4], "little")
    return out


GEAR = _gear_table()


@dataclasses.dataclass(frozen=True)
class ChunkParams:
    """Clamp geometry. ``avg_size`` must be a power of two (it becomes the
    boundary mask); a cut candidate at position ``c`` (exclusive end offset)
    is accepted only when ``cur + min_size <= c <= min(cur + max_size, n)``,
    else the chunk is force-cut at that upper bound."""

    min_size: int = 2048
    avg_size: int = 8192
    max_size: int = 65536

    def __post_init__(self) -> None:
        if self.avg_size & (self.avg_size - 1):
            raise ValueError("avg_size must be a power of two")
        if not (0 < self.min_size <= self.avg_size <= self.max_size):
            raise ValueError("need 0 < min <= avg <= max")

    @property
    def mask(self) -> int:
        return self.avg_size - 1


DEFAULT_PARAMS = ChunkParams()


# --------------------------------------------------------------------------
# pure-Python oracle (rung 0 — per-byte recurrence, tests/bench only)
# --------------------------------------------------------------------------


def chunk_boundaries_ref(data: bytes, params: ChunkParams = DEFAULT_PARAMS) -> list[int]:
    """Cut positions (exclusive end offsets) for one file, one byte at a
    time. The single source of truth the batched rungs are proven against."""
    n = len(data)
    mask = params.mask
    h = 0
    candidates = []
    for i in range(n):
        h = ((h << 1) + int(GEAR[data[i]])) & 0xFFFFFFFF
        if (h & mask) == 0:
            candidates.append(i + 1)
    return resolve_cuts(candidates, n, params)


def chunk_ref(data: bytes, params: ChunkParams = DEFAULT_PARAMS) -> list[tuple[int, int]]:
    """Oracle chunking as ``(offset, length)`` pairs."""
    return cuts_to_chunks(chunk_boundaries_ref(data, params))


# --------------------------------------------------------------------------
# shared clamp resolver (every rung funnels its candidate bitmap here)
# --------------------------------------------------------------------------


def resolve_cuts(candidates: "list[int] | np.ndarray", n: int,
                 params: ChunkParams = DEFAULT_PARAMS) -> list[int]:
    """Apply min/max clamps to ascending candidate positions: a forward scan
    that jumps to the first candidate inside the current chunk's admissible
    window, force-cutting at ``min(cur + max_size, n)`` when none lands.
    An empty file yields no chunks."""
    cuts: list[int] = []
    cur = 0
    ci = 0
    m = len(candidates)
    while cur < n:
        lo = cur + params.min_size
        hi = min(cur + params.max_size, n)
        cut = hi
        while ci < m and candidates[ci] <= hi:
            c = int(candidates[ci])
            ci += 1
            if c >= lo:
                cut = c
                break
        cuts.append(cut)
        cur = cut
    return cuts


def cuts_to_chunks(cuts: list[int]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    prev = 0
    for c in cuts:
        out.append((prev, c - prev))
        prev = c
    return out


# --------------------------------------------------------------------------
# rung 1: vectorized numpy (native-CPU router engine)
# --------------------------------------------------------------------------


def _candidates_numpy(buf: np.ndarray, lengths: np.ndarray,
                      mask: int) -> np.ndarray:
    """(B, L) u8 plane → (B, L) bool candidate bitmap (bit i ⇒ cut at i+1).
    32 in-place shifted adds; uint32 wraparound is the mod-2^32."""
    B, L = buf.shape
    g = GEAR[buf]  # (B, L) u32 table lookup
    h = np.zeros((B, L), np.uint32)
    for k in range(min(WINDOW, L)):
        h[:, k:] += g[:, : L - k] << np.uint32(k)
    cand = (h & np.uint32(mask)) == 0
    cand &= np.arange(L)[None, :] < lengths[:, None]
    return cand


# --------------------------------------------------------------------------
# rung 2: the same plane algebra, jit-compiled
# --------------------------------------------------------------------------


@jax.jit
def _candidates_xla(g: jax.Array, lengths: jax.Array,
                    mask: jax.Array) -> jax.Array:
    L = g.shape[1]
    h = jnp.zeros_like(g)
    for k in range(min(WINDOW, L)):
        h = h + (jnp.pad(g, ((0, 0), (k, 0)))[:, :L] << _u32(k))
    cand = (h & mask) == 0
    return cand & (jnp.arange(L)[None, :] < lengths[:, None])


# --------------------------------------------------------------------------
# rung 3: hand-tiled Pallas kernel
# --------------------------------------------------------------------------

#: sublane rows per grid step — the VPU's native u32 tile is (8, 128)
TILE_ROWS = 8
#: output columns per grid step; each input tile carries a full extra
#: 128-column left halo (only the last WINDOW-1 columns are read) so both
#: tile axes stay 128-aligned
TILE_COLS = 128


def _cdc_kernel(g_ref, mask_ref, out_ref):
    """One (TILE_ROWS, TILE_COLS) tile of boundary candidates. ``g_ref`` is
    the haloed gear plane block (TILE_ROWS, 2*TILE_COLS) of tile ``t``:
    local column ``TILE_COLS + j`` is global position ``t*TILE_COLS + j``,
    so the k-th window term for all 128 outputs is one static slice — 32
    shifted adds, all live values in vector registers, then the SMEM mask
    compare."""
    g = g_ref[...]
    h = jnp.zeros((TILE_ROWS, TILE_COLS), _u32)
    for k in range(WINDOW):
        h = h + (g[:, TILE_COLS - k : 2 * TILE_COLS - k] << _u32(k))
    out_ref[...] = jnp.where((h & mask_ref[0]) == 0, _u32(1), _u32(0))


@jax.jit
def _candidates_pallas(g: jax.Array, lengths: jax.Array,
                       mask: jax.Array) -> jax.Array:
    B, L = g.shape  # B % TILE_ROWS == 0, L % TILE_COLS == 0 (caller pads)
    nt = L // TILE_COLS
    # materialize haloed tiles with one pad + gather-free slicing: tile t
    # covers global columns [t*128 - 128, t*128 + 128). Tiles lead the
    # layout (nt, B, 256) so each block's last two dims are (8, 256) —
    # Mosaic tiles the last two dims of a block by (8, 128)
    gh = jnp.pad(g, ((0, 0), (TILE_COLS, 0)))
    tiles = jnp.stack(
        [gh[:, t * TILE_COLS : (t + 2) * TILE_COLS] for t in range(nt)], axis=0
    )  # (nt, B, 256)
    out = pl.pallas_call(
        _cdc_kernel,
        grid=(B // TILE_ROWS, nt),
        in_specs=[
            pl.BlockSpec((None, TILE_ROWS, 2 * TILE_COLS),
                         lambda i, j: (j, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((None, TILE_ROWS, TILE_COLS),
                               lambda i, j: (j, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((nt, B, TILE_COLS), _u32),
        interpret=interpret_mode(),
    )(tiles, jnp.asarray([mask], _u32).reshape(1))
    cand = out.transpose(1, 0, 2).reshape(B, L) != 0
    return cand & (jnp.arange(L)[None, :] < lengths[:, None])


# --------------------------------------------------------------------------
# batched entry point
# --------------------------------------------------------------------------

#: length tiers (padded plane width) so XLA compiles a handful of shapes
_LEN_TIER_MIN = 256
#: batch-size tiers (padded lane count)
_BATCH_TIERS = (8, 32, 128, 512)
#: per-call padded-cell ceiling (u32 plane cells ≈ 4 bytes each); groups
#: larger than this split into multiple device calls
_CELL_BUDGET = 1 << 23


def _len_tier(n: int) -> int:
    return max(_LEN_TIER_MIN, 1 << max(0, (n - 1)).bit_length())


def _batch_tier(b: int) -> int:
    for t in _BATCH_TIERS:
        if t >= b:
            return t
    return -(-b // _BATCH_TIERS[-1]) * _BATCH_TIERS[-1]


def candidate_bitmaps(datas: list[bytes], params: ChunkParams,
                      kernel: str) -> list[np.ndarray]:
    """Per-file boolean candidate bitmaps (bit i ⇒ cut at i+1) from the
    resolved rung, identical across rungs. Caller applies resolve_cuts."""
    Lp = _len_tier(max((len(d) for d in datas), default=1) or 1)
    Bp = _batch_tier(len(datas))
    plane = np.zeros((Bp, Lp), np.uint8)
    lengths = np.zeros(Bp, np.int32)
    for i, d in enumerate(datas):
        plane[i, : len(d)] = np.frombuffer(d, np.uint8)
        lengths[i] = len(d)
    if kernel == "numpy":
        cand = _candidates_numpy(plane, lengths, params.mask)
    else:
        g = jnp.take(jnp.asarray(GEAR), jnp.asarray(plane).astype(jnp.int32),
                     axis=0)
        fn = _candidates_pallas if kernel == "pallas" else _candidates_xla
        cand = np.asarray(fn(g, jnp.asarray(lengths),
                             jnp.asarray(params.mask, jnp.uint32)))
    return [cand[i, : len(d)] for i, d in enumerate(datas)]


def chunk_batch(datas: list[bytes], params: ChunkParams = DEFAULT_PARAMS,
                kernel: str | None = None) -> list[list[tuple[int, int]]]:
    """Chunk B files at once: per-file ``(offset, length)`` lists, in input
    order. Files group by padded-length tier under a cell budget so one
    pathological batch can't demand an unbounded plane."""
    k = resolve_kernel(kernel)
    results: list[list[tuple[int, int]] | None] = [None] * len(datas)
    groups: dict[int, list[int]] = {}
    for i, d in enumerate(datas):
        groups.setdefault(_len_tier(len(d)), []).append(i)
    for tier, idxs in sorted(groups.items()):
        per_call = max(1, _CELL_BUDGET // tier)
        for s in range(0, len(idxs), per_call):
            part = idxs[s : s + per_call]
            bitmaps = candidate_bitmaps([datas[i] for i in part], params, k)
            for i, bm in zip(part, bitmaps):
                cuts = resolve_cuts(np.flatnonzero(bm) + 1, len(datas[i]), params)
                results[i] = cuts_to_chunks(cuts)
    return results  # type: ignore[return-value]


# --------------------------------------------------------------------------
# per-chunk BLAKE3 ids (reuses the PR 2 kernel)
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=8)
def _b3_max_chunks(max_size: int) -> int:
    return max(1, -(-max_size // 1024))


def chunk_ids(datas: list[bytes], chunk_lists: list[list[tuple[int, int]]],
              params: ChunkParams = DEFAULT_PARAMS,
              kernel: str | None = None) -> list[list[str]]:
    """Per-file ordered chunk-id lists: every chunk of every file flattens
    into one blake3_batch_hex call (ids truncated to CHUNK_ID_HEX chars).
    ``kernel`` here picks the BLAKE3 compression rung (pallas for the CDC
    pallas rung, else the blake3 default) — chunk *boundaries* came from
    chunk_batch."""
    msgs: list[bytes] = []
    spans: list[int] = []
    for data, chunks in zip(datas, chunk_lists):
        spans.append(len(chunks))
        for off, ln in chunks:
            msgs.append(data[off : off + ln])
    b3_kernel = "pallas" if kernel == "pallas" else None
    hexes = blake3_batch_hex(msgs, max_chunks=_b3_max_chunks(params.max_size),
                             kernel=b3_kernel)
    out: list[list[str]] = []
    pos = 0
    for n in spans:
        out.append([h[:CHUNK_ID_HEX] for h in hexes[pos : pos + n]])
        pos += n
    return out


def build_manifest(data: bytes, params: ChunkParams = DEFAULT_PARAMS,
                   kernel: str | None = None) -> list[tuple[str, int]]:
    """One file → ordered ``(chunk_id, length)`` pairs — the manifest row
    payload, and what the delta sender/receiver compute locally."""
    chunks = chunk_batch([data], params, kernel)[0]
    ids = chunk_ids([data], [chunks], params, kernel)[0]
    return [(cid, ln) for cid, (_, ln) in zip(ids, chunks)]
