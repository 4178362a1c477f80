"""Batched predicate scorers for the columnar search index.

The device engine scores substring / exact-match predicates over the
index's byte-plane layout — ``hay[(W, N)] u8`` where plane ``w`` holds
byte ``w`` of every row, the same lane discipline as the BLAKE3 Pallas
kernel (ops/blake3_pallas.py: lanes are independent work items, the last
axis is the VPU's native 128). Three implementations share one contract
and are byte-identical (tests/test_search.py):

- **numpy** (`*_np`) — the CPU engine and the oracle the others are
  proven against;
- **XLA** (`SD_SEARCH_KERNEL=xla`) — plain jnp ops, one fused compare
  tree per (needle length, plane count);
- **Pallas** (`SD_SEARCH_KERNEL=pallas`) — a hand-tiled kernel beside
  blake3_pallas: planes are tiled to (W, 32, 128) u8 blocks (32 sublanes
  is the int8-native tile), the needle rides SMEM, and the
  (W−L+1)-offset × L-byte compare tree is fully unrolled at trace time
  so the accumulator never leaves vector registers. On non-TPU backends
  it runs in Pallas interpret mode (pure-JAX evaluation) — byte-identical
  parity is provable on CPU while the device relay is down, exactly the
  blake3 discipline.

Semantics contract (what makes the engine's answers reproduce the SQL
path byte-for-byte):

- ``substring``: SQLite ``LIKE '%needle%'`` with both sides ASCII-folded
  (SQLite's default LIKE is case-insensitive for A-Z only); haystack
  planes are stored pre-folded, the needle is folded by the caller.
  Rows are zero-padded past their length, and needles never contain
  NUL, so padding can produce no false positive. Rows whose value was
  TRUNCATED at the plane width (len > W) may under-match here — the
  caller patches those few rows host-side (ColumnarIndex.overflow).
- ``exact``: SQL ``=`` (BINARY collation — byte equality). The needle is
  zero-padded to the plane width; equality of padded vectors ⟺ string
  equality whenever the stored value fit (len ≤ W). Truncated rows are
  again the caller's host-side patch.
"""

from __future__ import annotations

import functools
import os

import numpy as np

#: needles longer than this fall back to SQLite (the unrolled compare
#: tree stays bounded; search strings this long are vanishingly rare)
MAX_NEEDLE = 48

#: sublane rows per Pallas grid step — 32×128 is the int8-native tile
TILE_ROWS = 32
LANES = 128
TILE = TILE_ROWS * LANES


def fold(raw: bytes) -> bytes:
    """ASCII-fold (A-Z → a-z) — exactly SQLite's default LIKE folding;
    non-ASCII bytes compare exact there and here."""
    return raw.lower() if raw.isascii() else \
        bytes(b + 32 if 0x41 <= b <= 0x5A else b for b in raw)


def resolve_kernel() -> str:
    """``SD_SEARCH_KERNEL=pallas|xla`` per call (the blake3 discipline:
    jit caches are keyed per kernel, so flipping the env mid-process is
    safe). Default: pallas on a real TPU, xla elsewhere (interpret-mode
    pallas costs pure-JAX emulation overhead with no hardware payoff)."""
    raw = os.environ.get("SD_SEARCH_KERNEL", "").strip().lower()
    if raw in ("pallas", "xla"):
        return raw
    import jax

    return "pallas" if jax.default_backend() == "tpu" else "xla"


# ---------------------------------------------------------------------------
# numpy — the CPU engine and the parity oracle
# ---------------------------------------------------------------------------


def substring_np(planes: np.ndarray, needle: bytes) -> np.ndarray:
    """``planes`` is (W, N) u8; returns an (N,) bool match mask."""
    w, n = planes.shape
    nlen = len(needle)
    if nlen == 0 or nlen > w:
        return np.zeros(n, dtype=bool)
    nb = np.frombuffer(needle, dtype=np.uint8)
    acc = np.zeros(n, dtype=bool)
    for j in range(w - nlen + 1):
        eq = planes[j] == nb[0]
        for k in range(1, nlen):
            if not eq.any():
                break
            eq = eq & (planes[j + k] == nb[k])
        acc |= eq
    return acc


def exact_np(planes: np.ndarray, needle: bytes) -> np.ndarray:
    """Byte equality of the zero-padded value vector (SQL ``=``)."""
    w, n = planes.shape
    if len(needle) > w:
        return np.zeros(n, dtype=bool)
    padded = np.zeros(w, dtype=np.uint8)
    padded[: len(needle)] = np.frombuffer(needle, dtype=np.uint8)
    eq = planes[0] == padded[0]
    for k in range(1, w):
        if not eq.any():
            break
        eq = eq & (planes[k] == padded[k])
    return eq


def presence_bitmap(planes: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """(N, 32) u8 byte-presence bitmap per row — the CPU engine's
    prescreen: a row can only contain a needle whose every byte is
    present in the row, so ``bitmap ⊇ needle-bytes`` prunes the exact
    window scan to a few percent of rows with zero false negatives.
    Byte value ``b`` lives at ``bits[:, b >> 3] & (1 << (b & 7))``
    (packbits bitorder='little'). Padding bytes (beyond ``lens``) are
    masked out so byte 0 means a literal NUL, not padding."""
    w, n = planes.shape
    bits = np.zeros((n, 32), dtype=np.uint8)
    chunk = 1 << 18  # bounds the (chunk, 256) one-hot temp to 64 MB
    for lo in range(0, n, chunk):
        hi = min(n, lo + chunk)
        onehot = np.zeros((hi - lo, 256), dtype=bool)
        rows = np.arange(hi - lo)
        for j in range(w):
            live = j < lens[lo:hi]
            onehot[rows[live], planes[j, lo:hi][live]] = True
        bits[lo:hi] = np.packbits(onehot, axis=1, bitorder="little")
    return bits


def prescreen_np(bits: np.ndarray, needle: bytes) -> np.ndarray:
    """(N,) bool — rows whose presence bitmap covers every needle byte."""
    cand = np.ones(bits.shape[0], dtype=bool)
    for b in set(needle):
        cand &= (bits[:, b >> 3] & np.uint8(1 << (b & 7))) != 0
    return cand


def lex_cmp_np(planes: np.ndarray, bound: bytes) -> np.ndarray:
    """(N,) i8 memcmp verdict (-1 | 0 | 1) of each zero-padded row value
    against the zero-padded bound — exactly SQLite's BINARY collation on
    TEXT (a proper prefix is smaller, which zero padding preserves)."""
    w, n = planes.shape
    padded = np.zeros(w, dtype=np.uint8)
    padded[: min(len(bound), w)] = np.frombuffer(
        bound[:w], dtype=np.uint8)
    res = np.zeros(n, dtype=np.int8)
    for k in range(w):
        undecided = res == 0
        if not undecided.any():
            break
        d = planes[k][undecided].astype(np.int16) - np.int16(padded[k])
        res[undecided] = np.sign(d).astype(np.int8)
    return res


# ---------------------------------------------------------------------------
# XLA — plain jnp ops (one fused compare tree per static shape)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=256)
def _substring_xla_jit(nlen: int, width: int):
    import jax
    import jax.numpy as jnp

    def run(planes, needle):  # (W, N) u8, (MAX_NEEDLE,) u8
        acc = jnp.zeros(planes.shape[1], dtype=jnp.bool_)
        for j in range(width - nlen + 1):
            eq = planes[j] == needle[0]
            for k in range(1, nlen):
                eq = eq & (planes[j + k] == needle[k])
            acc = acc | eq
        return acc

    return jax.jit(run)


@functools.lru_cache(maxsize=64)
def _exact_xla_jit(width: int):
    import jax
    import jax.numpy as jnp

    def run(planes, padded):  # (W, N) u8, (W,) u8
        eq = planes[0] == padded[0]
        for k in range(1, width):
            eq = eq & (planes[k] == padded[k])
        return eq

    return jax.jit(run)


@functools.lru_cache(maxsize=64)
def _lex_xla_jit(width: int):
    import jax
    import jax.numpy as jnp

    def run(planes, padded):  # (W, N) u8, (W,) u8 → (N,) i8 memcmp
        gt = jnp.zeros(planes.shape[1], dtype=jnp.bool_)
        lt = jnp.zeros(planes.shape[1], dtype=jnp.bool_)
        for k in range(width):
            undecided = ~gt & ~lt
            gt = gt | (undecided & (planes[k] > padded[k]))
            lt = lt | (undecided & (planes[k] < padded[k]))
        return gt.astype(jnp.int8) - lt.astype(jnp.int8)

    return jax.jit(run)


# ---------------------------------------------------------------------------
# Pallas — the hand-tiled kernel (interpret mode off-TPU)
# ---------------------------------------------------------------------------


def _interpret() -> bool:
    from ..ops.blake3_pallas import interpret_mode

    return interpret_mode()


def _widen(hay_ref, k: int):
    """Plane ``k`` of the u8 block as i32: Mosaic on v5e has no i8 vector
    compare or add, so the kernels compare in i32 registers (the needle
    already rides SMEM as i32). The block in VMEM stays u8."""
    import jax.numpy as jnp

    return hay_ref[k].astype(jnp.int32)


def _to_u8(mask):
    import jax.numpy as jnp

    return mask.astype(jnp.int32).astype(jnp.uint8)


@functools.lru_cache(maxsize=256)
def _substring_pallas_jit(nlen: int, width: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(needle_ref, hay_ref, out_ref):
        # hay_ref: (W, TILE_ROWS, LANES) u8; needle_ref: (1, MAX_NEEDLE)
        # i32 in SMEM. The offset × byte compare tree is unrolled at
        # trace time; acc lives in vector registers across all offsets.
        acc = jnp.zeros((TILE_ROWS, LANES), dtype=jnp.bool_)
        for j in range(width - nlen + 1):
            eq = _widen(hay_ref, j) == needle_ref[0, 0]
            for k in range(1, nlen):
                eq = eq & (_widen(hay_ref, j + k) == needle_ref[0, k])
            acc = acc | eq
        out_ref[...] = _to_u8(acc)

    def run(planes, needle):  # (W, R, 128) u8, (1, MAX_NEEDLE) i32
        rows = planes.shape[1]
        return pl.pallas_call(
            kernel,
            grid=(rows // TILE_ROWS,),
            in_specs=[
                pl.BlockSpec((1, MAX_NEEDLE), lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((width, TILE_ROWS, LANES),
                             lambda i: (0, i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.uint8),
            interpret=interpret,
        )(needle, planes)

    return jax.jit(run)


@functools.lru_cache(maxsize=64)
def _exact_pallas_jit(width: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(needle_ref, hay_ref, out_ref):
        eq = _widen(hay_ref, 0) == needle_ref[0, 0]
        for k in range(1, width):
            eq = eq & (_widen(hay_ref, k) == needle_ref[0, k])
        out_ref[...] = _to_u8(eq)

    def run(planes, padded):  # (W, R, 128) u8, (1, W) i32
        rows = planes.shape[1]
        return pl.pallas_call(
            kernel,
            grid=(rows // TILE_ROWS,),
            in_specs=[
                pl.BlockSpec((1, width), lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((width, TILE_ROWS, LANES),
                             lambda i: (0, i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.uint8),
            interpret=interpret,
        )(padded, planes)

    return jax.jit(run)


@functools.lru_cache(maxsize=64)
def _lex_pallas_jit(width: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(bound_ref, hay_ref, out_ref):
        # memcmp with tie propagation, unrolled over the plane axis;
        # encodes the verdict as u8 (0 = eq, 1 = gt, 2 = lt)
        gt = jnp.zeros((TILE_ROWS, LANES), dtype=jnp.bool_)
        lt = jnp.zeros((TILE_ROWS, LANES), dtype=jnp.bool_)
        for k in range(width):
            b = bound_ref[0, k]
            plane = _widen(hay_ref, k)
            undecided = ~gt & ~lt
            gt = gt | (undecided & (plane > b))
            lt = lt | (undecided & (plane < b))
        out_ref[...] = (gt.astype(jnp.int32)
                        + 2 * lt.astype(jnp.int32)).astype(jnp.uint8)

    def run(planes, bound):  # (W, R, 128) u8, (1, W) i32
        rows = planes.shape[1]
        return pl.pallas_call(
            kernel,
            grid=(rows // TILE_ROWS,),
            in_specs=[
                pl.BlockSpec((1, width), lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((width, TILE_ROWS, LANES),
                             lambda i: (0, i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.uint8),
            interpret=interpret,
        )(bound, planes)

    return jax.jit(run)


# ---------------------------------------------------------------------------
# device entry points (the engine's device backend)
# ---------------------------------------------------------------------------
# ``planes`` here is a DEVICE-RESIDENT jnp array of shape (W, CAP) u8 with
# CAP a whole number of tiles (the DeviceMirror keeps it in sync with the
# columnar master incrementally — no per-query host→device transfer).
# Returns host numpy over the full CAP; callers slice [:n].


def pad_cap(n: int) -> int:
    """Device capacity for ``n`` rows: a whole number of Pallas tiles."""
    return max(TILE, -(-n // TILE) * TILE)


def substring_jnp(planes, needle: bytes, kernel: str) -> np.ndarray:
    """(CAP,) bool via the selected device kernel; byte-identical to
    :func:`substring_np` on the live rows (tests/test_search.py)."""
    import jax.numpy as jnp

    w, cap = planes.shape
    nlen = len(needle)
    if nlen == 0 or nlen > min(w, MAX_NEEDLE):
        return np.zeros(cap, dtype=bool)
    if kernel == "pallas":
        ndl = np.zeros((1, MAX_NEEDLE), dtype=np.int32)
        ndl[0, :nlen] = np.frombuffer(needle, dtype=np.uint8)
        out = _substring_pallas_jit(nlen, w, _interpret())(
            planes.reshape(w, cap // LANES, LANES), jnp.asarray(ndl))
        return np.asarray(out).reshape(-1).astype(bool)
    ndl = np.zeros(MAX_NEEDLE, dtype=np.uint8)
    ndl[:nlen] = np.frombuffer(needle, dtype=np.uint8)
    return np.asarray(_substring_xla_jit(nlen, w)(planes,
                                                  jnp.asarray(ndl)))


def exact_jnp(planes, needle: bytes, kernel: str) -> np.ndarray:
    import jax.numpy as jnp

    w, cap = planes.shape
    if len(needle) > w:
        return np.zeros(cap, dtype=bool)
    if kernel == "pallas":
        ndl = np.zeros((1, w), dtype=np.int32)
        ndl[0, : len(needle)] = np.frombuffer(needle, dtype=np.uint8)
        out = _exact_pallas_jit(w, _interpret())(
            planes.reshape(w, cap // LANES, LANES), jnp.asarray(ndl))
        return np.asarray(out).reshape(-1).astype(bool)
    padded = np.zeros(w, dtype=np.uint8)
    padded[: len(needle)] = np.frombuffer(needle, dtype=np.uint8)
    return np.asarray(_exact_xla_jit(w)(planes, jnp.asarray(padded)))


def lex_cmp_jnp(planes, bound: bytes, kernel: str) -> np.ndarray:
    """(CAP,) i8 memcmp verdict; parity with :func:`lex_cmp_np`."""
    import jax.numpy as jnp

    w, cap = planes.shape
    if kernel == "pallas":
        ndl = np.zeros((1, w), dtype=np.int32)
        clipped = bound[:w]
        ndl[0, : len(clipped)] = np.frombuffer(clipped, dtype=np.uint8)
        out = np.asarray(_lex_pallas_jit(w, _interpret())(
            planes.reshape(w, cap // LANES, LANES),
            jnp.asarray(ndl))).reshape(-1)
        res = np.zeros(cap, dtype=np.int8)
        res[out == 1] = 1
        res[out == 2] = -1
        return res
    padded = np.zeros(w, dtype=np.uint8)
    clipped = bound[:w]
    padded[: len(clipped)] = np.frombuffer(clipped, dtype=np.uint8)
    return np.asarray(_lex_xla_jit(w)(planes, jnp.asarray(padded)))
