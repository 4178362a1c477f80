"""MinHash near-duplicate detection — the TPU-native dedup engine.

The reference's dedup is exact-only: identical cas_ids collapse to one Object
(file_identifier/mod.rs:136-335). This op family adds *near*-duplicate
detection (BASELINE.json config 4) designed for the TPU:

- **Signatures ride the identify batch.** During file_identifier the sampled
  message rows are already resident on device for BLAKE3; the MinHash kernel
  reuses them (objects/hasher.py ``TpuHasher._hash_bucket``): 8-byte
  shingles at 8-byte stride, K universal hash functions (odd-multiplier mix
  on the VPU), min-reduce over shingles. No extra host↔device traffic — the
  expensive transfer was already paid for cas_id. The dedup detector reads
  the stored signatures, and gathers only files that have none.
- **All-pairs compare is blocked compute.** Similarity(i,j) = fraction of
  equal signature components. A lax.scan over row-blocks compares
  (block, N, K) at once — O(N²K) element ops that saturate the VPU while
  only N*K*4 bytes ever cross the wire. The CPU equivalent (numpy blocked
  compare, same algorithm) is the bench baseline.

Estimator: P[min-hash match] = Jaccard(shingle sets), so `threshold=0.8`
finds files sharing ≥~80% of sampled content shingles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_u32 = jnp.uint32
_u64 = jnp.uint64

#: signature width (hash count) — 64 keeps the estimator std ≈ 0.05
K = 64

#: deterministic odd multipliers + offsets for the K universal hashes
_rng = np.random.default_rng(0x5D)  # stable seed
_A = (_rng.integers(0, 1 << 32, K, dtype=np.uint64) | 1).astype(np.uint32)
_B = (_rng.integers(0, 1 << 32, K, dtype=np.uint64) | 1).astype(np.uint32)
_C = _rng.integers(0, 1 << 32, K, dtype=np.uint64).astype(np.uint32)


def _mix(x: jax.Array) -> jax.Array:
    """xorshift-multiply finalizer (murmur-style avalanche) on u32 lanes."""
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


@functools.partial(jax.jit, static_argnames=("words",))
def minhash_rows(rows: jax.Array, lengths: jax.Array,
                 words: int | None = None) -> jax.Array:
    """Signatures for B messages. ``rows``: (B, W) uint32 — the same row
    layout blake3_batch_rows consumes; ``lengths``: (B,) true byte lengths.
    Returns (B, K) uint32. Shingle = consecutive u32 pair (8 bytes).
    ``words``: read only each row's first ``words`` words (a message that
    fits them gets the signature of a row that narrow)."""
    if words is not None:
        rows = rows[:, :words]
    B, W = rows.shape
    lo = rows[:, 0::2]  # (B, W/2)
    hi = rows[:, 1::2]
    n_shingles = jnp.maximum(1, (lengths.astype(jnp.int32) // 8))  # (B,)
    idx = jnp.arange(W // 2, dtype=jnp.int32)[None, :]  # (1, W/2)
    valid = idx < n_shingles[:, None]  # (B, W/2)

    def one_hash(carry, params):
        a, b, c = params
        h = _mix(lo * a + hi * b + c)  # (B, W/2)
        h = jnp.where(valid, h, jnp.uint32(0xFFFFFFFF))
        return carry, jnp.min(h, axis=1)  # (B,)

    _, sigs = lax.scan(one_hash, None,
                       (jnp.asarray(_A), jnp.asarray(_B), jnp.asarray(_C)))
    return jnp.transpose(sigs)  # (B, K)


#: rows per compare block — (BLOCK, N, K) u32 intermediate stays < ~2GB HBM
BLOCK = 512


@functools.partial(jax.jit, static_argnames=("threshold_k",))
def similar_pairs_count(sigs: jax.Array, valid: jax.Array,
                        threshold_k: int) -> tuple[jax.Array, jax.Array]:
    """All-pairs signature compare.

    ``sigs``: (N, K) uint32 (N must be a multiple of BLOCK — pad with
    invalid lanes); ``valid``: (N,) bool. A pair (i < j) is "similar" when
    >= threshold_k of K components match. Returns (total pair count,
    per-row flag marking rows that have a similar earlier row — the
    near-dup analogue of the identify step's exact-dup flag)."""
    N = sigs.shape[0]
    row_idx = jnp.arange(N, dtype=jnp.int32)

    def block_body(carry, start):
        total, dup = carry
        blk = lax.dynamic_slice(sigs, (start, 0), (BLOCK, K))  # (BLOCK, K)
        bvalid = lax.dynamic_slice(valid, (start,), (BLOCK,))
        bidx = start + jnp.arange(BLOCK, dtype=jnp.int32)
        eq = (blk[:, None, :] == sigs[None, :, :]).sum(axis=2)  # (BLOCK, N)
        pairmask = (eq >= threshold_k) & bvalid[:, None] & valid[None, :]
        earlier = bidx[:, None] > row_idx[None, :]  # j < i
        hits = pairmask & earlier
        total = total + hits.sum()
        dup = lax.dynamic_update_slice(dup, jnp.any(hits, axis=1), (start,))
        return (total, dup), None

    starts = jnp.arange(0, N, BLOCK, dtype=jnp.int32)
    (total, dup), _ = lax.scan(
        block_body, (jnp.zeros((), jnp.int64)
                     if jax.config.jax_enable_x64 else jnp.zeros((), jnp.int32),
                     jnp.zeros((N,), bool)),
        starts)
    return total, dup


def similar_pairs_count_cpu(sigs: np.ndarray, valid: np.ndarray,
                            threshold_k: int) -> tuple[int, np.ndarray]:
    """Reference/baseline: same blocked algorithm in numpy."""
    N, k = sigs.shape
    total = 0
    dup = np.zeros(N, bool)
    row_idx = np.arange(N)
    for start in range(0, N, BLOCK):
        blk = sigs[start : start + BLOCK]
        eq = (blk[:, None, :] == sigs[None, :, :]).sum(axis=2)
        pairmask = (eq >= threshold_k) & valid[start : start + BLOCK, None] & valid[None, :]
        earlier = (start + np.arange(blk.shape[0]))[:, None] > row_idx[None, :]
        hits = pairmask & earlier
        total += int(hits.sum())
        dup[start : start + BLOCK] = hits.any(axis=1)
    return total, dup


def pad_for_blocks(sigs: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Pad N up to a BLOCK multiple; padding lanes are invalid."""
    N = sigs.shape[0]
    Np = -(-N // BLOCK) * BLOCK
    valid = np.zeros(Np, bool)
    valid[:N] = True
    if Np != N:
        sigs = np.concatenate([sigs, np.zeros((Np - N, sigs.shape[1]),
                                              sigs.dtype)])
    return sigs, valid


# ---------------------------------------------------------------------------
# LSH banding — corpus-scale candidate generation (the all-pairs sweep is
# O(N²K); banding is O(N·BANDS) with exact verification only on candidates,
# the standard banded-MinHash construction the extreme-scale dedup
# literature builds on, e.g. LSHBloom, arxiv 2411.04257)
# ---------------------------------------------------------------------------

BANDS = 16
BAND_ROWS = K // BANDS  # 4

#: buckets larger than this pair members against ONE representative
#: instead of all-pairs (a bucket of thousands of identical signatures —
#: exactly the most-duplicated content — must stay detected without
#: re-quadratizing the pass); callers surface how many were collapsed
MAX_BUCKET = 256


def band_keys(sigs: np.ndarray) -> np.ndarray:
    """(N, BANDS) uint64 bucket keys: FNV-style fold of each band's rows,
    salted per band. Two rows sharing ≥ one band key are candidates.
    With s = true similarity, P[candidate] = 1 - (1 - s^BAND_ROWS)^BANDS:
    ≈ 0.9998 at s=0.8, ≈ 0.12 at s=0.3 — high-recall at the 0.8 default
    threshold, false positives removed by exact verification."""
    n = sigs.shape[0]
    bands = sigs.reshape(n, BANDS, BAND_ROWS).astype(np.uint64)
    with np.errstate(over="ignore"):
        key = np.full((n, BANDS), 0xCBF29CE484222325, np.uint64)
        for r in range(BAND_ROWS):
            key ^= bands[:, :, r]
            key *= np.uint64(0x100000001B3)
        key ^= np.arange(BANDS, dtype=np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    return key


def banded_candidate_pairs(keys: np.ndarray,
                           valid: np.ndarray) -> tuple[np.ndarray, int]:
    """Candidate pairs (ndarray (P, 2), i < j, unique) from shared band
    buckets; returns (pairs, oversized_bucket_count). Oversized buckets
    collapse to representative pairing — (first member, each other member)
    — keeping candidate generation linear while every member stays
    reachable (the later union-find re-joins the clique through the
    representative).

    Fully vectorized (BASELINE config 4 runs this over 1M objects): per
    band, a sort groups equal keys into runs; runs batch BY LENGTH so each
    batch emits its within-run pairs with one triu-indexed gather; the
    cross-band union dedups through one np.unique over packed (i<<32)|j
    codes. A Python dict/set version of the same construction tops out
    around 20k objects/s — this one sustains millions."""
    valid = np.asarray(valid, bool)
    if valid.shape[0] != keys.shape[0]:
        raise ValueError(f"valid mask has {valid.shape[0]} entries for "
                         f"{keys.shape[0]} signatures")
    idx_valid = np.flatnonzero(valid)
    chunks: list[np.ndarray] = []
    oversized = 0
    for b in range(BANDS):
        k = keys[idx_valid, b]
        order = np.argsort(k, kind="stable")
        ks = k[order]
        ids = idx_valid[order]
        if ks.size == 0:
            continue
        run_start = np.flatnonzero(np.r_[True, ks[1:] != ks[:-1]])
        run_len = np.diff(np.r_[run_start, ks.size])
        for length in np.unique(run_len):
            if length < 2:
                continue
            starts = run_start[run_len == length]
            members = ids[starts[:, None] + np.arange(length)]
            if length > MAX_BUCKET:
                oversized += len(starts)
                a = np.repeat(members[:, 0], length - 1)
                c = members[:, 1:].ravel()
            else:
                iu, ju = np.triu_indices(int(length), 1)
                a = members[:, iu].ravel()
                c = members[:, ju].ravel()
            lo = np.minimum(a, c).astype(np.uint64)
            hi = np.maximum(a, c).astype(np.uint64)
            chunks.append((lo << np.uint64(32)) | hi)
    if not chunks:
        return np.empty((0, 2), np.int64), oversized
    packed = np.unique(np.concatenate(chunks))
    pairs = np.empty((packed.size, 2), np.int64)
    pairs[:, 0] = (packed >> np.uint64(32)).astype(np.int64)
    pairs[:, 1] = (packed & np.uint64(0xFFFFFFFF)).astype(np.int64)
    return pairs, oversized


def verify_pairs(sigs: np.ndarray, pairs, threshold_k: int) -> list:
    """Exact signature compare over candidate pairs (vectorized);
    returns [(i, j, matching_components)] for pairs clearing threshold.
    ``pairs``: the (P, 2) array banded_candidate_pairs emits (a set of
    tuples still works)."""
    if isinstance(pairs, np.ndarray):
        arr = pairs
    else:
        if not pairs:
            return []
        arr = np.asarray(sorted(pairs), np.int64)
    if arr.size == 0:
        return []
    out = []
    for start in range(0, len(arr), 65536):
        chunk = arr[start:start + 65536]
        eq = (sigs[chunk[:, 0]] == sigs[chunk[:, 1]]).sum(axis=1)
        keep = eq >= threshold_k
        for (i, j), m in zip(chunk[keep], eq[keep]):
            out.append((int(i), int(j), int(m)))
    return out
