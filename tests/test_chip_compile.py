"""Main-path kernels compiled for a described TPU v5e, at real widths.

Nothing runs: each case lowers one kernel for a ``v5e:2x2`` topology the
installed TPU compiler describes without a chip attached, and compiles
it. That refuses what interpret mode accepts (unaligned block shapes,
dtypes Mosaic cannot vectorize, VMEM overruns) at no chip time.

The topology is described inside a module fixture and never at import:
only one process may load libtpu at a time, and xdist workers all
import this file (see the on-chip-measurement guide, section 2).
"""

from __future__ import annotations

import os

import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one — keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # kernels pick interpret mode from the default backend (CPU here);
    # these compiles are for the chip, so force the real Mosaic lowering
    prev = os.environ.get("SD_PALLAS_INTERPRET")
    os.environ["SD_PALLAS_INTERPRET"] = "0"
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        if prev is None:
            os.environ.pop("SD_PALLAS_INTERPRET", None)
        else:
            os.environ["SD_PALLAS_INTERPRET"] = prev
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _blake3_rows(kernel, lanes=4096, chunks=64):
    from spacedrive_tpu.ops.blake3_jax import _blake3_batch_rows_impl

    def build(spec):
        return (lambda r, n: _blake3_batch_rows_impl(r, n, kernel=kernel),
                [spec((lanes, chunks * 256), "uint32"),
                 spec((lanes,), "int32")])
    return build


def _minhash_row_heads(spec):
    from spacedrive_tpu.objects.hasher import SIGN_WORDS, bucket_rows
    from spacedrive_tpu.ops.minhash import minhash_rows

    lanes = bucket_rows(64)
    return (lambda r, n: minhash_rows(r, n, words=SIGN_WORDS),
            [spec((lanes, 64 * 256), "uint32"), spec((lanes,), "int32")])


def _minhash(spec):
    from spacedrive_tpu.objects.dedup import SAMPLED_STRIDE, SIG_BATCH
    from spacedrive_tpu.ops.minhash import minhash_rows

    return (minhash_rows, [spec((SIG_BATCH, SAMPLED_STRIDE // 4), "uint32"),
                           spec((SIG_BATCH,), "int32")])


def _similar_pairs(spec):
    from spacedrive_tpu.objects.dedup import ALL_PAIRS_LIMIT
    from spacedrive_tpu.ops.minhash import K, similar_pairs_count

    return (lambda s, v: similar_pairs_count(s, v, threshold_k=int(0.8 * K)),
            [spec((ALL_PAIRS_LIMIT, K), "uint32"),
             spec((ALL_PAIRS_LIMIT,), "bool")])


def _resize(spec):
    from spacedrive_tpu.ops.resize_jax import CANVAS, resize_batch

    # thumbnail batches: host pre-reduces to <= 2x the canvas edge
    return (resize_batch, [spec((32, 2 * CANVAS, 2 * CANVAS, 3), "uint8"),
                           spec((32, 2), "int32"), spec((32, 2), "int32")])


def _cdc(spec):
    from spacedrive_tpu.ops.cdc import _candidates_pallas

    # a 32-file group of the 64 KiB length tier (512 haloed tiles a row)
    return (_candidates_pallas, [spec((32, 1 << 16), "uint32"),
                                 spec((32,), "int32"), spec((), "uint32")])


def _search(kind):
    def build(spec):
        from spacedrive_tpu.search import kernels as k
        from spacedrive_tpu.search.columnar import W_NAME

        cap = 1 << 20
        planes = spec((W_NAME, cap // k.LANES, k.LANES), "uint8")
        if kind == "substring":
            fn = k._substring_pallas_jit(8, W_NAME, False)
            return fn, [planes, spec((1, k.MAX_NEEDLE), "int32")]
        fn = (k._exact_pallas_jit if kind == "exact"
              else k._lex_pallas_jit)(W_NAME, False)
        return fn, [planes, spec((1, W_NAME), "int32")]
    return build


CASES = {
    # the hasher's largest device tier: 4096 lanes x 64 chunks
    "blake3_rows_xla": _blake3_rows("xla"),
    "blake3_rows_pallas": _blake3_rows("pallas"),
    # the whole-file bucket of 101 chunks (100 KiB + the size word), the
    # one chunk count not a power of two, at its 256 rows a call
    "blake3_rows_xla_cap101": _blake3_rows("xla", 256, 101),
    # the signatures of a 64-chunk bucket call that holds a sampled
    # message, from the first 57 chunks of its rows
    "minhash_rows_cap64_heads": _minhash_row_heads,
    "minhash_rows": _minhash,
    "similar_pairs_count": _similar_pairs,
    "resize_batch": _resize,
    "cdc_candidates_pallas": _cdc,
    "search_substring_pallas": _search("substring"),
    "search_exact_pallas": _search("exact"),
    "search_lex_pallas": _search("lex"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    import jax
    import jax.numpy as jnp

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    fn, args = CASES[case](spec)
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    if mem is not None:
        # the v5e chip holds 16 GB of HBM
        used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
        assert used < 16e9, f"{case} needs {used / 1e9:.1f} GB"
