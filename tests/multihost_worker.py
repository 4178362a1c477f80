"""Worker process for the two-process DCN smoke test (SURVEY §5.8).

Each process pins the CPU backend, joins the jax.distributed coordinator
(parallel/mesh.py::init_multihost — the compute-plane analogue of the
reference joining its QUIC mesh at Node::new, core/src/lib.rs:130),
contributes its local devices to a GLOBAL (data, seq) mesh, and runs one
sharded identify step whose batch axis spans both processes. Process 0
byte-checks the digests against the pure-Python oracle and prints
MULTIHOST_OK.

Usage: multihost_worker.py <coordinator> <num_processes> <process_id>
"""

import sys

import jax

jax.config.update("jax_platforms", "cpu")  # a CPU-only test child

import numpy as np  # noqa: E402


def main() -> int:
    coordinator, num_processes, process_id = (
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]))

    from spacedrive_tpu.parallel.mesh import (DATA_AXIS, init_multihost,
                                              make_mesh, sharded_hasher)

    init_multihost(coordinator, num_processes, process_id)
    assert jax.process_count() == num_processes, jax.process_count()
    n_global = len(jax.devices())
    n_local = len(jax.local_devices())
    assert n_global == n_local * num_processes, (n_global, n_local)

    mesh = make_mesh()  # global mesh over every process's devices

    from jax.sharding import NamedSharding, PartitionSpec

    from spacedrive_tpu.ops.blake3_jax import digests_to_hex, pack_messages

    # deterministic global batch: one message per global device slot
    B = n_global * 2
    rng = np.random.default_rng(7)
    msgs = [rng.integers(0, 256, 200 + 90 * i, dtype=np.uint8).tobytes()
            for i in range(B)]  # all <= 1 chunk
    words, lengths = pack_messages(msgs, max_chunks=1)

    # words layout is (block, word, chunk, batch): the batch axis (last) is
    # sharded on `data`; each process feeds only ITS slice of the batch
    half = B // num_processes
    lo, hi = process_id * half, (process_id + 1) * half
    w_shard = NamedSharding(mesh, PartitionSpec(None, None, None, DATA_AXIS))
    l_shard = NamedSharding(mesh, PartitionSpec(DATA_AXIS))
    g_words = jax.make_array_from_process_local_data(
        w_shard, np.asarray(words)[..., lo:hi], global_shape=words.shape)
    g_lengths = jax.make_array_from_process_local_data(
        l_shard, np.asarray(lengths)[lo:hi], global_shape=lengths.shape)

    out = sharded_hasher(mesh)(g_words, g_lengths)

    from jax.experimental import multihost_utils

    gathered = np.asarray(multihost_utils.process_allgather(
        out, tiled=True)).reshape(8, B)

    if process_id == 0:
        from spacedrive_tpu.objects.blake3_ref import blake3

        got = digests_to_hex(gathered)
        want = [blake3(m).hex() for m in msgs]
        assert got == want, (got[:2], want[:2])
        print(f"MULTIHOST_OK processes={num_processes} devices={n_global} "
              f"batch={B}", flush=True)
    multihost_utils.sync_global_devices("done")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
