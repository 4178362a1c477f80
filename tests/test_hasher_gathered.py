"""The device hashers' gathered path against the CPU hasher.

``hash_gathered`` takes the messages the identify pipeline's prefetcher
gathered: sampled 57,352-byte messages of large files, whole-file messages
of small ones, and gather failures, which pass through in place. The
single-device and the mesh-sharded hasher must give the CPU path's cas_ids
position by position (the sharded one on the suite's 8 virtual devices).
"""

import random

import pytest

from spacedrive_tpu.objects.cas import SAMPLED_MESSAGE_LEN
from spacedrive_tpu.objects.hasher import CpuHasher, ShardedHasher, TpuHasher


def _gathered_messages() -> list:
    rng = random.Random(24)
    msgs: list = [rng.randbytes(SAMPLED_MESSAGE_LEN) for _ in range(5)]
    msgs += [rng.randbytes(n) for n in (8, 500, 1024, 1025, 4000)]
    msgs += [OSError("gather failed: a.arw"), OSError("gather failed: b.arw")]
    rng.shuffle(msgs)
    return msgs


@pytest.mark.parametrize("backend", [TpuHasher, ShardedHasher],
                         ids=["tpu", "tpu-sharded"])
def test_hash_gathered_matches_cpu_hasher(backend):
    msgs = _gathered_messages()
    want = CpuHasher().hash_gathered(msgs)
    got = backend().hash_gathered(msgs)
    assert len(got) == len(msgs)
    for msg, g, w in zip(msgs, got, want):
        if isinstance(msg, Exception):
            assert g is msg and w is msg
        else:
            assert g == w and len(g) == 16
