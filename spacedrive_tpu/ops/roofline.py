"""u32-VPU roofline model for the BLAKE3 kernel — MFU accounting.

Ops/byte — the 12.5 model
-------------------------
One BLAKE3 compression processes a 64-byte block with 7 rounds × 8 G
functions. Each G is 14 u32 VPU ops (6 adds, 4 xors, 4 rotates — a rotate
is one VPU op on TPU, as on any machine with a hardware rotate/funnel
shift), plus the 8 output-feedforward xors:

    7 × 8 × 14 + 8 = 792 ≈ 800 ops / 64 B = 12.5 ops/byte

Parent (merkle) compressions add ~1/16 on top (one parent per 1 KiB chunk
pair); the model deliberately excludes them — the figure tracks *payload*
bytes, so MFU is a slight underestimate, never flattered.

Peaks, keyed by ``device_kind``
-------------------------------
:data:`PEAKS` holds the published per-chip figures (Google Cloud TPU
documentation, "TPU v5e" and "TPU v4" system-architecture pages): bf16
MXU peak, HBM bandwidth and the MXU count. No page publishes a u32 VPU
peak, so it is derived: the clock is the bf16 peak over the MXUs'
per-cycle work (``mxus × 128 × 128 × 2``), and the VPU does
``8 sublanes × 128 lanes × 4 ALUs`` u32 ops per cycle. A device kind
missing from the table is an error, never a default; on the CPU there is
no MFU at all (``None``).
"""

from __future__ import annotations

from typing import NamedTuple

#: u32 VPU ops per payload byte (derivation above; rotate = 1 op)
OPS_PER_BYTE = 12.5


class Peak(NamedTuple):
    bf16_flops: float       # published, per chip
    hbm_bytes_per_s: float  # published, per chip
    mxus: int               # published, per chip (4 per TensorCore)

    @property
    def clock_hz(self) -> float:
        return self.bf16_flops / (self.mxus * 128 * 128 * 2)

    @property
    def u32_ops(self) -> float:
        """Derived VPU peak: 8×128 lanes × 4 ALUs per cycle per core."""
        cores = self.mxus // 4
        return 8 * 128 * 4 * self.clock_hz * cores


#: ``jax.Device.device_kind`` → per-chip peaks (source: module doc)
PEAKS: dict[str, Peak] = {
    "TPU v5 lite": Peak(bf16_flops=197e12, hbm_bytes_per_s=819e9, mxus=4),
    "TPU v4": Peak(bf16_flops=275e12, hbm_bytes_per_s=1228e9, mxus=8),
}


class UnknownDeviceKind(LookupError):
    """An accelerator whose peaks are not in :data:`PEAKS`."""


def peak(device) -> Peak | None:
    """Peaks of ``device`` (a ``jax.Device``); None on the CPU."""
    if device.platform == "cpu":
        return None
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise UnknownDeviceKind(
            f"no peaks for {device.platform} device kind "
            f"{device.device_kind!r}; add it to ops/roofline.PEAKS "
            "with its source") from None


def mfu(bytes_per_sec: float, device) -> float | None:
    """Achieved fraction of ``device``'s u32 roofline for a measured
    BLAKE3 payload rate; None on the CPU."""
    p = peak(device)
    if p is None:
        return None
    return max(0.0, bytes_per_sec) * OPS_PER_BYTE / p.u32_ops
