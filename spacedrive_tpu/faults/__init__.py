"""Deterministic, seedable fault injection (the chaos seam layer).

Production-scale scanning treats partial failure as the steady state; this
package makes every failure mode *rehearsable*. A fault plan is armed from
``SD_FAULTS`` (grammar in :mod:`.spec`; seed via ``SD_FAULTS_SEED``) and
consulted at named seams in the hot paths (kinds include ``enospc`` for
the full-disk story and ``kill`` — a literal SIGKILL at the seam — for
the crash-recovery harness):

    from spacedrive_tpu import faults
    faults.inject("gather", key=str(path))   # no-op unless armed

Zero overhead when unset: ``inject`` is one module-global read and an
immediate return — no env lookup, no dict walk, nothing allocated. The
plan is parsed once (at import from the environment, or by
:func:`install`/:func:`reload` in tests and benches).

The classification the seams synthesize (transient vs fatal, and which layer
absorbs what) is documented in docs/architecture/robustness.md.
"""

from __future__ import annotations

import logging
import os

from .spec import (INJECTED_ATTR, KINDS, DeviceWedgeError, FaultInjected,
                   FaultPlan, FaultSpecError, IngestOverloadError,
                   PeerBusyError)

__all__ = [
    "DeviceWedgeError", "FaultInjected", "FaultPlan", "FaultSpecError",
    "INJECTED_ATTR", "IngestOverloadError", "KINDS", "PeerBusyError",
    "active", "clear", "fired", "inject", "install", "is_injected",
    "reload", "seam_armed",
]

logger = logging.getLogger(__name__)

_PLAN: FaultPlan | None = None


def install(spec: str, seed: int | None = None) -> FaultPlan:
    """Arm a plan programmatically (tests, bench chaos mode)."""
    global _PLAN
    if seed is None:
        seed = _seed_from_env()
    _PLAN = FaultPlan(spec, seed=seed)
    logger.warning("fault injection ARMED: %s (seed %d)", spec, seed)
    return _PLAN


def clear() -> None:
    global _PLAN
    _PLAN = None


def reload() -> FaultPlan | None:
    """Re-read ``SD_FAULTS`` (after an in-process env change)."""
    global _PLAN
    spec = os.environ.get("SD_FAULTS", "").strip()
    _PLAN = FaultPlan(spec, seed=_seed_from_env()) if spec else None
    if _PLAN is not None:
        logger.warning("fault injection ARMED from env: %s", spec)
    return _PLAN


def active() -> FaultPlan | None:
    return _PLAN


def seam_armed(seam: str) -> bool:
    """True when the armed plan carries rules for ``seam`` — hot paths with
    a batch-granular fast lane (the native gather) use this to fall back to
    their per-item path so per-item rules keep their semantics."""
    return _PLAN is not None and _PLAN.has_seam(seam)


def inject(seam: str, key: str = "") -> None:
    """The seam entry point: raise/hang if an armed rule fires, else no-op."""
    plan = _PLAN
    if plan is None:
        return
    plan.check(seam, key)


def fired() -> dict[str, int]:
    plan = _PLAN
    return plan.fired() if plan is not None else {}


def is_injected(exc: BaseException) -> bool:
    return getattr(exc, INJECTED_ATTR, False)


def _seed_from_env() -> int:
    try:
        return int(os.environ.get("SD_FAULTS_SEED", "0"))
    except ValueError:
        return 0


# arm from the environment once at import — chaos runs set SD_FAULTS before
# the process starts, so seam checks never touch os.environ again
try:
    reload()
except FaultSpecError:
    logger.exception("SD_FAULTS spec rejected; fault injection DISARMED")
    _PLAN = None

# the link-level network fault model is a sibling dimension (SD_NET_PLAN);
# importing it here arms it from the environment alongside SD_FAULTS
from . import net  # noqa: E402  (import-time arming is the point)
