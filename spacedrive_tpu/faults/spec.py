"""Fault-spec grammar and the deterministic, seedable fault plan.

A spec is a ``;``-separated list of rules, each ``seam:kind[:trigger]``:

    SD_FAULTS="gather:eio:0.01;hash:wedge:once;commit:sqlite_busy:3"

- **seam** — the named injection point (`faults.inject("<seam>")` sites).
  Installed seams: ``gather`` (per-file cas sample read), ``hash`` (the
  identifier's hash dispatch; ``hash_dispatch`` is an accepted alias,
  normalized at parse), ``commit`` (DB transaction begin/commit),
  ``sync_apply`` (CRDT op materialization), ``sync_ingest`` (the receive
  path's admission check — kind ``overload`` synthesizes budget
  exhaustion there), ``p2p_send`` (outbound peer requests; kind ``busy``
  synthesizes a peer's BUSY answer), ``chunk`` (the manifest stage: per-file payload reads
  — inside the transient retry, so ``eio`` storms retry clean — and the
  CDC dispatch, where ``wedge`` exercises the chunk router's degrade
  ladder), ``manifest_commit`` (inside the identifier's transaction just
  before the chunk_manifest writes — the kill matrix pins a SIGKILL
  there). The set is open: any string names a seam; rules for seams that
  never fire are inert.
- **kind** — which failure to synthesize (:data:`KINDS`); each maps to
  the exception class the real failure mode raises, so the production
  handlers are exercised, not test doubles. ``hang`` blocks instead of
  raising (the wedged-device failure mode).
- **trigger** — when the rule fires at a seam hit:
    * absent            → every hit
    * ``once``          → the first hit only
    * integer ``N``     → the first N hits
    * ``skipN``         → every hit AFTER the first N (pins a ``kill`` to
      an exact mid-workload point: ``commit:kill:skip3`` dies at the 4th
      transaction seam hit)
    * float ``p``       → each hit independently with probability p,
      drawn from the rule's own seeded RNG (``SD_FAULTS_SEED``, default
      0) — two runs with the same seed and the same call sequence fire
      identically.

The plan is process-global and thread-safe; counters/RNGs live per rule
under one lock, so concurrent pipeline stages draw a deterministic
sequence per seam (each installed seam is hit from a single thread).
"""

from __future__ import annotations

import errno as _errno
import os
import sqlite3
import threading
import time
from dataclasses import dataclass, field
from random import Random
from typing import Callable

from .. import telemetry

#: every fired rule is visible to operators, not only to chaos benches:
#: ``sd_faults_fired_total{seam,kind}`` on the unified registry
_FIRED_TOTAL = telemetry.counter(
    "sd_faults_fired_total", "injected faults fired, per seam:kind",
    labels=("seam", "kind"))


class FaultInjected(RuntimeError):
    """Generic injected crash (kind ``crash``) — classified transient
    (``sd_transient``) so stage supervision checkpoint-pauses on it."""

    sd_transient = True


class DeviceWedgeError(RuntimeError):
    """Injected device wedge (kind ``wedge``): the mid-batch hasher
    degradation ladder (device → native CPU) must absorb it."""

    sd_transient = True


class PeerBusyError(RuntimeError):
    """A peer shed our request with an explicit BUSY answer (admission
    control) — kind ``busy`` synthesizes it at the ``p2p_send`` seam. The
    caller backs off for ``retry_after_ms`` and resumes from its
    acknowledged watermark; it must never treat BUSY as a dead peer."""

    sd_transient = True
    sd_busy = True

    def __init__(self, msg: str, retry_after_ms: int = 250) -> None:
        super().__init__(msg)
        self.retry_after_ms = retry_after_ms


class IngestOverloadError(RuntimeError):
    """Injected admission-budget exhaustion (kind ``overload``, seam
    ``sync_ingest``): forces the receive path's admission check to shed
    the window exactly as a real over-budget node would."""

    sd_transient = True


#: sentinel marker on every injected exception so reports/tests can tell
#: synthesized faults from organic ones
INJECTED_ATTR = "sd_injected"

#: how long a ``hang`` fault blocks; the pipeline drain must give up on
#: the thread long before this (it is the "never returns" simulation)
HANG_S = 3600.0


def _stall_s() -> float:
    """How long a ``stall`` fault sleeps before returning normally — the
    "slow, not broken" failure mode (cold cache, lock convoy, GC pause).
    The serving-tier slow-request ring is gated on exactly this shape."""
    try:
        return max(0.0, float(os.environ.get("SD_FAULT_STALL_S", "0.3")))
    except ValueError:
        return 0.3


def _oserror(no: int, msg: str) -> Callable[[str], BaseException]:
    def make(key: str) -> BaseException:
        exc = OSError(no, f"{msg} [injected{': ' + key if key else ''}]")
        return exc
    return make


def _mk(cls: type[BaseException], msg: str) -> Callable[[str], BaseException]:
    def make(key: str) -> BaseException:
        return cls(f"{msg} [injected{': ' + key if key else ''}]")
    return make


KINDS: dict[str, Callable[[str], BaseException]] = {
    "eio": _oserror(_errno.EIO, "I/O error"),
    "eintr": _oserror(_errno.EINTR, "interrupted system call"),
    "enoent": lambda key: FileNotFoundError(
        _errno.ENOENT, f"no such file [injected{': ' + key if key else ''}]"),
    "eacces": lambda key: PermissionError(
        _errno.EACCES, f"permission denied [injected{': ' + key if key else ''}]"),
    "enospc": _oserror(_errno.ENOSPC, "no space left on device"),
    "truncate": _mk(EOFError, "short read"),
    "sqlite_busy": _mk(sqlite3.OperationalError, "database is locked"),
    "wedge": _mk(DeviceWedgeError, "device wedge"),
    "crash": _mk(FaultInjected, "injected crash"),
    "flap": _mk(ConnectionRefusedError, "connection refused"),
    "busy": _mk(PeerBusyError, "peer busy"),
    "overload": _mk(IngestOverloadError, "ingest overload"),
    "hang": None,  # type: ignore[dict-item]  # blocks, never raises
    "kill": None,  # type: ignore[dict-item]  # SIGKILLs the process
    "stall": None,  # type: ignore[dict-item]  # sleeps STALL_S, then returns
}


class FaultSpecError(ValueError):
    """Malformed SD_FAULTS spec — raised at parse, never at a seam."""


#: spelling aliases accepted in specs (normalized at parse, so ``fired()``
#: and the telemetry series always carry the canonical seam name): the
#: identifier's hash-dispatch seam reads naturally either way
SEAM_ALIASES = {"hash_dispatch": "hash"}


@dataclass
class FaultRule:
    seam: str
    kind: str
    #: "always" | "count" | "prob" | "skip"
    mode: str
    remaining: int = 0
    prob: float = 0.0
    rng: Random = field(default_factory=Random)
    fired: int = 0

    def should_fire(self) -> bool:
        """Caller holds the plan lock."""
        if self.mode == "count":
            if self.remaining <= 0:
                return False
            self.remaining -= 1
        elif self.mode == "prob":
            if self.rng.random() >= self.prob:
                return False
        elif self.mode == "skip":
            # fire on every hit AFTER the first N — how the crash harness
            # pins a kill to "the (N+1)th transaction commit" exactly
            if self.remaining > 0:
                self.remaining -= 1
                return False
        self.fired += 1
        return True


class FaultPlan:
    """Parsed, armed rules; ``check()`` is the hot seam entry point."""

    def __init__(self, spec: str, seed: int = 0) -> None:
        self.spec = spec
        self.seed = seed
        self._lock = threading.Lock()
        self._rules: dict[str, list[FaultRule]] = {}
        for i, raw in enumerate(p for p in spec.split(";") if p.strip()):
            rule = self._parse_rule(raw.strip(), i, seed)
            self._rules.setdefault(rule.seam, []).append(rule)
        if not self._rules:
            raise FaultSpecError(f"empty fault spec {spec!r}")

    @staticmethod
    def _parse_rule(raw: str, index: int, seed: int) -> FaultRule:
        parts = raw.split(":")
        if len(parts) not in (2, 3):
            raise FaultSpecError(
                f"rule {raw!r}: expected seam:kind[:trigger]")
        seam, kind = parts[0].strip(), parts[1].strip()
        seam = SEAM_ALIASES.get(seam, seam)
        if kind not in KINDS:
            raise FaultSpecError(
                f"rule {raw!r}: unknown kind {kind!r} "
                f"(known: {', '.join(sorted(KINDS))})")
        rng = Random(f"{seed}:{index}:{seam}:{kind}")
        if len(parts) == 2:
            return FaultRule(seam, kind, "always", rng=rng)
        trig = parts[2].strip()
        if trig == "once":
            return FaultRule(seam, kind, "count", remaining=1, rng=rng)
        if trig.startswith("skip"):
            try:
                n = int(trig[4:])
            except ValueError:
                raise FaultSpecError(
                    f"rule {raw!r}: skip trigger must be 'skip<N>'") from None
            if n < 0:
                raise FaultSpecError(f"rule {raw!r}: skip count must be >= 0")
            return FaultRule(seam, kind, "skip", remaining=n, rng=rng)
        try:
            if "." in trig:
                p = float(trig)
                if not 0.0 < p <= 1.0:
                    raise FaultSpecError(
                        f"rule {raw!r}: probability must be in (0, 1]")
                return FaultRule(seam, kind, "prob", prob=p, rng=rng)
            n = int(trig)
            if n < 1:
                raise FaultSpecError(f"rule {raw!r}: count must be >= 1")
            return FaultRule(seam, kind, "count", remaining=n, rng=rng)
        except ValueError as e:
            if isinstance(e, FaultSpecError):
                raise
            raise FaultSpecError(
                f"rule {raw!r}: trigger must be 'once', an int count, or a "
                f"float probability") from None

    def has_seam(self, seam: str) -> bool:
        return seam in self._rules

    def check(self, seam: str, key: str = "") -> None:
        """Raise (or hang) if an armed rule for ``seam`` fires. At most ONE
        rule fires per hit (first in spec order): a hit can only fail one
        way, and co-armed once/count rules must not silently drain their
        budgets behind the rule that actually surfaced."""
        rules = self._rules.get(seam)
        if not rules:
            return
        fired_rule = None
        with self._lock:
            for r in rules:
                if r.should_fire():
                    fired_rule = r
                    break
        if fired_rule is None:
            return
        _FIRED_TOTAL.inc(seam=fired_rule.seam, kind=fired_rule.kind)
        # the firing is a flight-recorder event too: a chaos run tailed
        # live shows WHERE the storm is biting, not just how often
        telemetry.event("fault.fired", seam=fired_rule.seam,
                        kind=fired_rule.kind, key=key)
        if fired_rule.kind == "stall":
            # slow-not-broken: sleep a bounded window, then continue — the
            # call SUCCEEDS late (latency injection for the serving tier)
            time.sleep(_stall_s())
            return
        if fired_rule.kind == "hang":
            # the "never returns" failure mode (hung device, dead NFS):
            # block far past any drain deadline; daemon stage threads die
            # with the process
            threading.Event().wait(HANG_S)
            return
        if fired_rule.kind == "kill":
            # the real-crash failure mode: SIGKILL this process AT the seam
            # (no atexit, no flushes — exactly what the kernel OOM killer or
            # a power cut does). The crash-recovery harness arms this with a
            # skipN trigger to die mid-group-commit / mid-gather / mid-sync-
            # window deterministically.
            import os as _os
            import signal as _signal

            _os.kill(_os.getpid(), _signal.SIGKILL)
            threading.Event().wait(HANG_S)  # never reached; belt-and-braces
            return
        exc = KINDS[fired_rule.kind](key)
        setattr(exc, INJECTED_ATTR, True)
        raise exc

    def fired(self) -> dict[str, int]:
        """``{"seam:kind": hits}`` — for chaos benches and tests."""
        with self._lock:
            return {f"{r.seam}:{r.kind}": r.fired
                    for rules in self._rules.values() for r in rules
                    if r.fired}
