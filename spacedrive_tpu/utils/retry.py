"""The one retry-with-backoff policy (and the transient-error classification).

Every transient-failure class in the system retries through
:func:`retry_call` with an explicit :class:`RetryPolicy` — never an ad-hoc
``time.sleep`` loop (the ``retry-discipline`` sdlint pass enforces this in
jobs|objects|sync|p2p). Three properties the scattered loops never had:

- **budgeted**: attempts AND total wall time are bounded, so a permanently
  failing dependency degrades to its caller's fatal path instead of
  stalling a lane;
- **jittered exponential backoff**: concurrent retriers (pipeline stages,
  lanes) decorrelate instead of thundering back in lockstep;
- **pause/cancel-aware**: the backoff sleeps in poll quanta and runs
  ``cancel_check`` between quanta, so a worker whose ``check_commands``
  raises JobPaused/JobCanceled unwinds within one poll interval instead of
  sleeping out the window.

Classification (transient vs fatal) lives here too so every layer agrees:
SQLITE_BUSY, EINTR/EIO/EAGAIN reads, and connection flaps are retryable;
vanished/permission-denied/truncated items are NOT — those quarantine at
the item level (docs/architecture/robustness.md has the full classification).
"""

from __future__ import annotations

import dataclasses
import errno
import logging
import random
import sqlite3
import time
from typing import Any, Callable

from .. import telemetry

logger = logging.getLogger(__name__)

#: backoff sleep quantum — also the worst-case latency for a pause/cancel
#: arriving mid-backoff (matches the pipeline executor's poll cadence)
POLL_S = 0.05


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """attempts counts CALLS (attempts=1 → no retry); budget_s bounds the
    total time spent waiting between them."""

    attempts: int = 3
    base_s: float = 0.05
    max_s: float = 2.0
    multiplier: float = 2.0
    #: +/- fraction of the delay drawn uniformly (0.5 → 50%..150%)
    jitter: float = 0.5
    budget_s: float = 10.0

    def delay(self, retry_index: int, rng: random.Random) -> float:
        d = min(self.max_s, self.base_s * self.multiplier ** retry_index)
        if self.jitter:
            d *= 1.0 + self.jitter * (2.0 * rng.random() - 1.0)
        return max(0.0, d)


# -- the transient-vs-fatal classification ------------------------------------------

#: OSError errnos that mean "the same call can succeed if repeated"
TRANSIENT_ERRNOS = frozenset({errno.EINTR, errno.EIO, errno.EAGAIN,
                              errno.EBUSY})


def is_sqlite_busy(exc: BaseException) -> bool:
    """SQLITE_BUSY/SQLITE_LOCKED surface as OperationalError text."""
    if not isinstance(exc, sqlite3.OperationalError):
        return False
    msg = str(exc).lower()
    return "locked" in msg or "busy" in msg


def is_transient_io(exc: BaseException) -> bool:
    return isinstance(exc, OSError) and exc.errno in TRANSIENT_ERRNOS


def is_transient(exc: BaseException) -> bool:
    """The union class: what retry_call retries by default. Exceptions can
    also self-classify via an ``sd_transient`` attribute (injected crash
    markers, wedge errors). A refused/reset/timed-out link (a flapping
    peer) is transient too: retry before declaring it down."""
    return (is_sqlite_busy(exc) or is_transient_io(exc)
            or isinstance(exc, (ConnectionError, TimeoutError))
            or getattr(exc, "sd_transient", False))


def is_device_wedge(exc: BaseException) -> bool:
    """Device-backend failures that the hasher degradation ladder absorbs
    (device → native CPU): the injected wedge marker or anything raised
    out of the jax/jaxlib runtime."""
    if getattr(exc, "sd_transient", False) and "wedge" in type(exc).__name__.lower():
        return True
    return type(exc).__module__.split(".")[0] in ("jax", "jaxlib")


# -- process-wide accounting (telemetry registry) ------------------------------
# PR 4's bespoke module-global stats dict is gone: retry accounting lives on
# the unified registry (chaos benches read the deltas from a telemetry
# snapshot — sd_retry_attempts_total / sd_retry_backoff_seconds_total /
# sd_retry_gave_up_total).

_ATTEMPTS = telemetry.counter(
    "sd_retry_attempts_total",
    "re-calls made after a transient failure (utils/retry.py)")
_BACKOFF_S = telemetry.counter(
    "sd_retry_backoff_seconds_total",
    "total wall time spent in retry backoff")
_GAVE_UP = telemetry.counter(
    "sd_retry_gave_up_total",
    "retry budgets exhausted (attempts or wall budget)")


# -- the driver ----------------------------------------------------------------

def retry_call(fn: Callable[[], Any], *,
               policy: RetryPolicy,
               classify: Callable[[BaseException], bool] = is_transient,
               cancel_check: Callable[[], None] | None = None,
               rng: random.Random | None = None,
               sleep: Callable[[float], None] = time.sleep,
               label: str = "") -> Any:
    """Call ``fn`` until it succeeds, a non-retryable exception escapes, or
    the policy's attempt/time budget runs out (the last exception re-raises).

    ``cancel_check`` runs between backoff quanta (and before each retry);
    anything it raises — JobPaused, JobCanceled — propagates immediately,
    abandoning the backoff. The pending transient exception is dropped: by
    definition retrying it could have succeeded, and the checkpoint the
    pause serializes reflects only committed work either way.
    """
    rng = rng or random
    deadline = time.monotonic() + policy.budget_s
    retries = 0
    while True:
        try:
            return fn()
        except BaseException as exc:  # noqa: BLE001 — classified below
            if not classify(exc):
                raise
            retries += 1
            if retries >= policy.attempts:
                _GAVE_UP.inc()
                raise
            delay = policy.delay(retries - 1, rng)
            now = time.monotonic()
            if now + delay > deadline:
                _GAVE_UP.inc()
                raise
            logger.debug("retry %d/%d%s in %.3fs after %r",
                         retries, policy.attempts - 1,
                         f" [{label}]" if label else "", delay, exc)
            waited = 0.0
            while waited < delay:
                if cancel_check is not None:
                    cancel_check()
                quantum = min(POLL_S, delay - waited)
                sleep(quantum)
                waited += quantum
            if cancel_check is not None:
                cancel_check()
            _ATTEMPTS.inc()
            _BACKOFF_S.inc(waited)
