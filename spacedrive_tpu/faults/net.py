"""Link-level network fault model: the WAN as a first-class faults dimension.

``SD_FAULTS`` (spec.py) injects *node-local* failures — a flap at the dial,
a busy answer, a SIGKILL at a seam. Every peer pair is still a perfect
zero-latency pipe, so partitions, asymmetric loss, and slow links went
untested. This module models the **link itself**: a :class:`NetModel` keyed
by (src, dst) peer identity holds scheduled latency/jitter, probabilistic
drop, delay-modeled reorder, a bandwidth cap, and timed partition/heal
windows. The transport seams (``tests/fleet_harness.py`` wire-less sessions
and the ``p2p/nlm.py`` originate/responder paths) call :func:`link` — the
``p2p_link`` inject point — once per message traversal, so every push
window, BUSY frame, and hash batch crosses a modeled link.

Grammar (``SD_NET_PLAN``, rules ``;``-separated; seed via ``SD_NET_SEED``)::

    SD_NET_PLAN="*>*:lat=5,jitter=2,drop=0.01,bw=4MBps;part:peer-0*|*:@1.0+2.5"

- **link rule** — ``<srcpat>><dstpat>:<k>=<v>[,<k>=<v>...]``; patterns are
  ``fnmatch`` globs over peer identities, first matching rule wins (like
  SD_FAULTS, at most one rule shapes a traversal). Keys:
    * ``lat``     — base one-way latency; plain number = milliseconds,
      ``ms``/``s`` suffixes accepted (``lat=5``, ``lat=0.2s``)
    * ``jitter``  — ± uniform latency jitter, same units
    * ``drop``    — per-message drop probability in (0, 1]
    * ``reorder`` — probability a message is delivered LATE (an extra
      2×lat hold — the delay model of reordering: meaningful when
      concurrent streams share the link, pure jitter on a serial one)
    * ``bw``      — bandwidth cap as serialization delay, ``<float>``
      bytes/s with ``KBps``/``MBps``/``GBps`` (decimal) suffixes
- **partition rule** — ``part:<apat>|<bpat>:@<start>+<dur>`` cuts every
  link between a peer matching ``apat`` and one matching ``bpat`` (BOTH
  directions) during ``[start, start+dur)`` seconds from the model epoch
  (:meth:`NetModel.reset_epoch`; the fleet harness resets it at storm
  start so windows are storm-relative). Any number of windows; a link is
  cut while ANY window covers it.

Determinism: every (rule, concrete link) pair owns a seeded RNG
(``Random(f"{seed}:{rule_index}:{src}>{dst}")``) and each traversal draws
jitter → drop → reorder in fixed order, so two runs with the same seed,
plan, and per-link call sequence make identical decisions — the per-link
delivery :meth:`ledger` (seq, verdict, delay) is the byte-comparable proof
the determinism gate in tests/test_wan.py diffs. Partition membership is
time-based; tests that need partition determinism inject a virtual clock.

Verdicts surface as transient exceptions (:class:`LinkDropped`,
:class:`LinkCut` — ``ConnectionError`` subclasses, so the whole retry /
ack-watermark-resume stack absorbs them exactly like a real flap), and as
the bounded-cardinality ``sd_net_link_*`` telemetry families (no per-link
labels: a 64-peer mesh is 4k links).
"""

from __future__ import annotations

import fnmatch
import logging
import os
import threading
import time
from random import Random
from typing import Any, Callable

from .. import telemetry

__all__ = [
    "LinkCut", "LinkDropped", "NetModel", "NetPlanError", "PROFILES",
    "active", "clear", "install", "link", "profile_plan", "reload",
]

logger = logging.getLogger(__name__)

_MESSAGES = telemetry.counter(
    "sd_net_link_messages_total",
    "messages that crossed the modeled network, by verdict "
    "(ok | drop | cut)", labels=("verdict",))
_BYTES = telemetry.counter(
    "sd_net_link_bytes_total",
    "payload bytes delivered across the modeled network")
_DELAY_S = telemetry.counter(
    "sd_net_link_delay_seconds_total",
    "injected link delay (latency + jitter + serialization)")
_PARTITIONS = telemetry.gauge(
    "sd_net_link_partitions_active",
    "partition windows currently cutting at least one link")


class LinkDropped(ConnectionError):
    """The modeled link dropped this message (probabilistic loss). A
    ``ConnectionError`` so the transient classification retries it like a real
    flap; the session resumes from its acknowledged watermark."""


class LinkCut(ConnectionError):
    """The link is inside a partition window — every traversal fails until
    the heal. Transient: the retry/backoff loop keeps the session alive
    across the window and resumes, never restarts."""


class NetPlanError(ValueError):
    """Malformed SD_NET_PLAN — raised at parse/install, never at a seam."""


#: hard sanity cap on one traversal's injected delay (a typo'd plan must
#: not wedge a session for minutes)
MAX_DELAY_S = 30.0

#: per-link delivery-ledger bound; past it only counters advance (the
#: determinism gate uses short runs, the 64-peer soak ~dozens/link)
LEDGER_CAP = 4096


def _parse_duration_ms(raw: str, where: str) -> float:
    raw = raw.strip()
    try:
        if raw.endswith("ms"):
            return float(raw[:-2])
        if raw.endswith("s"):
            return float(raw[:-1]) * 1000.0
        return float(raw)
    except ValueError:
        raise NetPlanError(f"{where}: bad duration {raw!r} "
                           f"(number, 'Nms' or 'Ns')") from None


def _parse_rate(raw: str, where: str) -> float:
    raw = raw.strip()
    mult = 1.0
    for suffix, m in (("GBps", 1e9), ("MBps", 1e6), ("KBps", 1e3)):
        if raw.endswith(suffix):
            raw, mult = raw[: -len(suffix)], m
            break
    try:
        rate = float(raw) * mult
    except ValueError:
        raise NetPlanError(f"{where}: bad rate {raw!r} "
                           f"(bytes/s, KBps/MBps/GBps suffixes)") from None
    if rate <= 0:
        raise NetPlanError(f"{where}: rate must be > 0")
    return rate


class _LinkRule:
    __slots__ = ("index", "src_pat", "dst_pat", "lat_s", "jitter_s",
                 "drop", "reorder", "bw")

    def __init__(self, index: int, src_pat: str, dst_pat: str,
                 body: str) -> None:
        self.index = index
        self.src_pat = src_pat
        self.dst_pat = dst_pat
        self.lat_s = 0.0
        self.jitter_s = 0.0
        self.drop = 0.0
        self.reorder = 0.0
        self.bw = 0.0  # 0 = uncapped
        where = f"link rule {src_pat}>{dst_pat}"
        if not body.strip():
            raise NetPlanError(f"{where}: empty directive list")
        for kv in body.split(","):
            if "=" not in kv:
                raise NetPlanError(f"{where}: directive {kv!r} is not k=v")
            key, val = (s.strip() for s in kv.split("=", 1))
            if key == "lat":
                self.lat_s = _parse_duration_ms(val, where) / 1000.0
            elif key == "jitter":
                self.jitter_s = _parse_duration_ms(val, where) / 1000.0
            elif key in ("drop", "reorder"):
                try:
                    p = float(val)
                except ValueError:
                    raise NetPlanError(
                        f"{where}: {key} must be a probability") from None
                if not 0.0 < p <= 1.0:
                    raise NetPlanError(
                        f"{where}: {key} must be in (0, 1], got {p}")
                setattr(self, key, p)
            elif key == "bw":
                self.bw = _parse_rate(val, where)
            else:
                raise NetPlanError(
                    f"{where}: unknown key {key!r} "
                    f"(known: lat, jitter, drop, reorder, bw)")
        if self.lat_s < 0 or self.jitter_s < 0:
            raise NetPlanError(f"{where}: negative duration")

    def matches(self, src: str, dst: str) -> bool:
        return (fnmatch.fnmatchcase(src, self.src_pat)
                and fnmatch.fnmatchcase(dst, self.dst_pat))


class _PartitionRule:
    __slots__ = ("index", "a_pat", "b_pat", "start_s", "end_s", "announced")

    def __init__(self, index: int, a_pat: str, b_pat: str,
                 window: str) -> None:
        self.index = index
        self.a_pat = a_pat
        self.b_pat = b_pat
        where = f"part rule {a_pat}|{b_pat}"
        window = window.strip()
        if not window.startswith("@") or "+" not in window:
            raise NetPlanError(f"{where}: window must be '@<start>+<dur>'")
        start_raw, dur_raw = window[1:].split("+", 1)
        try:
            start, dur = float(start_raw), float(dur_raw)
        except ValueError:
            raise NetPlanError(
                f"{where}: window bounds must be seconds (floats)") from None
        if start < 0 or dur <= 0:
            raise NetPlanError(
                f"{where}: start must be >= 0 and duration > 0")
        self.start_s = start
        self.end_s = start + dur
        #: 0 = not yet entered, 1 = partition announced, 2 = heal announced
        self.announced = 0

    def covers(self, src: str, dst: str) -> bool:
        """Both directions: a partition severs the pair, not one arrow."""
        return ((fnmatch.fnmatchcase(src, self.a_pat)
                 and fnmatch.fnmatchcase(dst, self.b_pat))
                or (fnmatch.fnmatchcase(src, self.b_pat)
                    and fnmatch.fnmatchcase(dst, self.a_pat)))


class NetModel:
    """Parsed, armed link plan; :meth:`traverse` is the seam entry point.

    ``clock``/``sleep`` are injectable so determinism and partition tests
    drive a virtual timeline; production uses the real monotonic clock."""

    def __init__(self, spec: str, seed: int = 0,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep) -> None:
        self.spec = spec
        self.seed = seed
        self._clock = clock
        self._sleep = sleep
        self._lock = threading.Lock()
        self._links: list[_LinkRule] = []
        self._parts: list[_PartitionRule] = []
        #: (rule index, "src>dst") -> per-link seeded RNG
        self._rngs: dict[tuple[int, str], Random] = {}
        #: "src>dst" -> [(seq, verdict, delay_ms)] — the delivery ledger
        self._ledger: dict[str, list[tuple[int, str, float]]] = {}
        self._seq: dict[str, int] = {}
        #: "src>dst" -> delivered payload bytes (additive; never capped)
        self._bytes: dict[str, int] = {}
        self._overflow = 0
        for i, raw in enumerate(p for p in spec.split(";") if p.strip()):
            self._parse_rule(raw.strip(), i)
        if not self._links and not self._parts:
            raise NetPlanError(f"empty net plan {spec!r}")
        self._epoch = self._clock()

    def _parse_rule(self, raw: str, index: int) -> None:
        if raw.startswith("part:"):
            body = raw[len("part:"):]
            groups, sep, window = body.rpartition(":")
            if not sep or "|" not in groups:
                raise NetPlanError(
                    f"rule {raw!r}: expected part:<a>|<b>:@<start>+<dur>")
            a_pat, b_pat = (s.strip() for s in groups.split("|", 1))
            if not a_pat or not b_pat:
                raise NetPlanError(f"rule {raw!r}: empty partition group")
            self._parts.append(_PartitionRule(index, a_pat, b_pat, window))
            return
        head, sep, body = raw.partition(":")
        if not sep or ">" not in head:
            raise NetPlanError(
                f"rule {raw!r}: expected <src>><dst>:<k>=<v>,... "
                f"or part:<a>|<b>:@<start>+<dur>")
        src_pat, dst_pat = (s.strip() for s in head.split(">", 1))
        if not src_pat or not dst_pat:
            raise NetPlanError(f"rule {raw!r}: empty link pattern")
        self._links.append(_LinkRule(index, src_pat, dst_pat, body))

    # -- the seam ------------------------------------------------------------
    def reset_epoch(self) -> None:
        """Re-base partition windows on 'now' (the harness calls this at
        storm start so ``@<start>+<dur>`` is storm-relative, not
        armed-relative) and re-arm their one-shot edge events."""
        with self._lock:
            self._epoch = self._clock()
            for part in self._parts:
                part.announced = 0

    def elapsed(self) -> float:
        return self._clock() - self._epoch

    def traverse(self, src: str, dst: str, nbytes: int = 0) -> float:
        """One message crossing ``src → dst``: raise :class:`LinkCut`
        inside a partition window, :class:`LinkDropped` on probabilistic
        loss, otherwise sleep the modeled delay and return it (seconds)."""
        delay = self.decide(src, dst, nbytes)
        if delay > 0.0:
            self._sleep(delay)
        return delay

    def decide(self, src: str, dst: str, nbytes: int = 0) -> float:
        """The verdict half of :meth:`traverse` — raises cut/drop or
        returns the modeled delay WITHOUT sleeping it. Async callers
        (p2p/nlm.py) use this so the delay rides ``asyncio.sleep`` on the
        event loop instead of parking a shared executor thread per
        message. The decision + ledger + counters are identical either
        way (the delay counter records the delay the caller is contracted
        to sleep)."""
        link = f"{src}>{dst}"
        now = self._clock()
        delay = 0.0
        with self._lock:
            elapsed = now - self._epoch
            verdict = "ok"
            active_parts = 0
            for part in self._parts:
                inside = part.start_s <= elapsed < part.end_s
                if inside:
                    active_parts += 1
                self._announce_locked(part, inside, elapsed)
                if inside and part.covers(src, dst):
                    verdict = "cut"
            _PARTITIONS.set(active_parts)
            rule = next((r for r in self._links if r.matches(src, dst)),
                        None)
            if verdict != "cut" and rule is not None:
                rng = self._rngs.get((rule.index, link))
                if rng is None:
                    rng = Random(f"{self.seed}:{rule.index}:{link}")
                    self._rngs[(rule.index, link)] = rng
                # fixed draw order per traversal — the determinism contract
                jitter = rng.uniform(-rule.jitter_s, rule.jitter_s)
                dropped = rng.random() < rule.drop if rule.drop else False
                late = rng.random() < rule.reorder if rule.reorder else False
                if dropped:
                    verdict = "drop"
                else:
                    delay = max(0.0, rule.lat_s + jitter)
                    if late:
                        delay += 2.0 * rule.lat_s
                    if rule.bw and nbytes:
                        delay += nbytes / rule.bw
                    delay = min(delay, MAX_DELAY_S)
            seq = self._seq.get(link, 0)
            self._seq[link] = seq + 1
            log = self._ledger.setdefault(link, [])
            if len(log) < LEDGER_CAP:
                log.append((seq, verdict, round(delay * 1000.0, 3)))
            else:
                self._overflow += 1
        _MESSAGES.inc(verdict=verdict)
        if verdict == "cut":
            raise LinkCut(f"partition: link {src} -> {dst} is cut "
                          f"[net plan, t={elapsed:.2f}s]")
        if verdict == "drop":
            raise LinkDropped(f"link {src} -> {dst} dropped the message "
                              f"[net plan]")
        if delay > 0.0:
            _DELAY_S.inc(delay)
        if nbytes:
            _BYTES.inc(nbytes)
            # delivered-bytes tally (additive, unbounded — unlike the
            # capped ledger): the delta-transfer gate's bytes-on-wire
            # accounting reads this per link
            with self._lock:
                self._bytes[link] = self._bytes.get(link, 0) + nbytes
        return delay

    def _announce_locked(self, part: _PartitionRule, inside: bool,
                         elapsed: float) -> None:
        """One flight-recorder event per partition edge (lazy: fired by the
        first traversal that observes the transition)."""
        if inside and part.announced == 0:
            part.announced = 1
            telemetry.event("net.partition", groups=f"{part.a_pat}|{part.b_pat}",
                            start_s=part.start_s, end_s=part.end_s)
        elif not inside and part.announced == 1 and elapsed >= part.end_s:
            part.announced = 2
            telemetry.event("net.heal", groups=f"{part.a_pat}|{part.b_pat}",
                            end_s=part.end_s)

    # -- introspection -------------------------------------------------------
    def partitioned(self, src: str, dst: str) -> bool:
        with self._lock:
            elapsed = self._clock() - self._epoch
            return any(p.start_s <= elapsed < p.end_s and p.covers(src, dst)
                       for p in self._parts)

    def last_heal_s(self) -> float:
        """Latest partition-window end, seconds from epoch (0.0 when the
        plan has no partitions) — the bench's heal-to-lag-zero anchor."""
        return max((p.end_s for p in self._parts), default=0.0)

    def ledger(self) -> dict[str, list[tuple[int, str, float]]]:
        """Per-link delivery log ``{"src>dst": [(seq, verdict, delay_ms)]}``
        — identical across runs with the same seed/plan/per-link call
        sequence (the determinism gate's comparator)."""
        with self._lock:
            return {k: list(v) for k, v in self._ledger.items()}

    def drops(self) -> dict[str, list[int]]:
        """Per-link dropped-message seqs (the 'drop set')."""
        with self._lock:
            return {k: [seq for seq, verdict, _ in v if verdict == "drop"]
                    for k, v in self._ledger.items()}

    def bytes_by_link(self) -> dict[str, int]:
        """Delivered payload bytes per link ``{"src>dst": n}`` — the
        delta-transfer gate asserts chunked sends ship strictly less than
        the whole file from exactly this accounting (the capped ledger
        keeps its tuple format; byte totals live here so the determinism
        comparator is untouched)."""
        with self._lock:
            return dict(self._bytes)

    def status(self) -> dict[str, Any]:
        with self._lock:
            verdicts: dict[str, int] = {}
            for log in self._ledger.values():
                for _seq, verdict, _d in log:
                    verdicts[verdict] = verdicts.get(verdict, 0) + 1
            return {"links_seen": len(self._ledger),
                    "messages": sum(self._seq.values()),
                    "verdicts": verdicts,
                    "ledger_overflow": self._overflow,
                    "partitions": len(self._parts),
                    "elapsed_s": round(self._clock() - self._epoch, 3)}


# -- module-level plan (the inject-point fast path) ----------------------------

_MODEL: NetModel | None = None


def install(spec: str, seed: int | None = None,
            clock: Callable[[], float] = time.monotonic,
            sleep: Callable[[float], None] = time.sleep) -> NetModel:
    """Arm a plan programmatically (tests, bench WAN mode)."""
    global _MODEL
    if seed is None:
        seed = _seed_from_env()
    _MODEL = NetModel(spec, seed=seed, clock=clock, sleep=sleep)
    logger.warning("network fault model ARMED: %s (seed %d)", spec, seed)
    return _MODEL


def clear() -> None:
    global _MODEL
    _MODEL = None
    _PARTITIONS.set(0)


def reload() -> NetModel | None:
    """Re-read ``SD_NET_PLAN`` (after an in-process env change)."""
    global _MODEL
    spec = os.environ.get("SD_NET_PLAN", "").strip()
    _MODEL = NetModel(spec, seed=_seed_from_env()) if spec else None
    if _MODEL is not None:
        logger.warning("network fault model ARMED from env: %s", spec)
    return _MODEL


def active() -> NetModel | None:
    return _MODEL


def link(src: str, dst: str, nbytes: int = 0) -> None:
    """The ``p2p_link`` inject point: model one message traversal, or
    no-op (one module-global read) when no plan is armed."""
    model = _MODEL
    if model is None:
        return
    model.traverse(src, dst, nbytes)


async def alink(src: str, dst: str, nbytes: int = 0) -> None:
    """Async traversal for sender-side p2p frames (delta offers, whole-file
    spacedrop blocks, replica queries): ``decide()`` runs inline — a cut or
    drop raises out of the send exactly like :func:`link` — but the modeled
    delay is paid with ``asyncio.sleep`` so one shaped transfer never parks
    the p2p event loop for every other session."""
    model = _MODEL
    if model is None:
        return
    delay = model.decide(src, dst, nbytes)
    if delay > 0.0:
        import asyncio

        await asyncio.sleep(delay)


def _seed_from_env() -> int:
    try:
        return int(os.environ.get("SD_NET_SEED", "0"))
    except ValueError:
        return 0


# -- the shared WAN topology profiles ------------------------------------------
# ONE place for the soak matrices: tests/test_wan.py and ``bench.py --fleet
# --wan <profile>`` both arm these, so the gate and the bench always speak
# the same topology. Peer patterns follow the fleet harness's identity
# scheme (``fleet-peer-NN`` / ``fleet-target``); the wildcard link rule
# covers any identity scheme.

PROFILES: dict[str, str] = {
    # same-switch LAN: sub-ms latency, no loss — the control matrix
    "lan": "*>*:lat=0.2,jitter=0.1",
    # healthy WAN: regional RTT, rare loss, a shaped uplink
    "wan": "*>*:lat=5,jitter=2,drop=0.002,bw=8MBps",
    # hostile WAN: loss + jitter + two partition waves (storm-relative;
    # the first cuts peers 0x from everything, the second peers 1x) —
    # the flaky-wan chaos soak's matrix
    "flaky-wan": ("*>*:lat=3,jitter=2,drop=0.01,bw=4MBps;"
                  "part:fleet-peer-0*|*:@1.0+2.5;"
                  "part:fleet-peer-1*|*:@5.0+2.0"),
}


def profile_plan(name: str) -> str:
    try:
        return PROFILES[name]
    except KeyError:
        raise NetPlanError(
            f"unknown WAN profile {name!r} "
            f"(known: {', '.join(sorted(PROFILES))})") from None


# arm from the environment once at import, like SD_FAULTS
try:
    reload()
except NetPlanError:
    logger.exception("SD_NET_PLAN spec rejected; network model DISARMED")
    _MODEL = None
