"""Bounded-queue streaming executor for batched jobs.

Thread layout (one executor per pipelined job run):

    prefetcher ──pages──▶ dispatcher ──results──▶ committer (job thread)

Both queues are bounded (``SD_PIPELINE_DEPTH``), so a slow committer
backpressures the dispatcher and a slow dispatcher backpressures the
prefetcher — memory stays O((depth + group) × batch) no matter how far the
stages drift apart. The committer is the job's own worker thread: it polls
the command channel between commits exactly like the sequential step loop,
so Pause/Cancel/Shutdown land at a committed-GROUP boundary and the
serialized checkpoint only ever reflects committed work.

Group commit (``SD_COMMIT_GROUP``): up to N processed pages share one
durable transaction — each page's ``spec.commit`` runs in order and its own
``db.transaction()`` joins the outer scope, so BEGIN/COMMIT (and fsync/WAL
cost, and the ``commit`` fault seam) amortize over the group while row
contents and CRDT op order stay byte-identical to the per-page committer.
"""

from __future__ import annotations

import logging
import os
import queue
import sqlite3
import threading
import time
from typing import TYPE_CHECKING, Any

from .. import telemetry
from ..recovery import is_disk_full, note_disk_full
from ..utils.locks import SdLock
from ..utils.retry import RetryPolicy, is_device_wedge, is_transient, retry_call

if TYPE_CHECKING:
    from ..jobs.job import DynJob
    from ..jobs.worker import WorkerContext
    from .spec import PipelineSpec

logger = logging.getLogger(__name__)

#: per-stage accounting on the unified registry: busy = executing the
#: stage callable, blocked = waiting on a full downstream queue
#: (backpressure), idle = waiting on an empty upstream queue. busy time is
#: read straight off the stage spans, so /metrics, the jobTrace tree and
#: the report's pipeline_*_s metadata can never disagree.
_BUSY = telemetry.counter(
    "sd_pipeline_stage_busy_seconds",
    "time each pipeline stage spent executing its callable",
    labels=("stage",))
_BLOCKED = telemetry.counter(
    "sd_pipeline_stage_blocked_seconds",
    "time each stage spent blocked on a full downstream queue "
    "(backpressure)", labels=("stage",))
_IDLE = telemetry.counter(
    "sd_pipeline_stage_idle_seconds",
    "time each stage spent waiting on an empty upstream queue",
    labels=("stage",))
_COMMIT_TXNS = telemetry.counter(
    "sd_commit_txns_total",
    "durable transactions opened by the pipeline committer (group commit "
    "coalesces SD_COMMIT_GROUP pages into each)")
_COMMIT_PAGES = telemetry.counter(
    "sd_commit_txn_pages_total",
    "pipeline pages made durable through group-commit transactions")
_GATHER_SHARDS = telemetry.gauge(
    "sd_gather_shards",
    "parallel gather shards per page in the sharded prefetch stage "
    "(SD_SCAN_SHARDS; 1 = classic single-thread prefetch)")
_GATHER_INFLIGHT = telemetry.gauge(
    "sd_gather_inflight",
    "gather shard slices currently executing across the shard workers")
_SHARD_TASKS = telemetry.counter(
    "sd_gather_shard_tasks_total",
    "page slices executed per gather shard worker (occupancy skew across "
    "shards shows up as per-label imbalance)", labels=("shard",))

#: poll quantum for queue waits — also bounds pause latency, like the
#: sequential loop's between-steps command check cadence
_POLL_S = 0.05

#: how long a partial commit group may wait for more pages before it
#: flushes anyway. In a commit-bound pipeline the results queue never runs
#: dry and groups fill to SD_COMMIT_GROUP; in a page/hash-bound pipeline
#: this caps durability latency (pause itself is NOT delayed — a pause
#: discards the uncommitted group and serializes the last flushed state)
GROUP_LINGER_S = 0.5

#: the committer's own retry over ``spec.commit``: patient (it sits ABOVE
#: the _Txn-level busy retry, catching what escalates past that budget) and
#: cancel-aware — the backoff polls the command channel, so Pause/Cancel
#: unwinds within one poll interval. The retried batch never half-applies
#: because of the PipelineSpec commit contract (spec.py): durable effects
#: are transactional-or-idempotent and post-durable tail work is
#: best-effort/non-raising, so an exception out of ``spec.commit`` means
#: nothing durable happened for this batch.
COMMIT_RETRY = RetryPolicy(attempts=4, base_s=0.25, max_s=2.0,
                           multiplier=2.0, jitter=0.5, budget_s=15.0)

_DONE = object()


def drain_timeout() -> float:
    """Per-join bound when draining stage threads (``SD_PIPELINE_DRAIN_S``);
    a stage stuck in a hung device/IO call must not strand a pausing job."""
    try:
        return max(0.1, float(os.environ.get("SD_PIPELINE_DRAIN_S", "10")))
    except ValueError:
        return 10.0


class _StageFailure:
    """An exception captured on a stage thread, re-raised by the committer
    (sequential parity: a raised step exception is fatal to the job)."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException) -> None:
        self.exc = exc


class _PageTicket:
    """Ordered-merge ticket for one split page — the ``IngestLanes.submit``
    shape: the coordinator enqueues the ticket to the merger BEFORE its
    shard slices fan out, so pages re-serialize in exactly split order no
    matter how the shard workers interleave. Passive holder: the shard
    workers fill ``results`` and count down ``remaining`` under ``lock``
    (the last finisher sets ``done``); the merger barriers on ``done``."""

    __slots__ = ("header", "parts", "results", "remaining", "done", "span",
                 "lock")

    def __init__(self, header: dict, parts: list, span: Any) -> None:
        self.header = header
        self.parts = parts
        self.results: list[Any] = [None] * len(parts)
        self.remaining = len(parts)
        self.done = threading.Event()
        #: the page's detached ``pipeline.page`` span — entered by the
        #: coordinator, parent of every shard span, exited by the merger
        self.span = span
        self.lock = threading.Lock()


def pipeline_enabled() -> bool:
    """Streaming execution is the default for jobs that opt in;
    ``SD_PIPELINE=0`` forces every job back onto the sequential step loop
    (the equivalence baseline)."""
    return os.environ.get("SD_PIPELINE", "1").lower() not in ("0", "false", "off")


def pipeline_depth() -> int:
    """Bounded-queue depth between stages (``SD_PIPELINE_DEPTH``, min 1)."""
    try:
        return max(1, int(os.environ.get("SD_PIPELINE_DEPTH", "2")))
    except ValueError:
        return 2


def scan_shards() -> int:
    """Parallel gather shards per page (``SD_SCAN_SHARDS``, clamped 1..16;
    default min(4, cores)). 1 disables sharding — the classic single
    prefetch thread, which is also the byte-identity baseline the shard
    matrix compares against."""
    raw = os.environ.get("SD_SCAN_SHARDS", "").strip()
    if raw:
        try:
            return max(1, min(int(raw), 16))
        except ValueError:
            pass
    return min(4, os.cpu_count() or 1)


def commit_group() -> int:
    """Pages coalesced per durable transaction (``SD_COMMIT_GROUP``, min 1,
    default 8). 1 restores the PR 3 one-txn-per-page committer — the
    equivalence baseline for the group-commit byte-identity matrix."""
    try:
        return max(1, int(os.environ.get("SD_COMMIT_GROUP", "8")))
    except ValueError:
        return 8


class PipelineExecutor:
    """Drive one pipelined job run; mutates the job's ``JobState`` exactly
    like the sequential loop in ``DynJob.run`` would."""

    def __init__(self, spec: "PipelineSpec", ctx: "WorkerContext",
                 dyn_job: "DynJob", errors: list[str]) -> None:
        self.spec = spec
        self.ctx = ctx
        self.dyn_job = dyn_job
        self.state = dyn_job.state
        self.errors = errors
        depth = spec.depth or pipeline_depth()
        self._pages: queue.Queue[Any] = queue.Queue(maxsize=depth)
        self._results: queue.Queue[Any] = queue.Queue(maxsize=depth)
        # sharded prefetch (ISSUE 17): when the spec provides the
        # split/shard/merge callables and SD_SCAN_SHARDS > 1, the page
        # stage fans each cursor page across shard workers and an ordered
        # merger re-serializes them. Both queues are bounded: tickets by
        # pipeline depth (pages in flight), slices by shards per ticket.
        self._shards = (scan_shards()
                        if (spec.split is not None and spec.shard is not None
                            and spec.merge is not None) else 1)
        self._sharded = self._shards > 1
        self._tickets: queue.Queue[Any] = queue.Queue(maxsize=depth)
        self._shard_q: queue.Queue[Any] = queue.Queue(
            maxsize=self._shards * (depth + 1))
        self._stop = threading.Event()
        #: the job's trace (set by the worker; None with telemetry off) —
        #: stage spans pin the run() wall span as their parent
        self.trace = getattr(dyn_job, "trace", None)
        self._wall_sp = None
        # per-stage wall time, read off the stage spans. Three different
        # threads accumulate here (prefetcher, dispatcher, committer);
        # the per-batch lock hold replaces the old "each attribute is
        # written by exactly one thread" convention with an invariant the
        # lockset pass and the runtime sanitizer can actually check
        self._stats_lock = SdLock("pipeline.executor.stats")
        self._page_s = 0.0
        self._hash_s = 0.0
        self._commit_s = 0.0
        self._batches = 0
        self._txns = 0

    def _persist_checkpoint(self) -> None:
        """Write the current (fully committed) state into the job report row
        so process death resumes here (jobs/manager.cold_resume revives
        RUNNING rows from report.data). One small autocommit UPDATE per
        group transaction; failures cost re-run work, never correctness."""
        db = getattr(getattr(self.ctx, "library", None), "db", None)
        if db is None:
            return
        try:
            report = self.dyn_job.report
            report.data = self.dyn_job.serialize_state()
            report.upsert(db)
        except Exception:
            logger.exception(
                "pipeline %s: checkpoint persist failed (resume falls back "
                "to the previous checkpoint)", self.dyn_job.job.NAME)

    # -- bounded put/get that never deadlock a drain -------------------------
    def _put(self, q: queue.Queue, item: Any) -> bool:
        while not self._stop.is_set():
            try:
                q.put(item, timeout=_POLL_S)
                return True
            except queue.Full:
                continue
        return False

    def _put_nowait_or_drop(self, q: queue.Queue, item: Any) -> None:
        """Best-effort forward of a failure marker: make room if needed (the
        committer only cares that it eventually sees the failure)."""
        while True:
            try:
                q.put_nowait(item)
                return
            except queue.Full:
                try:
                    q.get_nowait()
                except queue.Empty:
                    pass

    def _observe_shares(self, scratch: dict[str, Any]) -> None:
        """Publish measured stage shares (fraction of the pipeline wall
        each stage has consumed so far) into ``scratch`` — the feedback
        signal adaptive page sizing (``spec.adaptive``) reads before
        sizing the next page. Measurement only: the sizing law lives with
        the job, which knows its own pin/override rules."""
        wall_sp = self._wall_sp
        if wall_sp is None:
            return
        wall = wall_sp.elapsed_s()
        if wall <= 0.05:
            return
        with self._stats_lock:
            shares = {"page": self._page_s / wall,
                      "hash": self._hash_s / wall,
                      "commit": self._commit_s / wall}
        scratch["stage_shares"] = shares

    # -- stage threads -------------------------------------------------------
    def _prefetch_loop(self, budget: int) -> None:
        scratch: dict[str, Any] = {
            "step_index": self.state.step_number,
            "steps": self.state.steps,
            "shards": 1,
        }
        try:
            while (budget > 0 or self.spec.adaptive) \
                    and not self._stop.is_set():
                self._observe_shares(scratch)
                with telemetry.span(self.trace, "pipeline.page",
                                    parent=self._wall_sp) as sp:
                    payload = self.spec.page(self.ctx, self.state.data,
                                             scratch)
                with self._stats_lock:
                    self._page_s += sp.duration_s
                _BUSY.inc(sp.duration_s, stage="page")
                if payload is None:
                    break
                budget -= 1
                t0 = time.perf_counter()
                ok = self._put(self._pages, payload)
                _BLOCKED.inc(time.perf_counter() - t0, stage="page")
                if not ok:
                    return  # draining
            self._put(self._pages, _DONE)
        except BaseException as e:  # noqa: BLE001 — forwarded, fatal
            self._put_nowait_or_drop(self._pages, _StageFailure(e))

    # -- sharded prefetch: split coordinator / shard workers / merger --------
    def _split_loop(self, budget: int) -> None:
        scratch: dict[str, Any] = {
            "step_index": self.state.step_number,
            "steps": self.state.steps,
            "shards": self._shards,
        }
        try:
            while (budget > 0 or self.spec.adaptive) \
                    and not self._stop.is_set():
                self._observe_shares(scratch)
                # the page span is DETACHED: entered here, exited by the
                # merger once the page reassembles — its duration is the
                # page's true wall (split + shard fan-out + merge), and
                # every shard span pins it as parent so the trace tree
                # keeps one pipeline.page node per page
                sp = telemetry.span(self.trace, "pipeline.page",
                                    parent=self._wall_sp, detached=True,
                                    shards=self._shards)
                sp.__enter__()
                try:
                    with telemetry.span(self.trace, "pipeline.split",
                                        parent=sp):
                        header = self.spec.split(self.ctx, self.state.data,
                                                 scratch)
                except BaseException:
                    sp.__exit__(None, None, None)
                    raise
                if header is None:
                    # out-of-work probe: close and count it, exactly like
                    # the None-returning page call on the classic path
                    sp.__exit__(None, None, None)
                    with self._stats_lock:
                        self._page_s += sp.duration_s
                    _BUSY.inc(sp.duration_s, stage="page")
                    break
                budget -= 1
                parts = header.pop("parts")
                ticket = _PageTicket(header, parts, sp)
                # ticket BEFORE fan-out (the IngestLanes.submit order):
                # merge order is fixed here, shard completion order is free
                t0 = time.perf_counter()
                ok = self._put(self._tickets, ticket)
                if ok:
                    for idx in range(len(parts)):
                        if not self._put(self._shard_q, (ticket, idx)):
                            return  # draining
                _BLOCKED.inc(time.perf_counter() - t0, stage="page")
                if not ok:
                    return  # draining
            self._put(self._tickets, _DONE)
        except BaseException as e:  # noqa: BLE001 — forwarded, fatal
            self._put_nowait_or_drop(self._tickets, _StageFailure(e))

    def _shard_loop(self, shard_idx: int) -> None:
        """One gather worker: drains page slices off the shared shard
        queue in arrival order (work-stealing across pages — a slow slice
        of page N never idles workers that could start page N+1)."""
        label = str(shard_idx)
        while not self._stop.is_set():
            try:
                t0 = time.perf_counter()
                ticket, idx = self._shard_q.get(timeout=_POLL_S)
            except queue.Empty:
                _IDLE.inc(time.perf_counter() - t0, stage="gather")
                continue
            _GATHER_INFLIGHT.inc()
            result = None
            try:
                with telemetry.span(self.trace, "pipeline.gather",
                                    parent=ticket.span, shard=shard_idx,
                                    part=idx) as sp:
                    try:
                        result = self.spec.shard(self.ctx, self.state.data,
                                                 ticket.parts[idx])
                    except BaseException as e:  # noqa: BLE001 — merged, fatal
                        result = _StageFailure(e)
                        sp.set(failed=repr(e))
                _BUSY.inc(sp.duration_s, stage="gather")
                _SHARD_TASKS.inc(shard=label)
            except BaseException as e:  # noqa: BLE001 — span/metric plumbing
                # a slice result that already exists survives a telemetry
                # failure; a missing one becomes a failed slice
                if result is None:
                    result = _StageFailure(e)
            finally:
                # ticket accounting is unconditional: a slice that dies for
                # ANY reason must fail its page at the merger, never leave
                # `remaining` stuck and hang the pipeline
                _GATHER_INFLIGHT.dec()
                with ticket.lock:
                    ticket.results[idx] = result
                    ticket.remaining -= 1
                    last = ticket.remaining == 0
                if last:
                    ticket.done.set()

    def _merge_loop(self) -> None:
        """The ordered merger: completes tickets strictly in split order,
        reassembles each page via ``spec.merge`` and forwards it — so the
        dispatcher (and therefore hash and commit) sees exactly the
        sequential page stream regardless of shard interleaving."""
        try:
            while not self._stop.is_set():
                try:
                    t0 = time.perf_counter()
                    item = self._tickets.get(timeout=_POLL_S)
                except queue.Empty:
                    _IDLE.inc(time.perf_counter() - t0, stage="merge")
                    continue
                if item is _DONE or isinstance(item, _StageFailure):
                    self._put(self._pages, item)
                    return
                ticket = item
                t0 = time.perf_counter()
                while not ticket.done.wait(timeout=_POLL_S):
                    if self._stop.is_set():
                        return  # draining; the page span is abandoned
                _IDLE.inc(time.perf_counter() - t0, stage="merge")
                failure = next((r for r in ticket.results
                                if isinstance(r, _StageFailure)), None)
                if failure is not None:
                    # first failed slice fails the page — sequential
                    # parity with a raised pipeline_page; transient
                    # classification happens in the committer
                    ticket.span.__exit__(type(failure.exc), failure.exc,
                                         None)
                    self._put_nowait_or_drop(self._pages, failure)
                    return
                with telemetry.span(self.trace, "pipeline.merge",
                                    parent=ticket.span):
                    payload = self.spec.merge(self.ctx, self.state.data,
                                              ticket.header, ticket.results)
                ticket.span.__exit__(None, None, None)
                with self._stats_lock:
                    self._page_s += ticket.span.duration_s
                _BUSY.inc(ticket.span.duration_s, stage="page")
                t0 = time.perf_counter()
                ok = self._put(self._pages, payload)
                _BLOCKED.inc(time.perf_counter() - t0, stage="page")
                if not ok:
                    return  # draining
        except BaseException as e:  # noqa: BLE001 — forwarded, fatal
            self._put_nowait_or_drop(self._pages, _StageFailure(e))

    def _dispatch_loop(self) -> None:
        try:
            while not self._stop.is_set():
                try:
                    t0 = time.perf_counter()
                    item = self._pages.get(timeout=_POLL_S)
                except queue.Empty:
                    _IDLE.inc(time.perf_counter() - t0, stage="hash")
                    continue
                if item is _DONE or isinstance(item, _StageFailure):
                    self._put(self._results, item)
                    return
                with telemetry.span(self.trace, "pipeline.hash",
                                    parent=self._wall_sp) as sp:
                    result = self.spec.process(self.ctx, self.state.data,
                                               item)
                with self._stats_lock:
                    self._hash_s += sp.duration_s
                _BUSY.inc(sp.duration_s, stage="hash")
                t0 = time.perf_counter()
                ok = self._put(self._results, result)
                _BLOCKED.inc(time.perf_counter() - t0, stage="hash")
                if not ok:
                    return  # draining
        except BaseException as e:  # noqa: BLE001 — forwarded, fatal
            self._put_nowait_or_drop(self._results, _StageFailure(e))

    # -- the committer (job thread) ------------------------------------------
    def run(self) -> None:
        from ..jobs.error import JobError, JobPaused
        from ..jobs.job import merge_metadata

        state = self.state
        budget = len(state.steps) - state.step_number
        # an adaptive spec may legitimately need more (or fewer) pages
        # than init's fixed-size estimate — completion is page()→None,
        # the budget is only the non-adaptive parity bound
        if budget <= 0 and not self.spec.adaptive:
            return
        # the wall-clock span the stage spans nest under; its duration IS
        # pipeline_wall_s (metadata reads span data, not a parallel clock).
        # Entered BEFORE the stage threads start so its span_id exists for
        # their explicit-parent pins.
        wall_sp = telemetry.span(self.trace, "pipeline.run",
                                 job=self.dyn_job.job.NAME)
        wall_sp.__enter__()
        self._wall_sp = wall_sp
        _GATHER_SHARDS.set(self._shards)
        if self._sharded:
            threads = [
                threading.Thread(target=self._split_loop, args=(budget,),
                                 daemon=True, name="pipeline-prefetch"),
                *[threading.Thread(target=self._shard_loop, args=(i,),
                                   daemon=True, name=f"pipeline-gather-{i}")
                  for i in range(self._shards)],
                threading.Thread(target=self._merge_loop,
                                 daemon=True, name="pipeline-merge"),
                threading.Thread(target=self._dispatch_loop,
                                 daemon=True, name="pipeline-dispatch"),
            ]
        else:
            threads = [
                threading.Thread(target=self._prefetch_loop, args=(budget,),
                                 daemon=True, name="pipeline-prefetch"),
                threading.Thread(target=self._dispatch_loop,
                                 daemon=True, name="pipeline-dispatch"),
            ]
        for t in threads:
            t.start()

        # -- group commit: coalesce up to SD_COMMIT_GROUP processed pages
        # into ONE durable transaction. Each page's spec.commit runs in
        # arrival order; its own db.transaction() joins the outer scope
        # (models/base._Txn re-entrancy), so durability — and the `commit`
        # fault seam — lands once per GROUP. The checkpoint cursor and
        # step_number still only advance with committed work: on any
        # failure the whole group rolls back AND the in-memory `data`
        # snapshot is restored before the exception escapes, so a pause
        # arriving during the retry backoff serializes the last durable
        # group boundary, never a torn group.
        group_n = self.spec.group or commit_group()
        db = getattr(getattr(self.ctx, "library", None), "db", None)
        pending: list[Any] = []
        pending_since = 0.0  # perf_counter of the oldest un-flushed page

        def _flush() -> None:
            if not pending:
                return
            # spec.commit mutates only top-level keys of `data` (the
            # checkpoint-cursor contract, spec.py) — a shallow snapshot
            # makes the group attempt restartable
            snapshot = dict(state.data)

            def attempt() -> list[Any]:
                try:
                    results: list[Any] = []
                    if len(pending) == 1 or db is None:
                        for it in pending:
                            results.append(
                                self.spec.commit(self.ctx, state.data, it))
                    else:
                        with db.transaction():
                            for it in pending:
                                results.append(
                                    self.spec.commit(self.ctx, state.data,
                                                     it))
                    return results
                except BaseException:
                    state.data.clear()
                    state.data.update(snapshot)
                    raise

            with telemetry.span(self.trace, "pipeline.commit",
                                pages=len(pending)) as sp:
                try:
                    results = retry_call(
                        attempt, policy=COMMIT_RETRY, classify=is_transient,
                        cancel_check=lambda: self.ctx.check_commands(
                            self.dyn_job),
                        label=f"{self.dyn_job.job.NAME}-commit")
                except (OSError, sqlite3.OperationalError) as e:
                    if not is_disk_full(e):
                        raise
                    # full disk mid-commit (OSError ENOSPC from artifact
                    # IO, or SQLite's own SQLITE_FULL "database or disk is
                    # full"): retrying cannot free space and failing would
                    # throw away the whole run — checkpoint-pause at the
                    # last durable group instead (the group rolled back and
                    # `data` was snapshot-restored above), resumable once
                    # the operator frees space
                    note_disk_full("commit")
                    self.errors.append(
                        f"commit hit a full disk (ENOSPC); checkpoint-"
                        f"paused at batch {self._batches}: {e!r}")
                    logger.error(
                        "pipeline %s: disk full during commit; pausing at "
                        "committed batch %d", self.dyn_job.job.NAME,
                        self._batches)
                    raise JobPaused(self.dyn_job.serialize_state(),
                                    errors=self.errors) from e
            with self._stats_lock:
                self._commit_s += sp.duration_s
                self._txns += 1
            _BUSY.inc(sp.duration_s, stage="commit")
            _COMMIT_TXNS.inc()
            _COMMIT_PAGES.inc(len(pending))
            pending.clear()
            for result in results:
                with self._stats_lock:
                    self._batches += 1
                if result.more_steps:
                    raise JobError(
                        f"{self.dyn_job.job.NAME}: pipelined jobs cannot "
                        f"append steps mid-run")
                if result.metadata:
                    merge_metadata(state.run_metadata, result.metadata)
                self.errors.extend(result.errors)
                state.step_number += 1
                if state.step_number > len(state.steps):
                    # adaptive paging produced more pages than init's
                    # fixed-size estimate: mirror the estimate (content
                    # cloned from the last step) so progress totals and
                    # resume budgets stay coherent
                    state.steps.append(dict(state.steps[-1]))
                self.ctx.progress(completed_task_count=state.step_number)
            # durable crash checkpoint (ISSUE 9): persist the serialized
            # state now that this group is committed, so a SIGKILL resumes
            # at this boundary instead of step 0. Best-effort and OUTSIDE
            # the group transaction: a kill between the commit and this
            # upsert resumes one group early, and re-running a committed
            # group is idempotent (its rows are no longer orphans).
            self._persist_checkpoint()
            # serve-pool invalidation (ISSUE 11): the group is durable —
            # bump the library's read watermark so a pool worker can
            # never serve a directory page cached before this commit.
            # Emitted AFTER COMMIT by construction (we are past the retry
            # block), per-txn not per-page, and a node-less library
            # (unit-test contexts) makes it a no-op.
            library = getattr(self.ctx, "library", None)
            if library is not None and hasattr(library, "emit"):
                library.emit("db.commit", {"source": "pipeline",
                                           "txns": self._txns})

        try:
            while True:
                # between-commits command poll: JobPaused serializes the
                # state as of the last committed group, nothing speculative
                self.ctx.check_commands(self.dyn_job)
                try:
                    t0 = time.perf_counter()
                    item = self._results.get(timeout=_POLL_S)
                except queue.Empty:
                    _IDLE.inc(time.perf_counter() - t0, stage="commit")
                    # upstream is slow: a partial group that lingered past
                    # its window commits now rather than holding completed
                    # pages hostage to queue cadence — page/hash-bound
                    # pipelines degrade toward smaller groups, never stall
                    if pending and (time.perf_counter() - pending_since
                                    > GROUP_LINGER_S):
                        _flush()
                    continue
                if item is _DONE:
                    _flush()
                    break
                if isinstance(item, _StageFailure):
                    # completed pages first: the drain lands on an ordered
                    # committed-group boundary before supervision acts
                    _flush()
                    # stage supervision: a prefetch/dispatch thread that
                    # crashed on a TRANSIENT class (flaky IO, device wedge,
                    # injected chaos) drains to an ordered checkpoint-pause
                    # — the serialized state reflects only committed
                    # batches, so resume re-runs the lost work exactly.
                    # Deterministic failures stay fatal (a poisoned-input
                    # pause would resume into the same crash forever).
                    exc = item.exc
                    if is_transient(exc) or is_device_wedge(exc):
                        self.errors.append(
                            f"pipeline stage failed transiently; checkpoint-"
                            f"paused at batch {self._batches}: {exc!r}")
                        logger.warning(
                            "pipeline %s: transient stage failure, pausing "
                            "at committed batch %d: %r",
                            self.dyn_job.job.NAME, self._batches, exc)
                        raise JobPaused(self.dyn_job.serialize_state(),
                                        errors=self.errors)
                    raise exc
                if not pending:
                    pending_since = time.perf_counter()
                pending.append(item)
                if len(pending) >= group_n:
                    _flush()
        finally:
            wall_sp.__exit__(None, None, None)
            self._stop.set()
            # unblock producers stuck on a full queue, then join
            for q in (self._pages, self._results, self._tickets,
                      self._shard_q):
                while True:
                    try:
                        q.get_nowait()
                    except queue.Empty:
                        break
            drain_s = drain_timeout()
            for t in threads:
                t.join(timeout=drain_s)
                if not t.is_alive():
                    continue
                # a stage stuck in a hung device/IO call (the hung-device
                # failure mode): escalate to one bounded hard-join, then
                # give the thread up — it is a daemon, its result is
                # discarded, and the leak becomes a REPORT soft error (not
                # just a log line) so a stuck gather cannot silently strand
                # a paused job; a resumed run shares the device with it
                # until it dies, which the operator must be able to see
                logger.warning(
                    "pipeline %s: %s still running after %.1fs drain "
                    "timeout (stuck stage call?); hard-joining once more",
                    self.dyn_job.job.NAME, t.name, drain_s)
                t.join(timeout=drain_s)
                if t.is_alive():
                    msg = (f"pipeline stage thread {t.name} leaked: still "
                           f"running {2 * drain_s:.1f}s after drain "
                           f"(stuck in a hung gather/device call); its "
                           f"result is discarded")
                    logger.error("pipeline %s: %s", self.dyn_job.job.NAME,
                                 msg)
                    self.errors.append(msg)

        # pages ran dry before the estimated step count (rows shrank since
        # init, exactly like sequential steps whose SELECT comes back empty):
        # fast-forward to the sequential loop's terminal step_number
        if state.step_number < len(state.steps):
            state.step_number = len(state.steps)
            self.ctx.progress(completed_task_count=state.step_number)
        # the report's stage timings are READ FROM SPAN DATA: the _page_s/
        # _hash_s/_commit_s accumulators sum exactly the pipeline.* span
        # durations above (and still work with telemetry off, where spans
        # degrade to bare timers), so jobTrace and the scan report reconcile
        # by construction
        merge_metadata(state.run_metadata, {
            "pipeline_page_s": self._page_s,
            "pipeline_hash_s": self._hash_s,
            "pipeline_commit_s": self._commit_s,
            "pipeline_wall_s": wall_sp.duration_s,
            "pipeline_batches": self._batches,
            # a string on purpose: merge_metadata SUMS numerics across
            # pause/resume cycles, and shard counts must overwrite
            "pipeline_shards": str(self._shards),
            "commit_txns": self._txns,
        })
        logger.debug(
            "pipeline %s: %d batches in %d txns, page %.3fs | hash %.3fs | "
            "commit %.3fs | wall %.3fs", self.dyn_job.job.NAME, self._batches,
            self._txns, self._page_s, self._hash_s, self._commit_s,
            wall_sp.duration_s)
