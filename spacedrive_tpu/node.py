"""Node: the core runtime every shell embeds.

Equivalent of the reference's ``Node::new`` (core/src/lib.rs:77-135): construct
config, event bus, managers; then start them in dependency order — locations
actor → libraries init → job cold-resume → p2p (the reference warns the
ordering is deadlock-critical, lib.rs:126; here the same order keeps watchers
and resumed jobs from racing library load).

TPU-native addition: the node probes its accelerator inventory at boot, in
process, and records it in config (advertised to peers for remote-hasher
routing).
"""

from __future__ import annotations

import logging
from pathlib import Path
from typing import Any

from .config import ConfigManager, NodeConfig
from .events import EventBus
from .jobs import Jobs
from .library import Libraries

logger = logging.getLogger(__name__)


def _probe_accelerator() -> dict[str, Any]:
    """Device inventory, probed in this process: the node is the process
    that uses the chip, so it asks JAX directly (a child would find the
    chip held by its parent). A host without an accelerator records
    ``devices: 0`` with the platform JAX fell back to."""
    from .utils.jax_guard import device_info

    info = device_info()
    accel = info["platform"] != "cpu"
    return {"platform": info["platform"], "kind": info["kind"],
            "devices": info["count"] if accel else 0,
            "mesh": [info["count"]] if accel else []}


class Node:
    def __init__(self, data_dir: str | Path,
                 probe_accelerator: bool | None = None,
                 watch_locations: bool | None = None) -> None:
        import os

        self.data_dir = Path(data_dir)
        self.data_dir.mkdir(parents=True, exist_ok=True)
        # rotating file log + stdout (Node::init_logger, lib.rs:137-194)
        from .utils.tracing import init_logger

        init_logger(self.data_dir)
        self.config = ConfigManager(NodeConfig.load(self.data_dir))
        # location-watcher feature gate (the reference's `location-watcher`
        # cargo feature, location/manager/mod.rs:23-32)
        if watch_locations is None:
            watch_locations = not os.environ.get("SD_NO_WATCHER")
        self.watch_locations = watch_locations
        if probe_accelerator is None:
            # env applies only when the caller didn't decide (like the
            # watcher gate)
            probe_accelerator = not os.environ.get("SD_NO_ACCEL_PROBE")
        self.events = EventBus()
        self.jobs = Jobs()
        self.libraries = Libraries(self.data_dir, node=self)
        self.locations = None  # attached by locations layer
        self.p2p = None  # attached by p2p layer
        # node-wide admission budget for the CRDT/p2p receive path: every
        # ingest source (p2p sync responder, remote hash serving, the
        # fleet harness) admits through this so overload sheds with an
        # explicit BUSY instead of buffering unboundedly
        from .sync.admission import IngestBudget

        self.ingest_budget = IngestBudget()
        # admission at rspc dispatch (ISSUE 20): the same fair-share
        # budget shape applied to the serving tier — the router sheds
        # over-budget dispatches with a 429 + retry-after instead of
        # queueing them unboundedly. SD_RSPC_ADMISSION=0 turns the gate
        # off (every dispatch admitted, e.g. for A/B benches).
        if os.environ.get("SD_RSPC_ADMISSION", "1") not in ("0", "off"):
            from .sync.admission import DispatchBudget

            self.dispatch_budget = DispatchBudget()
        else:
            self.dispatch_budget = None
        try:
            from .crypto.keymanager import KeyManager

            self.key_manager = KeyManager(self.data_dir / "keystore.json")
        except ImportError as e:
            # dependency-gated (no ``cryptography`` in the image): the node
            # runs scans/sync/media without a key manager; crypto jobs and
            # key routes fail at use instead of wedging boot
            logger.warning("crypto stack unavailable (%s); key manager "
                           "disabled", e)
            self.key_manager = None
        if self.key_manager is not None:
            try:
                # keyring-backed auto-unlock (crates/crypto keys/keyring
                # role): no-op unless the user enabled it on this keystore
                if self.key_manager.try_auto_unlock():
                    logger.info("key manager auto-unlocked from the OS keyring")
            except Exception:
                logger.exception("keyring auto-unlock failed; password unlock "
                                 "still available")
        from .objects.gc import ThumbnailRemoverActor

        self.thumbnail_remover = ThumbnailRemoverActor(self)

        # multi-process reader pool (ISSUE 11): attached by the server
        # shell (or tests) via server/pool.ReaderPool — None means every
        # query resolves in-process (the degraded mode). The router reads
        # this attribute on each pool-marked query dispatch.
        self.reader_pool = None
        # distributed replica rung (ISSUE 19): when armed, pool-marked
        # queries may be served by watermark-eligible mesh peers before
        # the local pool — the top of the degradation ladder. Wired after
        # p2p boots; the fleet harness installs wire-less routers here.
        self.replica_router = None

        if probe_accelerator:
            # compiled device programs persist across boots (the cache is
            # placed before the first compile)
            from .utils.jax_guard import configure_compile_cache

            configure_compile_cache()
            self.config.write(accelerator=_probe_accelerator())

        # ordering-critical start sequence (lib.rs:126-130)
        from .jobs import register_builtin_jobs

        register_builtin_jobs()  # JOB_REGISTRY must be full before cold_resume
        self._start_locations()
        self.libraries.init()
        for library in self.libraries.list():
            revived = self.jobs.cold_resume(library)
            if revived:
                logger.info("cold-resumed %d jobs for library %s", revived, library.id[:8])
        self._start_p2p()
        if self.p2p is not None:
            from .server.replica import ReplicaRouter

            self.replica_router = ReplicaRouter.maybe_start(self)

        # dev fixtures (util/debug_initializer.rs:32-56): applied once the
        # managers are live so declared libraries/locations/scans behave
        # exactly like API-driven ones
        from .utils import debug_initializer

        debug_initializer.apply(self)

        # mesh observability (ISSUE 7): bridge the telemetry flight
        # recorder onto this node's event bus (telemetry.watch / SSE tail
        # it from there) and start the SLO/alert evaluator against the
        # process registry. The hook is removed at shutdown — the registry
        # is process-global, and test suites boot many Nodes.
        from . import telemetry
        from .notifications import emit_node_notification
        from .telemetry.alerts import AlertEvaluator

        def _telemetry_event_hook(record: dict,
                                  _emit=self.events.emit_kind) -> None:
            _emit("telemetry.event", record)

        self._telemetry_event_hook = _telemetry_event_hook
        telemetry.add_event_hook(_telemetry_event_hook)

        def _alert_notify(rule, firing: bool, value) -> None:
            if not firing:
                return  # the resolved edge stays in the event ring
            emit_node_notification(self, {
                "type": "alert", "rule": rule.name, "series": rule.series,
                "severity": rule.severity, "value": value,
                "description": rule.description})

        self.alerts = AlertEvaluator(
            interval_s=float(os.environ.get("SD_ALERT_INTERVAL_S", "5")),
            notify=_alert_notify)
        self.alerts.start()

        # SLO engine (ISSUE 20): error-budget + multi-window burn rates
        # over the request/tenant families, narrated as `slo.burn` events
        # next to the alert evaluator's edges. Same ticker discipline.
        from .telemetry.slo import SloEngine

        self.slo = SloEngine(
            interval_s=float(os.environ.get("SD_SLO_INTERVAL_S", "5")))
        self.slo.start()

        # serving-tier observability (ISSUE 10): the process resource
        # watcher always runs (cheap slow ticker — sd_proc_* gauges plus
        # the request-p99 gauges the alert rules read); the span-tagged
        # sampling profiler only when SD_PROFILE_HZ is set (zero overhead
        # when off), exporting its folded stacks at shutdown
        from .telemetry.profiler import ResourceWatcher, SamplingProfiler

        self.resources = ResourceWatcher().start()
        self.profiler = SamplingProfiler().start()

        # device-resident query engine (ISSUE 15): columnar search index
        # scored by batched JAX/Pallas kernels, refreshed at the commit
        # watermark off this node's event bus. Gated: SD_SEARCH_ENGINE=
        # device arms it; default (sqlite) keeps every query on SQL.
        from .search.engine import SearchEngine

        self.search_engine = SearchEngine.maybe_start(self)

        # api::mount last — validates the invalidation-key contract
        # (api/mod.rs:102, invalidate.rs:82)
        from .api.router import mount as api_mount

        self.router = api_mount(self)

    def _start_locations(self) -> None:
        from .locations.manager import LocationsActor

        self.locations = LocationsActor(self)

    def _start_p2p(self) -> None:
        """Start the p2p control plane last in the boot sequence
        (lib.rs:126-130). ``p2p_enabled: false`` in node config (or
        SD_P2P_DISABLED=1) keeps a node offline."""
        import os

        cfg = self.config.get()
        if not cfg.get("p2p_enabled", True) or os.environ.get("SD_P2P_DISABLED"):
            return
        try:
            from .p2p.manager import P2PManager

            self.p2p = P2PManager(self)
            self.p2p.start()
        except Exception:
            logger.exception("p2p failed to start; node stays offline")
            self.p2p = None

    # -- events (lib.rs:203-229) -------------------------------------------
    def emit(self, kind: str, payload: Any = None, library_id: str | None = None) -> None:
        self.events.emit_kind(kind, payload, library_id)

    def shutdown(self) -> None:
        """Graceful: checkpoint all jobs, stop watchers, close DBs
        (Node::shutdown, lib.rs:196)."""
        pool = getattr(self, "reader_pool", None)
        if pool is not None:
            # defensive: the owning shell normally stops it first
            pool.stop()
            self.reader_pool = None
        if getattr(self, "search_engine", None) is not None:
            self.search_engine.stop()
            self.search_engine = None
        self.jobs.shutdown()
        from . import telemetry

        self.alerts.stop()
        self.slo.stop()
        self.resources.stop()
        if self.profiler is not None:
            self.profiler.stop()
            self.profiler.export(self.data_dir)
        telemetry.remove_event_hook(self._telemetry_event_hook)
        if self.locations is not None:
            self.locations.stop()
        if self.p2p is not None:
            self.p2p.stop()
        self.thumbnail_remover.stop()
        self.libraries.close()
