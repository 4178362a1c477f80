"""Node configuration.

Equivalent of the reference's ``NodeConfig`` (core/src/node/config.rs:21-41):
a versioned JSON file ``node_state.sdconfig`` in the data dir holding the node
identity (uuid), display name, p2p keypair seed, platform, and the runtime
feature flags that survive restarts (api/mod.rs:131-167 restores them at boot).

TPU-native addition: the node records its accelerator topology (device kind,
chip count, mesh shape) so remote peers can route hashing work to nodes with
spare TPU capacity (the "shared TPU hasher service" of BASELINE.json config 5).
"""

from __future__ import annotations

import os
import platform as _platform
import secrets
import threading
import uuid
from pathlib import Path
from typing import Any

from .utils.migrator import VersionedConfig


class Platform:
    """Reference core/src/node/platform.rs enum."""

    UNKNOWN = 0
    WINDOWS = 1
    MACOS = 2
    LINUX = 3
    IOS = 4
    ANDROID = 5

    @staticmethod
    def current() -> int:
        return {"Linux": Platform.LINUX, "Darwin": Platform.MACOS, "Windows": Platform.WINDOWS}.get(
            _platform.system(), Platform.UNKNOWN
        )


class BackendFeature:
    """Runtime-toggleable feature flags (reference api/mod.rs:28-48)."""

    SYNC_EMIT_MESSAGES = "syncEmitMessages"
    FILES_OVER_P2P = "filesOverP2P"
    #: route image-thumbnail resizing through the batched device kernel
    #: (ops/resize_jax.py) instead of scalar PIL — this framework's flag
    TPU_THUMBNAILS = "tpuThumbnails"
    ALL = (SYNC_EMIT_MESSAGES, FILES_OVER_P2P, TPU_THUMBNAILS)


class NodeConfig(VersionedConfig):
    VERSION = 1
    FILENAME = "node_state.sdconfig"

    @classmethod
    def defaults(cls) -> dict[str, Any]:
        return {
            "id": str(uuid.uuid4()),
            "name": os.uname().nodename if hasattr(os, "uname") else "spacedrive-tpu",
            # ed25519 seed, hex; public identity derived in p2p layer
            "keypair_seed": secrets.token_hex(32),
            "platform": Platform.current(),
            "p2p_enabled": True,
            "p2p_port": None,              # TCP listen port (None = ephemeral)
            "p2p_discovery_port": None,    # UDP beacon port (None = no discovery)
            "p2p_static_peers": [],        # ["host:port", ...] for filtered LANs
            "p2p_auto_accept_library": None,  # headless auto-pair target
            "features": [],
            # TPU-native: accelerator inventory advertised to peers
            "accelerator": {"platform": None, "kind": None, "devices": 0,
                            "mesh": []},
            "preferences": {},
        }

    @classmethod
    def load(cls, data_dir: str | Path) -> "NodeConfig":
        return cls.load_and_migrate(Path(data_dir) / cls.FILENAME)  # type: ignore[return-value]


class ConfigManager:
    """Thread-safe wrapper with write-through persistence, the analogue of the
    reference's ``config::Manager`` watch channel."""

    def __init__(self, config: NodeConfig) -> None:
        self._config = config
        self._lock = threading.Lock()

    def get(self) -> dict[str, Any]:
        with self._lock:
            return dict(self._config.data)

    def write(self, **updates: Any) -> dict[str, Any]:
        with self._lock:
            self._config.data.update(updates)
            self._config.save()
            return dict(self._config.data)

    def toggle_feature(self, feature: str) -> bool:
        """Returns the new enabled state (reference toggleFeatureFlag)."""
        if feature not in BackendFeature.ALL:
            raise ValueError(f"unknown feature flag: {feature}")
        with self._lock:
            features = set(self._config.data.get("features", []))
            enabled = feature not in features
            (features.add if enabled else features.discard)(feature)
            self._config.data["features"] = sorted(features)
            self._config.save()
            return enabled

    def has_feature(self, feature: str) -> bool:
        with self._lock:
            return feature in self._config.data.get("features", [])
