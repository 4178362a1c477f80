"""Library database schema.

Mirrors the reference's 26-model Prisma schema (core/prisma/schema.prisma) —
op-log tables :21-54, Instance :73, Location :130, FilePath :154, Object :204,
MediaData :296, Tag/Label/Space/Album + link tables :323-464, Job :407,
IndexerRule :482, Preference :509, Notification :516 — with sync annotations
(the ``/// @shared(id:..)`` / ``@local`` / ``@relation(item,group)``
doc-comments that sd-sync-generator consumes) carried as ``SYNC`` class
attributes so the CRDT layer needs no codegen.

Deviations from the reference, deliberate:
  - ``pub_id`` is stored as a TEXT uuid (the reference stores raw uuid Bytes;
    TEXT keys are debuggable and SQLite-index-friendly, and the sync protocol
    is ours to define).
  - ``inode``/``device`` are INTEGERs (SQLite INTEGER is i64; the reference
    works around prisma's lack of u64 with Bytes, schema.prisma:180-181).
  - ``size_in_bytes`` keeps only the non-deprecated bytes form, as INTEGER.
"""

from __future__ import annotations

from .base import Field, Model, Relation, Shared

_I = "INTEGER"
_T = "TEXT"
_B = "BOOLEAN"
_D = "DATETIME"
_BY = "BYTES"
_J = "JSON"


def _pk() -> Field:
    return Field(_I, primary_key=True, autoincrement=True)


def _pub_id() -> Field:
    return Field(_T, nullable=False, unique=True)


# ---- sync op log (schema.prisma:21-54) -----------------------------------


class SharedOperationRow(Model):
    TABLE = "shared_operation"
    FIELDS = {
        "id": Field(_T, primary_key=True),  # op uuid
        "timestamp": Field(_I, nullable=False),  # NTP64 HLC
        "model": Field(_T, nullable=False),
        "record_id": Field(_T, nullable=False),
        "kind": Field(_T, nullable=False),  # c | u:<field> | d
        "data": Field(_J),
        "instance_id": Field(_I, nullable=False, references="instance.id", on_delete="RESTRICT"),
    }
    #: (timestamp, id) serves get_ops' ORDER BY + LIMIT without a sort
    INDEXES = (("instance_id", "timestamp"), ("model", "record_id"),
               ("timestamp", "id"))


class RelationOperationRow(Model):
    TABLE = "relation_operation"
    FIELDS = {
        "id": Field(_T, primary_key=True),
        "timestamp": Field(_I, nullable=False),
        "relation": Field(_T, nullable=False),
        "item_id": Field(_T, nullable=False),
        "group_id": Field(_T, nullable=False),
        "kind": Field(_T, nullable=False),
        "data": Field(_J),
        "instance_id": Field(_I, nullable=False, references="instance.id", on_delete="RESTRICT"),
    }
    INDEXES = (("instance_id", "timestamp"),
               ("relation", "item_id", "group_id"), ("timestamp", "id"))


# ---- identity / stats (schema.prisma:57-127) -----------------------------


class NodeRow(Model):
    """Deprecated in the reference (schema.prisma:56-68) but kept for parity."""

    TABLE = "node"
    FIELDS = {
        "id": _pk(),
        "pub_id": _pub_id(),
        "name": Field(_T, nullable=False),
        "platform": Field(_I, nullable=False),
        "date_created": Field(_D, nullable=False),
        "identity": Field(_BY),
    }


class Instance(Model):
    """A paired `.db` instance of this library (schema.prisma:70-97).
    ``timestamp`` persists the per-instance HLC clock (sync ingest.rs:136-159)."""

    TABLE = "instance"
    SYNC = None  # @local(id: pub_id)
    FIELDS = {
        "id": _pk(),
        "pub_id": _pub_id(),
        "identity": Field(_T, nullable=False),  # IdentityOrRemoteIdentity encoding
        # owning NODE's RemoteIdentity (proven by the p2p handshake) — the
        # authorization anchor for sync sessions + files-over-p2p
        "node_remote_identity": Field(_T),
        "node_id": Field(_T, nullable=False),
        "node_name": Field(_T, nullable=False),
        "node_platform": Field(_I, nullable=False),
        "last_seen": Field(_D, nullable=False),
        "date_created": Field(_D, nullable=False),
        "timestamp": Field(_I),
    }


class Statistics(Model):
    TABLE = "statistics"
    FIELDS = {
        "id": _pk(),
        "date_captured": Field(_D, nullable=False),
        "total_object_count": Field(_I, default=0),
        "library_db_size": Field(_T, default="0"),
        "total_bytes_used": Field(_T, default="0"),
        "total_bytes_capacity": Field(_T, default="0"),
        "total_unique_bytes": Field(_T, default="0"),
        "total_bytes_free": Field(_T, default="0"),
        "preview_media_bytes": Field(_T, default="0"),
    }


class Volume(Model):
    TABLE = "volume"
    SYNC = None  # @local
    FIELDS = {
        "id": _pk(),
        "name": Field(_T, nullable=False),
        "mount_point": Field(_T, nullable=False),
        "total_bytes_capacity": Field(_T, default="0"),
        "total_bytes_available": Field(_T, default="0"),
        "disk_type": Field(_T),
        "filesystem": Field(_T),
        "is_system": Field(_B, default=0),
        "date_modified": Field(_D),
    }
    UNIQUES = (("mount_point", "name"),)


# ---- core domain (schema.prisma:129-318) ---------------------------------


class Location(Model):
    TABLE = "location"
    SYNC = Shared(id="pub_id")
    FIELDS = {
        "id": _pk(),
        "pub_id": _pub_id(),
        "name": Field(_T),
        "path": Field(_T),
        "total_capacity": Field(_I),
        "available_capacity": Field(_I),
        "is_archived": Field(_B),
        "generate_preview_media": Field(_B),
        "sync_preview_media": Field(_B),
        "hidden": Field(_B),
        "date_created": Field(_D),
        # declared FK so sync emission rewrites it as an instance-pub_id ref
        # (a raw local int would mis-attribute ownership on mirrored nodes)
        "instance_id": Field(_I, references="instance.id",
                             on_delete="SET NULL"),
        # TPU-native: which hasher backend identifies files in this location
        # ("cpu" | "tpu"), the `hasher = "tpu"` flag of BASELINE.json
        "hasher": Field(_T, default="tpu"),
    }


class FilePath(Model):
    TABLE = "file_path"
    SYNC = Shared(id="pub_id")
    FIELDS = {
        "id": _pk(),
        "pub_id": _pub_id(),
        "is_dir": Field(_B),
        "cas_id": Field(_T),
        "integrity_checksum": Field(_T),
        "location_id": Field(_I, references="location.id",
                             on_delete="CASCADE"),
        "materialized_path": Field(_T),
        "name": Field(_T),
        "extension": Field(_T),
        "hidden": Field(_B),
        "size_in_bytes": Field(_I),
        "inode": Field(_I),
        "device": Field(_I),
        "object_id": Field(_I, references="object.id", on_delete="SET NULL"),
        "key_id": Field(_I),  # no key table yet (keymanager keeps its own store)
        "date_created": Field(_D),
        "date_modified": Field(_D),
        "date_indexed": Field(_D),
    }
    UNIQUES = (
        ("location_id", "materialized_path", "name", "extension"),
        ("location_id", "inode", "device"),
    )
    # serving-tier read-path indexes (ISSUE 11 satellite): the explorer's
    # directory listing filters on materialized_path WITHOUT a location
    # (plain prefix index), the watcher/identifier/rename sweeps run
    # ``location_id = ? AND materialized_path LIKE 'prefix%'`` (the NOCASE
    # collation is what lets SQLite's LIKE optimization turn the default
    # case-insensitive LIKE into an index range scan), and the pathsCount
    # badge COUNTs over (location_id, hidden) — covering, index-only
    INDEXES = (("location_id",), ("location_id", "materialized_path"),
               ("cas_id",), ("object_id",),
               ("materialized_path", "is_dir", "name"),
               ("location_id", "materialized_path COLLATE NOCASE"),
               ("location_id", "hidden"))


class Object(Model):
    TABLE = "object"
    SYNC = Shared(id="pub_id")
    FIELDS = {
        "id": _pk(),
        "pub_id": _pub_id(),
        "kind": Field(_I),
        "key_id": Field(_I),
        "hidden": Field(_B),
        "favorite": Field(_B),
        "important": Field(_B),
        "note": Field(_T),
        "date_created": Field(_D),
        "date_accessed": Field(_D),
    }


class MediaData(Model):
    TABLE = "media_data"
    FIELDS = {
        "id": _pk(),
        "dimensions": Field(_J),
        "media_date": Field(_T),
        "media_location": Field(_J),
        "camera_data": Field(_J),
        "artist": Field(_T),
        "description": Field(_T),
        "copyright": Field(_T),
        "exif_version": Field(_T),
        # audio/video stream metadata (ffprobe extractor; the reference's
        # audio_data/video_data are stubs — schema.prisma:296 MediaData)
        "duration_seconds": Field("REAL"),
        "bit_rate": Field(_I),
        "streams": Field(_J),
        "object_id": Field(_I, nullable=False, unique=True, references="object.id", on_delete="CASCADE"),
    }


# ---- tags / labels / spaces / albums (schema.prisma:320-464) --------------


class Tag(Model):
    TABLE = "tag"
    SYNC = Shared(id="pub_id")
    FIELDS = {
        "id": _pk(),
        "pub_id": _pub_id(),
        "name": Field(_T),
        "color": Field(_T),
        "redundancy_goal": Field(_I),
        "date_created": Field(_D),
        "date_modified": Field(_D),
    }


class TagOnObject(Model):
    TABLE = "tag_on_object"
    SYNC = Relation(item="tag", group="object")
    FIELDS = {
        "tag_id": Field(_I, nullable=False, references="tag.id", on_delete="RESTRICT"),
        "object_id": Field(_I, nullable=False, references="object.id", on_delete="RESTRICT"),
    }
    UNIQUES = (("tag_id", "object_id"),)


class Label(Model):
    TABLE = "label"
    FIELDS = {
        "id": _pk(),
        "pub_id": _pub_id(),
        "name": Field(_T),
        "date_created": Field(_D),
        "date_modified": Field(_D),
    }


class LabelOnObject(Model):
    TABLE = "label_on_object"
    FIELDS = {
        "date_created": Field(_D),
        "label_id": Field(_I, nullable=False, references="label.id", on_delete="RESTRICT"),
        "object_id": Field(_I, nullable=False, references="object.id", on_delete="RESTRICT"),
    }
    UNIQUES = (("label_id", "object_id"),)


class Space(Model):
    TABLE = "space"
    FIELDS = {
        "id": _pk(),
        "pub_id": _pub_id(),
        "name": Field(_T),
        "description": Field(_T),
        "date_created": Field(_D),
        "date_modified": Field(_D),
    }


class ObjectInSpace(Model):
    TABLE = "object_in_space"
    FIELDS = {
        "space_id": Field(_I, nullable=False, references="space.id", on_delete="RESTRICT"),
        "object_id": Field(_I, nullable=False, references="object.id", on_delete="RESTRICT"),
    }
    UNIQUES = (("space_id", "object_id"),)


class Album(Model):
    TABLE = "album"
    FIELDS = {
        "id": _pk(),
        "pub_id": _pub_id(),
        "name": Field(_T),
        "is_hidden": Field(_B),
        "date_created": Field(_D),
        "date_modified": Field(_D),
    }


class ObjectInAlbum(Model):
    TABLE = "object_in_album"
    FIELDS = {
        "date_created": Field(_D),
        "album_id": Field(_I, nullable=False, references="album.id", on_delete="RESTRICT"),
        "object_id": Field(_I, nullable=False, references="object.id", on_delete="RESTRICT"),
    }
    UNIQUES = (("album_id", "object_id"),)


# ---- jobs (schema.prisma:407-436) ----------------------------------------


class JobRow(Model):
    """Persisted job reports; ``data`` holds the serialized checkpoint state for
    pause/resume (job/report.rs:41-62), ``parent_id`` chains job pipelines."""

    TABLE = "job"
    FIELDS = {
        "id": Field(_T, primary_key=True),  # job uuid
        "name": Field(_T),
        "action": Field(_T),
        "status": Field(_I),
        "errors_text": Field(_T),
        "data": Field(_BY),
        "metadata": Field(_J),
        "parent_id": Field(_T),
        "task_count": Field(_I),
        "completed_task_count": Field(_I),
        "date_estimated_completion": Field(_D),
        "date_created": Field(_D),
        "date_started": Field(_D),
        "date_completed": Field(_D),
    }
    INDEXES = (("status",), ("parent_id",))


# ---- indexer rules (schema.prisma:482-506) -------------------------------


class IndexerRule(Model):
    TABLE = "indexer_rule"
    FIELDS = {
        "id": _pk(),
        "pub_id": _pub_id(),
        "name": Field(_T),
        "default": Field(_B),
        "rules_per_kind": Field(_J),
        "date_created": Field(_D),
        "date_modified": Field(_D),
    }


class IndexerRulesInLocation(Model):
    TABLE = "indexer_rule_in_location"
    FIELDS = {
        "location_id": Field(_I, nullable=False, references="location.id", on_delete="RESTRICT"),
        "indexer_rule_id": Field(_I, nullable=False, references="indexer_rule.id", on_delete="RESTRICT"),
    }
    UNIQUES = (("location_id", "indexer_rule_id"),)


# ---- prefs / notifications (schema.prisma:508-524) -----------------------


class Preference(Model):
    TABLE = "preference"
    SYNC = Shared(id="key")
    SYNC_SKIP = ()
    FIELDS = {
        "key": Field(_T, primary_key=True),
        "value": Field(_J),
    }


class Notification(Model):
    TABLE = "notification"
    FIELDS = {
        "id": _pk(),
        "read": Field(_B, default=0),
        "data": Field(_J, nullable=False),
        "expires_at": Field(_D),
    }


class NearDuplicate(Model):
    """Near-duplicate pair found by the MinHash detector (this framework's
    extension — the reference only collapses exact cas_id matches). Derived,
    local-only data (like thumbnails): not synced, rebuilt by rescans, rows
    cascade away with their file_paths."""

    TABLE = "near_duplicate"
    FIELDS = {
        "id": _pk(),
        "file_path_a_id": Field(_I, nullable=False,
                                references="file_path.id", on_delete="CASCADE"),
        "file_path_b_id": Field(_I, nullable=False,
                                references="file_path.id", on_delete="CASCADE"),
        "similarity": Field("REAL", nullable=False),
        "date_detected": Field(_D),
    }
    UNIQUES = (("file_path_a_id", "file_path_b_id"),)


class ChunkManifest(Model):
    """One content-defined chunk of an object (ops/cdc.py gear chunker;
    this framework's extension — the reference has no sub-file identity).
    Row-per-chunk so the chunk-hash inverted map is one indexed GROUP BY.
    Derived, local-only data like NearDuplicate: not synced, rebuilt by
    rescans (the manifest stage overwrites per object), rows cascade away
    with their objects — but RowJournal-noted so the device query engine
    sees manifest churn."""

    TABLE = "chunk_manifest"
    FIELDS = {
        "id": _pk(),
        "object_id": Field(_I, nullable=False,
                           references="object.id", on_delete="CASCADE"),
        "seq": Field(_I, nullable=False),
        "chunk_hash": Field(_T, nullable=False),
        "length": Field(_I, nullable=False),
    }
    UNIQUES = (("object_id", "seq"),)
    INDEXES = (("chunk_hash",),)


class ObjectMinhash(Model):
    """MinHash signature (ops/minhash.py) of a sampled object's cas
    message: K little-endian uint32. The identify page computes it on the
    device from the rows it hashed, and the identify commit writes it; the
    dedup detector reads it instead of gathering the file again. Keyed by
    object: equal cas_ids are equal cas messages, so equal signatures.
    Derived, local-only data like NearDuplicate: not synced, written as
    objects are identified, rows cascade away with their objects. An object
    with no row (hashed on the CPU, synced in, or identified before the
    table existed) takes the dedup detector's own gather."""

    TABLE = "object_minhash"
    FIELDS = {
        "object_id": Field(_I, primary_key=True,
                           references="object.id", on_delete="CASCADE"),
        "signature": Field("BLOB", nullable=False),
    }


ALL_MODELS: tuple[type[Model], ...] = (
    Instance,  # referenced by op-log tables, create first
    SharedOperationRow,
    RelationOperationRow,
    NodeRow,
    Statistics,
    Volume,
    Location,
    FilePath,
    Object,
    MediaData,
    Tag,
    TagOnObject,
    Label,
    LabelOnObject,
    Space,
    ObjectInSpace,
    Album,
    ObjectInAlbum,
    JobRow,
    IndexerRule,
    IndexerRulesInLocation,
    Preference,
    Notification,
    NearDuplicate,
    ChunkManifest,
    ObjectMinhash,
)

SYNCED_MODELS: dict[str, type[Model]] = {
    m.TABLE: m for m in ALL_MODELS if m.SYNC is not None
}
