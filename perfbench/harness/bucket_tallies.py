"""The hasher's chunk-capacity bucket tallies over the window.

Each ``file_identifier`` job's run metadata carries ``hash_buckets``,
``{cap: {calls, files, lanes, payload_bytes}}`` summed over that job's own
device bucket calls (the program's ``hash.bucket`` spans). A checkout
whose program records none reads nothing, and the metric is left out of
the result line.
"""

from __future__ import annotations

KEYS = ("calls", "files", "lanes", "payload_bytes")


def totals(ctx: dict) -> dict[str, int] | None:
    """``calls``, ``files``, ``lanes`` and ``payload_bytes`` summed over
    every bucket of the window's ``file_identifier`` jobs, with
    ``row_bytes``, the bytes of the padded rows (lanes x cap x 1,024), or
    None when no job recorded a bucket call."""
    out = dict.fromkeys(KEYS + ("row_bytes",), 0)
    for name, meta in ctx["job_metadata"].values():
        if name != "file_identifier":
            continue
        for cap, tally in (meta.get("hash_buckets") or {}).items():
            for key in KEYS:
                out[key] += tally[key]
            out["row_bytes"] += tally["lanes"] * int(cap) * 1024
    return out if out["calls"] else None
