"""Per-layer metrics from the program's own spans.

Each job report's metadata carries ``trace.spans``: ``{span: {count,
total_s}}`` summed over the job's run (the program's telemetry). A
metric here is the seconds of some spans, summed over the window's jobs
of one name, per file. A checkout whose program has no such span reads
nothing, and the metric is left out of the result line.
"""

from __future__ import annotations


def us_per_file(ctx: dict, job: str, spans: tuple[str, ...],
                files: str) -> float | None:
    """Seconds of ``spans`` over the window's ``job`` reports, in
    microseconds per ``ctx[files]`` (``files`` or ``files_committed``), or
    None when they read 0."""
    s = 0.0
    for name, meta in ctx["job_metadata"].values():
        if name == job:
            totals = (meta.get("trace") or {}).get("spans") or {}
            s += sum((totals.get(x) or {}).get("total_s", 0.0) for x in spans)
    n = ctx[files]
    return s / n * 1e6 if s > 0 and n else None
