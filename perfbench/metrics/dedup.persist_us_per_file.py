"""Seconds in the dedup detector's near_duplicate write transaction
(dedup.persist spans), over the window's dedup_detector jobs, per file of
the window's locations."""
from harness import program_spans


def read(ctx):
    return program_spans.us_per_file(
        ctx, "dedup_detector", ("dedup.persist",), "files")
