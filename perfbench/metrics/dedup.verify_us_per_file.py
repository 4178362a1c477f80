"""Seconds in the dedup detector's host verification: best-match loop, union-
find, edge collapse (dedup.verify spans), over the window's dedup_detector
jobs, per file of the window's locations."""
from harness import program_spans


def read(ctx):
    return program_spans.us_per_file(
        ctx, "dedup_detector", ("dedup.verify",), "files")
