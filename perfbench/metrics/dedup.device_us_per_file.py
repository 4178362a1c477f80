"""Seconds in the dedup detector's device passes as the host sees them, H2D
and readback included: MinHash signatures and the all-pairs sweep
(dedup.minhash + dedup.sweep spans), over the window's dedup_detector
jobs, per file of the window's locations."""
from harness import program_spans


def read(ctx):
    return program_spans.us_per_file(
        ctx, "dedup_detector", ("dedup.minhash", "dedup.sweep"), "files")
