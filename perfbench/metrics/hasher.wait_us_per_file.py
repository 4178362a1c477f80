"""Seconds in the hasher's kernel call through the digests' readback
(hash.wait spans), over the window's file_identifier jobs, per file
committed."""
from harness import program_spans


def read(ctx):
    return program_spans.us_per_file(
        ctx, "file_identifier", ("hash.wait",), "files_committed")
