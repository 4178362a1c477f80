"""Seconds in the hasher's host packing into the device layout (hash.pack
spans), over the window's file_identifier jobs, per file committed."""
from harness import program_spans


def read(ctx):
    return program_spans.us_per_file(
        ctx, "file_identifier", ("hash.pack",), "files_committed")
