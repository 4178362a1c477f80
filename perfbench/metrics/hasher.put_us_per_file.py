"""Seconds in the hasher's H2D enqueue of words and lengths (hash.put spans),
over the window's file_identifier jobs, per file committed."""
from harness import program_spans


def read(ctx):
    return program_spans.us_per_file(
        ctx, "file_identifier", ("hash.put",), "files_committed")
