"""Seconds in the identify pipeline's sampled-message gathers
(identifier.gather spans; with the sharded prefetch, the slices' thread-
seconds summed), over the window's file_identifier jobs, per file
committed."""
from harness import program_spans


def read(ctx):
    return program_spans.us_per_file(
        ctx, "file_identifier", ("identifier.gather",), "files_committed")
