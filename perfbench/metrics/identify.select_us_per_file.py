"""Seconds in the identify pipeline's cursor, id and slice SELECTs
(identifier.select spans), over the window's file_identifier jobs, per
file committed."""
from harness import program_spans


def read(ctx):
    return program_spans.us_per_file(
        ctx, "file_identifier", ("identifier.select",), "files_committed")
