"""Seconds in the hasher's whole-file bucket calls (hash.bucket spans:
packing, H2D and the kernel through the digests' readback), over the
window's file_identifier jobs, per file committed."""
from harness import program_spans


def read(ctx):
    return program_spans.us_per_file(
        ctx, "file_identifier", ("hash.bucket",), "files_committed")
