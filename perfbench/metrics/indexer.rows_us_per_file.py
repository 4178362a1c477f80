"""Seconds in the indexer's row building after each walk (indexer.rows
spans), over the window's indexer jobs, per file of the window's
locations."""
from harness import program_spans


def read(ctx):
    return program_spans.us_per_file(
        ctx, "indexer", ("indexer.rows",), "files")
