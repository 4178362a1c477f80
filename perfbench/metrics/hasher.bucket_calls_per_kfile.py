"""Device calls of the hasher's bucket path over the window's
file_identifier jobs, per 1,000 files committed."""
from harness import bucket_tallies


def read(ctx):
    t = bucket_tallies.totals(ctx)
    n = ctx["files_committed"]
    return t["calls"] / n * 1e3 if t is not None and n else None
