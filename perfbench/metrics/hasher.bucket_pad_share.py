"""Share of the row bytes the hasher's bucket calls sent to the device
that is padding (bucket tails and tier lanes): 1 - payload bytes over
lanes x cap x 1,024, over the window's file_identifier jobs."""
from harness import bucket_tallies


def read(ctx):
    t = bucket_tallies.totals(ctx)
    if t is None or not t["row_bytes"]:
        return None
    return 1.0 - t["payload_bytes"] / t["row_bytes"]
