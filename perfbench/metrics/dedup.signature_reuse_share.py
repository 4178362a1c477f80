"""Share of the MinHash signatures the window's dedup_detector jobs
compared that the identify page had stored (the rest were gathered and
computed in the job): reused over reused + gathered, from each job's run
metadata ``signatures: {reused, gathered}``. A checkout whose program
records none reads nothing, and the metric is left out of the result
line."""


def read(ctx):
    reused = gathered = 0
    for name, meta in ctx["job_metadata"].values():
        if name == "dedup_detector":
            counts = meta.get("signatures") or {}
            reused += counts.get("reused", 0)
            gathered += counts.get("gathered", 0)
    total = reused + gathered
    return reused / total if total else None
