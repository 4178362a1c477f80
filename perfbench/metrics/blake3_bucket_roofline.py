"""Share of the BLAKE3 roofline over one traced location of a tree whose
files are mostly hashed whole through the bucket path: blake3_roofline's
arithmetic (work/blake3.py counts 8 + size at or below 100 KiB), read in
the cells that list this metric."""
from harness import spec


def read(ctx):
    return spec.reader("blake3_roofline").read(ctx)
