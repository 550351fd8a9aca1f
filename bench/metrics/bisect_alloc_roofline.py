"""Kernels: ``bisect_alloc``'s share of its roofline, in percent.
The kernel is the trace's custom call of that name (HLO ``%bisect_alloc.N``)."""
from bench.roofline import share

PATTERN = r"^%bisect_alloc(\.\d+)?$"


def read(r):
    return share(r, "bisect_alloc", PATTERN)
