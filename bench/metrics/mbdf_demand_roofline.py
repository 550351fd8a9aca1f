"""Kernels: ``mbdf_demand``'s share of its roofline, in percent.
The kernel is the trace's custom call of that name (HLO ``%mbdf_demand.N``)."""
from bench.roofline import share

PATTERN = r"^%mbdf_demand(\.\d+)?$"


def read(r):
    return share(r, "mbdf_demand", PATTERN)
