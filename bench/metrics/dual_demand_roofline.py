"""Kernels: ``dual_demand``'s share of its roofline, in percent, over the
warm dual solves of the traced periods.  A period launches the kernel
``WARM_ITERS`` times at ``WARM_INNER_ITERS`` inner trips (the Newton
trips) and once at ``BISECT_ITERS`` (the final demand), each over the
launch's rows (``kernel_calls``, trips left out): every ``WARM_ITERS`` + 1
of the trace's ``%dual_demand.N`` events make one period."""
from bench import harness

PATTERN = r"^%dual_demand(\.\d+)?$"


def read(r):
    if r.trace is None or r.peaks is None or "dual_demand" not in \
            r.kernel_calls:
        return None
    events = r.trace.op_events(PATTERN)
    if not events:
        return None
    from repro.core.disba import BISECT_ITERS, WARM_INNER_ITERS, WARM_ITERS

    cost = harness.kernel_cost(r.cell, "dual_demand").cost

    def least(iters):
        flops, nbytes = cost(iters=iters, **r.kernel_calls["dual_demand"])
        return max(flops / r.peaks["flops_per_s"],
                   nbytes / r.peaks["hbm_bytes_per_s"])

    per_period = WARM_ITERS * least(WARM_INNER_ITERS) + least(BISECT_ITERS)
    periods = len(events) / (WARM_ITERS + 1)
    return 100.0 * per_period * periods / sum(e.end - e.start
                                              for e in events)
