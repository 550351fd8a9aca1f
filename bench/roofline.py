"""Shared arithmetic of the kernels' roofline shares: the least time the
chip could take for one call's operations and bytes (``bench/costs``), at
its peaks (``bench/peaks.json``), over the call's device time in the
trace.  Nothing to read gives None, never 0."""


def share(r, kernel: str, pattern: str):
    if r.trace is None or r.peaks is None or kernel not in r.kernel_calls:
        return None
    events = r.trace.op_events(pattern)
    if not events:
        return None
    per_call = sum(e.end - e.start for e in events) / len(events)
    flops, nbytes = r.cost(kernel)
    least = max(flops / r.peaks["flops_per_s"],
                nbytes / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / per_call
