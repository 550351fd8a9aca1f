"""Fleet engine (host): the span ``repro.fleet.call`` less its child
``repro.fleet.device`` (the wait for the sweep's outputs), in
milliseconds, averaged over the window's ``run_fleet`` calls
(``repro.obs``)."""
from bench.fleet_log import window_calls


def read(r):
    calls = window_calls(r)
    if calls is None:
        return None
    return 1e3 * sum(c["call_s"] - c["device_s"] for c in calls) / len(calls)
