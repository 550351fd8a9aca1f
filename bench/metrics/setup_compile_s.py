"""Set-up: seconds of tracing, lowering and backend compiling (loads from
the persistent cache included) whose events ended before the window's
first ``run_fleet`` call began, as the union of the events' intervals
(``repro.obs``'s listeners on ``jax.monitoring``)."""
from bench.fleet_log import window_calls


def read(r):
    calls = window_calls(r)
    if calls is None:
        return None
    from repro import obs

    return obs.compile_seconds(until=calls[0]["start"])
