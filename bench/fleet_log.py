"""The program's own record of the window's ``run_fleet`` calls, as the
per-layer readers take it: the last ``calls`` records (the sweep generator
counts the window's calls) of the call log of ``repro.obs``, each holding
the call's span durations and work counts.  A program without ``repro.obs``
gives nothing to read, and its readers return None."""
from __future__ import annotations


def window_calls(r) -> list[dict] | None:
    try:
        from repro import obs
    except ImportError:
        return None
    n = int(r.counters.get("calls", 0))
    log = obs.calls()
    if n == 0 or len(log) < n:
        return None
    return log[-n:]


def share(r, live: str, launched: str) -> float | None:
    """The window's ``live`` count over its ``launched`` count, in percent;
    None where a call has no ``launched`` count (a policy whose solves
    share no one shape)."""
    calls = window_calls(r)
    if calls is None or any(c.get(launched) is None for c in calls):
        return None
    return 100.0 * sum(c[live] for c in calls) / sum(c[launched]
                                                     for c in calls)
