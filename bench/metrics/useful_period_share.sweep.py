"""Fleet engine: the periods up to the one in which every service of an
episode finished, over the ``max_periods`` the scan runs, in percent,
averaged over the window's episodes (``run_fleet``'s own ``periods``)."""


def read(r):
    share = r.counters.get("useful_period_share")
    return None if share is None else 100.0 * share
