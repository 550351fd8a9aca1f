"""Device: idle share of the traced window of a sweep cell, averaged over its chips, 1 - (union of
device-busy intervals / window), in percent."""


def read(r):
    share = None if r.trace is None else r.trace.idle_share()
    return None if share is None else 100.0 * share
