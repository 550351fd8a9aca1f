"""Solvers: active services over the service rows the policy's solves run
on (each episode's rows padded to the kernels' row tile) in the periods
in which the episode's chunk is still live, so that the dead periods
``chunk_period_share.sweep`` counts are left out, in percent, over the
window's ``run_fleet`` calls (``repro.obs``)."""
from bench.fleet_log import share


def read(r):
    return share(r, "live_rows", "rows_in_live_chunks")
