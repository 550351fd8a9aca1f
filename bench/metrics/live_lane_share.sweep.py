"""Kernels: enrolled clients of active services over the client lanes of
those services' rows (``k_max`` padded to 128 lanes), so that the dead
rows and periods the other two shares count are left out, in percent,
over the window's ``run_fleet`` calls (``repro.obs``)."""
from bench.fleet_log import share


def read(r):
    return share(r, "live_lanes", "lanes_of_live_rows")
