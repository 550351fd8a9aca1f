"""Kernels: VMEM bytes one grid step of the policy's widest kernel launch
holds, by the account its row tile was fitted to (``kernels/tiling``), over
the VMEM budget of that account, in percent, over the window's
``run_fleet`` calls (``repro.obs``).  A program whose call records give no
such bytes gives nothing to read."""
from bench.fleet_log import share


def read(r):
    return share(r, "block_bytes", "vmem_budget_bytes")
