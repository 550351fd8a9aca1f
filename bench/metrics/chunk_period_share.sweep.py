"""Fleet engine: the periods in which a chunk of episodes still held a
live episode, over the periods every chunk scans (step launches), in
percent, over the window's ``run_fleet`` calls (``repro.obs``)."""
from bench.fleet_log import share


def read(r):
    return share(r, "chunk_live_periods", "step_launches")
