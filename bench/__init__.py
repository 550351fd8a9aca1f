"""On-chip benchmark of the bandwidth allocator (see ``bench/run.py``)."""
