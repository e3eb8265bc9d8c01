"""On-chip benchmark of the Perona fleet system (see ``bench/run.py``)."""
