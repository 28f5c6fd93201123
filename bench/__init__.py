"""On-chip benchmark of NeoEngine's served path (see ``bench/run.py``)."""
