"""The on-chip benchmark of the DDM matcher (see ``bench/run.py``)."""
