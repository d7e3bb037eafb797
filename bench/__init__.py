"""Chip benchmark of the Laplacian solve service (see ``bench/run.py``)."""
