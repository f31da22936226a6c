"""Chip benchmark of the Polynesia HTAP session (see ``run.py``)."""
