"""Chip benchmark of the PBQP-planned conv nets (see ``run.py``)."""
