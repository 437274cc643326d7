"""Chip benchmark of the placed DLRM train step (see ``bench/run.py``)."""
