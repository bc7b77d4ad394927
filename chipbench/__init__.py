"""Chip benchmark of the serving engine (see ``chipbench/run.py``)."""
