"""Chip benchmark of packed, paged serving (see ``bench/run.py``)."""
