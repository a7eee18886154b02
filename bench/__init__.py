"""Chip benchmark of the plan-cache agent server (see ``bench/harness.py``)."""
