"""The benchmark: the yardstick later PRs are measured with and may not edit.

`run.py` is the one command; everything that belongs to one configuration,
one traffic mix or one per-layer metric is a file of its own that `run.py`
finds by name (see README.md).
"""
