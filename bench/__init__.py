"""The chip benchmark of the tiered paged-KV serving path.

``bench/run.py`` is the entry point; see ``bench/harness.py``.
"""
