"""The benchmark's harness: everything a cell's run shares.

Imported as ``harness`` with ``benchmarks/`` on ``sys.path`` (``run.py`` and
the tests put it there). Nothing here imports JAX at import time.
"""
