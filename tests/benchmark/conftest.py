"""The benchmark's tests import its harness as ``harness`` (with
``benchmarks/`` on ``sys.path``), the way ``benchmarks/run.py`` does."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (os.path.join(REPO, "benchmarks"), os.path.dirname(
        os.path.abspath(__file__))):
    if path not in sys.path:
        sys.path.insert(0, path)
