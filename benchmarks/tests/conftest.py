"""Tests of the benchmark's own yardstick. CPU only. Run with
``python -m pytest benchmarks/tests -q -p no:cacheprovider``; tier-1
(``pytest tests/``) does not collect this directory."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
