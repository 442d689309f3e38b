"""The benchmark's own tests: run by hand, not tier-1.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
