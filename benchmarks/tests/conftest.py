"""The benchmark's own tests run on the CPU, from the root of the repo:
`JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q`."""
import os
import sys

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
