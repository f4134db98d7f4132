"""The benchmark's own tests: CPU, toy sizes, not part of tier-1.

    python -m pytest benchmarks/ -q
"""

import os
import sys

os.environ.setdefault("KERAS_BACKEND", "jax")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
