"""The chipbench tests run on the CPU: ``python -m pytest chipbench/tests -q``.
The platform has to be explicit before jax is imported (the program's
TPU backend refuses to construct off-chip otherwise)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
