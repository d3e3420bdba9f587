"""The benchmark's own tests: CPU, tiny sizes. Run as
`python -m pytest benchmark/tests -q` from the root of the repo."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
