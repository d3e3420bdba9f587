"""Test configuration: run on a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI; sharding correctness is validated
on XLA's host platform with 8 virtual devices (the driver separately dry-runs
the multi-chip path via __graft_entry__.dryrun_multichip).
"""

import os
import sys

# Every pass apply in the suite runs under the pass sanitizer
# (framework/analysis.py): existing pass tests double as sanitizer tests.
# Hard-set (not setdefault): an inherited PTPU_VERIFY_PASSES=0 must not
# silently un-verify the tier; use flags.set_flag in a test to opt out.
os.environ["PTPU_VERIFY_PASSES"] = "1"

# Same discipline for the KV shadow-state sanitizer (serving/sanitizer.py):
# every KVPager the suite constructs mirrors its block-lifetime mutations
# against the abstract ownership model and raises SanitizerDivergence on
# the first drift — existing serving tests double as protocol tests.
os.environ["PTPU_KV_SANITIZE"] = "1"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# Single source of truth for the virtual-device bootstrap (shared with the
# driver's multichip dryrun).
from __graft_entry__ import _ensure_virtual_cpu_devices  # noqa: E402

_ensure_virtual_cpu_devices(8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# tiny_engines holds test bodies that the served models' files share: its
# asserts explain themselves as a test module's do
pytest.register_assert_rewrite("tiny_engines")


@pytest.fixture(autouse=True)
def fresh_state():
    """Each test gets fresh default programs / scope / name generator,
    and profiler/tracer state never bleeds between tests: the old
    profiler's module globals (_completed events, the _enabled bit) used
    to leak across suites — profiler.reset() restores every global and
    tracing.clear() empties the span ring."""
    import paddle_tpu as pt
    from paddle_tpu.core import unique_name
    from paddle_tpu.observability import flight_recorder, tracing
    pt.reset_default_programs()
    pt.reset_global_scope()
    pt.profiler.reset()
    tracing.clear()
    flight_recorder.reset()
    with unique_name.guard():
        yield
    pt.profiler.reset()
    flight_recorder.reset()


@pytest.fixture
def rng():
    return np.random.RandomState(42)
