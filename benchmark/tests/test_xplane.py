"""The trace reduction against a small trace recorded on a TPU v5e
(data/tiny_v5e.xplane.pb: three executions of one jitted step that holds a
matmul and the flash forward and backward kernels, each under a
`benchmark/step` annotation with the loss fetched to the host)."""

import os

import pytest

from benchmark import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace():
    return xplane.Trace(os.path.join(HERE, "data", "tiny_v5e.xplane.pb"))


def test_one_chip_three_executions(trace):
    assert [d.name for d in trace.devices] == ["/device:TPU:0"]
    mods = trace.devices[0].modules
    assert [m[2] for m in mods] == ["jit_f"] * 3
    assert trace.main_module() == "jit_f"
    # read off the recorded trace by hand: each execution lasts 163 us
    for s, e, _, _ in mods:
        assert e - s == pytest.approx(163.0e-6, abs=0.2e-6)


def test_busy_time_is_the_union_of_operations(trace):
    lo, hi = trace.window()
    assert hi - lo == pytest.approx(9.69e-3, rel=0.01)
    busy = trace.busy_seconds(lo, hi)
    per_exec = trace.module_busy_seconds()
    assert len(per_exec) == 3
    # all device work happens inside the three executions
    assert busy == pytest.approx(sum(per_exec), rel=1e-6)
    # an execution's busy time cannot pass its own length
    assert all(0.9 * 163e-6 < b <= 163.2e-6 for b in per_exec)
    # 1 - busy/window: the chip idles between the host's steps
    assert 1 - busy / (hi - lo) == pytest.approx(0.95, abs=0.01)


def test_kernels_are_found_and_named_as_the_ledger_names_them(trace):
    ops = trace.op_seconds()
    fwd = "jvp___custom-call_bf16_8_1024_64_"
    bwd = "transpose_jvp____custom-call_bf16_8_1024_64_"
    assert fwd in ops and bwd in ops
    # forward once, backward twice (dq, then dk and dv) per execution: the
    # recorded events last 40.1 us, 2 x 45.4 us
    assert ops[fwd] == pytest.approx(3 * 40.1e-6, rel=0.01)
    assert ops[bwd] == pytest.approx(3 * 90.9e-6, rel=0.01)
    per_exec = trace.module_mosaic_seconds()
    assert per_exec == pytest.approx([131.0e-6] * 3, rel=0.01)
    assert sum(per_exec) == pytest.approx(ops[fwd] + ops[bwd], rel=1e-6)


def test_instruction_names():
    key, op, mosaic = xplane.parse_instruction(
        '%copy.1 = f32[1025,16,16,64]{3,2,1,0:T(8,128)} copy(f32[1025,16,16,64]{2,3,1,0} %p.1)')
    assert (key, op, mosaic) == ("copy_f32_1025_16_16_64_", "copy", False)
    key, op, mosaic = xplane.parse_instruction(
        '%divide_subtract_fusion.3 = (f32[4096,1024]{1,0}, f32[]) fusion(f32[8]{0} %a), kind=kLoop')
    assert key == "divide_subtract_fusion_fusion_f32_4096_1024_"
    key, op, _ = xplane.parse_instruction(
        '%all-reduce-start.2 = f32[1024]{0} all-reduce-start(f32[1024]{0} %g)')
    assert op == "all-reduce-start" and xplane._COLLECTIVE.match(op)


def test_device_clock_is_aligned_by_run_ids(trace):
    off = trace.host_offset_s()
    # a program cannot start before the host enqueued it
    for s, _, _, rid in trace.devices[0].modules:
        assert s + off >= trace.enqueues[rid] - 1e-9
    assert 0.5e-3 < off < 3e-3


def test_idle_gaps_go_to_the_host_span_open_at_the_time(trace):
    gaps = trace.idle_gaps_by_host_span()
    lo, hi = trace.window()
    idle = (hi - lo) - trace.busy_seconds(lo, hi)
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-3)
    # the chip waits while the host fetches the loss and sleeps between steps
    assert gaps["np.asarray_jax.Array_"] > 1e-3
    assert gaps["_no_host_annotation_open_"] > 1e-3
    assert len(xplane.top(gaps, 3)) == 3


def test_interval_arithmetic():
    assert xplane.union([(3, 4), (0, 1), (0.5, 2)]) == [[0, 2], [3, 4]]
    assert xplane.measure([[0, 2], [3, 4]]) == 3
    assert xplane.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert xplane.subtract([(0, 1), (5, 6)], [(0, 6)]) == []
    assert xplane.clip([(0, 2), (3, 5)], 1, 4) == [(1, 2), (3, 4)]


def test_exposed_collectives():
    """A collective is exposed where it runs and no other operation does."""
    t = xplane.Trace.__new__(xplane.Trace)
    dev = xplane.DeviceTrace("/device:TPU:0")
    dev.ops = [(0.0, 1.0, "fusion", "fusion", False),
               (1.0, 1.5, "all-reduce", "all-reduce", False),
               (2.0, 3.0, "fusion", "fusion", False)]
    dev.async_ops = [(2.5, 4.0, "all-gather-start", "all-gather-start")]
    t.devices = [dev]
    assert t.exposed_collective_seconds() == pytest.approx(0.5 + 1.0)
