"""The reduction from a profiler trace to busy/idle share, kernel time
and program time."""
import pytest

import xplane
from conftest import BENCH

DATA = BENCH / "tests" / "data"

MS = 1_000_000  # ns


def synthetic():
    """Two decode programs and one fill on one chip, with host spans."""
    ops = [("fusion.1", 0, 1 * MS), ("_cim_mac_kernel", 1 * MS, 3 * MS),
           ("fusion.2", 3 * MS, 4 * MS),                       # decode 0-4
           ("_cim_mac_kernel", 6 * MS, 7 * MS),                # decode 6-8
           ("fusion.3", 7 * MS, 8 * MS),
           ("_cim_mac_kernel", 10 * MS, 14 * MS),              # fill 10-15
           ("fusion.4", 14 * MS, 15 * MS)]
    mods = [("jit_step(123)", 0, 4 * MS), ("jit_step(123)", 6 * MS, 8 * MS),
            ("jit_pf(7)", 10 * MS, 15 * MS), ("jit_other", 15 * MS, 16 * MS)]
    device = {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods}}
    host = [("bench.step", 0, 5 * MS), ("bench.record", 5 * MS, 6 * MS),
            ("bench.step", 6 * MS, 9 * MS), ("bench.wait", 9 * MS, 10 * MS),
            ("bench.step", 10 * MS, 16 * MS)]
    return device, host


def test_busy_idle_modules_and_kernel_time():
    s = xplane.summarize(*synthetic())
    assert s.window_s == pytest.approx(0.016)
    # ops cover 0-4, 6-8, 10-15: 11 ms busy, idle 4-6, 8-10, 15-16
    assert s.busy_s == pytest.approx(0.011)
    assert s.modules["decode"] == pytest.approx([0.004, 0.002])
    assert s.modules["prefill"] == pytest.approx([0.005])
    assert s.kernel_s["decode"] == pytest.approx(0.003)
    assert s.kernel_s["prefill"] == pytest.approx(0.004)
    # each idle gap goes whole to the innermost host span at its middle:
    # 4-6 (mid 5) to bench.record, 8-10 (mid 9) to bench.wait, 15-16 to
    # bench.step
    assert s.idle == pytest.approx({"bench.record": 0.002, "bench.wait": 0.002,
                                    "bench.step": 0.001})


def test_breakdown_lists_the_largest_first():
    b = xplane.summarize(*synthetic()).breakdown()
    assert b["device_ops"][0] == ["_cim_mac_kernel", pytest.approx(0.007)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_trace_without_the_chip_is_refused():
    with pytest.raises(ValueError, match="no TPU device plane"):
        xplane.summarize({}, [])


def test_nested_ops_count_their_own_time():
    ops = [("%while.3 = (...) while(...)", 0, 10 * MS),
           ("%ternary_cim_matmul.20 = f32[128,6528] custom-call(...)", 2 * MS, 5 * MS),
           ("%copy.7 = bf16[2] copy(...)", 6 * MS, 7 * MS)]
    device = {"/device:TPU:0": {"XLA Ops": ops,
                                "XLA Modules": [("jit_step(1)", 0, 10 * MS)]}}
    s = xplane.summarize(device, [("bench.step", 0, 10 * MS)])
    assert s.ops == pytest.approx({"while": 0.006, "ternary_cim_matmul": 0.003,
                                   "copy": 0.001})
    assert s.busy_s == pytest.approx(0.010)
    assert s.kernel_s == pytest.approx({"decode": 0.003})


def test_recorded_chip_trace(monkeypatch):
    """A trace recorded on a TPU v5 lite: three runs of a jitted step
    (``jit_f``: the blocked CiM Pallas kernel, then a fused tanh-sum),
    each under a ``bench.decode`` host span. The device's clock runs
    about 1.2 ms ahead of the host's, so the first run falls before the
    first span and is left out; the values below are read off the
    trace's events by hand (ns)."""
    monkeypatch.setattr(xplane, "MODULE_CLASSES",
                        (("decode", xplane.re.compile(r"^jit_f\(")),))
    s = xplane.summarize(*xplane.read_events(str(DATA / "cim_step.xplane.pb")))
    assert s.chips == 1
    assert s.window_s == pytest.approx((53_632_554 + 732_190 - 46_643_085) * 1e-9)
    assert s.busy_s == pytest.approx((2485 + 375 + 2487 + 375) * 1e-9)
    assert s.modules["decode"] == pytest.approx([2869e-9, 2871e-9])
    assert s.kernel_s["decode"] == pytest.approx((2485 + 2487) * 1e-9)
    assert s.ops == pytest.approx({"ternary_cim_matmul": 4972e-9,
                                   "tanh_reduce_fusion": 750e-9})
    assert s.programs == {"jit_f": 2}
    assert sum(s.idle.values()) == pytest.approx(s.window_s - s.busy_s)
