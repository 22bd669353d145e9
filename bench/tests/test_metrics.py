"""Each per-layer metric's reader, on a made-up run."""
import json

import pytest

import peaks
import run as R
import work
import xplane
from conftest import BENCH


def reader(name):
    return R.load_module(BENCH / "metrics" / f"{name}.py")


def fake_run(trace=True):
    c = json.loads((BENCH / "configs" / "smollm-135m.json").read_text())
    cell = R.Cell("smollm-decode-a16", {}, c, None, {}, {}, [], [])
    steps = [R.Step(0.0, 0.1, 0, 64, 0),       # decode only
             R.Step(0.1, 0.5, 2, 64, 128),     # fill + decode
             R.Step(0.5, 0.6, 0, 62, 0),
             R.Step(0.6, 1.0, 1, 63, 64)]
    t0 = 0.5
    due = [R.Track(None, seen=2), R.Track(None)]
    win = R.Window(0.0, 1.0, due, steps, 0, trace_t0=t0)
    summary = xplane.Summary(window_s=0.5, busy_s=0.4,
                             modules={"decode": [0.05, 0.07], "prefill": [0.3]},
                             kernel_s={"decode": 0.06, "prefill": 0.2},
                             ops={}, idle={}, chips=1) if trace else None
    return R.Run(cell, None, win, summary, 64, "TPU v5 lite")


def test_host_side_readers():
    run = fake_run()
    assert reader("slot_occupancy").read(run) == pytest.approx((64 + 64 + 62 + 63) / 4)
    assert reader("prefill_share").read(run) == pytest.approx(80.0)


def test_trace_readers():
    run = fake_run()
    p = peaks.peaks("TPU v5 lite")
    assert reader("decode_step_ms").read(run) == pytest.approx(60.0)
    assert reader("idle_share.decode").read(run) == pytest.approx(20.0)
    opt = work.ops_per_token(run.cell.config)
    # decode steps that started after the trace began: 62 + 63 tokens
    # bfloat16 activations: the bfloat16 peak
    assert reader("decode_mfu").read(run) == pytest.approx(
        100 * (62 + 63) * opt / (0.5 * p["bf16_flops"]))
    least = work.least_time(*work.cim_step(run.cell.config, 64), p["bf16_flops"], p)[0]
    assert reader("cim_roofline.decode").read(run) == pytest.approx(100 * 2 * least / 0.06)


@pytest.mark.parametrize("name", ["decode_step_ms", "decode_mfu", "cim_roofline.decode",
                                  "idle_share.decode"])
def test_trace_readers_read_nothing_without_a_trace(name):
    assert reader(name).read(fake_run(trace=False)) is None
