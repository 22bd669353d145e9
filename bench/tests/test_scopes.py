"""Device time by named scope, idle gaps by the engine's spans, the
engine's host time per step, and fill_token_use."""
import gzip
import json

import pytest

import run as R
import scopes
import xplane
from conftest import BENCH, small_cell
from scopes import Span

MS = 1_000_000  # ns


def synthetic():
    """One decode program (0-4 ms) and one fill (6-9 ms) on one chip; the
    benchmark's spans, and the engine's inside them."""
    ops = [("%fusion.1 = bf16[64] fusion(...)", 0, 1 * MS),            # attn in cim
           ("%copy.7 = bf16[2] copy(...)", 1 * MS, 2 * MS),           # arg relayout
           ("%ternary_cim_matmul.2 = f32[2] custom-call(...)", 2 * MS, 3 * MS),
           ("%dynamic-update-slice.3 = f32[2] dynamic-update-slice(...)",
            3 * MS, 4 * MS),                                           # scan's own
           ("%fusion.1 = bf16[64] fusion(...)", 6 * MS, 8 * MS),       # fill.merge
           ("%fusion.9 = bf16[64] fusion(...)", 8 * MS, 9 * MS)]       # not in map
    mods = [("jit_step(1)", 0, 4 * MS), ("jit_pf(2)", 6 * MS, 9 * MS)]
    device = {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods}}
    bench = [("bench.step", 0, 5 * MS), ("bench.record", 5 * MS, 6 * MS),
             ("bench.step", 6 * MS, 10 * MS)]
    serve = [Span("serve.step", 0, 5 * MS, {}),
             Span("serve.decode.dispatch", 0, MS // 2, {}),
             Span("serve.decode.fetch", MS // 2, 4 * MS + MS // 2, {}),
             Span("serve.decode.commit", 4 * MS + MS // 2, 5 * MS, {}),
             Span("serve.step", 6 * MS, 12 * MS, {}),                  # past the window
             Span("serve.fill.stage", 6 * MS, 6 * MS + MS // 2, {"rows": 1}),
             Span("serve.fill.fetch", 6 * MS + MS // 2, 9 * MS + MS // 2, {}),
             Span("serve.fill.commit", 9 * MS + MS // 2, 12 * MS, {})]
    op_names = {
        "decode": {"%fusion.1": "jit(step)/while/body/attn/cim/dot_general",
                   "%copy.7": "caches.k",
                   "%ternary_cim_matmul.2": "jit(step)/while/body/closed_call/cim/pallas_call",
                   "%dynamic-update-slice.3": "jit(step)/while/body/dynamic_update_slice"},
        "prefill": {"%fusion.1": "jit(pf)/fill.merge/select_n"},
    }
    return device, bench, serve, op_names


def test_innermost_scope_owns_an_op_and_the_rest_are_named():
    s = scopes.summarize(*synthetic())
    assert s.owners["decode"] == pytest.approx({
        "cim": 0.002, "arg caches.k": 0.001,
        "while/body/dynamic_update_slice": 0.001})
    assert s.owners["prefill"] == pytest.approx({"fill.merge": 0.002, "not in map": 0.001})
    assert s.unmapped_s == pytest.approx({"prefill": 0.001})
    assert s.scoped_share("decode", ["cim", "attn"]) == pytest.approx(50.0)
    assert s.owner_share("prefill", "fill.merge") == pytest.approx(200 / 3)


def test_an_op_xla_built_inside_a_scoped_loop_belongs_to_the_loop():
    """XLA turns a scatter into a loop of its own whose ops carry no
    metadata; they run inside the loop op, which carries the scatter's."""
    ops = [("%while.17 = (s32[]) while(...)", 0, 10 * MS),
           ("%fusion.138 = bf16[4] fusion(...)", 1 * MS, 3 * MS),
           ("%slice.233 = s32[1] slice(...)", 4 * MS, 5 * MS),
           ("%copy.5 = bf16[4] copy(...)", 11 * MS, 12 * MS)]
    device = {"/device:TPU:0": {"XLA Ops": ops,
                                "XLA Modules": [("jit_step(1)", 0, 12 * MS)]}}
    names = {"decode": {"%while.17": "jit(step)/attn/vmap()/scatter",
                        "%fusion.138": "", "%slice.233": "", "%copy.5": ""}}
    s = scopes.summarize(device, [("bench.step", 0, 12 * MS)], [], names)
    assert s.owners["decode"] == pytest.approx({"attn": 0.010, "no metadata": 0.001})


def test_a_name_that_differs_between_fill_buckets_is_told_apart_by_its_type():
    a = {"%fusion.1": ("jit(pf)/attn/mul", "bf16[4,16]{1,0}"),
         "%copy.2": ("caches.k", "bf16[2]{0}")}
    b = {"%fusion.1": ("jit(pf)/cim/dot_general", "(bf16[4,32]{1,0}, f32[])"),
         "%copy.2": ("caches.k", "bf16[2]{0}")}
    merged = scopes.merge_programs([a, b])
    assert merged["%copy.2"] == "caches.k"
    assert merged["%fusion.1"] == {"bf16[4,16]{1,0}": "jit(pf)/attn/mul",
                                   "(bf16[4,32]{1,0}, f32[])": "jit(pf)/cim/dot_general"}
    look = lambda ev: scopes.lookup(merged, ev)
    assert look("%fusion.1 = bf16[4,16]{1,0} fusion(bf16[4,16]{1,0} %p)") == "jit(pf)/attn/mul"
    assert look("%fusion.1 = (bf16[4,32]{1,0}, f32[]) fusion(%p)") == "jit(pf)/cim/dot_general"
    assert look("%fusion.1 = bf16[8]{0} fusion(%p)") == "?"
    assert look("%copy.2 = bf16[2]{0} copy(%a)") == "caches.k"
    assert look("%copy.3 = bf16[2]{0} copy(%a)") is None
    c = {"%fusion.1": ("jit(pf)/sample/argmax", "bf16[4,16]{1,0}")}
    assert scopes.merge_programs([a, c])["%fusion.1"] == {"bf16[4,16]{1,0}": "?"}


def test_owner_of_an_op_outside_any_scope():
    assert scopes.owner("jit(step)/jit(_take)/gather") == "jit(_take)/gather"
    assert scopes.owner("params['embed']") == "arg params['embed']"
    assert scopes.owner("reduce_sum") == "reduce_sum"
    assert scopes.owner("") == "no metadata"
    assert scopes.owner(None) == "not in map"


def test_idle_gaps_go_to_the_innermost_span_of_either_family():
    s = scopes.summarize(*synthetic())
    # device idle 4-6 (mid 5: bench.record starts at 5, serve.step ends
    # there too; bench.record is the latest to start) and 9-10 (mid 9.5:
    # serve.fill.fetch ends at 9.5, serve.fill.commit starts there)
    assert s.idle == pytest.approx({"bench.record": 0.002, "serve.fill.commit": 0.001})
    # xplane's own reduction still charges the same gaps to bench.* spans
    assert s.base.idle == pytest.approx({"bench.record": 0.002, "bench.step": 0.001})
    assert s.long_gaps == []


def test_the_window_is_bounded_by_bench_spans_alone():
    device, bench, serve, op_names = synthetic()
    s = scopes.summarize(device, bench, serve, op_names)
    # serve.step runs on to 12 ms; the window still ends at 10 ms
    assert s.base.window_s == pytest.approx(0.010)
    assert s.base.busy_s == pytest.approx(0.007)
    assert s.base == xplane.summarize(device, bench)


def test_long_gaps_name_every_span_around_them():
    device, bench, serve, op_names = synthetic()
    bench = bench + [("bench.wait", 10 * MS, 300 * MS)]
    serve = serve + [Span("serve.step", 100 * MS, 290 * MS, {}),
                     Span("serve.decode.stage", 100 * MS, 290 * MS, {"active": 3})]
    s = scopes.summarize(device, bench, serve, op_names)
    assert s.long_gaps[0][1] == ["bench.wait", "serve.step", "serve.decode.stage"]
    assert s.long_gaps[0][0] == pytest.approx(0.291)
    assert s.idle["serve.decode.stage"] == pytest.approx(0.291)


def test_step_host_ms_reads_decode_only_steps_less_their_fetch():
    s = scopes.summarize(*synthetic())
    # the first serve.step (5 ms, fetch 4 ms); the second holds a fill
    assert scopes.step_host_ms(s) == pytest.approx(1.0)


def test_recorded_chip_trace(tmp_path):
    """A trace recorded on a TPU v5 lite by ``data/record_engine_trace.py``:
    smollm-135m at smoke widths on 4 slots, from the window's opening
    fill to its first completion. The engine's ``stats()`` over that
    window read 11 decode steps over 43 active rows, and 7 fills that
    admitted 10 requests; the spans say the same."""
    data = BENCH / "tests" / "data"
    path = tmp_path / "engine_step.xplane.pb"
    path.write_bytes(gzip.decompress((data / "engine_step.xplane.pb.gz").read_bytes()))
    op_names = json.loads(gzip.decompress((data / "engine_step.op_names.json.gz").read_bytes()))
    device, bench, serve = scopes.read_trace(str(path))
    s = scopes.summarize(device, bench, serve, op_names)
    # every op the two programs ran is in the map
    assert s.unmapped_s == {}
    assert {c: len(v) for c, v in s.base.modules.items()} == {"decode": 11, "prefill": 7}
    fills = [x for x in serve if x.name == "serve.fill.stage"]
    decodes = [x for x in serve if x.name == "serve.decode.stage"]
    assert len(fills) == 7 and sum(x.args["rows"] for x in fills) == 10
    assert fills[0].args == {"rows": 4, "s_pad": 16, "rids": [3, 4, 5, 6]}
    assert len(decodes) == 11 and sum(x.args["active"] for x in decodes) == 43
    # each step's spans run inside it, one after another
    assert len(s.steps) == 11
    for step, children in s.steps:
        names = [c.name for c in children]
        assert names[-4:] == ["serve.decode.stage", "serve.decode.dispatch",
                              "serve.decode.fetch", "serve.decode.commit"]
        assert names[:-4] in ([], ["serve.fill.stage", "serve.fill.dispatch",
                                   "serve.fill.fetch", "serve.fill.commit"])
        assert all(a.t1 <= b.t0 for a, b in zip(children, children[1:]))
    # the idle time is all charged, now to the engine's spans as well
    assert sum(s.idle.values()) == pytest.approx(s.base.window_s - s.base.busy_s)
    assert s.idle["serve.decode.fetch"] > 0 and "bench.step" not in s.idle
    # scope shares as reduced on the chip when the trace was recorded
    from repro.profile.trace import SCOPES

    assert s.scoped_share("decode", SCOPES) == pytest.approx(88.2863, abs=1e-3)
    assert s.scoped_share("prefill", SCOPES) == pytest.approx(70.2798, abs=1e-3)
    assert max(s.owners["decode"], key=s.owners["decode"].get) == "attn"
    assert scopes.step_host_ms(s) == pytest.approx(2.01027)


def test_fill_token_use_from_two_stats():
    before = {"fill_tokens_prompt": 100, "fill_tokens_computed": 1000}
    after = {"fill_tokens_prompt": 164, "fill_tokens_computed": 9192}
    assert scopes.fill_token_use(before, after) == pytest.approx(100 * 64 / 8192)
    assert scopes.fill_token_use(before, before) is None


def test_fill_token_use_reader_equals_the_engines_counters():
    """The benchmark's reader, from the window's record of each fill,
    reads what the engine's own counters count over the window (a small
    cell on the CPU)."""
    import jax

    from repro.serve import engine
    import traffic as traffic_lib

    cell, cfg = small_cell("smollm-135m", 1.0)
    cfg = R.program_config(cell, cfg)
    params = cell.model.to_program(cell.model.make_weights(cell.config, R.seed_key(11)))
    b = R.build_engine(cell, params, cfg, 4, 64)
    drv = R.Driver(b, engine._next_pow2)
    R.warm_up(drv, cell, cfg.vocab, 11)
    before = b.stats()
    win = R.drive(drv, traffic_lib.Traffic(cell.mix, 11, 4, cfg.vocab), 0.5)
    jax.block_until_ready(b.caches)
    run = R.Run(cell, cfg, win, None, 4, "cpu")
    reader = R.load_module(BENCH / "metrics" / "fill_token_use.py")
    got = reader.read(run)
    assert sum(1 for st in win.steps if st.filled) >= 2
    assert got == pytest.approx(scopes.fill_token_use(before, b.stats()))
    assert 0 < got < 100
