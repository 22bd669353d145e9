#!/usr/bin/env python3
"""Run one cell's window as ``run.py`` does and read the engine's own
spans, counters and named scopes from it; prints one JSON line.

    python3 bench/engine_trace.py --workload mamba2-decode-a16 --seed 7 --seconds 40 --trace 1

Set-up, warm-up and the closed-loop window are ``run.py``'s own code
(same engine, traffic and window rule), without the reference's check
afterwards. ``--trace 1`` profiles the window's last seconds as
``run.py`` does and reduces the trace with ``scopes.py``: device time
of the fused programs by owner (named scope, else what the op_name
says), idle gaps by the innermost ``bench.*`` or ``serve.*`` span, each
gap over 100 ms, and the engine's host time per decode step.
``--trace 0`` prints the window's rate alone, for the cost of tracing.
``--keep DIR`` keeps the trace and the programs' instruction map
(``<cell>.op_names.json``) in DIR.

Readings, not benchmark metrics: ``BENCHMARK.json`` reads none of them.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run as R  # noqa: E402
import traffic as traffic_lib  # noqa: E402


def measure(cell, seed: int, seconds: float, trace: bool = False,
            keep: str | None = None, whole: bool = False, host_level: int = 2,
            cfg=None) -> dict:
    """One window of ``cell``. ``whole`` traces all of it (from its
    opening fill) with the profiler's host level ``host_level`` (1: the
    annotations alone); ``cfg`` replaces the registry's configuration
    (a small one, for the recorded test trace and the CPU tests)."""
    import jax

    sys.path.insert(0, str(R.ROOT / "src"))
    from repro.serve import engine

    device = jax.devices()[0]
    cfg = R.program_config(cell, cfg)
    vocab_ids = min(getattr(cell.model, "VOCAB_IDS", cfg.vocab), cfg.vocab)
    n, s_max = cell.settings["n_slots"], cell.settings["s_max"]
    weights = cell.model.make_weights(cell.config, R.seed_key(seed))
    params = cell.model.to_program(weights)
    R.check_tree(params, cfg)
    batcher = R.build_engine(cell, params, cfg, n, s_max)
    del weights, params
    gc.collect()
    drv = R.Driver(batcher, engine._next_pow2)
    tr = traffic_lib.Traffic(cell.mix, seed, n, vocab_ids)
    buckets = R.warm_up(drv, cell, vocab_ids, seed)
    jax.block_until_ready(batcher.caches)
    setup_s = time.perf_counter() - T_START
    out = {"workload": cell.name, "seed": seed, "setup_s": setup_s}

    log_dir = None
    start = None
    if trace:
        import scopes
        import xplane

        t_map = time.perf_counter()
        try:
            op_names = scopes.program_op_names(batcher, buckets)
        except ImportError:       # a program that names no scopes
            op_names = {}
        out["op_map_s"] = time.perf_counter() - t_map
        drv.span = xplane.Tracer.span
        log_dir = tempfile.mkdtemp(prefix="engine_trace_")

        def start():
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = host_level
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            return time.perf_counter()

    before = batcher.stats()
    if trace and whole:
        start()
        win = R.drive(drv, tr, seconds)
    else:
        win = R.drive(drv, tr, seconds, on_trace_start=start)
    jax.block_until_ready(batcher.caches)
    after = batcher.stats()
    steps = win.steps
    out.update(
        tokens_per_s=sum(t.seen for t in win.due) / (win.t1 - win.t0),
        window_s=win.t1 - win.t0, steps=len(steps),
        fills=sum(1 for s in steps if s.filled),
        completed=sum(1 for t in win.due if t.req.done),
        window_compiles=win.compiles,
        peak_hbm_gb=int((device.memory_stats() or {}).get("peak_bytes_in_use", 0)) / 1e9,
        engine={k: after[k] - before.get(k, 0) for k in after},
        device={"platform": device.platform, "kind": device.device_kind})
    if "fill_tokens_computed" in after:
        import scopes

        out["fill_token_use"] = scopes.fill_token_use(before, after)
    if not trace:
        return out

    jax.profiler.stop_trace()
    try:
        path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
        if keep:
            dest = pathlib.Path(keep)
            dest.mkdir(parents=True, exist_ok=True)
            shutil.copy(path, dest / f"{cell.name}.xplane.pb")
            (dest / f"{cell.name}.op_names.json").write_text(json.dumps(op_names))
        s = scopes.summarize(*scopes.read_trace(path), op_names)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    base = s.base
    try:
        from repro.profile.trace import SCOPES
    except ImportError:           # a program that names no scopes
        SCOPES = ()

    out["trace"] = {
        "window_s": base.window_s, "busy_s": base.busy_s,
        "idle_share": 100.0 * (1 - base.busy_s / base.window_s),
        "programs": base.programs,
        "program_ms": {c: 1e3 * sum(v) / len(v) for c, v in base.modules.items()},
        "kernel_s": base.kernel_s,
        "scoped_share": {c: s.scoped_share(c, SCOPES) for c in base.modules},
        "owners": {c: sorted(([k, v, s.owner_share(c, k)] for k, v in d.items()),
                             key=lambda x: -x[1]) for c, d in s.owners.items()},
        "unmapped_s": s.unmapped_s,
        "idle_by_span": sorted(([k, v] for k, v in s.idle.items()), key=lambda x: -x[1]),
        "long_gaps": s.long_gaps,
        "step_host_ms": scopes.step_host_ms(s),
        "serve_steps": len(s.steps),
    }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    cell = R.find_cell(args.workload)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("engine_trace: needs a TPU chip", file=sys.stderr)
        return 3
    R.enable_compile_cache()
    out = measure(cell, args.seed, args.seconds, bool(args.trace), args.keep)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
