#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one result line.

    python3 bench/run.py --workload smollm-decode-a16 --seed 7 --seconds 10 --trace 0

The cell is found by name in ``BENCHMARK.json``; its configuration
(``bench/configs/<config>.json`` with the plain reference beside it in
``<config>.py``), its traffic (``bench/traffic/<mix>.json``), its engine
settings and check limit (``bench/cells/<workload>.json``) and each
per-layer metric's reader (``bench/metrics/<metric>.py``) are found by
their names. A new cell or metric is new files plus new entries.

One run: weights made on the device from the seed, the program's
``ContinuousBatcher`` built on the path the configuration states (quant
mode cim, blocked Pallas kernels, fused prefill and decode), every
prefill bucket and the decode step warmed up (set-up), then
``--seconds`` of the cell's closed loop driven through
``submit()``/``step()`` with the benchmark's own clock. ``--trace 0``
prints the cell's end-to-end metrics; ``--trace 1`` traces the last
seconds of the window with the profiler and prints the per-layer
metrics. After the window the plain reference replays every engine call
of the run and judges each served token (``correct``).

With no TPU, or fewer chips than the cell asks for, it exits non-zero
before set-up and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import traffic as traffic_lib  # noqa: E402

# seconds at the end of a --trace 1 window that the profiler records
TRACE_SECONDS = 3.0
# warm-up requests decode this many tokens (compiles the decode step)
WARM_NEW = 2


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict
    config: dict
    model: object          # the configuration's module: weights, reference
    mix: dict
    settings: dict
    end_to_end: list
    per_layer: list


def find_cell(name: str, spec: dict | None = None) -> Cell:
    spec = spec or json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"unknown workload {name!r}")

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    config = json.loads((BENCH / "configs" / f"{entry['config']}.json").read_text())
    return Cell(
        name=name, entry=entry, config=config,
        model=load_module(BENCH / "configs" / f"{entry['config']}.py"),
        mix=traffic_lib.load(BENCH / "traffic" / f"{entry['traffic']}.json"),
        settings=json.loads((BENCH / "cells" / f"{name}.json").read_text()),
        end_to_end=mine(spec["end_to_end"]), per_layer=mine(spec["per_layer"]))


def enable_compile_cache():
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    when it is set, else at the fixed ``<checkout>/.jax_cache``; every
    program is cached, so only a checkout's first run compiles."""
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def seed_key(seed: int):
    """A JAX key from any non-negative seed, 64-bit ones included."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed >> 31)


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------


def program_config(cell: Cell, cfg=None):
    """The program's ArchConfig for the cell, checked against the
    configuration file (a registry that drifted from it is refused),
    with the served quantization the file states."""
    from repro.models.registry import get_config

    cfg = cfg or get_config(cell.config["program_arch"])
    want = cell.config["served"]["quant"]
    cfg = cfg.replace(quant=dataclasses.replace(
        cfg.quant, quantize_activations=want["quantize_activations"]))
    bad = [(k, cell.config[k], getattr(cfg, a))
           for k, a in cell.model.PROGRAM_KEYS.items()
           if getattr(cfg, a) != cell.config[k]]
    q, served = cfg.quant, cell.config["served"]
    for name, got, exp in (
            ("quant.mode", q.mode, want["mode"]),
            ("quant.block", q.block, want["block"]),
            ("quant.adc_max", q.adc_max, want["adc_max"]),
            ("quant.threshold_factor", q.threshold_factor, want["threshold_factor"]),
            ("quant.exec_spec", q.exec_spec, None),
            ("quant.cache_dtype", q.cache_dtype, "bf16"),
            ("dtype", cfg.dtype, served["dtype"])):
        if got != exp:
            bad.append((name, exp, got))
    if bad:
        raise SystemExit(f"program config differs from {cell.config['name']}: {bad}")
    return cfg


def check_tree(params, cfg):
    """The benchmark's weights must have the program's own layout."""
    import jax

    from repro.models import transformer as T

    want = jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise SystemExit("benchmark weights do not match the program's layout")


def build_engine(cell: Cell, params, cfg, n_slots: int, s_max: int):
    """The program's ContinuousBatcher on the path the configuration
    states: its execution spec, and the weights folded once at load."""
    from repro.core.execution import CiMExecSpec
    from repro.serve.engine import ContinuousBatcher

    served = cell.config["served"]
    spec = CiMExecSpec(*served["exec_spec"].split("/"))
    return ContinuousBatcher(params, cfg, n_slots=n_slots, s_max=s_max,
                             exec_spec=spec,
                             prepare_weights=served["prepare_weights"])


@dataclasses.dataclass
class Step:
    t0: float             # host clock around one engine step()
    t1: float
    filled: int           # slots given a request by its fill (0: no fill)
    decoded: int          # slots its fused decode step served
    s_pad: int            # padded prompt width of the fill


@dataclasses.dataclass
class Track:
    req: object           # engine Request
    seen: int = 0         # tokens it had after the last step()


class Driver:
    """Drives a ContinuousBatcher through submit()/step() and records
    what a reference needs to replay every engine call: for a fill, the
    rows and prompts and the padded width; for a decode step, each
    slot's input token, cache position and left-pad start. It keeps its
    own copy of the slots' host state (from the engine's documented
    discipline: fills take free slots in order from the queue head,
    prompts left-padded to the power-of-two bucket) and checks it
    against the engine's ``slot_pos``/``slot_start`` after every step."""

    def __init__(self, batcher, next_pow2, clock=time.perf_counter):
        import numpy as np

        self.np = np
        self.b = batcher
        self.n, self.s_max = batcher.n_slots, batcher.s_max
        self.next_pow2 = next_pow2
        self.clock = clock
        self.last_tok = np.zeros(self.n, np.int32)
        self.pos = np.zeros(self.n, np.int32)
        self.start = np.zeros(self.n, np.int32)
        self.slot_track = [None] * self.n
        self.log = []        # ("fill", s_pad, rows, served) | ("decode", tok, pos, start, served)
        self.inflight = []
        self.steps = []      # Step per engine step()
        self.finished = []   # tracks completed by the last step
        self._rid = 0
        self.span = contextlib.nullcontext

    def submit(self, prompt, max_new):
        from repro.serve.engine import Request

        r = Request(self._rid, list(prompt), max_new)
        self._rid += 1
        tr = Track(r)
        self.b.submit(r)
        self.inflight.append(tr)
        return tr

    def step(self):
        np, b = self.np, self.b
        empty = [s for s in range(self.n) if b.slot_req[s] is None]
        k = min(len(empty), len(b.queue))
        fill = list(zip(empty[:k], b.queue[:k]))
        old = [(s, self.slot_track[s], len(b.slot_req[s].generated))
               for s in range(self.n) if b.slot_req[s] is not None]
        t0 = self.clock()
        with self.span("bench.step"):
            b.step()
        t1 = self.clock()
        with self.span("bench.record"):
            decoded, s_pad = self._record(fill, old)
            self.steps.append(Step(t0, t1, len(fill), decoded, s_pad))
            self.finished = []
            keep = []
            for tr in self.inflight:
                tr.seen = len(tr.req.generated)
                if tr.req.done:
                    self.finished.append(tr)
                else:
                    keep.append(tr)
            self.inflight = keep

    def _record(self, fill, old):
        np = self.np
        active = list(old)
        s_pad = 0
        if fill:
            width = max(len(r.prompt) for _, r in fill)
            s_pad = self.next_pow2(width)
            if s_pad >= self.s_max:
                s_pad = width
            rows, served = [], {}
            for s, r in fill:
                tr = next(t for t in self.inflight if t.req is r)
                self.slot_track[s] = tr
                self.start[s] = s_pad - len(r.prompt)
                self.pos[s] = s_pad
                self.last_tok[s] = r.generated[0]
                rows.append((s, r.prompt))
                served[s] = r.generated[0]
                if len(r.generated) > 1:  # decoded in this step too
                    active.append((s, tr, 1))
            self.log.append(("fill", s_pad, rows, served))
        if active:
            entry = ("decode", self.last_tok.copy(), self.pos.copy(),
                     self.start.copy(), {})
            for s, tr, idx in active:
                tok = tr.req.generated[idx]
                entry[4][s] = tok
                self.last_tok[s] = tok
                self.pos[s] += 1
            self.log.append(entry)
        if not (np.array_equal(self.pos, self.b.slot_pos)
                and np.array_equal(self.start, self.b.slot_start)):
            raise RuntimeError(
                "engine slot state departs from the documented fill/decode "
                f"discipline: pos {self.pos.tolist()} vs {self.b.slot_pos.tolist()}")
        return len(active), s_pad

    def drain(self):
        """Serve everything submitted (warm-up)."""
        while self.inflight:
            self.step()


def warm_up(drv: Driver, cell: Cell, vocab_ids: int, seed: int):
    """One fill at every prompt bucket the mix can produce, each decoded
    a step, so the window compiles nothing."""
    import numpy as np
    from repro.serve.engine import _next_pow2

    rng = np.random.default_rng([seed, 2**20])
    buckets = traffic_lib.prompt_buckets(cell.mix, drv.s_max, _next_pow2)
    for width in buckets:
        drv.submit(rng.integers(1, vocab_ids, size=width).tolist(), WARM_NEW)
        drv.drain()
    return buckets


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    due: list             # tracks submitted in the window
    steps: list
    compiles: int
    trace_t0: float | None = None


def drive(drv: Driver, tr: traffic_lib.Traffic, seconds: float,
          on_trace_start=None) -> Window:
    """The measured window: fill every slot and the backlog, then step,
    count tokens, and submit one request for each completion.

    The closed loop's work comes in cycles: a fill, then decode steps up
    to the next completion. The window ends at the first completion once
    ``seconds`` have passed, so it holds whole cycles and its rate does
    not hang on whether the clock ran out during a fill (a mamba2-780m
    fill lasts about a second). Where no request completes, it ends at
    twice ``seconds``."""
    import jax

    compiles = []

    def listener(event, duration, **kw):
        if "backend_compile" in event or "trace" in event.split("/")[-1]:
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    clock = drv.clock
    first_step = len(drv.steps)
    t0 = clock()
    end = t0 + seconds
    trace_at = end - TRACE_SECONDS if on_trace_start else None
    trace_t0 = None
    due = []
    with drv.span("bench.window"):
        with drv.span("bench.submit"):
            for _ in range(drv.n + tr.backlog):
                q = tr.next()
                due.append(drv.submit(q.prompt, q.max_new))
        while True:
            if trace_at is not None and trace_t0 is None and clock() >= trace_at:
                trace_t0 = on_trace_start()
            drv.step()
            with drv.span("bench.submit"):
                for _ in drv.finished:
                    q = tr.next()
                    due.append(drv.submit(q.prompt, q.max_new))
            now = clock()
            if now >= end and (drv.finished or now >= end + seconds):
                break
    jax.monitoring.unregister_event_duration_listener(listener)
    t1 = drv.steps[-1].t1
    return Window(t0, t1, due, drv.steps[first_step:], len(compiles), trace_t0)


# ---------------------------------------------------------------------------
# correctness: the reference replays every engine call
# ---------------------------------------------------------------------------


def replay(ref, log, n: int, vocab: int, control=None):
    """Widest gap by which a served token's logit lies below the
    reference's best, over every token the engine served, and how many
    were judged. With ``control`` (the reference in a narrower type) it
    also returns the widest gap of the control's own first choices at
    the same positions (else None)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def gap(logits, tok, mask):
        g = jnp.max(logits, axis=-1) - jnp.take_along_axis(
            logits, tok[:, None], axis=-1)[:, 0]
        return jnp.max(jnp.where(mask, g, -jnp.inf))

    @jax.jit
    def gaps(logits, tok, mask, other):
        ctrl = None if other is None else gap(
            logits, jnp.argmax(other, axis=-1).astype(jnp.int32), mask)
        return gap(logits, tok, mask), ctrl, jnp.sum(mask)

    worst, worst_ctrl, count = [], [], []
    for e in log:
        tok = np.zeros(n, np.int32)
        mask = np.zeros(n, bool)
        for s, t in e[-1].items():
            if not 0 <= t < vocab:
                raise ValueError(f"served token {t} outside the vocabulary")
            tok[s], mask[s] = t, True
        if e[0] == "fill":
            _, s_pad, rows, _ = e
            tokens = np.zeros((n, s_pad), np.int32)
            start = np.zeros(n, np.int32)
            fill = np.zeros(n, bool)
            for s, prompt in rows:
                tokens[s, s_pad - len(prompt):] = prompt
                start[s], fill[s] = s_pad - len(prompt), True
            logits = ref.prefill(tokens, start, fill)
            other = control.prefill(tokens, start, fill) if control else None
        else:
            _, toks, pos, start, _ = e
            logits = ref.decode(toks, pos, start)
            other = control.decode(toks, pos, start) if control else None
        g, gc, c = gaps(logits, tok, mask, other)
        worst.append(g)
        worst_ctrl.append(gc)
        count.append(c)
    if not worst:
        return float("nan"), 0, None
    top = lambda v: float(np.max(np.asarray(jnp.stack(v))))
    return (top(worst), int(np.sum(np.asarray(jnp.stack(count)))),
            top(worst_ctrl) if control is not None else None)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             cfg=None, control: bool = False, serve_hook=None,
             trace_dir: str | None = None) -> dict:
    """Set up, warm up, measure, check. ``cfg`` replaces the registry's
    configuration (tests run a small one); ``serve_hook(batcher)`` may
    break the engine underneath (tests of the check); ``control``
    judges the control's tokens instead of the engine's."""
    import jax
    import numpy as np

    sys.path.insert(0, str(ROOT / "src"))
    from repro.serve import engine

    device = jax.devices()[0]
    cfg = program_config(cell, cfg)
    vocab_ids = getattr(cell.model, "VOCAB_IDS", cfg.vocab)
    vocab_ids = min(vocab_ids, cfg.vocab)
    n, s_max = cell.settings["n_slots"], cell.settings["s_max"]
    weights = cell.model.make_weights(cell.config, seed_key(seed))
    params = cell.model.to_program(weights)
    check_tree(params, cfg)
    batcher = build_engine(cell, params, cfg, n, s_max)
    # the engine keeps what it serves; the benchmark's copy is not held
    # through the window, and is made again from the seed for the check
    del weights, params
    gc.collect()
    if serve_hook is not None:
        serve_hook(batcher)
    drv = Driver(batcher, engine._next_pow2)
    tr = traffic_lib.Traffic(cell.mix, seed, n, vocab_ids)
    buckets = warm_up(drv, cell, vocab_ids, seed)
    jax.block_until_ready(batcher.caches)
    setup_s = time.perf_counter() - T_START
    mem_setup = device.memory_stats() or {}

    tracer = None
    if trace:
        import xplane

        tracer = xplane.Tracer(trace_dir or tempfile.mkdtemp(prefix="bench_trace_"))
        drv.span = tracer.span
    win = drive(drv, tr, seconds,
                on_trace_start=tracer.start if tracer else None)
    jax.block_until_ready(batcher.caches)
    trace_summary = tracer.stop() if tracer else None
    mem = device.memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    engine_stats = batcher.stats()

    # free the program's state before the reference takes the chip
    log = drv.log
    drv.b = None
    del batcher
    gc.collect()
    weights = cell.model.make_weights(cell.config, seed_key(seed))
    ref = cell.model.Reference(cell.config, weights, n, s_max)
    ctrl = None
    if control:
        ctrl = cell.model.Reference(cell.config, weights, n, s_max,
                                    dtype=cell.config["served"]["control_dtype"])
    t_check = time.perf_counter()
    max_gap, judged, ctrl_gap = replay(ref, log, n, cfg.vocab, ctrl)
    check_s = time.perf_counter() - t_check

    limit = cell.settings["check"]["max_gap"]
    engine_gap = max_gap
    if control:
        max_gap = ctrl_gap
    check = {"max_gap": {"value": max_gap, "limit": limit}}
    correct = (judged > 0 and limit is not None and max_gap == max_gap
               and max_gap <= limit)
    run = Run(cell=cell, cfg=cfg, window=win, trace=trace_summary,
              n_slots=n, device_kind=device.device_kind)
    failed = sum(1 for t in win.due if t.req.truncated)
    device_info = {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak}
    if trace:
        metrics = per_layer_metrics(cell, run)
        device_info.update(busy_s=trace_summary.busy_s,
                           window_s=trace_summary.window_s)
    else:
        metrics = end_to_end_metrics(cell, run, setup_s, peak)
    out = {"correct": bool(correct),
           "attempted": len(win.due),
           "failed": failed, "metrics": metrics, "device": device_info}
    if trace:
        out["breakdown"] = trace_summary.breakdown()
    out["check"] = check
    out["_info"] = {
        "buckets": buckets, "judged_tokens": judged, "check_s": check_s,
        "engine_max_gap": engine_gap,
        "window_compiles": win.compiles, "engine": engine_stats,
        "window_s": win.t1 - win.t0, "steps": len(win.steps),
        "fills": sum(1 for st in win.steps if st.filled),
        "completed": sum(1 for t in win.due if t.req.done),
        "setup_s": setup_s,
        "bytes_in_use": {"setup": mem_setup.get("bytes_in_use"),
                         "window_end": mem.get("bytes_in_use")},
        "peak_bytes_setup": mem_setup.get("peak_bytes_in_use"),
        "traced_programs": trace_summary.programs if trace else None,
    }
    return out


@dataclasses.dataclass
class Run:
    """What a per-layer metric's reader reads."""
    cell: Cell
    cfg: object
    window: Window
    trace: object         # xplane.Summary or None
    n_slots: int
    device_kind: str


def _tokens(win: Window):
    return sum(t.seen for t in win.due)


def end_to_end_metrics(cell: Cell, run: Run, setup_s: float, peak: int) -> dict:
    win = run.window
    values = {
        "tokens_per_s": _tokens(win) / (win.t1 - win.t0),
        "peak_hbm_gb": peak / 1e9,
        "setup_s": setup_s,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def per_layer_metrics(cell: Cell, run: Run) -> dict:
    out = {}
    for m in cell.per_layer:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        v = reader.read(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the control's tokens (calibration only)")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    cell = find_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.entry["chips"]:
        print(f"bench: needs {cell.entry['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 3
    enable_compile_cache()

    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   control=bool(args.control))
    info = out.pop("_info")
    print(f"bench: {args.workload} seed {args.seed}: " + json.dumps(info),
          file=sys.stderr)
    for name, c in out["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
