"""Production serving launcher: continuous batching over a ternary model.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
        --requests 8 --slots 4

``--serve-http`` starts the async front door instead of the one-shot
batch run (DESIGN.md §12): an HTTP + WebSocket server (stdlib asyncio)
streaming tokens per request, with ``--replicas N`` engine replicas
behind a least-loaded router and bounded admission (``--queue-limit``,
429 on overflow). ``--selftest`` runs the front door against itself —
stream one request, cancel a second mid-stream, verify /stats, clean
shutdown — and exits; CI uses it as the front-door smoke:

    PYTHONPATH=src python -m repro.launch.serve --smoke --serve-http \
        --replicas 2 --selftest

The serving CiM execution spec is selected with ``--exec-spec`` as
``formulation[/backend[/packing[/flavor]]]``, e.g. ``exact/jnp`` (the
near-memory exact baseline), ``blocked`` (faithful per-16-block ADC
clamp), or ``bitplane/jnp/bitplane_u8/II`` (2-bit packed planes, flavor
II); combined with ``--prepare-weights`` the quantization is folded
offline once (quant.prepare.prepare_for_spec) and packed planes are
prepared up front instead of per step.

``--tp N`` serves tensor-parallel over an N-device ("data", "model")
mesh (DESIGN.md §8): params/caches/planes sharded, same token streams,
same host-sync discipline. On CPU the devices are virtualized — the
bootstrap below forces enough host devices, and it MUST run before the
first jax import (jax locks the device count at first init, same
contract as launch/dryrun.py). ``--compress-tp`` opts the quantized
layers' TP all-reduces into the int8-compressed collective.
"""
from __future__ import annotations

import sys

from repro.launch._boot import force_host_devices_for_tp

force_host_devices_for_tp(sys.argv)  # before the jax import below

import argparse
import functools
import time

import jax

from repro.core.execution import CiMExecSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.models.registry import get_config
from repro.quant.prepare import ternarize_params
from repro.serve.engine import ContinuousBatcher, Request


def parse_exec_spec(text: str) -> CiMExecSpec:
    """``formulation[/backend[/packing[/flavor]]]`` -> CiMExecSpec."""
    parts = text.split("/")
    if len(parts) > 4:
        raise ValueError(f"bad exec spec {text!r} (at most 4 '/'-fields)")
    fields = ("formulation", "backend", "packing", "flavor")
    return CiMExecSpec(**dict(zip(fields, parts)))


def init_params(cfg, tp: int, seed: int = 0):
    """Random parameters from ``seed``; with ``tp > 1`` each leaf is
    created already sharded over the first ``tp`` devices (the mesh the
    engine, or replica 0 of the front door, serves on)."""
    key = jax.random.PRNGKey(seed)
    if tp <= 1:
        return T.init_params(key, cfg)
    from repro.dist.sharding import init_sharded
    from repro.launch.mesh import make_tp_mesh

    return init_sharded(
        functools.partial(T.init_params, cfg=cfg), key, make_tp_mesh(tp))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--s-max", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--exec-spec", default=None, metavar="FORM[/BACKEND[/PACKING[/FLAVOR]]]",
                    help="serve under an explicit CiM execution spec, e.g. "
                         "'exact/jnp', 'blocked', 'bitplane/jnp/bitplane_u8/II'")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy), applied on device")
    ap.add_argument("--seed", type=int, default=0, help="sampling PRNG seed")
    ap.add_argument("--loop-decode", action="store_true",
                    help="use the legacy per-slot-loop decode baseline "
                         "instead of the fused ragged-position step")
    ap.add_argument("--tp", type=int, default=1, metavar="N",
                    help="tensor-parallel degree: serve over an N-device "
                         "('data', 'model') mesh (params/caches/planes "
                         "sharded; CPU forces virtual host devices)")
    ap.add_argument("--compress-tp", action="store_true",
                    help="route the quantized layers' TP all-reduces "
                         "through the int8-compressed collective "
                         "(requires --tp > 1 and a quantized mode)")
    ap.add_argument("--prepare-weights", action="store_true",
                    help="run quant.prepare.prepare_for_spec once at startup "
                         "(requires --exec-spec): folded ternary weights, and "
                         "pre-packed planes for bitplane_u8 packing")
    ap.add_argument("--pre-quantize", action="store_true",
                    help="fold ternarization into weights offline")
    ap.add_argument("--profile", default=None, metavar="TRACE.jsonl",
                    help="record per-step timing events (serve.prefill / "
                         "serve.decode_step / serve.prepare) to a JSON-lines "
                         "trace file — repro.profile reads it back for "
                         "calibration and replay")
    ap.add_argument("--serve-http", action="store_true",
                    help="start the async HTTP/WebSocket front door "
                         "(repro.serve.frontdoor) instead of the one-shot "
                         "batch run; serves until interrupted")
    ap.add_argument("--replicas", type=int, default=1, metavar="N",
                    help="engine replicas behind the front-door router "
                         "(each a full ContinuousBatcher; with --tp > 1 "
                         "each replica gets its own disjoint (1, tp) mesh)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8471,
                    help="front-door TCP port (0 = ephemeral)")
    ap.add_argument("--queue-limit", type=int, default=64,
                    help="admission cap: total in-flight requests across "
                         "replicas; over it, new requests get 429")
    ap.add_argument("--pace-us", type=float, default=0.0, dest="pace_us",
                    help="modeled per-step device latency in microseconds, "
                         "slept off-GIL in each replica's worker thread "
                         "(benchmarks/bench_traffic.py uses this to make "
                         "replica scaling measurable on CPU hosts; 0 = off)")
    ap.add_argument("--selftest", action="store_true",
                    help="front-door smoke: start --serve-http on an "
                         "ephemeral port, stream one request, cancel a "
                         "second mid-stream, check /stats, shut down "
                         "cleanly, exit 0")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    params = init_params(cfg, args.tp)
    if args.pre_quantize:
        import dataclasses

        params = ternarize_params(params)
        cfg = cfg.replace(quant=dataclasses.replace(cfg.quant, pre_quantized=True))
    exec_spec = parse_exec_spec(args.exec_spec) if args.exec_spec else None
    if args.prepare_weights and exec_spec is None:
        ap.error("--prepare-weights requires --exec-spec")
    if args.compress_tp and args.tp <= 1:
        ap.error("--compress-tp requires --tp > 1")
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    if args.selftest:
        args.serve_http = True
        args.port = 0  # ephemeral: the selftest races no other listener
    if args.serve_http:
        return _serve_http_main(args, cfg, params, exec_spec)
    mesh = None
    if args.tp > 1:
        from repro.launch.mesh import make_tp_mesh

        mesh = make_tp_mesh(args.tp)
    batcher = ContinuousBatcher(
        params, cfg, n_slots=args.slots, s_max=args.s_max,
        exec_spec=exec_spec, temperature=args.temperature, seed=args.seed,
        fused=not args.loop_decode, prepare_weights=args.prepare_weights,
        mesh=mesh, compress_tp=args.compress_tp, profile=args.profile,
    )
    reqs = [
        Request(i, [1 + (i * 7 + j) % (cfg.vocab - 1) for j in range(1 + i % 4)],
                max_new=2 + i % args.max_new)
        for i in range(args.requests)
    ]
    for r in reqs:
        batcher.submit(r)
    t0 = time.perf_counter()
    batcher.run()
    dt = time.perf_counter() - t0
    toks = sum(len(r.generated) for r in reqs)
    stats = batcher.stats()
    dev = jax.devices()[0]
    print(f"[serve] {len(reqs)} requests, {toks} tokens, {dt:.2f}s "
          f"({toks / max(dt, 1e-9):.1f} tok/s on {dev.platform} {dev.device_kind}), "
          f"{stats['decode_steps']} decode steps, "
          f"{stats['host_syncs']} host syncs "
          f"({'looped' if args.loop_decode else 'fused'} decode"
          + (f", tp={args.tp}" + (" int8-compressed" if args.compress_tp else "")
             if args.tp > 1 else "") + ")")
    if args.profile:
        n_ev = len(batcher.profiler.events)
        print(f"[serve] profile: {n_ev} trace events -> {args.profile}")
    assert all(r.done for r in reqs)
    return 0


# ---------------------------------------------------------------------------
# --serve-http: the async front door (repro.serve.frontdoor)
# ---------------------------------------------------------------------------


def build_frontdoor(args, cfg, params, exec_spec, batchers=None):
    """(FrontDoor, profiler) for the parsed args: N replica batchers
    (disjoint (1, tp) meshes when --tp > 1 or the devices suffice for
    one each), one router, one tracker. ``batchers`` puts the door in
    front of engines already built (``args.profile`` must then be off).
    Shared with benchmarks/bench_traffic.py and chip_smoke.py so they
    serve through the identical stack."""
    from repro.serve.frontdoor import (
        EngineWorker,
        FrontDoor,
        ReplicaRouter,
        SLOTracker,
    )

    meshes = [None] * args.replicas
    if args.tp > 1 or 1 < args.replicas <= len(jax.devices()):
        # one disjoint device group per replica (a (1, 1) mesh each at
        # tp=1); with fewer devices than tp=1 replicas they share one
        from repro.launch.mesh import make_replica_meshes

        meshes = make_replica_meshes(args.replicas, args.tp)
    profiler = None
    if args.profile:
        from repro.profile.trace import Profiler

        # one trace file for every replica AND the frontdoor.request
        # events — the profiler appends per event, so streams interleave
        profiler = Profiler(args.profile)
    batchers = batchers or [
        ContinuousBatcher(
            params, cfg, n_slots=args.slots, s_max=args.s_max,
            exec_spec=exec_spec, temperature=args.temperature,
            seed=args.seed, fused=not args.loop_decode,
            prepare_weights=args.prepare_weights, mesh=meshes[i],
            compress_tp=args.compress_tp, profile=profiler,
        )
        for i in range(args.replicas)
    ]
    tracker = SLOTracker(
        profiler=profiler,
        exec_spec=args.exec_spec or "mode:off",
        mesh={"data": args.replicas, "model": args.tp} if args.tp > 1 else None,
    )
    workers = [EngineWorker(f"r{i}", b, tracker,
                            pace_us=getattr(args, "pace_us", 0.0))
               for i, b in enumerate(batchers)]
    router = ReplicaRouter(workers, queue_limit=args.queue_limit)
    return FrontDoor(router, tracker, host=args.host, port=args.port), profiler


async def selftest_session(door) -> None:
    """The CI front-door smoke: one full streamed request, one
    cancelled mid-stream, /stats agrees, nothing left in flight."""
    from repro.serve.frontdoor.client import WSClient, http_json

    host, port = door.host, door.port
    ws = await WSClient.connect(host, port)
    full = await ws.generate([1, 2, 3], max_new=6)
    assert len(full["tokens"]) == 6, full
    assert full["done"]["cancelled"] is False, full
    part = await ws.generate([4, 5], max_new=32, cancel_after=2)
    assert part["done"]["cancelled"] is True, part
    assert 2 <= len(part["tokens"]) < 32, part
    await ws.close()
    status, stats = await http_json(host, port, "GET", "/stats")
    assert status == 200, (status, stats)
    reqs = stats["slo"]["requests"]
    assert reqs["completed"] == 1 and reqs["cancelled"] == 1, reqs
    assert stats["router"]["in_flight"] == 0, stats["router"]
    print(f"[serve] selftest: streamed {len(full['tokens'])} tokens, "
          f"cancelled after {len(part['tokens'])}, /stats consistent")


async def _serve_http_async(args, cfg, params, exec_spec) -> int:
    import asyncio
    import signal

    door, profiler = build_frontdoor(args, cfg, params, exec_spec)
    host, port = await door.start()
    n_rep, n_tp = args.replicas, args.tp
    print(f"[serve] front door on http://{host}:{port} "
          f"({n_rep} replica{'s' if n_rep != 1 else ''}"
          + (f", tp={n_tp}" if n_tp > 1 else "")
          + f", queue-limit {args.queue_limit}) — "
          "routes: /healthz /stats /v1/generate /v1/stream")
    try:
        if args.selftest:
            await selftest_session(door)
        else:
            stop = asyncio.Event()
            loop = asyncio.get_running_loop()
            for sig in (signal.SIGINT, signal.SIGTERM):
                try:
                    loop.add_signal_handler(sig, stop.set)
                except NotImplementedError:
                    pass  # non-unix event loops: rely on KeyboardInterrupt
            await stop.wait()
            print("[serve] draining...")
    finally:
        await door.stop()
        if profiler is not None:
            profiler.close()
    for w in door.router.workers:
        assert not w.load, f"replica {w.name} still has load after stop"
    print("[serve] clean shutdown"
          + (" — selftest ok" if args.selftest else ""))
    return 0


def _serve_http_main(args, cfg, params, exec_spec) -> int:
    import asyncio

    try:
        return asyncio.run(_serve_http_async(args, cfg, params, exec_spec))
    except KeyboardInterrupt:
        print("[serve] interrupted")
        return 130


if __name__ == "__main__":
    raise SystemExit(main())
