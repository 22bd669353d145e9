#!/usr/bin/env python3
"""Chip smoke: drive the served path once on a TPU and check what comes out.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # four chips of one host

One chip, in one process:

  kernels    each CiM Pallas kernel (and the exact baseline) compiled,
             at smollm-135m decode and prefill widths, equal to the
             ``kernels/ref.py`` oracle;
  serve      smollm-135m at its published widths through
             ``ContinuousBatcher`` (8 slots, s_max 2048, seeded prompts
             of 16-512 tokens): the fused decode step holds the Mosaic
             kernel (``tpu_custom_call``), and its greedy tokens equal
             the same engine under ``blocked/jnp``;
  frontdoor  the ``--serve-http --selftest`` session against that engine.

``--four-chips`` runs only the paths that exist across chips:

  tp         starcoder2-15b at full width, parameters created sharded,
             served at tp=4 (per-device memory printed), and the same
             widths cut to 4 layers served at tp=4 and unsharded on one
             chip with identical tokens;
  replicas   four one-chip smollm-135m engines behind ``ReplicaRouter``,
             one per device, running the front-door selftest.

Weights are random from ``--seed``. Each phase prints its findings;
the last line is ``{"ok": true, "device": {...}}`` only when every phase
passed. With no TPU (``JAX_PLATFORMS=cpu`` included) the script exits
non-zero before any phase: there is no CPU fallback.
"""
from __future__ import annotations

import argparse
import asyncio
import gc
import json
import pathlib
import sys
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

SMOLLM = "smollm-135m"
STARCODER = "starcoder2-15b"


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def kernel_phase(seed: int) -> None:
    """Every CiM kernel, compiled, bit-equal to its oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.ternary import interleave_planes, pack_ternary
    from repro.kernels import ref
    from repro.kernels.packed_mac import (
        packed_cim_matmul,
        packed_cim_matmul_decode,
        packed_cim_matmul_decode_stream,
    )
    from repro.kernels.ternary_mac import ternary_cim_matmul, ternary_exact_matmul

    key = jax.random.PRNGKey(seed)

    def tern(k, shape, p_zero=0.3):
        # sparse-ish ternary operands so some 16-row blocks saturate the
        # ADC bound and others do not
        u = jax.random.uniform(k, shape)
        return jnp.where(u < p_zero, 0, jnp.where(u < (1 + p_zero) / 2, -1, 1))

    # smollm widths padded to the dispatch tiles: K 576 -> 768, N 1536
    k_dim, n_dim = 768, 1536
    kx, kw = jax.random.split(key)
    w = tern(kw, (k_dim, n_dim)).astype(jnp.int8)
    w_pos, w_neg = pack_ternary(w, axis=0)
    w_int = interleave_planes(w_pos, w_neg)
    wb = w.astype(jnp.bfloat16)
    cases = []
    for m in (8, 512):
        x = tern(jax.random.fold_in(kx, m), (m, k_dim))
        xb, x8 = x.astype(jnp.bfloat16), x.astype(jnp.int8)
        bm = 8 if m == 8 else 128
        cim = ref.ref_cim_matmul(xb, wb)
        exact = ref.ref_exact_matmul(xb, wb)
        packed = ref.ref_packed_matmul(xb, w_pos, w_neg)
        cls = "decode" if m == 8 else "prefill"
        cases += [
            (f"ternary_cim_matmul/{cls}",
             ternary_cim_matmul(xb, wb, bm=bm, bk=128, bn=128), cim),
            (f"ternary_exact_matmul/{cls}",
             ternary_exact_matmul(xb, wb, bm=bm, bk=256, bn=128), exact),
        ]
        if m == 8:
            cases += [
                ("packed_cim_matmul_decode",
                 packed_cim_matmul_decode(x8, w_pos, w_neg), packed),
                ("packed_cim_matmul_decode_stream",
                 packed_cim_matmul_decode_stream(x8, w_int), packed),
            ]
        else:
            cases.append(("packed_cim_matmul",
                          packed_cim_matmul(xb, w_pos, w_neg, bm=bm), packed))
    bad = []
    for name, got, want in cases:
        got = np.asarray(got).astype(np.float32)
        want = np.asarray(want)
        same = got.shape == want.shape and np.array_equal(got, want)
        log(f"kernel {name}: {got.shape} "
            + ("== oracle" if same else
               f"!= oracle (max |diff| {np.abs(got - want).max()})"))
        if not same:
            bad.append(name)
    if bad:
        raise AssertionError(f"kernels differ from the oracle: {bad}")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def request_mix(vocab: int, seed: int, lengths, max_news):
    """Seeded prompts: [(prompt tokens, max_new)]."""
    import numpy as np

    rng = np.random.default_rng(seed)
    return [(rng.integers(1, vocab, size=n).tolist(), m)
            for n, m in zip(lengths, max_news)]


def serve(batcher, mix):
    """Serve ``mix`` to completion; (generated tokens per request, s)."""
    from repro.serve.engine import Request

    reqs = [Request(i, list(p), max_new=m) for i, (p, m) in enumerate(mix)]
    for r in reqs:
        batcher.submit(r)
    t0 = time.perf_counter()
    batcher.run()
    dt = time.perf_counter() - t0
    assert all(r.done and not r.truncated for r in reqs), "unfinished request"
    return [r.generated for r in reqs], dt


def first_mismatch(a, b) -> str:
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            j = next((j for j, (p, q) in enumerate(zip(x, y)) if p != q),
                     min(len(x), len(y)))
            return f"request {i} differs first at token {j}: {x[j:j+4]} vs {y[j:j+4]}"
    return "token lists differ in length"


def fused_step_hlo(batcher) -> str:
    """Compiled HLO of the engine's fused decode step at its current
    state (the same function and donation the batcher jits)."""
    import jax
    import jax.numpy as jnp

    from repro.serve.engine import fused_decode_fn

    n = batcher.n_slots
    step = jax.jit(fused_decode_fn(batcher.cfg, batcher.temperature),
                   donate_argnums=(2,))
    zeros = jnp.zeros((n,), jnp.int32)
    return step.lower(batcher.params, zeros[:, None], batcher.caches, zeros,
                      zeros, jax.random.PRNGKey(0)).compile().as_text()


def peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", -1))


def serving_phase(cfg, params, *, n_slots, s_max, mix, device):
    """Serve ``mix`` through the default (compiled Pallas) path twice —
    cold, then warm for the rate — and once under blocked/jnp; the
    tokens must agree exactly. Returns the Pallas engine."""
    from repro.core.execution import CiMExecSpec
    from repro.serve.engine import ContinuousBatcher

    batcher = ContinuousBatcher(params, cfg, n_slots=n_slots, s_max=s_max)
    toks, cold = serve(batcher, mix)
    hlo = fused_step_hlo(batcher)
    n_kernels = hlo.count("tpu_custom_call")
    log(f"serve {cfg.name}: fused decode step holds {n_kernels} "
        "tpu_custom_call ops")
    if n_kernels == 0:
        raise AssertionError("fused decode step runs no Mosaic kernel")
    warm_toks, warm = serve(batcher, mix)
    if warm_toks != toks:
        raise AssertionError("warm rerun changed tokens: "
                             + first_mismatch(toks, warm_toks))
    n_tok = sum(len(t) for t in toks)
    log(f"serve {cfg.name} ({cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab}): {len(mix)} requests, {n_tok} tokens, slots {n_slots},"
        f" s_max {s_max}; cold {cold:.3f} s (compiles included), warm "
        f"{warm:.3f} s = {n_tok / warm:.2f} tokens/s on "
        f"{device.platform} {device.device_kind}; peak_bytes_in_use "
        f"{peak_bytes(device)}")
    ref = ContinuousBatcher(params, cfg, n_slots=n_slots, s_max=s_max,
                            exec_spec=CiMExecSpec("blocked", "jnp"))
    ref_toks, _ = serve(ref, mix)
    if ref_toks != toks:
        raise AssertionError("Pallas and blocked/jnp tokens differ: "
                             + first_mismatch(toks, ref_toks))
    log(f"serve {cfg.name}: greedy tokens identical to blocked/jnp "
        f"({n_tok} tokens)")
    return batcher


def frontdoor_args(**kw):
    base = dict(replicas=1, tp=1, profile=None, exec_spec=None, slots=8,
                s_max=2048, temperature=0.0, seed=0, loop_decode=False,
                prepare_weights=False, compress_tp=False, pace_us=0.0,
                queue_limit=64, host="127.0.0.1", port=0)
    base.update(kw)
    return argparse.Namespace(**base)


def run_selftest(door) -> None:
    """One ``--serve-http --selftest`` session against ``door``."""
    from repro.launch.serve import selftest_session

    async def session():
        await door.start()
        try:
            await selftest_session(door)
        finally:
            await door.stop()

    asyncio.run(session())
    busy = [w.name for w in door.router.workers if w.load]
    if busy:
        raise AssertionError(f"replicas still loaded after stop: {busy}")


def frontdoor_phase(cfg, batcher) -> None:
    from repro.launch.serve import build_frontdoor

    door, _ = build_frontdoor(frontdoor_args(), cfg, batcher.params, None,
                              batchers=[batcher])
    run_selftest(door)
    log("frontdoor: selftest ok against the full-width engine")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def device_memory(devices) -> str:
    parts = []
    for d in devices:
        st = d.memory_stats() or {}
        parts.append(f"{d.id}:{st.get('bytes_in_use', -1)}/"
                     f"{st.get('peak_bytes_in_use', -1)}")
    return "bytes_in_use/peak per device " + " ".join(parts)


def tp_phase(cfg, *, seed, n_slots, s_max, mix, cut_layers=4, tp=4) -> None:
    """Full-width TP serving, then the cut-depth TP-vs-one-chip identity."""
    import jax

    from repro.launch.mesh import make_tp_mesh
    from repro.launch.serve import init_params
    from repro.models import transformer as T
    from repro.serve.engine import ContinuousBatcher

    mesh = make_tp_mesh(tp)
    devs = list(mesh.devices.flat)

    cut = cfg.replace(n_layers=cut_layers)
    params = T.init_params(jax.random.PRNGKey(seed), cut)
    one, _ = serve(ContinuousBatcher(params, cut, n_slots=n_slots,
                                     s_max=s_max), mix)
    sharded, _ = serve(ContinuousBatcher(params, cut, n_slots=n_slots,
                                         s_max=s_max, mesh=mesh), mix)
    mismatch = None
    if sharded != one:
        # reported now, raised after the full-width run still ran
        mismatch = (f"{cut.name} cut to {cut_layers} layers: tp={tp} tokens "
                    "differ from one chip: " + first_mismatch(one, sharded))
        log(mismatch)
    else:
        log(f"tp {cut.name} cut to {cut_layers} layers: tp={tp} tokens "
            f"identical to one chip ({sum(map(len, one))} tokens)")
    # the engines hold reference cycles (their jitted closures): collect
    # them so the cut model's device buffers are gone before the full one
    del params
    gc.collect()

    t0 = time.perf_counter()
    params = init_params(cfg, tp, seed=seed)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"tp {cfg.name}: {n_params} parameters created sharded over {tp} "
        f"chips in {time.perf_counter() - t0:.3f} s; "
        + device_memory(devs))
    batcher = ContinuousBatcher(params, cfg, n_slots=n_slots, s_max=s_max,
                                mesh=mesh)
    toks, cold = serve(batcher, mix)
    toks2, warm = serve(batcher, mix)
    if toks2 != toks:
        raise AssertionError("warm rerun changed tokens: "
                             + first_mismatch(toks, toks2))
    n_tok = sum(map(len, toks))
    log(f"tp {cfg.name} ({cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads) tp={tp}: {len(mix)} "
        f"requests, {n_tok} tokens; cold {cold:.3f} s, warm {warm:.3f} s = "
        f"{n_tok / warm:.2f} tokens/s; " + device_memory(devs))
    if mismatch:
        raise AssertionError(mismatch)


def replica_phase(cfg, *, seed, replicas=4) -> None:
    """``replicas`` one-chip engines behind the router, one per device."""
    import jax

    from repro.launch.serve import build_frontdoor, init_params

    params = init_params(cfg, 1, seed=seed)
    args = frontdoor_args(replicas=replicas, slots=4, s_max=256)
    door, _ = build_frontdoor(args, cfg, params, None)
    placed = []
    for w in door.router.workers:
        devs = {d for leaf in jax.tree.leaves(w.batcher.params)
                for d in leaf.devices()}
        placed.append(devs)
    if any(len(d) != 1 for d in placed) or len(set().union(*placed)) != replicas:
        raise AssertionError(f"replica placement {placed}")
    log(f"replicas: {replicas} {cfg.name} engines on devices "
        f"{[next(iter(d)).id for d in placed]}")
    run_selftest(door)
    log(f"replicas: front-door selftest ok across {replicas} replicas")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the tp=4 and four-replica paths (4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[smoke] no TPU: JAX found {dev.platform} devices; "
              "this smoke runs on the chip only", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"[smoke] needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import init_params
    from repro.models.registry import get_config

    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {enable_compile_cache()}")
    phases = []
    if args.four_chips:
        sc = get_config(STARCODER)
        sc_mix = request_mix(sc.vocab, args.seed, (16, 64, 100, 128), (8, 6, 4, 8))
        phases.append(("tp", lambda: tp_phase(
            sc, seed=args.seed, n_slots=4, s_max=256, mix=sc_mix)))
        phases.append(("replicas", lambda: replica_phase(
            get_config(SMOLLM), seed=args.seed)))
    else:
        cfg = get_config(SMOLLM)
        mix = request_mix(
            cfg.vocab, args.seed,
            (16, 512, 97, 260, 33, 400, 128, 64, 40, 60),
            (24, 8, 32, 16, 12, 20, 28, 10, 16, 24))
        state = {}

        def serve_smollm():
            params = init_params(cfg, 1, seed=args.seed)
            state["engine"] = serving_phase(
                cfg, params, n_slots=8, s_max=2048, mix=mix, device=dev)

        def frontdoor():
            if "engine" not in state:
                raise AssertionError("no engine: the serve phase failed")
            frontdoor_phase(cfg, state["engine"])

        phases += [("kernels", lambda: kernel_phase(args.seed)),
                   ("serve", serve_smollm), ("frontdoor", frontdoor)]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
            log(f"phase {name}: ok ({time.perf_counter() - t0:.3f} s)")
        except Exception:
            traceback.print_exc()
            log(f"phase {name}: FAILED ({time.perf_counter() - t0:.3f} s)")
            failed.append(name)
        gc.collect()  # free the phase's engines before the next one
    if failed:
        log(f"failed phases: {failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
