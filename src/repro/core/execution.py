"""Declarative CiM execution API — the single dispatch point for every
signed-ternary MAC in the repo (re-exported as ``repro.api``).

Motivation (DESIGN.md §3): the SiTe CiM dot product used to be reachable
through seven parallel entry points (``site_cim_matmul``,
``site_cim_matmul_corrected``, ``site_cim_matmul_bitplane``,
``nm_ternary_matmul``, ``kernels.ops.cim_matmul``,
``exact_ternary_matmul``, ``packed_cim_matmul``), each with its own
padding/dtype/VJP/backend-selection logic. TiM-DNN and STeP-CiM show the
same functional MAC semantics recur across array technologies — so the
dispatch is now data, not code:

    spec = CiMExecSpec(formulation="blocked", backend="auto")
    out  = execute(spec, x_t, w_t)

``CiMExecSpec`` names *what* to compute (formulation, ADC clamp, flavor,
sensing-error channel) and *how* (backend kernel, weight packing). A
registry maps resolved ``(formulation, backend, packing)`` keys to kernel
functions; new formulations/kernels plug in with ``register_backend``
without touching any call site. One shared shim owns:

  * leading-batch-dim flattening (kernels see (M, K) x (K, N)),
  * contraction-dim padding to the block granularity (zero rows are
    inert under the a/b event-count semantics),
  * the straight-through-estimator ``custom_vjp`` (backward = exact
    matmul; the ADC clamp is piecewise linear with slope 1 almost
    everywhere — DESIGN.md §4),
  * the stochastic sensing-error channel (±1 ADC-level flips per block
    partial, paper rate 3.1e-3),
  * output dtype restoration (results return in the input dtype).

Built-in formulations:

  exact     — near-memory baseline, no clamp (paper's NM design).
  blocked   — faithful per-16-row a/b event counts + 3-bit ADC clamp.
  corrected — clip-as-correction: exact full-depth dot + rare clamp
              correction term (numerically == blocked, DESIGN.md §2).
  bitplane  — event counting over the (M1, M2) bitplanes; mirrors the
              circuit directly and serves as the structural test oracle.
  fused     — two full-depth dots (signed + magnitude) + elementwise
              combine; the Pallas kernel's HLO cost structure for
              dry-run/roofline work (numerically == exact).

Backends: ``jnp`` lowers everywhere (CPU, autodiff tracing, pjit);
``pallas`` uses the TPU kernels in repro.kernels (interpret mode off
TPU); ``pallas_stream`` is the double-buffered streaming decode variant
(plane DMA overlapped with the MAC — DESIGN.md §14); ``auto`` resolves
to pallas on TPU else jnp. Packing ``bitplane_u8`` stores weights as two
packed uint8 bitplanes, 2 bits per ternary weight (the memory-macro
layout; 8x less HBM weight traffic than int8).

Shape-aware dispatch (DESIGN.md §9): pallas registry entries carry a
*tile table* — ``(bm, bk, bn)`` as a function of (M, K, N) — with a
**decode class** (M <= :data:`DECODE_M_MAX`) that selects small-M tiles
instead of padding every activation to the 128-row MXU tile (a 3-slot
decode step would waste >97% of the MXU rows). ``tiles_for`` resolves
the tiles for a call (autotuned winners first, then the entry's table)
*outside* the jit boundary, so the choice participates in the trace
cache key; :func:`autotune` benchmarks the registered candidates per
(spec, shape-class) and caches winners for every later ``execute``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import threading
from typing import Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import ternary as tern
from repro.kernels import ref
from repro.kernels.packed_mac import (
    packed_cim_matmul,
    packed_cim_matmul_decode,
    packed_cim_matmul_decode_stream,
)
from repro.kernels.ternary_mac import ternary_cim_matmul, ternary_exact_matmul

FORMULATIONS = ("exact", "blocked", "corrected", "bitplane", "fused")
BACKENDS = ("auto", "pallas", "jnp")
PACKINGS = ("none", "bitplane_u8")
FLAVORS = ("I", "II")


@dataclasses.dataclass(frozen=True)
class CiMExecSpec:
    """Declarative description of one ternary-MAC execution.

    formulation: exact | blocked | corrected | bitplane | fused (or any
      name later added via :func:`register_backend`).
    backend:     auto | pallas | jnp ("auto" = pallas on TPU, else jnp).
    packing:     none | bitplane_u8 (2-bit differential weight storage).
    flavor:      "I" | "II" — identical MAC math; the flavors differ in
      circuits/latency/energy (core/cost_model.py; see
      :func:`spec_design` for the cost-model mapping).
    block:       rows asserted per array cycle (paper N_A = 16).
    adc_max:     ADC clamp bound for the a/b event counts (3-bit + extra
      sense amp = 8). Only clamping formulations consume it.
    error_prob:  per-block sensing-error probability (paper: 3.1e-3);
      requires a PRNG key at :func:`execute` time when > 0.
    """

    formulation: str = "blocked"
    backend: str = "auto"
    packing: str = "none"
    flavor: str = "I"
    block: int = 16
    adc_max: int = 8
    error_prob: float = 0.0

    def __post_init__(self):
        # formulation/backend/packing are open sets: anything a plugin
        # has put in the registry is valid, so validation accepts the
        # built-ins plus every registered key dimension (typos still die
        # early; genuinely new names registered via register_backend
        # pass). "auto" stays backend-only.
        if not self.formulation or not isinstance(self.formulation, str):
            raise ValueError(f"bad formulation {self.formulation!r}")
        formulations = set(FORMULATIONS) | {k[0] for k in _REGISTRY}
        if self.formulation not in formulations:
            raise ValueError(
                f"unknown formulation {self.formulation!r} "
                f"(use one of {sorted(formulations)})"
            )
        backends = set(BACKENDS) | {k[1] for k in _REGISTRY}
        if self.backend not in backends:
            raise ValueError(
                f"unknown backend {self.backend!r} (use one of {sorted(backends)})"
            )
        packings = set(PACKINGS) | {k[2] for k in _REGISTRY}
        if self.packing not in packings:
            raise ValueError(
                f"unknown packing {self.packing!r} (use one of {sorted(packings)})"
            )
        if self.flavor not in FLAVORS:
            raise ValueError(f"unknown SiTe CiM flavor {self.flavor!r}")
        if self.block <= 0:
            raise ValueError(f"block must be positive, got {self.block}")
        if self.adc_max <= 0:
            raise ValueError(f"adc_max must be positive, got {self.adc_max}")

    def resolve(self) -> "CiMExecSpec":
        """Fix "auto" to a concrete backend for the current platform."""
        if self.backend != "auto":
            return self
        backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
        return dataclasses.replace(self, backend=backend)

    @property
    def clamps(self) -> bool:
        entry = _REGISTRY.get(self.resolve().registry_key)
        if entry is not None:
            return entry.clamps
        return self.formulation in ("blocked", "corrected", "bitplane")

    @property
    def registry_key(self) -> Tuple[str, str, str]:
        return (self.formulation, self.backend, self.packing)

    @property
    def name(self) -> str:
        return "/".join(self.registry_key)


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BackendEntry:
    """One registered MAC kernel: the callable plus the registry's
    static metadata about it (whether the formulation clamps, and the
    tile table for tiled backends) — see :func:`register_backend`."""

    fn: Callable  # fn(x2d, w, spec[, tiles]) -> (M, N); K padded to block
    clamps: bool  # whether the formulation applies the ADC clamp
    # (m, k, n) -> (bm, bk, bn) tile table; None = kernel has no tiling
    # dimension (jnp formulations). When set, ``fn`` takes a 4th ``tiles``
    # argument and the shim resolves it via tiles_for outside the jit.
    # Streaming entries return 4-tuples (bm, bk, bn, nbuf) — nbuf is the
    # VMEM buffer depth of the DMA double buffer.
    tiles: Optional[Callable[[int, int, int], Tuple[int, ...]]] = None
    # per-shape-class autotune candidates overriding the global
    # _TILE_CANDIDATES (entries whose tile tuples carry extra dimensions
    # — e.g. the stream backend's buffer depth — sweep their own grid)
    tile_candidates: Optional[Dict[str, Tuple[Tuple[int, ...], ...]]] = None


_REGISTRY: Dict[Tuple[str, str, str], BackendEntry] = {}


def _parse_key(name) -> Tuple[str, str, str]:
    if isinstance(name, tuple):
        key = name
    else:
        key = tuple(str(name).split("/"))
    if len(key) != 3:
        raise ValueError(
            f"backend key must be 'formulation/backend/packing', got {name!r}"
        )
    return key  # type: ignore[return-value]


def register_backend(name, fn: Callable, *, clamps: bool = True,
                     tiles: Optional[Callable] = None,
                     tile_candidates: Optional[Dict] = None) -> None:
    """Register a MAC kernel under a ``"formulation/backend/packing"``
    key (or an equivalent 3-tuple). ``fn(x2d, w_t, spec)`` receives the
    flattened (M, K) inputs with K padded to the block/packing
    granularity and must return the (M, N) product. ``clamps`` records
    whether the formulation applies the per-block ADC clamp (tests use it
    to pick the right oracle configuration).

    ``tiles``: optional ``(m, k, n) -> (bm, bk, bn)`` tile table for
    tiled (pallas) kernels. When given, ``fn`` is called as
    ``fn(x2d, w_t, spec, tiles)`` with the resolved tile triple (an
    autotuned winner when one is cached, else the table's answer for the
    call's shape class — see :func:`tiles_for`).

    ``tile_candidates``: optional per-shape-class candidate grid for
    :func:`autotune` (entries with non-standard tile tuples — the stream
    backend's ``(bm, bk, bn, nbuf)`` — own their sweep)."""
    key = _parse_key(name)
    if key[1] == "auto":
        raise ValueError("register concrete backends, not 'auto'")
    _REGISTRY[key] = BackendEntry(fn, bool(clamps), tiles, tile_candidates)


def get_backend(spec: CiMExecSpec) -> BackendEntry:
    """The :class:`BackendEntry` registered for ``spec`` (after
    ``resolve()``); raises KeyError listing the known keys."""
    key = spec.resolve().registry_key
    entry = _REGISTRY.get(key)
    if entry is None:
        known = ", ".join("/".join(k) for k in sorted(_REGISTRY))
        raise KeyError(f"no backend registered for {'/'.join(key)} (known: {known})")
    return entry


def registered_specs() -> Iterator[CiMExecSpec]:
    """One CiMExecSpec per registered (formulation, backend, packing)."""
    for f, b, p in sorted(_REGISTRY):
        yield CiMExecSpec(formulation=f, backend=b, packing=p)


# ---------------------------------------------------------------------------
# Shape classes, tile tables, autotune (DESIGN.md §9)
# ---------------------------------------------------------------------------

# decode regime boundary: at M <= 8 the MAC is weight-streaming-bound and
# padding M to the 128-row MXU tile wastes >93% of the rows
DECODE_M_MAX = 8

SHAPE_CLASSES = ("decode", "prefill")

# autotuned winners: {(registry_key, block, shape_class): (bm, bk, bn)}
# — block is part of the key because it sets the bk validity granularity
# (a winner tuned at block=16 may not tile a block=64 spec)
_TILE_CACHE: Dict[Tuple, Tuple[int, int, int]] = {}

# benchmark/test lever: force every call into one shape class (None = off)
_CLASS_OVERRIDE: Optional[str] = None

# Guards _TILE_CACHE and _CLASS_OVERRIDE: the front door's ReplicaRouter
# drives N ContinuousBatchers from N single-thread executors, so
# tiles_for races autotune/override writes without it. Dict reads of
# CPython builtins are atomic, but the override read-compose-lookup in
# tiles_for is not — and the override context manager below must
# restore the *pre-entry* value even under interleaving.
_DISPATCH_LOCK = threading.Lock()


def shape_class(m: int) -> str:
    """The dispatch class of an (M, K) x (K, N) MAC: "decode" for
    M <= DECODE_M_MAX (ragged decode steps, M = occupied slots), else
    "prefill" (prompt/training shapes that fill MXU tiles)."""
    return "decode" if m <= DECODE_M_MAX else "prefill"


class _ShapeClassOverride:
    """Handle returned by :func:`set_shape_class_override`. The override
    is already installed at construction; using the handle as a context
    manager restores the previous value on exit, so

        with set_shape_class_override("prefill"):
            ...

    is exception-safe, while the historical imperative call (ignore the
    return value, later call ``set_shape_class_override(None)``) keeps
    working unchanged."""

    def __init__(self, prev: Optional[str]):
        self._prev = prev

    def __enter__(self) -> "_ShapeClassOverride":
        return self

    def __exit__(self, *exc) -> bool:
        set_shape_class_override(self._prev)
        return False


def set_shape_class_override(cls: Optional[str]) -> _ShapeClassOverride:
    """Force tile resolution into one shape class regardless of M (the
    pre-PR behaviour is ``"prefill"`` — decode shapes padded to the
    128-row tile). Benchmarks use it to measure old-vs-new on the same
    shape; None restores shape-derived dispatch. Affects new traces only
    (tiles are resolved per call, outside jit). Returns a context
    manager restoring the previous override on exit (optional — plain
    imperative use stays valid). Thread-safe."""
    global _CLASS_OVERRIDE
    if cls is not None and cls not in SHAPE_CLASSES:
        raise ValueError(f"unknown shape class {cls!r} (use {SHAPE_CLASSES})")
    with _DISPATCH_LOCK:
        prev = _CLASS_OVERRIDE
        _CLASS_OVERRIDE = cls
    return _ShapeClassOverride(prev)


def clear_tile_cache() -> None:
    """Drop every autotuned winner (tests / re-tuning). Thread-safe."""
    with _DISPATCH_LOCK:
        _TILE_CACHE.clear()


def tiles_for(
    spec: CiMExecSpec, m: int, k: int, n: int
) -> Optional[Tuple[int, int, int]]:
    """Resolve the (bm, bk, bn) tiles an ``execute`` call will use: an
    autotuned winner for (spec, shape-class) when cached, else the
    registry entry's tile table. None for untiled (jnp) backends.

    Resolved *outside* the jitted forward so the choice is part of the
    trace cache key — flipping the override or re-autotuning retraces
    instead of silently reusing a stale executable."""
    spec = spec.resolve()
    entry = _REGISTRY.get(spec.registry_key)
    if entry is None or entry.tiles is None:
        return None
    with _DISPATCH_LOCK:
        cls = _CLASS_OVERRIDE or shape_class(m)
        cached = _TILE_CACHE.get((spec.registry_key, spec.block, cls))
    if cached is not None:
        return cached
    # an override crossing the natural class substitutes a representative
    # M so the entry table answers for the *forced* class
    if cls != shape_class(m):
        m = DECODE_M_MAX if cls == "decode" else 128
    return entry.tiles(m, k, n)


# tile candidates swept by autotune(), per shape class
_TILE_CANDIDATES: Dict[str, Tuple[Tuple[int, int, int], ...]] = {
    "decode": ((8, 128, 128), (8, 256, 128), (8, 512, 128), (8, 256, 256)),
    "prefill": ((128, 128, 128), (128, 256, 128), (128, 512, 128),
                (256, 256, 128), (128, 256, 256)),
}

# the stream backend's own grid: the 4th element is the VMEM buffer
# depth nbuf ∈ {2, 3} of the DMA double/triple buffer (prefill rows
# delegate to the non-stream prefill kernel, so only tiles matter there)
_STREAM_TILE_CANDIDATES: Dict[str, Tuple[Tuple[int, ...], ...]] = {
    "decode": ((8, 128, 128, 2), (8, 256, 128, 2), (8, 256, 128, 3),
               (8, 512, 128, 2), (8, 512, 128, 3), (8, 256, 256, 2)),
    "prefill": ((128, 256, 128, 2), (128, 512, 128, 2), (128, 256, 256, 2)),
}


def _tiles_valid(spec: CiMExecSpec, tiles: Tuple[int, ...]) -> bool:
    if len(tiles) not in (3, 4):
        return False
    bm, bk, bn = tiles[:3]
    if len(tiles) == 4 and tiles[3] not in (2, 3):
        return False  # stream buffer depth: double or triple buffering
    if spec.packing == "bitplane_u8":
        return bk % (8 * spec.block) == 0  # whole packed bytes, whole blocks
    return bk % spec.block == 0  # the ADC clamp never straddles a K tile


def autotune(
    spec: CiMExecSpec,
    shapes: Tuple[Tuple[int, int, int], ...] = ((4, 1024, 512), (256, 1024, 512)),
    *,
    candidates: Optional[Dict[str, Tuple[Tuple[int, int, int], ...]]] = None,
    repeats: int = 3,
    calibration=None,
) -> Dict[str, Dict]:
    """Benchmark the registered tile candidates for ``spec`` on one
    representative (M, K, N) per shape class and cache the winners —
    every later :func:`execute`/:func:`execute_packed` at that
    (spec, shape-class) picks them up (new traces; run before serving).

    With ``calibration=`` (a ``repro.profile.CalibrationTable`` or any
    object with a ``tile_winners`` mapping), no timing runs: the table's
    recorded winners for ``spec`` are validated and installed directly —
    replaying a past autotune instead of re-measuring on a possibly
    noisy host.

    Entries with their own candidate grids (``tile_candidates`` on the
    registry entry) sweep those instead of the global table — the
    ``pallas_stream`` backend's grid includes the DMA buffer depth
    ``nbuf`` ∈ {2, 3} as a 4th tile element.

    Returns ``{shape_class: {"tiles": winner, "us": best_us,
    "candidates": {"bmxbkxbn": us}}}``. Raises for untiled backends —
    jnp formulations have no tile dimension to tune."""
    import time

    import numpy as np

    spec = spec.resolve()
    entry = get_backend(spec)
    if entry.tiles is None:
        raise ValueError(
            f"{spec.name} has no tile table to autotune (jnp backends "
            f"lower through XLA; only tiled pallas entries tune)"
        )
    if calibration is not None:
        winners = dict(getattr(calibration, "tile_winners", {}) or {})
        per_spec = winners.get(spec.name)
        if not per_spec:
            raise ValueError(
                f"calibration table has no tile winners for {spec.name} "
                f"(known: {sorted(winners)})"
            )
        report = {}
        for cls, tiles in sorted(per_spec.items()):
            if cls not in SHAPE_CLASSES:
                raise ValueError(f"unknown shape class {cls!r} in calibration")
            tiles = tuple(int(t) for t in tiles)
            if not _tiles_valid(spec, tiles):
                raise ValueError(
                    f"calibrated tiles {tiles} invalid for {spec.name} "
                    f"(block={spec.block})"
                )
            with _DISPATCH_LOCK:
                _TILE_CACHE[(spec.registry_key, spec.block, cls)] = tiles
            report[cls] = {"tiles": tiles, "us": None, "candidates": {},
                           "source": "calibration"}
        return report
    key = jax.random.PRNGKey(0)
    report: Dict[str, Dict] = {}
    for m, k, n in shapes:
        cls = shape_class(m)
        kx, kw = jax.random.split(jax.random.fold_in(key, m))
        x = jnp.sign(jax.random.normal(kx, (m, k))).astype(jnp.float32)
        w = jnp.sign(jax.random.normal(kw, (k, n))).astype(jnp.float32)
        if spec.packing == "bitplane_u8":
            from repro.core import ternary as _tern

            p1, p2 = _tern.pack_ternary(w.astype(jnp.int8), axis=0)

            def run(tiles):
                return _packed_forward(spec, tiles, x, p1, p2, n)
        else:

            def run(tiles):
                return _jit_execute(spec, tiles, x, w)

        cands = (candidates or entry.tile_candidates or _TILE_CANDIDATES)[cls]
        timings: Dict[str, float] = {}
        best: Optional[Tuple[int, int, int]] = None
        for tiles in cands:
            if not _tiles_valid(spec, tiles):
                continue
            # analysis: host-sync ok — autotune timing must block the host
            run(tiles).block_until_ready()  # compile outside the clock
            times = []
            for _ in range(max(1, repeats)):
                t0 = time.perf_counter()
                # analysis: host-sync ok — autotune timing must block the host
                run(tiles).block_until_ready()
                times.append(time.perf_counter() - t0)
            us = float(np.min(times) * 1e6)
            timings["x".join(map(str, tiles))] = round(us, 2)
            if best is None or us < timings["x".join(map(str, best))]:
                best = tiles
        if best is None:
            raise ValueError(f"no valid tile candidate for {spec.name}/{cls}")
        with _DISPATCH_LOCK:
            _TILE_CACHE[(spec.registry_key, spec.block, cls)] = best
        report[cls] = {
            "tiles": best,
            "us": timings["x".join(map(str, best))],
            "candidates": timings,
        }
    return report


def canonical_plane_layout(spec: CiMExecSpec) -> Tuple[int, int]:
    """(K multiple, N multiple) of the **canonical stored-plane layout**
    for ``spec``: the granularity ``quant.prepare.prepare_for_spec`` pads
    packed bitplanes to at prepare time, chosen so the *default* tile
    tables of both shape classes divide it — ``execute_packed`` then
    consumes the stored planes with zero per-step padding/relayout
    (autotuned non-default winners may still re-pad per call, which is
    correct, merely slower). jnp packed backends tile nothing; their
    canonical granularity is the block/byte lcm."""
    spec = spec.resolve()
    entry = _REGISTRY.get(spec.registry_key)
    base = math.lcm(spec.block, 8)
    if entry is None or entry.tiles is None:
        return base, 1
    k_mult, n_mult = base, 1
    # query the table at a representative large (K, N): the canonical
    # layout is one granularity for the whole weight tree, so tables
    # that scale tiles with the shape answer for the unclamped regime
    big = 1 << 20
    for m in (1, 128):
        t = entry.tiles(m, big, big)  # (bm, bk, bn[, nbuf])
        k_mult = math.lcm(k_mult, max(int(t[1]), 1))
        n_mult = math.lcm(n_mult, max(int(t[2]), 1))
    return k_mult, n_mult


# ---------------------------------------------------------------------------
# The shared execution shim
# ---------------------------------------------------------------------------


def _pad_axis(x: jax.Array, mult: int, axis: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


def _forward(
    spec: CiMExecSpec, x: jax.Array, w: jax.Array, tiles=None
) -> jax.Array:
    entry = get_backend(spec)
    lead, k, n = x.shape[:-1], x.shape[-1], w.shape[-1]
    x2 = x.reshape((-1, k))
    mult = spec.block if spec.packing == "none" else math.lcm(spec.block, 8)
    xp, wp = _pad_axis(x2, mult, 1), _pad_axis(w, mult, 0)
    if entry.tiles is None:
        out = entry.fn(xp, wp, spec)
    else:
        out = entry.fn(xp, wp, spec, tiles or tiles_for(spec, x2.shape[0], k, n))
    return out.reshape(lead + (n,)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ste_execute(
    spec: CiMExecSpec, tiles, x: jax.Array, w: jax.Array
) -> jax.Array:
    return _forward(spec, x, w, tiles)


def _ste_fwd(spec, tiles, x, w):
    return _ste_execute(spec, tiles, x, w), (x, w)


def _ste_bwd(spec, tiles, res, g):
    # Straight-through past the clamp: exact-matmul gradients (for the
    # exact/fused formulations this IS the true gradient). Clamping
    # formulations accumulate the STE backward in f32; exact/fused keep
    # the operand dtype so backward TP partial-sum all-reduces stay at
    # the activation width (bf16 in training — §Perf A4).
    x, w = res
    acc = jnp.float32 if spec.clamps else x.dtype
    gf = g.astype(acc)
    dx = jnp.einsum("...n,kn->...k", gf, w.astype(acc)).astype(x.dtype)
    dw = jnp.einsum("...k,...n->kn", x.astype(acc), gf).astype(w.dtype)
    return dx, dw


_ste_execute.defvjp(_ste_fwd, _ste_bwd)

_jit_execute = jax.jit(_ste_execute, static_argnums=(0, 1))


# ---------------------------------------------------------------------------
# Profiler sink (repro.profile.trace — DESIGN.md §11)
# ---------------------------------------------------------------------------

#: installed by repro.profile.trace.set_profiler; None = profiling off.
#: The disabled cost is one None comparison per entry-point call.
_PROFILE_SINK: Optional[Callable] = None


def set_profile_sink(sink: Optional[Callable]) -> None:
    """Install (or, with None, remove) the kernel-event sink the eager
    ``execute``/``execute_packed`` entry points report wall times to.
    Wired by :func:`repro.profile.trace.set_profiler` — use that, not
    this, unless you are building a custom trace consumer."""
    global _PROFILE_SINK
    _PROFILE_SINK = sink


def _profiled_call(entry, spec, probe, m, k, n, weight_bytes, thunk):
    """Run ``thunk()``; when a profiler sink is installed AND the call
    is eager (``probe`` is not a tracer — timing under a jit trace is
    meaningless and would force a callback into the jaxpr), time it and
    emit one kernel-level trace event."""
    sink = _PROFILE_SINK
    if sink is None or isinstance(probe, jax.core.Tracer):
        return thunk()
    import time

    t0 = time.perf_counter()
    out = thunk()
    t1 = time.perf_counter()
    # analysis: host-sync ok — profiler wall-time capture; opt-in (sink
    # installed) and never under a jit trace (tracer-probed above)
    jax.block_until_ready(out)
    t2 = time.perf_counter()
    sink(
        entry_point=entry,
        exec_spec=spec.name,
        shape_class=_CLASS_OVERRIDE or shape_class(m),
        mesh=None,
        wall_us=(t2 - t0) * 1e6,
        dispatch_us=(t1 - t0) * 1e6,
        meta={"m": int(m), "k": int(k), "n": int(n),
              "macs": int(m) * int(k) * int(n),
              "weight_bytes": int(weight_bytes)},
    )
    return out


def _apply_sense_channel(spec, out, k_dim, key):
    """Shared post-MAC sensing-error application (validation + noise)."""
    if spec.error_prob <= 0.0:
        return out
    if not spec.clamps:
        raise ValueError(
            f"the sensing-error channel models the ADC readout; the "
            f"{spec.formulation!r} formulation has no ADC (use a "
            f"clamping formulation or error_prob=0)"
        )
    if key is None:
        raise ValueError("spec.error_prob > 0 requires a PRNG key")
    kb = -(-k_dim // spec.block)
    noise = _sense_noise(key, out.shape, kb, spec.error_prob, out.dtype)
    return out + jax.lax.stop_gradient(noise)


def _sense_noise(key, shape, kb: int, prob: float, dtype) -> jax.Array:
    """Additive equivalent of the per-block ±1 ADC-level error channel:
    each of the ``kb`` block partials behind an output flips one level
    with probability ``prob``; the PCU sums them."""
    ku, ks = jax.random.split(key)
    flip = jax.random.bernoulli(ku, prob, shape + (kb,))
    base = dtype if jnp.issubdtype(dtype, jnp.floating) else jnp.int32
    sign = jax.random.rademacher(ks, shape + (kb,), dtype=base)
    return jnp.sum(flip.astype(base) * sign, axis=-1).astype(dtype)


def execute(
    spec: CiMExecSpec,
    x_t: jax.Array,
    w_t: jax.Array,
    *,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Run one ternary MAC under ``spec``.

    x_t: (..., K) ternary values in {-1, 0, +1} (any numeric dtype).
    w_t: (K, N) ternary values.
    key: PRNG key for the sensing-error channel (required iff
      ``spec.error_prob > 0``).

    Returns (..., N) in the dtype of ``x_t``, with gradients defined
    straight-through (exact-matmul backward).

    NOTE: with ``packing="bitplane_u8"`` this functional entry point
    packs ``w_t`` on the fly inside the forward — correct, and what the
    equivalence tests pin, but it realizes none of the packed format's
    weight-traffic savings. Serving should pack offline
    (``quant.prepare.prepare_for_spec``) and call
    :func:`execute_packed` with the stored planes.
    """
    spec = spec.resolve()
    clean = dataclasses.replace(spec, error_prob=0.0)
    m = math.prod(x_t.shape[:-1])
    k_dim, n_dim = x_t.shape[-1], w_t.shape[-1]
    tiles = tiles_for(clean, m, k_dim, n_dim)
    out = _profiled_call(
        "execution.execute", clean, x_t, m, k_dim, n_dim,
        k_dim * n_dim * jnp.dtype(w_t.dtype).itemsize,
        lambda: _jit_execute(clean, tiles, x_t, w_t),
    )
    return _apply_sense_channel(spec, out, x_t.shape[-1], key)


@functools.partial(jax.jit, static_argnums=(0, 1, 5))
def _packed_forward(spec, tiles, x, w_pos, w_neg, n_out):
    lead, k = x.shape[:-1], x.shape[-1]
    x2 = x.reshape((-1, k))
    # lift x to the stored planes' K depth (canonical planes carry K
    # already padded — zero activation rows are inert); legacy same-K
    # planes pad both sides to the block/byte granularity as before
    mult = math.lcm(spec.block, 8)
    k_target = max(w_pos.shape[-2] * 8, -(-k // mult) * mult)
    out = _packed_stored(
        _pad_axis(x2, k_target, 1),
        _pad_axis(w_pos, k_target // 8, 0),
        _pad_axis(w_neg, k_target // 8, 0),
        spec,
        tiles,
    )
    return out[:, :n_out].reshape(lead + (n_out,)).astype(x.dtype)


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _packed_stream_forward(spec, tiles, x, w_int, n_out):
    """Stream-backend twin of :func:`_packed_forward`: the weight side is
    ONE (K/4, N) plane-interleaved array (layout version 1 — see
    ``repro.core.ternary.interleave_planes``), DMA'd tile-by-tile by the
    streaming decode kernel. Canonical version-1 planes enter with zero
    per-step padding/relayout, exactly like the legacy path."""
    lead, k = x.shape[:-1], x.shape[-1]
    x2 = x.reshape((-1, k))
    mult = math.lcm(spec.block, 8)
    k_target = max(w_int.shape[-2] * 4, -(-k // mult) * mult)
    out = _packed_stream_mac(
        _pad_axis(x2, k_target, 1),
        _pad_axis(w_int, k_target // 4, 0),
        spec,
        tiles,
        spec.clamps,
    )
    return out[:, :n_out].reshape(lead + (n_out,)).astype(x.dtype)


def execute_packed(
    spec: CiMExecSpec,
    x_t: jax.Array,
    w_pos,
    w_neg: Optional[jax.Array] = None,
    *,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Packed-weight fast path: run a ternary MAC from **pre-packed**
    (M1, M2) bitplanes — the 2-bit storage format ``quant.prepare``
    emits — without ever materializing the dense weight or re-packing
    per call (this is where the 8x-vs-int8 weight-traffic saving of
    ``bitplane_u8`` is actually realized; :func:`execute` with
    ``packing="bitplane_u8"`` packs on the fly and is for functional
    work only).

    x_t: (..., K) ternary values. The weight side is either

      * ``w_pos``/``w_neg``: (K/8, N) uint8 planes
        (``repro.core.ternary.pack_ternary`` layout along K), or
      * one :class:`repro.core.ternary.PackedPlanes` — the canonical
        pre-padded layout ``quant.prepare.prepare_for_spec`` stores
        (pass it as ``w_pos``, leave ``w_neg`` unset). Its planes enter
        the kernel with **zero** per-step padding/relayout and the
        result slices back to the recorded logical N; decode-class M
        (<= DECODE_M_MAX) pads M only to the small decode tile, never
        to 128 (both pinned by jaxpr tests).

    The spec's formulation selects clamped ("blocked") or exact MAC
    semantics. Inference path — no custom VJP over the packed planes.

    ``x_t`` must hold exact ternary values: the decode-class pallas path
    computes in int8/int32 (DESIGN.md §9), so fractional activations —
    already outside this function's contract — would *truncate* there
    while the bf16 prefill path would not.
    """
    from repro.core.ternary import PackedPlanes

    spec = spec.resolve()
    if spec.packing != "bitplane_u8":
        raise ValueError("execute_packed requires packing='bitplane_u8'")
    if spec.formulation not in ("exact", "blocked"):
        raise ValueError(
            f"packed kernels implement exact|blocked, not {spec.formulation!r}"
        )
    stream = spec.backend == "pallas_stream"
    if isinstance(w_pos, PackedPlanes):
        planes = w_pos
        if w_neg is not None:
            raise ValueError("pass PackedPlanes alone (it carries both planes)")
        if planes.pos.ndim != 2:
            raise ValueError(
                f"stacked planes {planes.pos.shape}: slice one layer first "
                f"(PackedPlanes.layer(i))"
            )
        if x_t.shape[-1] != planes.k:
            raise ValueError(
                f"plane/input shape mismatch: x K={x_t.shape[-1]}, "
                f"logical plane K={planes.k}"
            )
        n_out = planes.n
        if stream:
            # free on canonical version-1 planes; an (eager) interleave
            # on legacy-layout planes — old stored planes still load
            w_int = planes.interleaved()
        else:
            # free on legacy planes; de-interleaves version-1 storage
            w_pos, w_neg = planes.planes()
    else:
        if w_neg is None:
            raise ValueError("raw planes need both w_pos and w_neg")
        if x_t.shape[-1] != w_pos.shape[0] * 8 or w_pos.shape != w_neg.shape:
            raise ValueError(
                f"plane/input shape mismatch: x K={x_t.shape[-1]}, "
                f"planes {w_pos.shape} / {w_neg.shape}"
            )
        n_out = w_pos.shape[-1]
        if stream:
            w_int = tern.interleave_planes(w_pos, w_neg)
    clean = dataclasses.replace(spec, error_prob=0.0)
    m = math.prod(x_t.shape[:-1])
    if stream:
        k_dim, n_cols = w_int.shape[-2] * 4, w_int.shape[-1]
        tiles = tiles_for(clean, m, k_dim, n_cols)
        out = _profiled_call(
            "execution.execute_packed", clean, x_t, m, k_dim, n_out,
            int(w_int.size),
            lambda: _packed_stream_forward(clean, tiles, x_t, w_int, n_out),
        )
    else:
        k_dim = w_pos.shape[0] * 8
        tiles = tiles_for(clean, m, k_dim, w_pos.shape[-1])
        out = _profiled_call(
            "execution.execute_packed", clean, x_t, m, k_dim, n_out,
            int(w_pos.size) + int(w_neg.size),
            lambda: _packed_forward(clean, tiles, x_t, w_pos, w_neg, n_out),
        )
    return _apply_sense_channel(spec, out, x_t.shape[-1], key)


# ---------------------------------------------------------------------------
# Tensor-parallel execution (explicit shard_map path)
# ---------------------------------------------------------------------------

def needs_manual_spmd(spec: CiMExecSpec) -> bool:
    """Whether ``spec`` runs a Pallas kernel. On a TPU a Pallas kernel
    is a Mosaic custom call that XLA's SPMD partitioner refuses to
    split, so under a sharded mesh it must run inside a shard_map
    (:func:`execute_tp`)."""
    return spec.resolve().backend in ("pallas", "pallas_stream")


def execute_tp(
    spec: CiMExecSpec,
    x_t: jax.Array,
    w_t: jax.Array,
    mesh,
    *,
    axis_name: str = "model",
    compressed: bool = False,
    key: Optional[jax.Array] = None,
    split: str = "row",
) -> jax.Array:
    """Tensor-parallel ternary MAC over a mesh axis (explicit manual SPMD).

    ``split="row"`` (the default) splits the contraction dim K over
    ``axis_name``: each device runs the registered kernel on its
    K-shard and the partial sums all-reduce
    through :func:`repro.dist.collectives.tp_allreduce`. K is padded so
    every shard holds *whole* ``spec.block`` blocks — the per-block ADC
    clamp then never straddles a device boundary, the per-shard partials
    are integer event counts, and the f32 psum is exact: TP execution is
    **bit-identical** to :func:`execute` for every built-in formulation
    (pinned in tests/test_tp_serve.py).

    ``compressed=True`` narrows the all-reduce wire to int8 (stochastic
    rounding; ``key`` seeds the per-shard rounding streams). Without a
    ``key`` the stream is **deterministic and idempotent** — a pure
    function of the operand shape — so identical calls return identical
    results and serving stays reproducible across retraces. The flip
    side: same-shaped call sites, scan-stacked layers, and repeated
    decode steps all reuse the same noise, making the rounding error a
    fixed perturbation rather than zero-mean noise that averages out.
    The *unbiasedness* property (tests/test_collectives.py) applies
    across fresh keys — thread ``key`` per call to get it. This is the
    opt-in trade: 4x less collective traffic for quantization-level
    error — the exact path is the default.

    ``split="col"`` splits the output dim N instead (column-parallel
    weights): x is replicated, each device computes its own output
    columns, no collective. When N does not divide the axis the weight
    is replicated (``param_specs`` leaves it unsharded too) and every
    device computes the whole product. ``compressed`` applies to the
    row split only.

    This is the *explicit* TP entry point (shard_map — the collective is
    named in the program). Serving under plain sharded params/caches uses
    the implicit GSPMD path for jnp backends; the engine routes through
    this function for ``compress_tp=True`` (the partitioner cannot be
    told to compress its own all-reduces) and for Pallas backends, whose
    Mosaic kernels the partitioner cannot split at all
    (:func:`needs_manual_spmd`). Inference-only: no custom VJP is
    defined over the shard_map.
    """
    from repro.dist.collectives import tp_allreduce

    spec = spec.resolve()
    if spec.packing != "none":
        raise ValueError(
            "execute_tp splits the contraction dim; packed (K-major 2-bit) "
            "planes shard over N instead — use execute_packed with "
            "N-sharded planes (dist.sharding.packed_specs) or the "
            "explicit column-parallel execute_packed_tp"
        )
    if spec.error_prob > 0.0:
        raise ValueError(
            "execute_tp is the serving TP path; drive the sensing-error "
            "channel through execute/execute_packed (error_prob=0 here)"
        )
    if split not in ("row", "col"):
        raise ValueError(f"unknown split {split!r} (row | col)")
    entry = get_backend(spec)
    tp = int(mesh.shape[axis_name])
    lead, k, n = x_t.shape[:-1], x_t.shape[-1], w_t.shape[-1]
    x2 = x_t.reshape((-1, k))
    from jax.sharding import PartitionSpec as _P

    if split == "col":
        n_axis = axis_name if n % tp == 0 else None
        xc = _pad_axis(x2, spec.block, 1)
        wc = _pad_axis(w_t, spec.block, 0)
        tiles = tiles_for(spec, xc.shape[0], xc.shape[1],
                          n // tp if n_axis else n)

        def local_col(xs, ws):
            if entry.tiles is None:
                return entry.fn(xs, ws, spec)
            return entry.fn(xs, ws, spec, tiles)

        f = jax.shard_map(
            local_col, mesh=mesh,
            in_specs=(_P(), _P(None, n_axis)), out_specs=_P(None, n_axis),
            check_vma=False,  # pallas_call has no replication rule
        )
        return f(xc, wc).reshape(lead + (n,)).astype(x_t.dtype)
    # whole blocks per shard: pad K to (block granularity) * tp
    mult = spec.block * tp
    x2 = _pad_axis(x2, mult, 1)
    wp = _pad_axis(w_t, mult, 0)
    if key is None:
        # idempotent default stream — a pure function of the operand
        # shape (trace-time constants), so identical calls round
        # identically; see the docstring for what stays correlated
        salt = (k * 1000003 + n * 8191) % (1 << 30)
        key = jax.random.fold_in(jax.random.PRNGKey(0), salt)
    keys = jax.random.split(key, tp)
    # per-shard tiles for tiled (pallas) entries, resolved on the shard's
    # local K extent (the shape the kernel actually sees)
    tiles = tiles_for(spec, x2.shape[0], x2.shape[1] // tp, n)

    def local(xs, ws, ks):
        if entry.tiles is None:
            part = entry.fn(xs, ws, spec)
        else:
            part = entry.fn(xs, ws, spec, tiles)
        return tp_allreduce(part, axis_name, key=ks[0], compressed=compressed)

    f = jax.shard_map(
        local, mesh=mesh,
        in_specs=(_P(None, axis_name), _P(axis_name, None), _P(axis_name)),
        out_specs=_P(),
        check_vma=False,  # pallas_call has no replication rule
    )
    return f(x2, wp, keys).reshape(lead + (n,)).astype(x_t.dtype)


def execute_packed_tp(
    spec: CiMExecSpec,
    x_t: jax.Array,
    planes,
    mesh,
    *,
    axis_name: str = "model",
) -> jax.Array:
    """Column-parallel packed MAC over N-sharded stored planes (explicit
    shard_map) — the TP twin of :func:`execute_packed`.

    The packed (K-major 2-bit) planes shard over their *output* dim N
    (``dist.sharding.packed_specs`` layout): each device runs the packed
    kernel on its local (rows, N/tp) plane shard and the shards
    concatenate. Column sharding never splits the contraction, so no
    collective runs and TP is trivially **bit-identical** to the
    single-device :func:`execute_packed` (pinned in
    tests/test_stream_decode.py).

    Decode-class shapes under a ``pallas_stream`` spec route through the
    double-buffered streaming kernel per shard — each device overlaps
    its own plane DMA with its MAC, which is exactly the regime the
    N-sharded serving weights are in. ``planes`` must be a 2-D
    :class:`repro.core.ternary.PackedPlanes`; its *padded* N must divide
    the mesh axis.
    """
    from jax.sharding import PartitionSpec as _P

    spec = spec.resolve()
    if spec.packing != "bitplane_u8":
        raise ValueError("execute_packed_tp requires packing='bitplane_u8'")
    if spec.error_prob > 0.0:
        raise ValueError(
            "execute_packed_tp is the serving TP path; drive the sensing-"
            "error channel through execute_packed (error_prob=0 here)"
        )
    if not isinstance(planes, tern.PackedPlanes):
        raise ValueError("execute_packed_tp consumes stored PackedPlanes")
    if planes.pos.ndim != 2:
        raise ValueError(
            f"stacked planes {planes.pos.shape}: slice one layer first "
            f"(PackedPlanes.layer(i))"
        )
    if x_t.shape[-1] != planes.k:
        raise ValueError(
            f"plane/input shape mismatch: x K={x_t.shape[-1]}, "
            f"logical plane K={planes.k}"
        )
    tp = int(mesh.shape[axis_name])
    n_pad = int(planes.pos.shape[-1])
    if n_pad % tp != 0:
        raise ValueError(
            f"padded plane N={n_pad} does not divide the {axis_name!r} "
            f"axis ({tp} devices) — re-prepare with the mesh "
            f"(quant.prepare.prepare_for_spec(mesh=...))"
        )
    stream = spec.backend == "pallas_stream"
    lead, k = x_t.shape[:-1], x_t.shape[-1]
    x2 = x_t.reshape((-1, k))
    m = x2.shape[0]
    if stream:
        w_int = planes.interleaved()
        k_dim = w_int.shape[-2] * 4
        tiles = tiles_for(spec, m, k_dim, n_pad // tp)

        def local(xs, wl):
            return _packed_stream_forward(spec, tiles, xs, wl, wl.shape[-1])

        f = jax.shard_map(
            local, mesh=mesh,
            in_specs=(_P(), _P(None, axis_name)),
            out_specs=_P(None, axis_name),
            check_vma=False,  # pallas_call has no replication rule
        )
        out = f(x2, w_int)
    else:
        w_pos, w_neg = planes.planes()
        k_dim = w_pos.shape[-2] * 8
        tiles = tiles_for(spec, m, k_dim, n_pad // tp)

        def local(xs, wp, wn):
            return _packed_forward(spec, tiles, xs, wp, wn, wp.shape[-1])

        f = jax.shard_map(
            local, mesh=mesh,
            in_specs=(_P(), _P(None, axis_name), _P(None, axis_name)),
            out_specs=_P(None, axis_name),
            check_vma=False,  # pallas_call has no replication rule
        )
        out = f(x2, w_pos, w_neg)
    return out[:, :planes.n].reshape(lead + (planes.n,)).astype(x_t.dtype)


# ---------------------------------------------------------------------------
# Built-in backends
# ---------------------------------------------------------------------------


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---- jnp ------------------------------------------------------------------


def _exact_jnp(x2, w, spec):
    # operand-dtype dot: keeps TP partial-sum all-reduces at the
    # activation width (bf16 in training — §Perf A4)
    return jnp.einsum("mk,kn->mn", x2, w.astype(x2.dtype))


def _blocked_jnp(x2, w, spec):
    return ref.ref_cim_matmul(
        x2.astype(jnp.float32), w.astype(jnp.float32),
        block=spec.block, adc_max=spec.adc_max,
    )


def _corrected_jnp(x2, w, spec):
    """exact + sum_blk(relu(b-adc) - relu(a-adc)): the bulk contraction
    is one full-depth MXU matmul; only the rare saturation correction
    needs blocked arithmetic (DESIGN.md §2)."""
    xf = x2.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    exact = xf @ wf
    kb = xf.shape[1] // spec.block
    xb = xf.reshape(xf.shape[0], kb, spec.block)
    wb = wf.reshape(kb, spec.block, wf.shape[1])
    p = jnp.einsum("mki,kin->mkn", xb, wb)
    m = jnp.einsum("mki,kin->mkn", jnp.abs(xb), jnp.abs(wb))
    a = (m + p) * 0.5
    b = (m - p) * 0.5
    adc = float(spec.adc_max)
    corr = jnp.maximum(b - adc, 0.0) - jnp.maximum(a - adc, 0.0)
    return exact + jnp.sum(corr, axis=1)


def _bitplane_jnp(x2, w, spec):
    """Event counting over (M1, M2) bitplanes — mirrors the circuit:
    a = #(RWL1&M1) + #(RWL2&M2), b = #(RWL1&M2) + #(RWL2&M1)."""
    m1 = (w > 0).astype(jnp.int32)
    m2 = (w < 0).astype(jnp.int32)
    r1 = (x2 > 0).astype(jnp.int32)
    r2 = (x2 < 0).astype(jnp.int32)
    kb = x2.shape[1] // spec.block
    r1b = r1.reshape(r1.shape[0], kb, spec.block)
    r2b = r2.reshape(r2.shape[0], kb, spec.block)
    m1b = m1.reshape(kb, spec.block, m1.shape[1])
    m2b = m2.reshape(kb, spec.block, m2.shape[1])
    a = jnp.einsum("mki,kin->mkn", r1b, m1b) + jnp.einsum("mki,kin->mkn", r2b, m2b)
    b = jnp.einsum("mki,kin->mkn", r1b, m2b) + jnp.einsum("mki,kin->mkn", r2b, m1b)
    part = jnp.minimum(a, spec.adc_max) - jnp.minimum(b, spec.adc_max)
    return jnp.sum(part, axis=1)


def _fused_jnp(x2, w, spec):
    """Pallas-kernel cost structure: signed + magnitude full-depth dots,
    elementwise combine (== exact; per-block clamping happens inside the
    kernel's VMEM tiles on TPU). The large `minimum` bound keeps XLA from
    folding the magnitude dot away."""
    wd = w.astype(x2.dtype)
    p = jnp.einsum("mk,kn->mn", x2, wd)
    m = jnp.einsum("mk,kn->mn", jnp.abs(x2), jnp.abs(wd))
    big = jnp.asarray(2.0**14, jnp.float32)
    pf, mf = p.astype(jnp.float32), m.astype(jnp.float32)
    return jnp.minimum((mf + pf) * 0.5, big) - jnp.minimum((mf - pf) * 0.5, big)


# ---- pallas ---------------------------------------------------------------

# built-in tile tables: decode class (M <= DECODE_M_MAX) takes the small
# 8-row M tile — the kernels then pad M to 8, not 128 — prefill keeps the
# pre-§9 MXU-filling tiles


def _blocked_tiles(m, k, n):
    return (8, 128, 128) if m <= DECODE_M_MAX else (128, 128, 128)


def _exact_tiles(m, k, n):
    return (8, 512, 128) if m <= DECODE_M_MAX else (128, 512, 128)


def _packed_tiles(m, k, n):
    return (8, 256, 128) if m <= DECODE_M_MAX else (128, 256, 128)


def _packed_stream_tiles(m, k, n):
    # 4th element = DMA buffer depth (nbuf); prefill rows delegate to
    # the non-stream prefill kernel, which ignores it
    return (8, 256, 128, 2) if m <= DECODE_M_MAX else (128, 256, 128, 2)


def _blocked_pallas(x2, w, spec, tiles):
    m, n = x2.shape[0], w.shape[1]
    bm, bk, bn = tiles
    xp = _pad_axis(_pad_axis(x2, bm, 0), bk, 1)
    wp = _pad_axis(_pad_axis(w, bk, 0), bn, 1)
    out = ternary_cim_matmul(
        xp.astype(jnp.bfloat16), wp.astype(jnp.bfloat16),
        block=spec.block, adc_max=spec.adc_max,
        bm=bm, bk=bk, bn=bn,
        interpret=not _on_tpu(),
    )
    return out[:m, :n]


def _exact_pallas(x2, w, spec, tiles):
    m, n = x2.shape[0], w.shape[1]
    bm, bk, bn = tiles
    xp = _pad_axis(_pad_axis(x2, bm, 0), bk, 1)
    wp = _pad_axis(_pad_axis(w, bk, 0), bn, 1)
    out = ternary_exact_matmul(
        xp.astype(jnp.bfloat16), wp.astype(jnp.bfloat16),
        bm=bm, bk=bk, bn=bn,
        interpret=not _on_tpu(),
    )
    return out[:m, :n]


def _pad_planes(w_pos, w_neg, rows: int, cols: int):
    """Pad stored (K/8, N) planes to a kernel tile granularity — a no-op
    (nothing enters the jaxpr) when the planes are already canonical
    (quant.prepare.prepare_for_spec emits them pre-padded)."""
    return (
        _pad_axis(_pad_axis(w_pos, rows, 0), cols, 1),
        _pad_axis(_pad_axis(w_neg, rows, 0), cols, 1),
    )


def _packed_planes_mac(x2, w_pos, w_neg, spec, tiles, cim: bool, pallas: bool):
    """The shared packed-plane MAC behind both the functional `_packed`
    path and the stored-plane `_packed_stored` fast path: pad planes to
    the tile granularity (shared helper; no-op on canonical layouts) and
    dispatch the decode- or prefill-shaped kernel by the M tile."""
    m, n = x2.shape[0], w_pos.shape[1]
    if not pallas:
        return ref.ref_packed_matmul(
            x2.astype(jnp.float32), w_pos, w_neg,
            block=spec.block, adc_max=spec.adc_max, cim=cim,
        )
    bm, bk, bn = tiles or _packed_tiles(m, x2.shape[1], n)
    xp = _pad_axis(x2, bk, 1)
    pp, pn = _pad_planes(w_pos, w_neg, bk // 8, bn)
    if bm <= DECODE_M_MAX:
        # decode class: whole-M grid steps, int8 operands, int32 a/b
        # accumulation — M pads to the 8-row decode tile, never to 128
        out = packed_cim_matmul_decode(
            _pad_axis(xp, bm, 0).astype(jnp.int8), pp, pn,
            block=spec.block, adc_max=spec.adc_max, cim=cim,
            bk=bk, bn=bn, interpret=not _on_tpu(),
        ).astype(jnp.float32)
    else:
        out = packed_cim_matmul(
            _pad_axis(xp, bm, 0).astype(jnp.bfloat16), pp, pn,
            block=spec.block, adc_max=spec.adc_max, cim=cim,
            bm=bm, bk=bk, bn=bn, interpret=not _on_tpu(),
        )
    return out[:m, :n]


def _packed_stream_mac(x2, w_int, spec, tiles, cim: bool):
    """Streaming MAC from ONE plane-interleaved (K/4, N) uint8 array
    (layout version 1). Decode-class M takes the double-buffered
    streaming kernel — the (k, j) tile DMA rides ``nbuf`` VMEM slots
    ahead of the int32 MAC; prefill-class M de-interleaves (a reshape,
    never a pad) and delegates to the prefill kernel, which already
    pipelines its grid."""
    m, n = x2.shape[0], w_int.shape[1]
    tl = tiles or _packed_stream_tiles(m, x2.shape[1], n)
    bm, bk, bn = tl[0], tl[1], tl[2]
    nbuf = tl[3] if len(tl) > 3 else 2
    if bm <= DECODE_M_MAX:
        xp = _pad_axis(x2, bk, 1)
        wi = _pad_axis(_pad_axis(w_int, bk // 4, 0), bn, 1)
        out = packed_cim_matmul_decode_stream(
            _pad_axis(xp, bm, 0).astype(jnp.int8), wi,
            block=spec.block, adc_max=spec.adc_max, cim=cim,
            bk=bk, bn=bn, nbuf=nbuf, interpret=not _on_tpu(),
        ).astype(jnp.float32)
        return out[:m, :n]
    w_pos, w_neg = tern.deinterleave_planes(w_int)
    return _packed_planes_mac(
        x2, w_pos, w_neg, spec, (bm, bk, bn), cim, pallas=True
    )


def _packed(x2, w, spec, tiles=None, *, cim: bool, pallas: bool):
    """Functional packed path (dense ternary w in hand): pack **once**
    at the logical K extent, then pad the 2-bit planes — not the dense
    weight — to the tile granularity (the pre-§9 version padded w to the
    full K tile first and packed the padded array every call)."""
    w_pos, w_neg = tern.pack_ternary(w.astype(jnp.int8), axis=0)
    return _packed_planes_mac(x2, w_pos, w_neg, spec, tiles, cim, pallas)


def _packed_stream(x2, w, spec, tiles=None, *, cim: bool):
    """Functional stream path: pack once, interleave the planes (layout
    version 1), stream."""
    w_pos, w_neg = tern.pack_ternary(w.astype(jnp.int8), axis=0)
    return _packed_stream_mac(
        x2, tern.interleave_planes(w_pos, w_neg), spec, tiles, cim
    )


def _packed_stored(x2, w_pos, w_neg, spec, tiles=None):
    """Packed MAC from stored planes (no per-call pack) — the
    execute_packed fast path."""
    if spec.backend == "pallas_stream":
        return _packed_stream_mac(
            x2, tern.interleave_planes(w_pos, w_neg), spec, tiles,
            spec.clamps,
        )
    return _packed_planes_mac(
        x2, w_pos, w_neg, spec, tiles, spec.clamps,
        pallas=spec.backend == "pallas",
    )


register_backend("exact/jnp/none", _exact_jnp, clamps=False)
register_backend("exact/pallas/none", _exact_pallas, clamps=False,
                 tiles=_exact_tiles)
register_backend(
    "exact/jnp/bitplane_u8",
    functools.partial(_packed, cim=False, pallas=False), clamps=False,
)
register_backend(
    "exact/pallas/bitplane_u8",
    functools.partial(_packed, cim=False, pallas=True), clamps=False,
    tiles=_packed_tiles,
)
register_backend("blocked/jnp/none", _blocked_jnp, clamps=True)
register_backend("blocked/pallas/none", _blocked_pallas, clamps=True,
                 tiles=_blocked_tiles)
register_backend(
    "blocked/jnp/bitplane_u8",
    functools.partial(_packed, cim=True, pallas=False), clamps=True,
)
register_backend(
    "blocked/pallas/bitplane_u8",
    functools.partial(_packed, cim=True, pallas=True), clamps=True,
    tiles=_packed_tiles,
)
register_backend(
    "exact/pallas_stream/bitplane_u8",
    functools.partial(_packed_stream, cim=False), clamps=False,
    tiles=_packed_stream_tiles, tile_candidates=_STREAM_TILE_CANDIDATES,
)
register_backend(
    "blocked/pallas_stream/bitplane_u8",
    functools.partial(_packed_stream, cim=True), clamps=True,
    tiles=_packed_stream_tiles, tile_candidates=_STREAM_TILE_CANDIDATES,
)
register_backend("corrected/jnp/none", _corrected_jnp, clamps=True)
register_backend("bitplane/jnp/none", _bitplane_jnp, clamps=True)
register_backend("fused/jnp/none", _fused_jnp, clamps=False)


# ---------------------------------------------------------------------------
# Spec -> hardware-model mapping (paper Section V / repro.hw)
# ---------------------------------------------------------------------------


def spec_design(spec: CiMExecSpec) -> str:
    """Map an execution spec onto the registered array designs. "exact"
    is the near-memory baseline; every CiM formulation — including
    "fused", the Pallas kernel's cost stand-in — executes on a SiTe
    array, flavor choosing the design through the ``repro.hw`` design
    registry. Unknown (plugged-in) formulations fall back on whether
    they clamp."""
    if spec.formulation == "exact":
        return "NM"
    if spec.formulation in FORMULATIONS or spec.clamps:
        from repro.hw import design_for_flavor

        return design_for_flavor(spec.flavor)
    return "NM"


def _bind_array(spec: CiMExecSpec, tech, array):
    """Bind an execution spec to a concrete ArraySpec: the ArraySpec
    supplies technology and geometry, the *execution* spec decides the
    design (an "exact" spec costs as the NM baseline of that array no
    matter how the ArraySpec was labelled). Without an array, a
    default-geometry array on ``tech`` (default 8T-SRAM). ``tech`` and
    ``array`` are mutually exclusive — the ArraySpec already names its
    technology, so accepting both would silently ignore one."""
    from repro import hw

    design = spec_design(spec)
    if array is None:
        return hw.ArraySpec(technology=tech or "8T-SRAM", design=design)
    if tech is not None:
        raise ValueError(
            f"pass either tech= or array=, not both (array already "
            f"names technology {array.technology!r}, got tech={tech!r})"
        )
    return array.with_design(design)


def spec_array_cost(spec: CiMExecSpec, tech=None, array=None):
    """Absolute array-level cost (latency/energy/area) of executing this
    spec — the dry-run/roofline's bridge from the execution API to the
    hardware model (``repro.hw``). See :func:`_bind_array` for how the
    optional ``tech`` (technology name, default 8T-SRAM) / ``array``
    (an :class:`repro.hw.ArraySpec`) binding works."""
    from repro import hw

    return hw.array_cost(_bind_array(spec, tech, array))


def spec_cost_summary(
    spec: CiMExecSpec, tech=None, array=None
) -> Dict[str, float]:
    """JSON-ready per-MAC-pass cost summary of ``spec`` on the bound
    array (same binding rules as :func:`spec_array_cost`): technology /
    design names plus the pass latency, energy, and relative area."""
    from repro import hw

    bound = _bind_array(spec, tech, array)
    cost = hw.array_cost(bound)
    return {
        "tech": cost.tech,
        "design": cost.design,
        "array": bound.name,
        "mac_pass_ns": cost.mac_pass_ns,
        "mac_pass_pj": cost.mac_pass_pj,
        "macro_area_vs_nm": cost.macro_area,
    }


# ---------------------------------------------------------------------------
# Tracing contracts (repro.analysis — DESIGN.md §10)
#
# The execution-shim invariants, declared where the shim lives. These
# drive the jaxpr auditor, the migrated jaxpr-pin tests, and the
# `python -m repro.analysis` CI ratchet from one table.
# ---------------------------------------------------------------------------

from repro.analysis.contracts import (  # noqa: E402
    PrimRule,
    SkipTrace,
    TraceContract,
    forbid_convert,
    register_trace_contract,
)


def _audit_planes(spec: CiMExecSpec, k: int = 512, n: int = 256):
    """Deterministic canonical PackedPlanes for audit traces — the
    prepare-time layout without initializing a model. K/N are chosen so
    no plane dim collides with the 128-row M tile (the decode-M rule
    below keys on a literal 128 leading dim)."""
    kw = jax.random.PRNGKey(7)
    w = jax.random.choice(kw, jnp.asarray([-1, 0, 1], jnp.int8), (k, n))
    p1, p2 = tern.pack_ternary(w, axis=0)
    k_mult, n_mult = canonical_plane_layout(spec)
    p1 = _pad_axis(_pad_axis(p1, k_mult // 8, 0), n_mult, 1)
    p2 = _pad_axis(_pad_axis(p2, k_mult // 8, 0), n_mult, 1)
    if spec.resolve().backend == "pallas_stream":
        # the canonical layout prepare_for_spec emits for stream specs:
        # plane-interleaved version 1 (DESIGN.md §14)
        wi = tern.interleave_planes(p1, p2)
        return tern.PackedPlanes(
            pos=wi, neg=wi[:0], scale=jnp.ones((n,), jnp.float32), k=k, n=n,
            layout_version=tern.PLANE_LAYOUT_STREAM,
        )
    return tern.PackedPlanes(
        pos=p1, neg=p2, scale=jnp.ones((n,), jnp.float32), k=k, n=n
    )


def no_decode_m128_rule() -> PrimRule:
    """No Pallas kernel on a decode-class trace may consume a 2-D
    operand padded to the 128-row MXU tile — the decode fast path pads
    M only to the 8-row decode tile (DESIGN.md §9)."""

    def _m128(eqn) -> bool:
        # uint8 operands are the stored 2-bit planes — their leading dim
        # is K/8 (or K/4 interleaved), not M, and may legitimately be 128
        return any(
            getattr(v.aval, "ndim", 0) == 2 and v.aval.shape[0] == 128
            and str(getattr(v.aval, "dtype", "")) != "uint8"
            for v in eqn.invars
        )

    return PrimRule(
        rule="decode-m-pad-128", prim="pallas_call", when=_m128,
        reason="decode shapes pad M to the 8-row decode tile, never 128",
    )


def _packed_decode_point(backend: str):
    """execute_packed over canonical stored planes at a decode shape
    (M=3) — the serving weight path."""

    def build():
        spec = CiMExecSpec(formulation="blocked", backend=backend,
                           packing="bitplane_u8")
        planes = _audit_planes(spec)
        kx = jax.random.PRNGKey(3)
        x = jax.random.choice(
            kx, jnp.asarray([-1, 0, 1], jnp.float32), (3, planes.k))

        def f(xv, pos, neg):
            lay = tern.PackedPlanes(pos=pos, neg=neg, scale=planes.scale,
                                    k=planes.k, n=planes.n,
                                    layout_version=planes.layout_version)
            return execute_packed(spec, xv, lay)

        return f, (x, planes.pos, planes.neg)

    return build


_PACKED_DECODE_RULES = dict(
    max_host_callbacks=0,
    no_pad_on_dtypes=("uint8",),
)

register_trace_contract(
    "execution.execute_packed.decode.jnp",
    _packed_decode_point("jnp"),
    TraceContract(**_PACKED_DECODE_RULES),
)

register_trace_contract(
    "execution.execute_packed.decode.pallas",
    _packed_decode_point("pallas"),
    TraceContract(
        **_PACKED_DECODE_RULES,
        accum_dtype="int32",
        forbid_prims=(
            no_decode_m128_rule(),
            forbid_convert(
                from_kinds=("int",), to=("float32", "float64", "bfloat16"),
                within="pallas_call",
                reason="decode-class event counts stay integer end-to-end",
            ),
        ),
    ),
)

# The streaming decode path inherits every pallas decode rule (int32
# accumulation, no uint8 pad — canonical version-1 planes enter the
# kernel untouched — no int→float convert, M never padded to 128) and
# adds the DMA-eqn pin: exactly nbuf (= 2 at the default tiles) async
# copy *starts* — the unrolled warm-up plus the in-loop prefetch — and
# ONE wait per trace. The pin is what makes the overlap auditable: a
# kernel that silently stops prefetching, or blocks on every tile,
# changes these counts before any benchmark notices (DESIGN.md §14).
register_trace_contract(
    "execution.execute_packed.decode.stream",
    _packed_decode_point("pallas_stream"),
    TraceContract(
        **_PACKED_DECODE_RULES,
        accum_dtype="int32",
        pin_prims=(("dma_start", 2), ("dma_wait", 1)),
        forbid_prims=(
            no_decode_m128_rule(),
            forbid_convert(
                from_kinds=("int",), to=("float32", "float64", "bfloat16"),
                within="pallas_call",
                reason="the streaming decode path keeps the int8/int32 "
                       "event-count datapath",
            ),
        ),
    ),
)


def _ste_backward_point(formulation: str = "exact"):
    """grad of ``formulation`` on bf16 operands — §Perf A4: the exact
    STE backward dots keep the operand dtype so TP all-reduce payloads
    stay at activation width (no f32[4,32] dx anywhere in the trace).
    The blocked formulation accumulates its STE backward in f32 by
    design — the tests use it as the rule's positive control."""

    def build():
        spec = CiMExecSpec(formulation=formulation, backend="jnp")
        x = jnp.ones((4, 32), jnp.bfloat16)
        w = jnp.ones((32, 3), jnp.bfloat16)
        f = jax.grad(
            lambda a, b: execute(spec, a, b).astype(jnp.float32).sum(),
            argnums=(0, 1),
        )
        return f, (x, w)

    return build


register_trace_contract(
    "execution.ste_backward.exact",
    _ste_backward_point(),
    TraceContract(forbid_dtype_shapes=(("float32", (4, 32)),)),
)


def _execute_tp_point():
    """The explicit shard_map TP route with the compressed int8
    collective: one primitive per all-reduce regardless of mesh size —
    the traced program must not grow with tp."""

    def build(tp: int = 2):
        if jax.device_count() < tp:
            raise SkipTrace(
                f"needs {tp} devices, have {jax.device_count()} "
                f"(XLA_FLAGS=--xla_force_host_platform_device_count=8)"
            )
        from repro.launch.mesh import make_tp_mesh

        mesh = make_tp_mesh(tp)
        spec = CiMExecSpec(formulation="blocked", backend="jnp")
        x = jnp.ones((4, 64), jnp.float32)
        w = jnp.ones((64, 32), jnp.float32)

        def f(a, b):
            return execute_tp(spec, a, b, mesh, compressed=True)

        return f, (x, w)

    return build


register_trace_contract(
    "execution.execute_tp.compressed",
    _execute_tp_point(),
    TraceContract(max_host_callbacks=0),
    axes={"tp": (2, 4)},
)
