"""Per-op trace capture — the "profile" leg of profile → calibrate →
replay (DESIGN.md §11).

The serving engine and the execution shim are instrumented with opt-in
timing hooks that record one :class:`TraceEvent` per jitted segment —
the fused decode step, the batched prefill, the offline weight prepare
(``ContinuousBatcher(profile=...)`` / ``launch/serve --profile``), and
every *eager* ``execute``/``execute_packed`` call while a profiler is
installed (:func:`set_profiler`). Events go to an in-memory list and,
when the profiler is path-backed, to a JSON-lines trace file
(:func:`read_trace` round-trips it).

Measuring device wall time requires blocking the host — exactly the
host-sync class the analysis lint polices (DESIGN.md §10). The
discipline here:

  * profiling is **opt-in**: with no profiler, :func:`wrap_step`
    returns the step function **unchanged** (the same object — bit- and
    jaxpr-identical by construction; the
    ``profile.step_instrumentation.disabled`` contract below pins it),
    and the execution shim's sink check is one ``None`` comparison;
  * the profiler's syncs happen **outside** the jit boundary and are
    never counted in the engine's ``host_syncs`` discipline stat;
  * every deliberate sync carries the standard justification marker.

Event schema (JSON-lines; ``v`` is :data:`TRACE_SCHEMA_VERSION`)::

    {"v": 1, "entry_point": "serve.decode_step", "exec_spec": "mode:off",
     "shape_class": "decode", "mesh": null, "wall_us": 812.4,
     "dispatch_us": 101.2, "meta": {"arch": "smollm-135m", "step": 3,
     "occupancy": 2, ...}}

``wall_us`` is host call → device completion (includes dispatch);
``dispatch_us`` is the host time to *enqueue* the work — their
difference isolates what the profiler's own sync added to the step, so
fused-step analyses can subtract it.

Apart from these events, :func:`span` marks host work in JAX's own
profiler trace (the engine's ``serve.*`` spans), and :data:`SCOPES`
names the ``jax.named_scope`` regions of the fused programs, which
:func:`hlo_op_names` and :func:`scope_of` read back from a compiled
program's HLO text. Neither blocks the host or changes a program.
"""
from __future__ import annotations

import dataclasses
import json
import re
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Mapping, Optional, Union

import jax

#: bump when the event schema changes; readers reject unknown versions
TRACE_SCHEMA_VERSION = 1

#: the fields every event must carry (the ISSUE-level contract)
REQUIRED_FIELDS = ("entry_point", "exec_spec", "shape_class", "mesh", "wall_us")


@dataclasses.dataclass(frozen=True)
class TraceEvent:
    """One timed jitted segment.

    entry_point: dotted hook name — ``serve.decode_step``,
      ``serve.prefill``, ``serve.prepare``, ``execution.execute``,
      ``execution.execute_packed``.
    exec_spec:   the CiM execution spec name (``"blocked/jnp/none"``) or
      a quant-mode tag (``"mode:off"``) when the engine serves without
      an explicit spec.
    shape_class: the dispatch class the segment ran in (``"decode"`` /
      ``"prefill"`` — DESIGN.md §9) or a hook-specific tag
      (``"prepare"``).
    mesh:        ``{axis: size}`` for TP serving, ``None`` unsharded.
    wall_us:     host call to device completion (includes dispatch and
      the profiler's own sync).
    dispatch_us: host time to enqueue (call returned, device still
      running) — ``wall_us - dispatch_us`` is pure device+sync time.
    meta:        hook-specific payload (m/k/n/macs/weight_bytes for
      kernel events; arch/step/occupancy for engine events).
    """

    entry_point: str
    exec_spec: str
    shape_class: str
    mesh: Optional[Mapping[str, int]]
    wall_us: float
    dispatch_us: float = 0.0
    meta: Mapping[str, Any] = dataclasses.field(default_factory=dict)

    def to_json(self) -> Dict[str, Any]:
        return {
            "v": TRACE_SCHEMA_VERSION,
            "entry_point": self.entry_point,
            "exec_spec": self.exec_spec,
            "shape_class": self.shape_class,
            "mesh": dict(self.mesh) if self.mesh is not None else None,
            "wall_us": self.wall_us,
            "dispatch_us": self.dispatch_us,
            "meta": dict(self.meta),
        }


def validate_event(d: Mapping[str, Any]) -> None:
    """Raise ``ValueError`` unless ``d`` is a well-formed serialized
    event of the current schema version."""
    if not isinstance(d, Mapping):
        raise ValueError(f"trace event must be an object, got {type(d).__name__}")
    v = d.get("v")
    if v != TRACE_SCHEMA_VERSION:
        raise ValueError(
            f"trace schema version {v!r} != {TRACE_SCHEMA_VERSION} "
            f"(re-capture the trace with this tree)"
        )
    for field in REQUIRED_FIELDS:
        if field not in d:
            raise ValueError(f"trace event missing required field {field!r}: {d}")
    for field in ("entry_point", "exec_spec", "shape_class"):
        if not d[field] or not isinstance(d[field], str):
            raise ValueError(f"trace event field {field!r} must be a "
                             f"non-empty string, got {d[field]!r}")
    if d["mesh"] is not None and not isinstance(d["mesh"], Mapping):
        raise ValueError(f"trace event mesh must be null or an object: {d['mesh']!r}")
    wall = d["wall_us"]
    if not isinstance(wall, (int, float)) or wall < 0:
        raise ValueError(f"trace event wall_us must be >= 0, got {wall!r}")


def event_from_json(d: Mapping[str, Any]) -> TraceEvent:
    validate_event(d)
    return TraceEvent(
        entry_point=d["entry_point"],
        exec_spec=d["exec_spec"],
        shape_class=d["shape_class"],
        mesh=dict(d["mesh"]) if d["mesh"] is not None else None,
        wall_us=float(d["wall_us"]),
        dispatch_us=float(d.get("dispatch_us", 0.0)),
        meta=dict(d.get("meta", {})),
    )


class Profiler:
    """Collects :class:`TraceEvent`\\ s; optionally streams them as
    JSON-lines to ``path`` (append mode, flushed per event so a crashed
    run keeps its trace). Use as a context manager, or call
    :meth:`close` when done with a path-backed profiler."""

    def __init__(self, path: Optional[Union[str, Path]] = None):
        self.path = Path(path) if path is not None else None
        self.events: List[TraceEvent] = []
        self._fh = None

    def record(self, event: Optional[TraceEvent] = None, **kw) -> TraceEvent:
        """Append one event (an explicit :class:`TraceEvent`, or the
        constructor kwargs)."""
        if event is None:
            event = TraceEvent(**kw)
        elif kw:
            raise ValueError("pass an event or kwargs, not both")
        self.events.append(event)
        if self.path is not None:
            if self._fh is None:
                self._fh = open(self.path, "a")
            self._fh.write(json.dumps(event.to_json(), sort_keys=True) + "\n")
            self._fh.flush()
        return event

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    def __enter__(self) -> "Profiler":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def read_trace(path: Union[str, Path]) -> List[TraceEvent]:
    """Load and validate a JSON-lines trace file."""
    events: List[TraceEvent] = []
    for i, line in enumerate(Path(path).read_text().splitlines(), 1):
        line = line.strip()
        if not line:
            continue
        try:
            d = json.loads(line)
        except json.JSONDecodeError as e:
            raise ValueError(f"{path}:{i}: not JSON: {e}") from None
        events.append(event_from_json(d))
    return events


# ---------------------------------------------------------------------------
# The global profiler hook (eager execution-shim calls)
# ---------------------------------------------------------------------------

_ACTIVE: Optional[Profiler] = None


def set_profiler(p: Optional[Profiler]) -> Optional[Profiler]:
    """Install ``p`` as the process-wide profiler (``None`` uninstalls)
    and wire the execution shim's sink to it: every *eager*
    ``execute``/``execute_packed`` call is timed while installed (calls
    under a jit trace are never timed — timing a tracer is meaningless
    and would poison the jaxpr). Returns the previous profiler so
    callers can restore it."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = p
    from repro.core import execution

    execution.set_profile_sink(p.record if p is not None else None)
    return prev


def current_profiler() -> Optional[Profiler]:
    """The installed process-wide profiler, or None."""
    return _ACTIVE


# ---------------------------------------------------------------------------
# Spans and named scopes in the profiler's own trace
# ---------------------------------------------------------------------------

#: the ``jax.named_scope`` names the model and the engine give their
#: parts of the fused programs; an HLO op belongs to the innermost one
#: on its ``op_name`` path
SCOPES = ("attn", "ssm", "cim", "unembed", "sample", "fill.merge", "kv.write")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%\S+)\s+=\s")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def span(name: str, **args):
    """A host span: ``jax.profiler.TraceAnnotation(name, **args)``, so it
    lands in the profiler's trace on the same clock as the device's
    operations, with ``args`` as the event's stats. With no profiler
    session active it records nothing and costs what an inactive
    annotation costs (about a microsecond)."""
    return jax.profiler.TraceAnnotation(name, **args)


def scope_of(op_name: str) -> Optional[str]:
    """The innermost of :data:`SCOPES` on an HLO ``op_name`` path
    (``jit(step)/while/body/attn/cim/dot_general`` -> ``cim``)."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return None


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """Map every instruction of a compiled program's HLO text, by its
    full name (``%copy.7``), to the ``op_name`` of its metadata (``""``
    where it has none): the path of named scopes and primitives that
    emitted it, or the name of the program argument an inserted copy
    relays out (``caches.k``). :func:`scope_of` reads its scope."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = op.group(1) if op else ""
    return out


def backend_block() -> Dict[str, Any]:
    """Measurement provenance: the ``"backend"`` block every
    BENCH_*.json embeds so validators know *where* numbers came from.
    ``interpret`` is the load-bearing bit — off-TPU the Pallas kernels
    run through the interpreter (the repo's ``interpret=not _on_tpu()``
    convention), where timings prove bit-exactness and plumbing but
    never compiled speed, so validators must refuse any compiled-
    speedup claim made under it."""
    dev = jax.devices()[0]
    return {
        "platform": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", "unknown"),
        "device_count": jax.device_count(),
        "interpret": jax.default_backend() != "tpu",
    }


# ---------------------------------------------------------------------------
# Step instrumentation (the serving engine's hook)
# ---------------------------------------------------------------------------


def wrap_step(
    fn: Callable,
    profiler: Optional[Profiler],
    entry_point: str,
    *,
    exec_spec: str = "mode:off",
    shape_class: str = "decode",
    mesh: Optional[Mapping[str, int]] = None,
    meta_fn: Optional[Callable[..., Mapping[str, Any]]] = None,
) -> Callable:
    """Wrap a jitted step function with wall-time capture.

    With ``profiler=None`` this returns ``fn`` **unchanged** — the same
    object, so the disabled path is bit- and jaxpr-identical to an
    uninstrumented engine (pinned by the
    ``profile.step_instrumentation.disabled`` contract and
    tests/test_profile.py). With a profiler, the wrapper times the call,
    blocks on the outputs (outside the jit boundary — the jaxpr is
    untouched), and records one event; ``meta_fn(*args)`` supplies the
    hook-specific payload at record time.
    """
    if profiler is None:
        return fn

    def timed(*args):
        t0 = time.perf_counter()
        out = fn(*args)
        t1 = time.perf_counter()
        # analysis: host-sync ok — profiler wall-time capture, opt-in and
        # outside the jitted step (never on the disabled path)
        jax.block_until_ready(out)
        t2 = time.perf_counter()
        profiler.record(TraceEvent(
            entry_point=entry_point,
            exec_spec=exec_spec,
            shape_class=shape_class,
            mesh=mesh,
            wall_us=(t2 - t0) * 1e6,
            dispatch_us=(t1 - t0) * 1e6,
            meta=dict(meta_fn(*args)) if meta_fn is not None else {},
        ))
        return out

    return timed


# ---------------------------------------------------------------------------
# Tracing contract (repro.analysis — DESIGN.md §10/§11)
#
# Instrumentation must be free when disabled: wrap_step(fn, None) IS fn,
# so the fused decode step traced through the profile layer has the same
# equation count as the raw step (invariance over the `wrapped` axis)
# and still zero host callbacks. A future wrapper that traced timing
# logic into the step would break both.
# ---------------------------------------------------------------------------

from repro.analysis.contracts import (  # noqa: E402
    TraceContract,
    register_trace_contract,
)


def _instrumented_step_point():
    """The production fused decode step, traced raw (``wrapped=0``) and
    through the disabled profile wrapper (``wrapped=1``) — the auditor
    requires one equation count across both."""

    def build(wrapped: int = 0):
        import jax.numpy as jnp

        from repro.models import transformer as T
        from repro.models.layers import QuantConfig
        from repro.models.registry import get_config
        from repro.serve.engine import fused_decode_fn

        n_slots = 3
        cfg = get_config("smollm-135m", smoke=True).replace(
            quant=QuantConfig(mode="off"))
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        caches = T.init_caches(cfg, n_slots, 32)
        step = fused_decode_fn(cfg)
        if wrapped:
            step = wrap_step(step, None, "serve.decode_step")
        args = (params, jnp.zeros((n_slots, 1), jnp.int32), caches,
                jnp.zeros((n_slots,), jnp.int32),
                jnp.zeros((n_slots,), jnp.int32), jax.random.PRNGKey(1))
        return step, args

    return build


register_trace_contract(
    "profile.step_instrumentation.disabled",
    _instrumented_step_point(),
    TraceContract(max_host_callbacks=0),
    axes={"wrapped": (0, 1)},
)
