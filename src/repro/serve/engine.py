"""Serving: prefill/decode steps, sampling, and a continuous batcher.

``serve_step`` is the unit the dry-run lowers for the decode shape cells:
one new token for every sequence in the batch against a seq_len-deep KV
cache. ``prefill`` reuses the same cached block path with S > 1.

The ``ContinuousBatcher`` keeps a fixed pool of slots; finished sequences
are immediately replaced from the queue (slot-level continuous batching,
the standard production serving discipline), demonstrated end-to-end in
examples/serve_ternary.py.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.execution import CiMExecSpec
from repro.models import transformer as T
from repro.profile.trace import span

PyTree = Any


def apply_exec_spec(cfg: ArchConfig, spec: Optional[CiMExecSpec]) -> ArchConfig:
    """Serve the model under an explicit CiM execution spec (e.g. a
    packed-bitplane backend or flavor II) without touching the
    architecture config: the spec overrides the QuantConfig's
    mode-derived dispatch in every dense layer.

    The stochastic sensing-error channel needs a per-layer PRNG key,
    which the model-assembly code does not thread — noisy specs are for
    direct ``api.execute`` / ``layers.dense(key=...)`` calls (see
    benchmarks/bench_accuracy.py), so they are rejected here up front
    rather than crashing inside the first forward.
    """
    if spec is None:
        return cfg
    if spec.error_prob > 0.0:
        raise ValueError(
            "serving does not thread PRNG keys into dense layers; use a "
            "spec with error_prob=0 here and drive the sensing-error "
            "channel through api.execute/layers.dense directly"
        )
    if spec.packing != "none":
        # dense() holds dense weights, so a packed spec re-packs every
        # weight inside every forward — functionally correct (this is
        # the equivalence-test path) but it realizes none of the packed
        # format's weight-traffic savings; that needs
        # prepare_for_spec + api.execute_packed over stored planes
        warnings.warn(
            f"serving under packing={spec.packing!r} packs weights "
            "per-forward (functional path only); use "
            "quant.prepare.prepare_for_spec + api.execute_packed for "
            "the stored-plane fast path",
            stacklevel=2,
        )
    # mode="off" short-circuits dense() before the spec is consulted —
    # upgrade it so the requested spec actually executes (ternarizing
    # weights/activations on the fly, like any quantized mode)
    mode = "cim" if cfg.quant.mode == "off" else cfg.quant.mode
    return cfg.replace(
        quant=dataclasses.replace(cfg.quant, mode=mode, exec_spec=spec)
    )


def sample(logits: jax.Array, key: jax.Array, temperature: float = 0.0) -> jax.Array:
    """logits: (B, 1, V) -> token ids (B, 1)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits.astype(jnp.float32) / temperature
    flat = scaled[:, 0, :]
    toks = jax.random.categorical(key, flat, axis=-1)
    return toks[:, None].astype(jnp.int32)


def prefill(
    params, tokens: jax.Array, caches, cfg: ArchConfig, enc: Optional[jax.Array] = None
) -> Tuple[jax.Array, PyTree]:
    """Run the prompt through the cached path (index 0). Returns
    (last_logits (B, 1, V), caches)."""
    logits, caches = T.decode_step(params, tokens, caches, jnp.int32(0), cfg, enc)
    return logits[:, -1:, :], caches


def serve_step(
    params,
    tokens: jax.Array,
    caches,
    index: jax.Array,
    cfg: ArchConfig,
    enc: Optional[jax.Array] = None,
) -> Tuple[jax.Array, PyTree]:
    """One decode step: tokens (B, 1) at cache position ``index``."""
    return T.decode_step(params, tokens, caches, index, cfg, enc)


def make_jit_serve_step(cfg: ArchConfig, donate_caches: bool = True):
    def f(params, tokens, caches, index, enc=None):
        return serve_step(params, tokens, caches, index, cfg, enc)

    return jax.jit(f, donate_argnums=(2,) if donate_caches else ())


def fused_decode_fn(cfg: ArchConfig, temperature: float = 0.0):
    """The function the fused batcher jits for every decode step: one
    ragged-position ``decode_step`` over all slots plus on-device
    sampling — tokens out are the step's ONLY device->host payload.
    Module-level (not a closure inside the batcher) so the registered
    ``serve.fused_decode_step`` tracing contract audits the *same*
    function production serves with, not a test replica."""

    def step(params, tokens, caches, positions, start, key):
        logits, caches = T.decode_step(
            params, tokens, caches, positions, cfg, start=start)
        with jax.named_scope("sample"):
            toks = sample(logits[:, -1:, :], key, temperature)[:, 0]
        return toks, caches

    return step


def generate(
    params,
    prompt: jax.Array,
    cfg: ArchConfig,
    max_new: int = 16,
    s_max: int = 128,
    temperature: float = 0.0,
    key: Optional[jax.Array] = None,
    enc: Optional[jax.Array] = None,
    exec_spec: Optional[CiMExecSpec] = None,
) -> jax.Array:
    """Greedy/temperature generation (host loop — example/test path)."""
    cfg = apply_exec_spec(cfg, exec_spec)
    b, s0 = prompt.shape
    caches = T.init_caches(cfg, b, s_max)
    logits, caches = prefill(params, prompt, caches, cfg, enc)
    key = key if key is not None else jax.random.PRNGKey(0)
    step_fn = make_jit_serve_step(cfg)
    out = []
    tok = sample(logits, key, temperature)
    out.append(tok)
    for i in range(max_new - 1):
        key, sub = jax.random.split(key)
        logits, caches = step_fn(params, tok, caches, jnp.int32(s0 + i), enc)
        tok = sample(logits, sub, temperature)
        out.append(tok)
    return jnp.concatenate(out, axis=1)


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------

# analysis: dataclass-unregistered ok — host-side bookkeeping, never jitted
@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # set when the slot hit cache capacity (s_max) before max_new tokens;
    # with left-padded batched prefill the pad dead zone counts against
    # capacity, so a short prompt co-batched with a long one can run out
    # of slots earlier than per-request generate() would
    truncated: bool = False
    # set by ContinuousBatcher.cancel(): the request was withdrawn (from
    # the queue, or mid-decode — its slot freed) before max_new tokens
    cancelled: bool = False


def _next_pow2(n: int, lo: int = 4) -> int:
    v = lo
    while v < n:
        v *= 2
    return v


class ContinuousBatcher:
    """Slot-pool continuous batcher over one fused, jitted decode step.

    Each slot owns a cache region (per-slot caches batched along axis 1 of
    every stacked cache leaf). Finished slots are refilled without
    stalling the others.

    The fused path (default) exploits the ragged-position decode contract
    (DESIGN.md §6) end-to-end:

      * **one** batched ``decode_step`` serves all slots at heterogeneous
        cache positions via a ``(n_slots,)`` position vector — no
        per-slot Python loop inside jit, so the traced program size and
        compile count are independent of ``n_slots``;
      * newly assigned slots prefill **together** in one left-padded
        batch (prompts right-aligned so every row's last real token sits
        in the last column; the per-row ``start`` vector masks the dead
        pad slots for the slot's lifetime); padded lengths are bucketed
        to powers of two to bound recompiles. The pad dead zone counts
        against the slot's s_max capacity, so a short prompt co-batched
        with a much longer one can hit the cache limit before max_new —
        such requests finish with ``truncated=True``;
      * sampling happens on device inside the jitted step — the host
        fetches exactly one small token vector per decode step
        (``host_syncs`` counts these).

    ``fused=False`` keeps the legacy per-slot-loop decode (a static
    Python loop of single-row steps inside jit, per-slot prefill, one
    host sync per active slot) as the measured baseline for
    ``benchmarks/bench_serve.py``.

    ``prepare_weights=True`` runs ``quant.prepare.prepare_for_spec`` once
    at construction so the per-step STE re-quantization is skipped
    (``pre_quantized``); for a bitplane-packed spec the stored 2-bit
    planes are kept on ``self.packed`` as canonical
    ``repro.core.ternary.PackedPlanes`` — pre-padded to the packed
    kernels' tile granularity with the logical (K, N) recorded, so
    ``api.execute_packed`` callers stream them across steps with zero
    per-step padding/relayout (DESIGN.md §9) — and the in-model dense
    path serves from the folded ternary weights (packing downgraded to
    "none" so nothing re-packs per forward).

    Quantized fused serving is **exactly** token-identical to
    per-request ``generate()`` when the quant config uses
    ``act_scale="per_row"`` (row-independent activation quantization);
    the default per-tensor scale couples co-batched rows through one
    amax (DESIGN.md §9).

    ``mesh`` turns on tensor-parallel serving (DESIGN.md §8): params are
    sharded under ``dist.sharding.param_specs`` (attention/FFN column- and
    row-parallel over the "model" axis), decode caches under
    ``cache_specs``, and any prepared 2-bit bitplanes under
    ``packed_specs`` (N-sharded — each device stores only its weight
    shard). The fused step stays ONE jitted dispatch with one host fetch
    per decode step; the GSPMD partitioner inserts the TP collectives, so
    token streams are identical to the unsharded engine (pinned in
    tests/test_tp_serve.py) and ``stats()`` is unchanged by TP.
    ``compress_tp=True`` additionally routes the row-parallel quantized
    MACs through the explicit shard_map path (``execution.execute_tp``)
    whose per-layer partial-sum all-reduce moves int8 instead of f32 —
    approximate (quantization-level error), opt-in, quantized modes only.

    ``cache_dtype`` overrides ``cfg.quant.cache_dtype`` (DESIGN.md §13):
    ``"int8"``/``"ternary"`` store the KV cache as codes + per-(row,
    position) f32 scales — 2x/4x the resident slots at equal cache
    memory, and proportionally smaller TP cache shards — with dequant
    fused into the attention contractions. ``"bf16"`` (the default via
    QuantConfig) is pinned bit-identical to the unquantized engine. The
    donated-buffer reset path (`_build_prefill_fused`'s in-jit
    ``T.init_caches``) follows the same config, so freed slots are
    rebuilt in cache_dtype layout with no host round-trip.
    """

    def __init__(
        self,
        params,
        cfg: ArchConfig,
        n_slots: int = 4,
        s_max: int = 128,
        exec_spec: Optional[CiMExecSpec] = None,
        temperature: float = 0.0,
        seed: int = 0,
        fused: bool = True,
        prepare_weights: bool = False,
        mesh=None,
        compress_tp: bool = False,
        profile=None,
        cache_dtype: Optional[str] = None,
    ):
        self.packed = None
        self.mesh = mesh
        # opt-in measured-time observability (DESIGN.md §11): `profile`
        # is a repro.profile.Profiler, or a path to stream JSON-lines
        # events to, or None (the default — the step builders then get
        # the *unwrapped* jitted functions back from wrap_step, so the
        # disabled engine is bit- and jaxpr-identical to one built
        # before this feature existed).
        self.profiler = None
        self._owns_profiler = False
        if profile is not None:
            from repro.profile.trace import Profiler

            if isinstance(profile, Profiler):
                self.profiler = profile
            else:
                self.profiler = Profiler(profile)
                self._owns_profiler = True
        self._mesh_dict = (
            {str(k): int(v) for k, v in mesh.shape.items()}
            if mesh is not None else None
        )
        self._prefill_meta = {}
        if mesh is not None:
            from repro.dist import sharding as shd  # placement, below

            if "model" not in mesh.axis_names:
                raise ValueError(
                    f"TP serving shards over a 'model' mesh axis; got axes "
                    f"{mesh.axis_names} (use launch.mesh.make_tp_mesh)"
                )
        if compress_tp and mesh is None:
            raise ValueError("compress_tp=True requires a mesh (TP serving)")
        if prepare_weights and exec_spec is None:
            raise ValueError(
                "prepare_weights=True requires exec_spec (the surgery is "
                "matched to the spec's packing); for spec-less offline "
                "ternarization use quant.prepare.ternarize_params + "
                "QuantConfig(pre_quantized=True)"
            )
        params_placed = False
        if prepare_weights and exec_spec is not None:
            from repro.quant.prepare import prepare_for_spec

            # prepare_for_spec(mesh=...) owns placement of BOTH surgery
            # outputs (folded params under param_specs, planes under
            # packed_specs) — don't re-place the params below
            def _prepare():
                return prepare_for_spec(params, exec_spec, mesh=mesh)

            if self.profiler is not None:
                from repro.profile.trace import wrap_step

                _prepare = wrap_step(
                    _prepare, self.profiler, "serve.prepare",
                    exec_spec=exec_spec.name, shape_class="prepare",
                    mesh=self._mesh_dict)
            prepared = _prepare()
            params_placed = mesh is not None
            if exec_spec.packing == "bitplane_u8":
                params, self.packed = prepared
                # the in-model dense path serves the folded ternary
                # weights, so drop the packing; packed-only backends
                # (pallas_stream has no dense kernel — it exists to
                # stream stored planes) fall back to "auto" for the
                # dense path while self.packed keeps the stream layout
                # for api.execute_packed / execute_packed_tp consumers
                from repro.core.execution import get_backend

                dense_spec = dataclasses.replace(exec_spec, packing="none")
                try:
                    get_backend(dense_spec)
                except KeyError:
                    dense_spec = dataclasses.replace(dense_spec, backend="auto")
                exec_spec = dense_spec
            else:
                params = prepared
            cfg = cfg.replace(
                quant=dataclasses.replace(cfg.quant, pre_quantized=True)
            )
        self.cfg = cfg = apply_exec_spec(cfg, exec_spec)
        if cache_dtype is not None:
            # KV-cache storage precision override (DESIGN.md §13) —
            # validated by QuantConfig.__post_init__; None keeps the
            # config's own cache_dtype (default "bf16", bit-identical
            # to the pre-§13 engine)
            self.cfg = cfg = cfg.replace(
                quant=dataclasses.replace(cfg.quant, cache_dtype=cache_dtype)
            )
        if compress_tp:
            if cfg.quant.mode == "off":
                raise ValueError(
                    "compress_tp compresses the quantized dense path's TP "
                    "all-reduce; serve a quantized mode (or an exec_spec) "
                    "to use it"
                )
            spec_now = cfg.quant.exec_spec
            if spec_now is not None and spec_now.packing != "none":
                # dense() routes to execute_tp only for unpacked specs
                # (the packed planes shard over N, not K) — accepting
                # this would silently serve with exact collectives
                raise ValueError(
                    f"compress_tp cannot engage under packing="
                    f"{spec_now.packing!r}: use prepare_weights=True "
                    "(which folds the packing offline and downgrades the "
                    "in-model spec to packing='none') or an unpacked spec"
                )
            self.cfg = cfg = cfg.replace(
                quant=dataclasses.replace(cfg.quant, tp_reduce="int8")
            )
        if mesh is not None and not params_placed:
            axis_sizes = shd.mesh_axis_sizes(mesh)
            params = jax.device_put(
                params,
                shd.named_shardings(
                    mesh, shd.param_specs(params, axis_sizes=axis_sizes)),
            )
        self.params = params
        self.n_slots = n_slots
        self.s_max = s_max
        self.temperature = float(temperature)
        self.fused = fused
        self._key = jax.random.PRNGKey(seed)
        self.caches = T.init_caches(cfg, n_slots, s_max)
        self._cache_ns = None
        if mesh is not None:
            self._cache_ns = shd.named_shardings(
                mesh, shd.cache_specs(self.caches, mesh, batch=n_slots))
            self.caches = jax.device_put(self.caches, self._cache_ns)
        self.slot_req: List[Optional[Request]] = [None] * n_slots
        self.slot_pos = np.zeros((n_slots,), np.int32)    # next cache write slot
        self.slot_start = np.zeros((n_slots,), np.int32)  # left-pad dead zone
        self._last_tok = np.zeros((n_slots,), np.int32)
        self.queue: List[Request] = []
        self.decode_steps = 0
        self.host_syncs = 0
        self.prefill_batches = 0
        # what the fused fills and decode steps computed, and how much of
        # it served a request (stats(); fused path only)
        self.fill_rows_new = 0
        self.fill_rows_computed = 0
        self.fill_tokens_prompt = 0
        self.fill_tokens_computed = 0
        self.decode_rows_active = 0
        self.decode_rows_computed = 0
        self._step_idx = 0
        self._prefill_idx = 0
        if not fused and self.temperature != 0.0:
            raise ValueError(
                "temperature sampling is only implemented for the fused "
                "decode path (the looped baseline is greedy-only)"
            )
        if fused:
            self._decode = self._build_decode_fused()
            self._prefill = self._build_prefill_fused()
        else:
            self._decode = self._build_decode_looped()

    # -- fused path ---------------------------------------------------------

    def _sample_on_device(self, last_logits, key):
        """last_logits: (B, V) -> (B,) int32, greedy or temperature —
        the module-level :func:`sample`, traced into the jitted step."""
        return sample(last_logits[:, None, :], key, self.temperature)[:, 0]

    def _jit_step(self, f, donate, entry_point=None, shape_class="decode",
                  meta_fn=None):
        """jit with the TP output shardings pinned: sampled tokens
        replicated (they are THE one host fetch of the step), caches kept
        under their cache_specs sharding so the donated-buffer layout is
        a fixpoint across steps (no per-step reshard, no recompiles).

        Under a mesh the call is additionally scoped under THIS
        batcher's mesh via the dist.sharding TP-mesh switch — installed
        around the call (where tracing happens) and restored after, so
        two batchers on different meshes in one process never read each
        other's mesh and nothing leaks once the batcher is done. dense()
        reads it to run ``compress_tp`` MACs and Pallas kernels (which
        the SPMD partitioner cannot split) per shard.

        With a profiler installed and ``entry_point`` named, the built
        step is wrapped with wall-time capture (repro.profile.trace);
        with no profiler ``wrap_step`` returns it unchanged."""
        if self._cache_ns is None:
            jitted = jax.jit(f, donate_argnums=donate)
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P

            tok_ns = NamedSharding(self.mesh, P())
            jitted = jax.jit(f, donate_argnums=donate,
                             out_shardings=(tok_ns, self._cache_ns))
        if self.mesh is not None:
            inner = jitted

            def scoped(*args):
                from repro.dist import sharding as shd

                prev = shd.tp_mesh()
                shd.set_tp_mesh(self.mesh)
                try:
                    return inner(*args)
                finally:
                    shd.set_tp_mesh(prev)

            jitted = scoped
        if self.profiler is None or entry_point is None:
            return jitted
        from repro.profile.trace import wrap_step

        return wrap_step(
            jitted, self.profiler, entry_point,
            exec_spec=self._spec_tag, shape_class=shape_class,
            mesh=self._mesh_dict, meta_fn=meta_fn)

    @property
    def _spec_tag(self) -> str:
        spec = self.cfg.quant.exec_spec
        return spec.name if spec is not None else f"mode:{self.cfg.quant.mode}"

    def _build_decode_fused(self):
        def meta(*_args):
            # called at record time, BEFORE _step_fused mutates slots —
            # occupancy is the number of rows this step decoded for
            return {
                "arch": self.cfg.name,
                "step": self._step_idx,
                "occupancy": sum(r is not None for r in self.slot_req),
                "n_slots": self.n_slots,
            }

        return self._jit_step(
            fused_decode_fn(self.cfg, self.temperature), (2,),
            entry_point="serve.decode_step", shape_class="decode",
            meta_fn=meta)

    def _build_prefill_fused(self):
        cfg, n, s_max = self.cfg, self.n_slots, self.s_max

        def pf(params, caches, tokens, start, fill_mask, key):
            # prefill all n_slots rows against fresh zero caches (dummy
            # rows compute garbage that the merge mask discards), then
            # select per row: filling slots take the new cache row,
            # in-flight slots keep theirs.
            fresh = T.init_caches(cfg, n, s_max)
            logits, new = T.decode_step(
                params, tokens, fresh, jnp.int32(0), cfg, start=start)
            # left-padding: the last column is every row's last real token
            with jax.named_scope("sample"):
                toks = self._sample_on_device(logits[:, -1, :], key)

            def merge(old, nw):
                m = fill_mask.reshape((1, n) + (1,) * (old.ndim - 2))
                return jnp.where(m, nw.astype(old.dtype), old)

            with jax.named_scope("fill.merge"):
                return toks, jax.tree.map(merge, caches, new)

        def meta(*_args):
            # _fill_slots_fused stages the batch description here right
            # before invoking the step (replay.requests_from_trace
            # reconstructs the request mix from these events)
            return dict(self._prefill_meta)

        return self._jit_step(pf, (1,), entry_point="serve.prefill",
                              shape_class="prefill", meta_fn=meta)

    def _fill_slots_fused(self):
        free = [s for s in range(self.n_slots) if self.slot_req[s] is None]
        admitted = self.queue[:len(free)]
        if not admitted:
            return
        max_len = max(len(r.prompt) for r in admitted)
        s_pad = _next_pow2(max_len)  # bucketed: bounds prefill recompiles
        if s_pad >= self.s_max:
            # don't let the bucket make a servable prompt unservable:
            # fall back to the exact length (one extra compile, worth it)
            s_pad = max_len
        # the host's part of a fill, span by span (repro.profile.trace):
        # stage the inputs, dispatch the program, wait for its tokens,
        # commit them to the requests
        with span("serve.fill.stage", rows=len(admitted), s_pad=s_pad,
                  rids=[r.rid for r in admitted]):
            newly = free[:len(admitted)]
            del self.queue[:len(admitted)]
            tokens = np.zeros((self.n_slots, s_pad), np.int32)
            start = np.zeros((self.n_slots,), np.int32)
            fill = np.zeros((self.n_slots,), bool)
            for s, req in zip(newly, admitted):
                self.slot_req[s] = req
                pad = s_pad - len(req.prompt)
                tokens[s, pad:] = req.prompt
                start[s] = pad
                fill[s] = True
            # decode steps draw even fold_in streams, prefill batches odd ones
            key = jax.random.fold_in(self._key, 2 * self._prefill_idx + 1)
            self._prefill_idx += 1
            if self.profiler is not None:
                self._prefill_meta = {
                    "arch": self.cfg.name,
                    "prompts": [(r.rid, len(r.prompt), r.max_new)
                                for r in admitted],
                    "s_pad": s_pad,
                    "filled": len(newly),
                }
            inputs = (jnp.asarray(tokens), jnp.asarray(start), jnp.asarray(fill))
        with span("serve.fill.dispatch"):
            toks, self.caches = self._prefill(
                self.params, self.caches, *inputs, key)
        with span("serve.fill.fetch"):
            # analysis: host-sync ok — the one documented fetch per fill batch
            toks = np.asarray(toks)
        with span("serve.fill.commit"):
            self.host_syncs += 1
            self.prefill_batches += 1
            self.fill_rows_new += len(newly)
            self.fill_rows_computed += self.n_slots
            self.fill_tokens_prompt += sum(len(r.prompt) for r in admitted)
            self.fill_tokens_computed += self.n_slots * s_pad
            for s in newly:
                req = self.slot_req[s]
                req.generated.append(int(toks[s]))
                self._last_tok[s] = toks[s]
                self.slot_pos[s] = s_pad
                self.slot_start[s] = start[s]
                if len(req.generated) >= req.max_new:
                    req.done = True
                    self.slot_req[s] = None

    def _step_fused(self, active) -> int:
        with span("serve.decode.stage", active=len(active)):
            tokens = jnp.asarray(self._last_tok[:, None])
            positions = jnp.asarray(self.slot_pos)
            start = jnp.asarray(self.slot_start)
            key = jax.random.fold_in(self._key, 2 * self._step_idx)
        with span("serve.decode.dispatch"):
            toks, self.caches = self._decode(
                self.params, tokens, self.caches, positions, start, key)
        self.decode_steps += 1
        self._step_idx += 1
        with span("serve.decode.fetch"):
            # analysis: host-sync ok — the single documented fetch of this step
            toks = np.asarray(toks)
        with span("serve.decode.commit"):
            self.host_syncs += 1
            self.decode_rows_active += len(active)
            self.decode_rows_computed += self.n_slots
            for s in active:
                req = self.slot_req[s]
                req.generated.append(int(toks[s]))
                self._last_tok[s] = toks[s]
                self.slot_pos[s] += 1
                # capacity boundary: slot_pos is the NEXT cache write
                # offset, so decoding may continue while slot_pos <=
                # s_max - 1 (the last cache slot is usable); `>= s_max - 1`
                # here wasted it
                if (len(req.generated) >= req.max_new
                        or self.slot_pos[s] >= self.s_max):
                    req.done = True
                    req.truncated = len(req.generated) < req.max_new
                    self.slot_req[s] = None
        return len(active)

    # -- legacy per-slot-loop baseline (benchmarks/bench_serve.py) ----------

    def _build_decode_looped(self):
        cfg = self.cfg

        def step(params, tokens, caches, positions):
            # the pre-ragged-decode formulation: a static per-slot Python
            # loop of single-row steps inside jit — the traced program
            # grows linearly with n_slots and recompiles when it changes.
            b = tokens.shape[0]
            flat, treedef = jax.tree_util.tree_flatten(caches)
            row_caches = [
                jax.tree_util.tree_unflatten(
                    treedef,
                    [leaf[:, i : i + 1] if leaf.ndim > 1 else leaf for leaf in flat],
                )
                for i in range(b)
            ]
            outs = []
            for i in range(b):
                lg, nc = serve_step(
                    params, tokens[i : i + 1], row_caches[i], positions[i], cfg
                )
                outs.append((lg, nc))
            logits = jnp.concatenate([o[0] for o in outs], axis=0)
            merged = jax.tree.map(
                lambda *rows: jnp.concatenate(rows, axis=1), *[o[1] for o in outs]
            )
            return logits, merged

        return jax.jit(step)

    def _fill_slots_looped(self):
        for s in range(self.n_slots):
            if self.slot_req[s] is None and self.queue:
                req = self.queue.pop(0)
                self.slot_req[s] = req
                # prefill this slot alone (recompiles per prompt length)
                prompt = jnp.asarray(req.prompt, jnp.int32)[None]
                flat, treedef = jax.tree_util.tree_flatten(self.caches)
                row = jax.tree_util.tree_unflatten(
                    treedef,
                    [leaf[:, s : s + 1] if leaf.ndim > 1 else leaf for leaf in flat],
                )
                logits, row = prefill(self.params, prompt, row, self.cfg)
                flat_row = jax.tree_util.tree_leaves(row)
                new_flat = []
                for leaf, rl in zip(flat, flat_row):
                    if leaf.ndim > 1:
                        leaf = jax.lax.dynamic_update_slice_in_dim(leaf, rl, s, axis=1)
                    new_flat.append(leaf)
                self.caches = jax.tree_util.tree_unflatten(treedef, new_flat)
                # analysis: host-sync ok — looped baseline syncs per slot by design
                tok = int(jnp.argmax(logits[0, -1]))
                self.host_syncs += 1
                self.prefill_batches += 1  # looped prefill is per-slot
                req.generated.append(tok)
                self._last_tok[s] = tok
                self.slot_pos[s] = len(req.prompt)
                self.slot_start[s] = 0
                if len(req.generated) >= req.max_new:
                    req.done = True
                    self.slot_req[s] = None

    def _step_looped(self, active) -> int:
        tokens = jnp.asarray(self._last_tok[:, None])
        logits, self.caches = self._decode(
            self.params, tokens, self.caches, jnp.asarray(self.slot_pos))
        self.decode_steps += 1
        self._step_idx += 1
        toks = jnp.argmax(logits[:, 0, :], axis=-1)
        for s in active:
            req = self.slot_req[s]
            tok = int(toks[s])  # one host sync per active slot
            self.host_syncs += 1
            req.generated.append(tok)
            self._last_tok[s] = tok
            self.slot_pos[s] += 1
            # same capacity boundary as _step_fused: finish at s_max, not
            # s_max - 1 (the last cache slot is a legal write target)
            if len(req.generated) >= req.max_new or self.slot_pos[s] >= self.s_max:
                req.done = True
                req.truncated = len(req.generated) < req.max_new
                self.slot_req[s] = None
        return len(active)

    # -- shared driver ------------------------------------------------------

    def submit(self, req: Request):
        if not req.prompt:
            raise ValueError(
                "empty prompt: serving needs at least one prompt token "
                "(the first sampled token conditions on it)"
            )
        if len(req.prompt) >= self.s_max:
            raise ValueError(
                f"prompt length {len(req.prompt)} does not fit a cache of "
                f"s_max={self.s_max} (needs at least one decode slot)"
            )
        self.queue.append(req)

    def cancel(self, request_id: int) -> bool:
        """Withdraw a request by rid: drop it from the queue, or — if it
        is mid-decode — free its slot so the next fill reuses it.

        Freeing a slot is exactly the completion path (``slot_req[s] =
        None``): the row keeps riding the fused step as a dead lane until
        refilled, its sampled tokens discarded like any finished slot's,
        and no other row's cache state or token stream is perturbed
        (pinned by tests/test_frontdoor.py). The request finishes with
        ``done=True, cancelled=True`` and keeps whatever it generated.

        Host-side bookkeeping only — call it between steps (the async
        front door applies cancels at the step boundary; see
        repro.serve.frontdoor.worker). Returns False when rid is not in
        flight (already finished, or never submitted)."""
        for i, req in enumerate(self.queue):
            if req.rid == request_id:
                del self.queue[i]
                req.done = True
                req.cancelled = True
                return True
        for s in range(self.n_slots):
            req = self.slot_req[s]
            if req is not None and req.rid == request_id:
                req.done = True
                req.cancelled = True
                req.truncated = len(req.generated) < req.max_new
                self.slot_req[s] = None
                return True
        return False

    def _fill_slots(self):
        if self.fused:
            self._fill_slots_fused()
        else:
            self._fill_slots_looped()

    def step(self) -> int:
        """One decode step over all active slots; returns #active."""
        with span("serve.step"):
            self._fill_slots()
            active = [s for s in range(self.n_slots)
                      if self.slot_req[s] is not None]
            if not active:
                return 0
            if self.fused:
                return self._step_fused(active)
            return self._step_looped(active)

    def stats(self) -> Dict[str, int]:
        return {
            "decode_steps": self.decode_steps,
            "host_syncs": self.host_syncs,
            "prefill_batches": self.prefill_batches,
            "fill_rows_new": self.fill_rows_new,
            "fill_rows_computed": self.fill_rows_computed,
            "fill_tokens_prompt": self.fill_tokens_prompt,
            "fill_tokens_computed": self.fill_tokens_computed,
            "decode_rows_active": self.decode_rows_active,
            "decode_rows_computed": self.decode_rows_computed,
        }

    def run(self) -> None:
        try:
            while self.queue or any(r is not None for r in self.slot_req):
                self.step()
        finally:
            if self._owns_profiler and self.profiler is not None:
                # the batcher opened the trace file (profile=<path>), so
                # it releases the handle; events stay readable mid-run
                # because the profiler flushes per event
                self.profiler.close()


# ---------------------------------------------------------------------------
# Tracing contracts (repro.analysis — DESIGN.md §10)
#
# The serving invariants the paper's throughput claims rest on, declared
# next to the engine that must uphold them:
#
#   * the fused decode step is ONE batched traced program: its equation
#     count is invariant to the slot count and the TP mesh size (the
#     per-slot python work of the looped baseline must never leak back
#     into the trace);
#   * no host callbacks inside the step — the single documented host
#     fetch (`np.asarray(toks)`) happens outside the jit boundary;
#   * no pad on uint8 operands — stored 2-bit planes enter kernels in
#     their prepare-time canonical layout.
# ---------------------------------------------------------------------------

from repro.analysis.contracts import (  # noqa: E402
    PrimRule,
    SkipTrace,
    TraceContract,
    register_trace_contract,
)


def _fused_step_point(quant_mode: str, cache_dtype: str = "bf16",
                      s_max: int = 32):
    """Build (fn, args) tracing the production fused decode step on the
    smoke serving arch under ``quant_mode`` (weights) and ``cache_dtype``
    (KV cache — DESIGN.md §13). TP variants trace under an installed
    ("data", "model") mesh, exactly like the engine's ``compress_tp``
    scoping."""

    def build(n_slots: int = 3, tp: int = 1):
        if jax.device_count() < tp:
            raise SkipTrace(
                f"needs {tp} devices, have {jax.device_count()} "
                f"(XLA_FLAGS=--xla_force_host_platform_device_count=8)"
            )
        from repro.models.layers import QuantConfig
        from repro.models.registry import get_config

        cfg = get_config("smollm-135m", smoke=True).replace(
            quant=QuantConfig(mode=quant_mode, cache_dtype=cache_dtype))
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        caches = T.init_caches(cfg, n_slots, s_max)
        step = fused_decode_fn(cfg)
        args = (params, jnp.zeros((n_slots, 1), jnp.int32), caches,
                jnp.zeros((n_slots,), jnp.int32),
                jnp.zeros((n_slots,), jnp.int32), jax.random.PRNGKey(1))
        if tp == 1:
            return step, args

        from repro.dist import sharding as shd
        from repro.launch.mesh import make_tp_mesh

        mesh = make_tp_mesh(tp)

        def step_under_mesh(*a):
            prev = shd.tp_mesh()
            shd.set_tp_mesh(mesh)
            try:
                return step(*a)
            finally:
                shd.set_tp_mesh(prev)

        return step_under_mesh, args

    return build


_FUSED_STEP_CONTRACT = TraceContract(
    max_host_callbacks=0,
    no_pad_on_dtypes=("uint8",),
)

register_trace_contract(
    "serve.fused_decode_step",
    _fused_step_point("off"),
    _FUSED_STEP_CONTRACT,
    axes={"n_slots": (2, 6), "tp": (1, 2, 4)},
)

register_trace_contract(
    "serve.fused_decode_step.cim",
    _fused_step_point("cim"),
    _FUSED_STEP_CONTRACT,
    axes={"n_slots": (2, 6)},
)


# Quantized KV cache (DESIGN.md §13): the fused step over an int8 cache
# must never materialize a full-precision copy of the *stacked* cache —
# dequant stays fused (codes into the contractions, scales onto the
# score/prob matrices). The per-layer compute-dtype code conversion is
# inherent to the jnp path (rank-4 int8, one layer's codes at a time);
# the regression this rule catches is cache-level dequant: an integer
# code tensor shaped like the *stacked* cache (rank 5 with the
# contract's s_max at axis 2 — picked to collide with no legitimate
# dimension of the smoke arch) converted to a float tensor. Matching on
# the eqn's integer *input* keeps legitimate rank-5 float activations
# (the GQA score dot_general also carries s_max) out of scope.
_KVQ_S_MAX = 48


def _kvq_stacked_dequant(eqn) -> bool:
    import numpy as np  # local: predicate must stay import-light

    def stacked(v, pred):
        aval = getattr(v, "aval", None)
        return (hasattr(aval, "dtype") and pred(aval.dtype)
                and len(aval.shape) == 5 and aval.shape[2] == _KVQ_S_MAX)

    # int/uint stacked codes in AND a float tensor of the same stacked
    # shape out = the cache-level dequant. Control-flow eqns (scan
    # carries the int8 cache in and float logits out) don't match: their
    # float outputs are not stacked-cache shaped.
    if not any(stacked(v, lambda d: d in (np.int8, np.uint8))
               for v in eqn.invars):
        return False
    return any(stacked(v, lambda d: np.issubdtype(d, np.floating))
               for v in eqn.outvars)


register_trace_contract(
    "serve.fused_decode_step.kvq",
    _fused_step_point("off", cache_dtype="int8", s_max=_KVQ_S_MAX),
    TraceContract(
        max_host_callbacks=0,
        # int8 codes and ternary-packed uint8 planes both enter the
        # attention contractions in their stored layout — zero relayout
        no_pad_on_dtypes=("uint8", "int8"),
        forbid_prims=(
            PrimRule(
                rule="kvq-stacked-dequant",
                when=_kvq_stacked_dequant,
                reason="full-precision copy of the stacked quantized KV "
                       "cache — dequant must stay fused in the attention "
                       "contractions (DESIGN.md §13)",
            ),
        ),
        # future Pallas attention kernels must accumulate f32
        accum_dtype="float32",
    ),
    axes={"n_slots": (2, 6), "tp": (1, 2)},
)
