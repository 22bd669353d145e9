"""Shared neural-net building blocks (pure JAX, functional).

Every weight-bearing matmul flows through :func:`dense`, which implements
the paper's technique as a first-class mode switch:

  * quant_mode="off"     — bf16/f32 matmul (fp baseline),
  * quant_mode="ternary" — STE-ternarized weights & activations, exact
                           matmul (the software-level ternary DNN the
                           paper's accelerator executes),
  * quant_mode="cim"     — STE-ternarized weights & activations computed
                           with the SiTe CiM array semantics (16-row block
                           ADC clamp) via the execution API
                           (repro.api.execute with a CiMExecSpec).

Every ternary MAC goes through ``repro.core.execution.execute``: the
``QuantConfig`` mode (plus an optional explicit ``exec_spec`` override)
resolves to a declarative ``CiMExecSpec``, and the registry picks the
kernel. Scales: output = (x_t @ w_t) * sx * sw  — activation scale
(per-tensor by default, per-row under ``QuantConfig.act_scale=
"per_row"`` for row-independent batched serving) and per-output-channel
weight scale, both folded after the ternary MAC, which is exactly where
the TiM-DNN peripheral applies them.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import ternary as tern
from repro.core.execution import CiMExecSpec, execute as exec_mac

Param = jax.Array


def scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``, so
    every HLO op it emits carries ``name`` on its ``op_name`` path
    (metadata only; repro.profile.trace.SCOPES lists the names). The
    scope is looked up at call time."""
    def deco(f):
        @functools.wraps(f)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return f(*args, **kwargs)
        return inner
    return deco

# --- einsum accumulation strategy -----------------------------------------
# TPU MXU consumes bf16 operands with f32 accumulation natively
# (preferred_element_type) — no f32 copies of big operands (KV caches!).
# XLA:CPU *compiles* that form but cannot execute it, so CPU execution
# falls back to f32 casts. The dry-run (compile-only) forces native mode
# to produce the TPU-target HLO.
_NATIVE_ACCUM: bool | None = None  # None = auto (native unless CPU)


def set_native_accum(on: bool | None) -> None:
    global _NATIVE_ACCUM
    _NATIVE_ACCUM = on


def _native() -> bool:
    if _NATIVE_ACCUM is not None:
        return _NATIVE_ACCUM
    return jax.default_backend() != "cpu"


def accum_einsum(spec: str, *ops: jax.Array) -> jax.Array:
    """einsum with f32 accumulation; bf16-native on TPU, f32-cast on CPU."""
    if _native():
        return jnp.einsum(spec, *ops, preferred_element_type=jnp.float32)
    return jnp.einsum(spec, *[o.astype(jnp.float32) for o in ops])


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Paper-technique mode switch.

    mode:
      off       — fp baseline.
      ternary   — STE-quantized weights/activations, exact matmul (the
                  software-level ternary DNN).
      cim       — SiTe CiM array semantics via the blocked jnp formulation
                  (bit-exact per-16-block ADC clamp). XLA materializes the
                  (tokens, K/16, N) block intermediates in HBM — this is
                  the faithful *naive* lowering and the §Perf baseline.
      cim_fused — cost-faithful stand-in for the Pallas CiM kernel
                  (kernels/ternary_mac.py): two full-depth dots (signed +
                  magnitude) + elementwise combine; on TPU the per-block
                  clamp happens inside the kernel's VMEM tiles, so no
                  block intermediates reach HBM. Clamp numerics are
                  validated against the oracle in tests/test_kernels.py;
                  this mode's HLO reproduces the kernel's FLOP/byte
                  structure for the dry-run/roofline.
    """
    mode: str = "off"            # off | ternary | cim | cim_fused
    block: int = 16              # N_A rows per CiM cycle
    adc_max: int = 8             # 3-bit ADC + extra SA
    quantize_activations: bool = True
    # Activation-scale granularity. "per_tensor" (default, the TiM-DNN
    # peripheral's single scale) couples every row of a batched MAC
    # through one amax — co-batched serving rows then perturb each other.
    # "per_row" scales each (..., K) row independently (the per-input
    # granularity RRAM ternary-TNN work like Laborieux et al. uses):
    # fused-batch rows become numerically independent, so quantized
    # fused/TP serving is exactly token-identical to per-request
    # generate() (DESIGN.md §9; pinned in tests/test_tp_serve.py).
    act_scale: str = "per_tensor"   # per_tensor | per_row
    corrected: bool = False      # clip-as-correction formulation (perf opt)
    # TWN threshold factor: delta = factor * E[|w|] (Li et al.)
    threshold_factor: float = tern.TWN_THRESHOLD_FACTOR
    # Explicit execution spec. When set it overrides the mode-derived
    # spec entirely (new backends/formulations plug in here without any
    # layer-code change); when None, ``resolved_spec`` derives one from
    # (mode, block, adc_max, corrected).
    exec_spec: Optional[CiMExecSpec] = None
    # Serving: weights were ternarized offline (quant.prepare) — skip the
    # per-step STE re-quantization (which costs ~4 passes over every
    # weight). Per-channel scales are folded into the stored weights.
    pre_quantized: bool = False
    # TP serving: how the row-parallel (contraction-dim-sharded) dense
    # layers all-reduce their partial sums. "none" leaves it to the GSPMD
    # partitioner (exact, implicit). "int8" routes the MAC through the
    # explicit shard_map path (execution.execute_tp) with the
    # int8-compressed collective — 4x less TP wire traffic for
    # quantization-level error. Needs dist.sharding.set_tp_mesh (the
    # serving engine installs its mesh); inference-only.
    tp_reduce: str = "none"      # none | int8
    # KV-cache storage precision (DESIGN.md §13). "bf16" stores the
    # cache full-precision (bit-identical to the pre-§13 engine, pinned
    # by test). "int8" stores symmetric int8 codes + one f32 scale per
    # (row, position); "ternary" stores {-1,0,1} codes nibble-packed two
    # per byte + the TWN per-(row, position) scale — 2x / 4x slot
    # capacity at equal cache memory. Orthogonal to ``mode`` (the cache
    # holds activations, not weights); SSM conv/state caches stay exact.
    cache_dtype: str = "bf16"    # bf16 | int8 | ternary

    def __post_init__(self):
        if self.mode not in ("off", "ternary", "cim", "cim_fused"):
            raise ValueError(self.mode)
        if self.cache_dtype not in ("bf16", "int8", "ternary"):
            raise ValueError(
                f"unknown cache_dtype {self.cache_dtype!r} "
                "(bf16 | int8 | ternary)"
            )
        if self.tp_reduce not in ("none", "int8"):
            raise ValueError(f"unknown tp_reduce {self.tp_reduce!r}")
        if self.act_scale not in ("per_tensor", "per_row"):
            raise ValueError(
                f"unknown act_scale {self.act_scale!r} (per_tensor | per_row)"
            )
        if self.tp_reduce != "none" and self.mode == "off":
            raise ValueError(
                "tp_reduce compresses the quantized dense path's TP "
                "all-reduce; mode='off' runs no ternary MAC to compress"
            )
        if self.mode == "off" and self.exec_spec is not None:
            # dense() short-circuits to the fp matmul on mode="off" and
            # would never consult the spec — reject rather than ignore
            raise ValueError(
                "exec_spec has no effect with mode='off'; pick a "
                "quantized mode (serve.engine.apply_exec_spec upgrades "
                "the mode for you)"
            )

    def resolved_spec(self) -> CiMExecSpec:
        """The CiMExecSpec this config executes ternary MACs under."""
        if self.exec_spec is not None:
            return self.exec_spec
        if self.mode == "off":
            # fp baseline executes no ternary MAC — fabricating a CiM
            # spec here would attribute CiM semantics/costs to a model
            # that never runs them (dense() short-circuits before this)
            raise ValueError("mode='off' has no CiM execution spec")
        if self.mode == "ternary":
            # operand-dtype exact dot (bf16 TP all-reduces — §Perf A4)
            return CiMExecSpec(formulation="exact", backend="jnp",
                               block=self.block, adc_max=self.adc_max)
        if self.mode == "cim_fused":
            return CiMExecSpec(formulation="fused", backend="jnp",
                               block=self.block, adc_max=self.adc_max)
        formulation = "corrected" if self.corrected else "blocked"
        backend = "jnp" if self.corrected else "auto"
        return CiMExecSpec(formulation=formulation, backend=backend,
                           block=self.block, adc_max=self.adc_max)


def _ternarize_weight(
    w: jax.Array, factor: float = tern.TWN_THRESHOLD_FACTOR
) -> Tuple[jax.Array, jax.Array]:
    """Per-output-channel (last dim) ternarization with STE.

    Returns (w_t, scale) where w_t in {-1,0,1} and scale has shape (1, N).
    Gradients flow straight-through to the latent fp weight.
    """
    t, scale = tern.ternarize(w, axis=tuple(range(w.ndim - 1)), factor=factor)
    # STE: forward EXACTLY t (w + sg(t - w) is not value-exact in bf16 —
    # the rounding perturbs the CiM event counts), backward identity.
    w_t = t + (w - jax.lax.stop_gradient(w))
    return w_t, jax.lax.stop_gradient(scale)


def _ternarize_act(
    x: jax.Array,
    factor: float = tern.TWN_THRESHOLD_FACTOR,
    per_row: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Activation ternarization with STE; returns (x_t, scale).

    ``per_row=False``: one scale for the whole tensor (scalar).
    ``per_row=True``: threshold and scale per (..., K) row — shape
    (..., 1) — so each batched row quantizes independently of its
    batchmates (row-independent numerics; DESIGN.md §9).
    """
    axis = (x.ndim - 1,) if per_row else None
    t, scale = tern.ternarize(x, axis=axis, factor=factor)
    x_t = t + (x - jax.lax.stop_gradient(x))  # value-exact STE
    return x_t, jax.lax.stop_gradient(scale)


def dense(
    x: jax.Array,
    w: jax.Array,
    qc: QuantConfig,
    bias: Optional[jax.Array] = None,
    key: Optional[jax.Array] = None,
    tp: str = "none",
) -> jax.Array:
    """The mode-switched linear layer. x: (..., K), w: (K, N).

    ``key`` feeds the stochastic sensing-error channel and is required
    when the resolved spec has ``error_prob > 0`` (the model-assembly
    code does not thread per-layer RNG, so noisy specs are for direct
    dense()/api.execute callers — see serve.engine.apply_exec_spec).

    ``tp`` marks how this layer parallelizes under a "model"-axis mesh
    (DESIGN.md §8): "row" = the contraction dim K is the sharded one
    (wo / w_down / w_out — the layers whose partial sums need a TP
    all-reduce every step). With ``qc.tp_reduce="int8"`` and a TP mesh
    installed (dist.sharding.set_tp_mesh), row-parallel quantized MACs
    route through the explicit ``execution.execute_tp`` shard_map path
    so that all-reduce moves an int8 payload; everything else keeps the
    implicit GSPMD collectives (exact).
    """
    if qc.mode != "off":
        return _cim_dense(x, w, qc, bias, key, tp)
    out = x @ w.astype(x.dtype)
    if bias is not None:
        out = out + bias.astype(out.dtype)
    return out


@scoped("cim")
def _cim_dense(x, w, qc: QuantConfig, bias, key, tp: str) -> jax.Array:
    """The quantized path of :func:`dense`: weight (re-)derivation, the
    activation quantizer, the execution shim and its kernel, the scales
    and the bias, all under the ``cim`` scope."""
    if qc.pre_quantized:
        # weights were ternarized offline with the per-channel scale
        # folded in (values in {-s_n, 0, +s_n}); recover (t, s) with a
        # single max-reduce — the CiM event counts need pure {-1,0,1}
        # operands, and this is one pass over w instead of the ~4 the
        # STE threshold quantizer costs.
        sw = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)), keepdims=True)
        w_t = w / jnp.maximum(sw, jnp.asarray(1e-12, w.dtype))
        sw = jax.lax.stop_gradient(sw)
    else:
        w_t, sw = _ternarize_weight(w, qc.threshold_factor)
    if qc.quantize_activations:
        x_t, sx = _ternarize_act(x, qc.threshold_factor,
                                 per_row=qc.act_scale == "per_row")
    else:
        x_t, sx = x, jnp.ones((), x.dtype)
    # One dispatch point for every ternary MAC: the spec (derived from
    # the mode, or an explicit qc.exec_spec) picks the registered
    # kernel; the shim owns padding, dtype policy, and the STE VJP.
    #   ternary    -> exact/jnp: operand-dtype dot (the TP partial-sum
    #                 all-reduce then moves bf16, not f32 — §Perf A4)
    #   cim        -> blocked/auto: faithful per-16-block ADC clamp
    #                 (Pallas kernel on TPU, jnp formulation on CPU)
    #   cim_fused  -> fused/jnp: the kernel's HLO cost structure for
    #                 dry-run/roofline work (numerically exact; on TPU
    #                 the clamp happens inside the kernel's VMEM
    #                 tiles, so no block intermediates reach HBM)
    spec = qc.resolved_spec()
    mac = exec_mac
    from repro.core.execution import execute_tp, needs_manual_spmd
    from repro.dist.sharding import tp_mesh

    mesh = tp_mesh()
    if mesh is not None and "model" in mesh.axis_names \
            and spec.resolve().packing == "none":
        if qc.tp_reduce == "int8" and tp == "row":
            # explicit row-parallel shard_map MAC: the per-layer TP
            # partial-sum all-reduce moves int8 (inference-only);
            # the caller's key (if any) seeds the rounding stream
            def mac(spec, x_q, w_q, key=None):
                return execute_tp(spec, x_q, w_q, mesh,
                                  compressed=True, key=key)
        elif needs_manual_spmd(spec) and mesh.shape["model"] > 1:
            # a Pallas kernel cannot be split by the SPMD
            # partitioner: run it per shard, row- or column-parallel
            # as the layer's weight is sharded (exact either way)
            split = "row" if tp == "row" else "col"

            def mac(spec, x_q, w_q, key=None):
                return execute_tp(spec, x_q, w_q, mesh, split=split)

    if spec.clamps:
        out = mac(spec, x_t.astype(jnp.float32), w_t.astype(jnp.float32),
                  key=key)
    else:
        out = mac(spec, x_t.astype(x.dtype), w_t.astype(x.dtype),
                  key=key)
    # fold scales in the output dtype: an f32 round-trip here makes
    # every backward cotangent (and its all-reduce) f32 (§Perf A5)
    out = out.astype(x.dtype) * (sx * sw).astype(x.dtype)
    if bias is not None:
        out = out + bias.astype(out.dtype)
    return out


# ---------------------------------------------------------------------------
# Norms / activations / embeddings
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * gamma.astype(x.dtype)


def layer_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * gamma.astype(x.dtype) + beta.astype(x.dtype)


def swiglu(x_gate: jax.Array, x_up: jax.Array) -> jax.Array:
    return jax.nn.silu(x_gate) * x_up


def embed(tokens: jax.Array, table: jax.Array) -> jax.Array:
    return jnp.take(table, tokens, axis=0)


def unembed(x: jax.Array, table: jax.Array, qc: QuantConfig) -> jax.Array:
    # The unembedding is a dense layer too; ternary mode applies when the
    # config enables it (logit layers are usually kept high precision —
    # controlled by the arch config's `quantize_unembed`).
    return dense(x, table.T, qc)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """x: (B, S, H, Dh), positions: (B, S) int32."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)           # (Dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, Dh/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP blocks
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = d_model ** -0.5
    s_ff = d_ff ** -0.5
    return {
        "w_gate": (jax.random.normal(k1, (d_model, d_ff)) * s_in).astype(dtype),
        "w_up": (jax.random.normal(k2, (d_model, d_ff)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(k3, (d_ff, d_model)) * s_ff).astype(dtype),
    }


def mlp(params, x: jax.Array, qc: QuantConfig) -> jax.Array:
    g = dense(x, params["w_gate"], qc)
    u = dense(x, params["w_up"], qc)
    return dense(swiglu(g, u), params["w_down"], qc, tp="row")


def init_dense_weight(key, shape, fan_in: Optional[int] = None, dtype=jnp.float32):
    fan_in = shape[0] if fan_in is None else fan_in
    return (jax.random.normal(key, shape) * fan_in ** -0.5).astype(dtype)
