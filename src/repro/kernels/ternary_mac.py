"""Pallas TPU kernel: signed-ternary CiM matmul (a/b decomposition + ADC clamp).

TPU-native formulation of the SiTe CiM array semantics (DESIGN.md §2):
for each 16-element block of the contraction dimension we need the event
counts

    a = (|x|·|w| + x·w) / 2,     b = (|x|·|w| - x·w) / 2

clamped at the ADC bound (8) and accumulated. Inside a (bm, bk, bn) tile
:func:`block_event_mac` stacks ``bk/16`` lane-masked copies of the x tile
along the sublane axis — copy ``s`` keeps only the lanes of the 16-row
sub-block ``s`` (the N_A row-assertion granularity) — so one full-depth
MXU contraction against the unchanged w tile yields every sub-block's
signed and magnitude partials at once. The clamp/recombine is
elementwise, and the ``bk/16`` row groups are summed into the output
tile, accumulating across the K grid dimension. Nothing is reshaped
across the 128-lane axis, which the TPU's Mosaic compiler refuses.

VMEM budget per grid step (bf16 in, f32 acc), kb = bk/16:
    x tile: bm*bk*2 B, stacked x: kb*bm*bk*(4+2) B (f32 masking, bf16
    operand), w tile: bk*bn*2 B, out tile: bm*bn*4 B, two (kb*bm, bn)
    f32 partials: 2*kb*bm*bn*4 B.
Default (bm, bk, bn) = (128, 128, 128): 32 KiB + 768 KiB + 32 KiB +
64 KiB + 2*512 KiB = 1.9 MiB — comfortably inside the ~16 MiB VMEM of a
v5e core, leaving room for double buffering. The stacked contraction does
kb times the MXU work of an exact matmul, an inherent cost of the
faithful per-block ADC semantics (the clip-as-correction formulation
amortizes it — DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 16
DEFAULT_ADC_MAX = 8


def block_event_mac(x, w, *, sub: int, adc_max, acc_dtype):
    """Per-``sub``-row ADC-clamped event-count MAC of one tile.

    x: (m, bk), w: (bk, bn), ternary values; ``acc_dtype`` is float32
    (bf16 MXU operands) or int32 (int8 operands). Returns the (m, bn)
    sum over the ``bk/sub`` sub-blocks of min(a, adc) - min(b, adc).
    Masking, stacking and abs run in the 32-bit ``acc_dtype`` (its
    8-row sublane tile keeps the (m, bk) copies aligned for any m that
    is a multiple of 8, and Mosaic has no int8 abs); only the MXU
    operands are narrowed.
    """
    m, bk = x.shape
    kb = bk // sub
    xw = x.astype(acc_dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, (m, bk), 1)
    zero = jnp.zeros_like(xw)
    xs = jnp.concatenate(
        [
            jnp.where((lane >= s * sub) & (lane < (s + 1) * sub), xw, zero)
            for s in range(kb)
        ],
        axis=0,
    )  # (kb*m, bk): row group s holds sub-block s's lanes only
    op = w.dtype
    dims = (((1,), (0,)), ((), ()))
    p = jax.lax.dot_general(
        xs.astype(op), w, dims, preferred_element_type=acc_dtype
    )
    mm = jax.lax.dot_general(
        jnp.abs(xs).astype(op), jnp.abs(w.astype(acc_dtype)).astype(op), dims,
        preferred_element_type=acc_dtype,
    )
    if xw.dtype.kind == "i":
        # a/b are the RBL1/RBL2 discharge-event counts: small non-negative
        # integers bounded by `sub` (TiM-DNN's partial-sum range
        # analysis), so the halving and the clamp stay exact integer
        # arithmetic
        a = (mm + p) // 2
        b = (mm - p) // 2
    else:
        a = (mm + p) * 0.5
        b = (mm - p) * 0.5
    part = jnp.minimum(a, adc_max) - jnp.minimum(b, adc_max)
    out = part[0:m]
    for s in range(1, kb):
        out = out + part[s * m:(s + 1) * m]
    return out


def _cim_mac_kernel(x_ref, w_ref, o_ref, *, sub: int, adc_max: float):
    """One (i, j, k) grid step: accumulate the CiM partial for this K tile."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += block_event_mac(
        x_ref[...], w_ref[...], sub=sub, adc_max=adc_max,
        acc_dtype=jnp.float32,
    )


@functools.partial(
    jax.jit,
    static_argnames=("block", "adc_max", "bm", "bk", "bn", "interpret"),
)
def ternary_cim_matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    block: int = DEFAULT_BLOCK,
    adc_max: int = DEFAULT_ADC_MAX,
    bm: int = 128,
    bk: int = 128,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """CiM ternary matmul. x: (M, K), w: (K, N), values in {-1, 0, 1}.

    Shapes must tile evenly (callers pad; repro.kernels.ops handles this).
    Returns f32 (M, N) with per-``block`` ADC clamping at ``adc_max``.
    """
    m_dim, k_dim = x.shape
    k2, n_dim = w.shape
    assert k_dim == k2, (x.shape, w.shape)
    assert m_dim % bm == 0 and k_dim % bk == 0 and n_dim % bn == 0, (
        x.shape,
        w.shape,
        (bm, bk, bn),
    )
    assert bk % block == 0, (bk, block)
    grid = (m_dim // bm, n_dim // bn, k_dim // bk)

    kernel = functools.partial(
        _cim_mac_kernel, sub=block, adc_max=float(adc_max)
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_dim, n_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w)


def _exact_mac_kernel(x_ref, w_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(
        x_ref[...],
        w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit, static_argnames=("bm", "bk", "bn", "interpret")
)
def ternary_exact_matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    bm: int = 128,
    bk: int = 512,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Near-memory baseline kernel: exact ternary matmul with full-depth
    MXU contractions (no per-block clamp). Also the fast path of the
    clip-as-correction optimization."""
    m_dim, k_dim = x.shape
    _, n_dim = w.shape
    assert m_dim % bm == 0 and k_dim % bk == 0 and n_dim % bn == 0
    grid = (m_dim // bm, n_dim // bn, k_dim // bk)
    return pl.pallas_call(
        _exact_mac_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_dim, n_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w)
