"""Pallas TPU kernels: bitplane-packed ternary CiM matmul.

The SiTe CiM cell stores a ternary weight as two binary bit-cells (M1,
M2). These kernels keep weights in exactly that differential format,
packed 8-per-byte along K (repro.core.ternary.pack_ternary): two uint8
arrays of shape (K/8, N). Per ternary weight that is 2 bits of HBM
traffic — 8x less than int8 and 16x less than bf16, which is the win in
the weight-streaming-bound decode regime (see EXPERIMENTS.md §Perf).

Two variants share the format (DESIGN.md §9):

  * :func:`packed_cim_matmul` — the prefill-shaped kernel (M-tiled grid,
    bf16 operands, f32 accumulation). In-kernel, the bitplanes are
    expanded to ternary bf16 in VMEM (cheap VPU work overlapped with the
    MXU) and fed to the same a/b-decomposition CiM MAC as
    kernels/ternary_mac.py.
  * :func:`packed_cim_matmul_decode` — the decode-shaped (small-M)
    variant: the whole M extent rides inside every grid step (grid is
    (N, K) only), so each (k, j) plane tile is unpacked exactly once per
    call instead of once per M-tile, and the a/b event counts — small
    integers bounded by ``block`` — are computed and accumulated in
    int32 from int8 operands. Bit-identical to the prefill kernel
    (integer event counts are exact in both f32 and int32).

Both unpack the planes through int32 (Mosaic casts uint8 only to other
integers) and run the per-16-row MAC of
:func:`repro.kernels.ternary_mac.block_event_mac`.

VMEM budget per grid step, default (bm, bk, bn) = (128, 256, 128), with
kb = bk/16 = 16 stacked copies of the x tile:
  x: 128*256*2 = 64 KiB; packed planes: 2 * (256/8)*128 = 8 KiB;
  unpacked w: 256*128*2 = 64 KiB; out: 64 KiB; stacked x
  16*128*256*(4+2) = 3 MiB (f32 masking + bf16 operand); partials
  2*16*128*128*4 = 2 MiB -> ~5.2 MiB.
Decode variant, default (bk, bn) = (256, 128) at M <= 8: the stacked x
is 16*8*256*(4+1) = 160 KiB and the partials 2*16*8*128*4 = 128 KiB —
the grid-step footprint shrinks ~16x with the M extent.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ternary_mac import block_event_mac

DEFAULT_BLOCK = 16
DEFAULT_ADC_MAX = 8


def _unpack_bits(plane: jax.Array) -> jax.Array:
    """(kp, bn) uint8 -> (kp, 8, bn) {0,1} int32: bit ``b`` of byte-row
    ``r`` is K index ``8r + b``. The bytes widen to int32 before any
    shift (Mosaic casts uint8 only to other integers)."""
    kp, bn = plane.shape
    shifts = jax.lax.broadcasted_iota(jnp.int32, (kp, 8, bn), 1)
    return (plane.astype(jnp.int32)[:, None, :] >> shifts) & 1


def _unpack_ternary(w_pos: jax.Array, w_neg: jax.Array, dtype) -> jax.Array:
    """Two (bk/8, bn) uint8 planes -> the (bk, bn) ternary tile, K-major."""
    kp, bn = w_pos.shape
    w = _unpack_bits(w_pos) - _unpack_bits(w_neg)
    return w.reshape(kp * 8, bn).astype(dtype)


def _packed_kernel(x_ref, wp_ref, wn_ref, o_ref, *, sub, adc_max, cim):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]  # (bm, bk) bf16 ternary values
    w = _unpack_ternary(wp_ref[...], wn_ref[...], x.dtype)  # (bk, bn)
    if not cim:
        o_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return
    o_ref[...] += block_event_mac(
        x, w, sub=sub, adc_max=adc_max, acc_dtype=jnp.float32
    )


@functools.partial(
    jax.jit,
    static_argnames=("block", "adc_max", "cim", "bm", "bk", "bn", "interpret"),
)
def packed_cim_matmul(
    x: jax.Array,
    w_pos: jax.Array,
    w_neg: jax.Array,
    *,
    block: int = DEFAULT_BLOCK,
    adc_max: int = DEFAULT_ADC_MAX,
    cim: bool = True,
    bm: int = 128,
    bk: int = 256,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """x: (M, K) ternary values; w_pos/w_neg: (K/8, N) packed bitplanes.

    ``cim=True`` applies the per-16-block ADC clamp; ``cim=False`` is the
    exact (NM-baseline) product from the packed format.
    """
    m_dim, k_dim = x.shape
    kp, n_dim = w_pos.shape
    assert w_neg.shape == w_pos.shape
    assert kp * 8 == k_dim, (x.shape, w_pos.shape)
    assert m_dim % bm == 0 and k_dim % bk == 0 and n_dim % bn == 0
    assert bk % (8 * block) == 0 or not cim
    grid = (m_dim // bm, n_dim // bn, k_dim // bk)
    kernel = functools.partial(
        _packed_kernel, sub=block, adc_max=float(adc_max), cim=cim
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // 8, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk // 8, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_dim, n_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w_pos, w_neg)


def _int_mac(x, w, *, sub, adc_max, cim):
    """int8 (m, bk) x int8 (bk, bn) -> int32 (m, bn): the decode kernels'
    exact or per-block ADC-clamped MAC."""
    if not cim:
        return jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
        )
    return block_event_mac(x, w, sub=sub, adc_max=adc_max, acc_dtype=jnp.int32)


def _packed_decode_kernel(x_ref, wp_ref, wn_ref, o_ref, *, sub, adc_max, cim):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = _unpack_ternary(wp_ref[...], wn_ref[...], jnp.int8)  # (bk, bn)
    o_ref[...] += _int_mac(x_ref[...], w, sub=sub, adc_max=adc_max, cim=cim)


@functools.partial(
    jax.jit,
    static_argnames=("block", "adc_max", "cim", "bk", "bn", "interpret"),
)
def packed_cim_matmul_decode(
    x: jax.Array,
    w_pos: jax.Array,
    w_neg: jax.Array,
    *,
    block: int = DEFAULT_BLOCK,
    adc_max: int = DEFAULT_ADC_MAX,
    cim: bool = True,
    bk: int = 256,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Decode-shaped packed MAC: x (M, K) int8 ternary values with a
    *small* M (the whole extent rides in every grid step — callers pad M
    to the decode tile, 8, not to 128); w_pos/w_neg (K/8, N) packed
    bitplanes.

    The grid is (N/bn, K/bk): with no M grid dimension each (k, j) plane
    tile is unpacked exactly once per call, and the per-16-row a/b event
    counts accumulate in int32 (they are bounded by ``block``, so the
    integer pipeline is bit-identical to the f32 prefill kernel — pinned
    in tests/test_decode_fastpath.py). Returns int32 (M, N).
    """
    m_dim, k_dim = x.shape
    kp, n_dim = w_pos.shape
    assert w_neg.shape == w_pos.shape
    assert kp * 8 == k_dim, (x.shape, w_pos.shape)
    assert m_dim <= 128, f"decode kernel is for small M, got {m_dim}"
    assert k_dim % bk == 0 and n_dim % bn == 0
    assert bk % (8 * block) == 0 or not cim
    grid = (n_dim // bn, k_dim // bk)
    kernel = functools.partial(
        _packed_decode_kernel, sub=block, adc_max=int(adc_max), cim=cim
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m_dim, bk), lambda j, k: (0, k)),
            pl.BlockSpec((bk // 8, bn), lambda j, k: (k, j)),
            pl.BlockSpec((bk // 8, bn), lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((m_dim, bn), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m_dim, n_dim), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w_pos, w_neg)


def _packed_decode_stream_kernel(
    x_ref, w_ref, o_ref, *, sub, adc_max, cim, bk, nbuf, nk
):
    """Streaming decode body: K is not a grid dimension — the (k, j)
    plane tiles are hand-DMA'd from ``w_ref`` (ANY memory space, i.e.
    HBM on TPU) into an ``nbuf``-deep VMEM scratch while the previous
    tile's MAC runs. ``pl.run_scoped`` owns the scratch + DMA
    semaphores; the ``lax.fori_loop`` slot rotation is the same trace in
    interpret mode, so the fallback is bit-identical by construction.
    """
    j = pl.program_id(0)
    o_ref[...] = jnp.zeros_like(o_ref)
    bn = o_ref.shape[-1]
    tk = bk // 4  # interleaved byte-rows per (k, j) tile: pos+neg

    def body(scratch, sem):
        def tile_dma(slot, kidx):
            return pltpu.make_async_copy(
                w_ref.at[pl.ds(kidx * tk, tk), pl.ds(j * bn, bn)],
                scratch.at[slot],
                sem.at[slot],
            )

        # Warm-up: the first nbuf-1 tiles go in flight before any MAC
        # (statically unrolled — these are the extra dma_start eqns the
        # tracing contract pins).
        for kidx in range(min(nbuf - 1, nk)):
            tile_dma(kidx, kidx).start()

        def step(i, carry):
            slot = jax.lax.rem(i, nbuf)

            @pl.when(i + nbuf - 1 < nk)
            def _prefetch():
                tile_dma(jax.lax.rem(i + nbuf - 1, nbuf), i + nbuf - 1).start()

            tile_dma(slot, i).wait()
            # (bk/4, bn) uint8 tile, pos/neg byte-rows interleaved: split
            # the leading dim of the unpacked bits, never the sublanes
            bits = _unpack_bits(scratch[slot]).reshape(bk // 8, 2, 8, bn)
            w = (bits[:, 0] - bits[:, 1]).reshape(bk, bn).astype(jnp.int8)
            xc = x_ref[:, pl.ds(pl.multiple_of(i * bk, bk), bk)]
            o_ref[...] += _int_mac(xc, w, sub=sub, adc_max=adc_max, cim=cim)
            return carry

        jax.lax.fori_loop(0, nk, step, 0)

    pl.run_scoped(
        body,
        scratch=pltpu.VMEM((nbuf, tk, bn), jnp.uint8),
        sem=pltpu.SemaphoreType.DMA((nbuf,)),
    )


@functools.partial(
    jax.jit,
    static_argnames=("block", "adc_max", "cim", "bk", "bn", "nbuf", "interpret"),
)
def packed_cim_matmul_decode_stream(
    x: jax.Array,
    w_int: jax.Array,
    *,
    block: int = DEFAULT_BLOCK,
    adc_max: int = DEFAULT_ADC_MAX,
    cim: bool = True,
    bk: int = 256,
    bn: int = 128,
    nbuf: int = 2,
    interpret: bool = False,
) -> jax.Array:
    """Double-buffered streaming variant of :func:`packed_cim_matmul_decode`.

    x: (M, K) int8 ternary values, small M (callers pad to the decode
    tile). ``w_int``: ONE (K/4, N) uint8 array holding both bitplanes in
    the layout-version-1 plane-interleaved ordering
    (``repro.core.ternary.interleave_planes``): byte-row 2r is the pos
    byte-row r, 2r+1 the neg byte-row r, so a single contiguous DMA
    fetches both planes of a (k, j) tile.

    The grid is (N/bn,) — K is streamed inside the kernel: while tile
    ``i``'s int32 a/b event-count MAC runs, tiles ``i+1 .. i+nbuf-1``
    are already in flight into the rotating VMEM scratch
    (``nbuf`` ∈ {2, 3} buffer slots, ``pltpu.make_async_copy`` against
    per-slot DMA semaphores). The MAC math is byte-for-byte the decode
    kernel's (int8 operands, int32 accumulation, integer halving and
    ADC clamp), so the result is bit-identical to
    :func:`packed_cim_matmul_decode` and the bitplane oracle — pinned in
    tests/test_stream_decode.py and by the
    ``execution.execute_packed.decode.stream`` tracing contract.
    Returns int32 (M, N).
    """
    m_dim, k_dim = x.shape
    rows, n_dim = w_int.shape
    assert rows * 4 == k_dim, (x.shape, w_int.shape)
    assert m_dim <= 128, f"stream decode kernel is for small M, got {m_dim}"
    assert k_dim % bk == 0 and n_dim % bn == 0
    assert bk % (8 * block) == 0 or not cim
    assert nbuf in (2, 3), f"buffer depth {nbuf} not in {{2, 3}}"
    nk = k_dim // bk
    kernel = functools.partial(
        _packed_decode_stream_kernel,
        sub=block, adc_max=int(adc_max), cim=cim, bk=bk, nbuf=nbuf, nk=nk,
    )
    return pl.pallas_call(
        kernel,
        grid=(n_dim // bn,),
        in_specs=[
            pl.BlockSpec((m_dim, k_dim), lambda j: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((m_dim, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m_dim, n_dim), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(x, w_int)


# ---------------------------------------------------------------------------
# Tracing contracts (repro.analysis — DESIGN.md §10)
#
# The kernel-level invariants, declared next to the kernels they pin:
#
#   * the decode kernel's a/b event counts accumulate in int32 — an f32
#     accumulator would still be numerically exact (counts are bounded
#     by `block`) but silently abandons the integer ADC pipeline the
#     TiM-DNN macro contract costs against, and converts would creep
#     into the int8 decode datapath;
#   * the prefill kernel deliberately accumulates in f32 (bf16 MXU
#     operands) — pinned too, so a change to either side is a conscious
#     contract edit, not drift.
# ---------------------------------------------------------------------------

from repro.analysis.contracts import (  # noqa: E402
    TraceContract,
    forbid_convert,
    register_trace_contract,
)


def _decode_kernel_point():
    x = jnp.ones((8, 256), jnp.int8)
    planes = jnp.zeros((32, 128), jnp.uint8)

    def f(xv, wp, wn):
        return packed_cim_matmul_decode(xv, wp, wn, interpret=True)

    return f, (x, planes, planes)


def _prefill_kernel_point():
    x = jnp.ones((128, 256), jnp.bfloat16)
    planes = jnp.zeros((32, 128), jnp.uint8)

    def f(xv, wp, wn):
        return packed_cim_matmul(xv, wp, wn, interpret=True)

    return f, (x, planes, planes)


register_trace_contract(
    "kernels.packed_decode_kernel",
    _decode_kernel_point,
    TraceContract(
        max_host_callbacks=0,
        accum_dtype="int32",
        forbid_prims=(
            forbid_convert(
                from_kinds=("int",), to=("float32", "float64", "bfloat16"),
                within="pallas_call",
                reason="the decode kernel's int8/int32 event-count "
                       "datapath must not promote to float",
            ),
        ),
    ),
)

register_trace_contract(
    "kernels.packed_prefill_kernel",
    _prefill_kernel_point,
    TraceContract(max_host_callbacks=0, accum_dtype="float32"),
)


def _stream_kernel_point():
    x = jnp.ones((8, 512), jnp.int8)
    w_int = jnp.zeros((128, 256), jnp.uint8)  # (K/4, N) plane-interleaved

    def f(xv, wi):
        return packed_cim_matmul_decode_stream(xv, wi, interpret=True)

    return f, (x, w_int)


# The DMA-eqn pin is the overlap guarantee: exactly nbuf (= 2) dma_start
# eqns — the unrolled warm-up plus the single in-loop prefetch — and one
# dma_wait per trace. A kernel that quietly stopped prefetching (0 or 1
# starts) or began blocking per tile (more waits) breaks the pin before
# any benchmark notices.
register_trace_contract(
    "kernels.packed_decode_stream_kernel",
    _stream_kernel_point,
    TraceContract(
        max_host_callbacks=0,
        accum_dtype="int32",
        pin_prims=(("dma_start", 2), ("dma_wait", 1)),
        forbid_prims=(
            forbid_convert(
                from_kinds=("int",), to=("float32", "float64", "bfloat16"),
                within="pallas_call",
                reason="the streaming decode kernel keeps the int8/int32 "
                       "event-count datapath of the decode kernel",
            ),
        ),
    ),
)
