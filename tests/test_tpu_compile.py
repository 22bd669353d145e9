"""Ahead-of-time compiles of the CiM kernels for a described TPU v5e.

Interpret mode (every other kernel test) accepts shapes, casts and
slices that the TPU's Mosaic compiler refuses. These tests hand the
real compiler each kernel at smollm-135m widths (d_model 576, d_ff 1536,
q+k+v 960), padded to the dispatch tiles of ``core/execution.py``, in the
decode and prefill tile classes, for a v5e chip that is described, not
attached. Nothing runs, so they pin compilability only; numerics are the
interpret-mode bit-identity tests' job.

The topology is described inside a module-scoped fixture, never at
import time: only one process at a time may load the TPU library, and
under pytest-xdist every worker imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.packed_mac import (
    packed_cim_matmul,
    packed_cim_matmul_decode,
    packed_cim_matmul_decode_stream,
)
from repro.kernels.ternary_mac import ternary_cim_matmul, ternary_exact_matmul

D_MODEL, D_FF, QKV = 576, 1536, 960


def _pad(n, mult):
    return -(-n // mult) * mult


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A v5e device to compile for, with the persistent compilation
    cache off: an entry compiled for a described chip cannot be read
    back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _ternary_case(kernel, m, bm, bk, k, n, **kw):
    """(fn, [(shape, dtype)]) of a dense-ternary kernel call: x (M, K),
    w (K, N) bf16, both padded to the tiles."""
    fn = functools.partial(kernel, bm=bm, bk=bk, bn=128, **kw)
    return fn, [((_pad(m, bm), _pad(k, bk)), jnp.bfloat16),
                ((_pad(k, bk), _pad(n, 128)), jnp.bfloat16)]


def _packed_case(m, bm, k, n, **kw):
    kp = _pad(k, 256)
    fn = functools.partial(packed_cim_matmul, bm=bm, bk=256, bn=128, **kw)
    plane = ((kp // 8, _pad(n, 128)), jnp.uint8)
    return fn, [((_pad(m, bm), kp), jnp.bfloat16), plane, plane]


def _decode_case(k, n, **kw):
    kp = _pad(k, 256)
    fn = functools.partial(packed_cim_matmul_decode, bk=256, bn=128, **kw)
    plane = ((kp // 8, _pad(n, 128)), jnp.uint8)
    return fn, [((8, kp), jnp.int8), plane, plane]


def _stream_case(k, n, nbuf, **kw):
    kp = _pad(k, 256)
    fn = functools.partial(
        packed_cim_matmul_decode_stream, bk=256, bn=128, nbuf=nbuf, **kw)
    return fn, [((8, kp), jnp.int8), ((kp // 4, _pad(n, 128)), jnp.uint8)]


CASES = {
    # blocked/pallas/none — every dense layer of the served model
    "cim-decode-qkv": lambda: _ternary_case(
        ternary_cim_matmul, 8, 8, 128, D_MODEL, QKV),
    "cim-decode-down": lambda: _ternary_case(
        ternary_cim_matmul, 8, 8, 128, D_FF, D_MODEL),
    "cim-prefill-up": lambda: _ternary_case(
        ternary_cim_matmul, 512, 128, 128, D_MODEL, D_FF),
    # exact/pallas/none — the near-memory baseline
    "exact-decode": lambda: _ternary_case(
        ternary_exact_matmul, 8, 8, 512, D_MODEL, D_FF),
    "exact-prefill": lambda: _ternary_case(
        ternary_exact_matmul, 256, 128, 512, D_FF, D_MODEL),
    # */pallas/bitplane_u8 — 2-bit planes, prefill and decode classes
    "packed-prefill-cim": lambda: _packed_case(256, 128, D_MODEL, D_FF),
    "packed-prefill-exact": lambda: _packed_case(
        256, 128, D_MODEL, D_FF, cim=False),
    "packed-decode-cim": lambda: _decode_case(D_FF, D_MODEL),
    "packed-decode-exact": lambda: _decode_case(D_MODEL, QKV, cim=False),
    # */pallas_stream/bitplane_u8 — hand-DMA'd plane tiles
    "stream-decode-nbuf2": lambda: _stream_case(D_MODEL, D_FF, 2),
    "stream-decode-nbuf3": lambda: _stream_case(D_FF, D_MODEL, 3),
    "stream-decode-exact": lambda: _stream_case(D_MODEL, QKV, 2, cim=False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, args = CASES[case]()
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text(), case
