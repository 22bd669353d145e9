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
import re

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
from repro.models import transformer as T
from repro.models.layers import QuantConfig
from repro.models.registry import get_config
from repro.serve.engine import fused_decode_fn

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


def _layer_layout(layout):
    """The layout of one layer's slice (axis 0 dropped) of a stacked
    array laid out as ``layout`` (``"2,4,3,1,0:T(8,128)(2,1)"``)."""
    order, _, tiles = layout.partition(":")
    dims = [int(d) for d in order.split(",")]
    kept = ",".join(str(d - 1) for d in dims if d != 0)
    return f"{kept}:{tiles}" if tiles else kept


def test_fused_decode_writes_the_kv_cache_in_place(one_chip):
    """smollm-135m's fused decode step at 64 slots and s_max 2048 (bf16
    cache, donated) never relays out its KV cache: every instruction
    whose result is a whole stacked cache or one layer's slice of it has
    the argument's own layout, none is a copy, and the temporaries stay
    small (a layout change of the two 1.5 GB caches needs GBs)."""
    cfg = get_config("smollm-135m").replace(quant=QuantConfig(mode="off"))
    n, s_max = 64, 2048

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.PRNGKey(0)))
    caches = on_chip(jax.eval_shape(lambda: T.init_caches(cfg, n, s_max)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = jax.jit(fused_decode_fn(cfg), donate_argnums=(2,)).lower(
        params, i32(n, 1), caches, i32(n), i32(n), key).compile()
    text = compiled.as_text()

    stacked = "bf16[" + ",".join(map(str, caches.k.shape)) + "]"
    layer = "bf16[" + ",".join(map(str, caches.k.shape[1:])) + "]"
    arg = re.search(re.escape(stacked) + r"\{([^}]*)\} parameter\(\d+\).*op_name=\"caches\.k\"",
                    text)
    assert arg, "no caches.k parameter in the program"
    want = {stacked: arg.group(1), layer: _layer_layout(arg.group(1))}
    instr = re.compile(r"^\s*(?:ROOT\s+)?(%\S+) = (" + re.escape(stacked) + "|"
                       + re.escape(layer) + r")\{([^}]*)\} ([\w-]+)\(", re.M)
    found = instr.findall(text)
    assert any(op == "dynamic-update-slice" for *_, op in found)
    bad = [(name, shape, lay, op) for name, shape, lay, op in found
           if lay != want[shape] or op == "copy"]
    assert not bad, bad
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
