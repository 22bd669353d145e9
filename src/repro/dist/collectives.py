"""Explicit compressed collectives (shard_map manual SPMD).

optim/compress.py models the *numerics* of a compressed gradient
reduction under pjit autodiff (encode/decode round trip). These
primitives actually narrow the wire format: each shard quantizes its
local payload to int8 (stochastic rounding, globally shared scale) and
the all-reduce moves the int8 payload; the f32 decode happens after the
sum. Tested on a forced multi-device host mesh in
tests/test_collectives.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def compressed_psum_int8(x: jax.Array, key: jax.Array, axis_name: str) -> jax.Array:
    """Int8-compressed psum over ``axis_name`` (call inside shard_map).

    All shards agree on one scale (pmax of the local amax), quantize with
    unbiased stochastic rounding, and all-reduce the payload in an int32
    accumulator (sums of int8 across any realistic axis size fit).
    Returns the decoded f32 sum.
    """
    amax = jax.lax.pmax(jnp.max(jnp.abs(x)).astype(jnp.float32), axis_name)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    noise = jax.random.uniform(key, x.shape, jnp.float32, -0.5, 0.5)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale + noise), -127, 127)
    total = jax.lax.psum(q.astype(jnp.int32), axis_name)
    return total.astype(jnp.float32) * scale


def tp_allreduce(
    x: jax.Array,
    axis_name: str,
    *,
    key: jax.Array | None = None,
    compressed: bool = False,
) -> jax.Array:
    """Tensor-parallel partial-sum all-reduce (call inside shard_map).

    The serving TP entry point: row-parallel (contraction-dim-sharded)
    ternary GEMMs produce per-device partial sums that must be summed
    over the "model" axis every layer. ``compressed=False`` is the exact
    ``psum`` — for CiM formulations the partials are integer ADC event
    counts, so the f32 sum is exact and TP serving stays bit-identical.
    ``compressed=True`` narrows the wire to int8
    (:func:`compressed_psum_int8`, needs ``key`` for the stochastic
    rounding) — the 4x-narrower collective the SiTe bitplane format pairs
    with, at quantization-level error (bounded in tests/test_collectives).
    """
    if not compressed:
        return jax.lax.psum(x, axis_name)
    if key is None:
        raise ValueError("compressed tp_allreduce needs a PRNG key "
                         "(stochastic-rounding stream)")
    return compressed_psum_int8(x, key, axis_name)


def mean_grads_int8(
    mesh, grads: jax.Array, keys: jax.Array, axis_name: str = "data"
) -> jax.Array:
    """Mean-reduce per-shard gradients over ``axis_name`` with an int8
    wire format.

    grads: (n_shards, ...) — one local gradient per shard along dim 0.
    keys:  (n_shards, 2) uint32 PRNG keys (one rounding stream per shard).
    Returns the replicated f32 mean with shape ``grads.shape[1:]``.
    """
    n = int(mesh.shape[axis_name])

    def local(g, k):
        g = g.reshape(g.shape[1:])        # drop the size-1 sharded dim
        s = compressed_psum_int8(g, k[0], axis_name)
        return s / n

    f = jax.shard_map(
        local, mesh=mesh, in_specs=(P(axis_name), P(axis_name)), out_specs=P()
    )
    return f(grads, keys)
