"""Compressed all-reduce (shard_map manual collectives) on the real
multi-device host mesh — this is the path that actually narrows the
gradient/TP wire format (optim/compress.py only models the numerics
under pjit autodiff).

Historically these assertions hid in a subprocess (the suite ran
single-device); the session conftest now forces 8 virtual devices, so
they run in-process on the shared ``tp_mesh`` fixture, including the
hypothesis error-bound property sweep.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.dist.collectives import (
    compressed_psum_int8,
    mean_grads_int8,
    tp_allreduce,
)


def _data_mesh(tp_mesh, n=4):
    """(n,)-device "data" mesh carved from the session fixture's pool."""
    return jax.sharding.Mesh(
        tp_mesh.devices.reshape(-1)[:n], ("data",)
    )


def test_int8_mean_reduce_error_bound(tp_mesh):
    mesh = _data_mesh(tp_mesh)
    g = jax.random.normal(jax.random.PRNGKey(0), (4, 512))
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    exact = np.asarray(g).mean(0)
    out = np.asarray(mean_grads_int8(mesh, g, keys))
    amax = np.abs(np.asarray(g)).max()
    err = np.abs(out - exact).max()
    assert err < 0.02 * amax, (err, amax)  # quantization-level error


def test_int8_mean_reduce_unbiased(tp_mesh):
    """Averaging over many stochastic-rounding keys converges to the
    exact mean (the rounding is unbiased)."""
    mesh = _data_mesh(tp_mesh)
    g = jax.random.normal(jax.random.PRNGKey(0), (4, 512))
    exact = np.asarray(g).mean(0)
    amax = np.abs(np.asarray(g)).max()
    outs = []
    for i in range(48):
        ks = jax.random.split(jax.random.PRNGKey(100 + i), 4)
        outs.append(np.asarray(mean_grads_int8(mesh, g, ks)))
    bias = np.abs(np.mean(outs, 0) - exact).max()
    assert bias < 0.004 * amax, (bias, amax)


def test_tp_allreduce_exact_matches_psum(tp_mesh):
    """compressed=False is the plain psum — bit-exact TP reduction
    (integer payloads, the CiM event-count case: any summation order is
    exact in f32)."""
    mesh = _data_mesh(tp_mesh)
    x = jnp.round(
        10 * jax.random.normal(jax.random.PRNGKey(2), (4, 64))
    ).astype(jnp.float32)

    f = shard_map(
        lambda s: tp_allreduce(s.reshape(s.shape[1:]), "data"),
        mesh=mesh, in_specs=(P("data"),), out_specs=P(),
    )
    np.testing.assert_array_equal(
        np.asarray(f(x)), np.asarray(x.sum(0)))


def test_tp_allreduce_compressed_requires_key(tp_mesh):
    mesh = _data_mesh(tp_mesh)
    x = jnp.ones((4, 8), jnp.float32)
    f = shard_map(
        lambda s: tp_allreduce(
            s.reshape(s.shape[1:]), "data", compressed=True),
        mesh=mesh, in_specs=(P("data"),), out_specs=P(),
    )
    try:
        f(x)
    except ValueError as e:
        assert "key" in str(e)
    else:
        raise AssertionError("compressed tp_allreduce without key accepted")


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 2**16),
    size=st.sampled_from([64, 256, 1000]),
    scale=st.floats(1e-3, 1e3),
    shards=st.sampled_from([2, 4, 8]),
)
def test_compressed_psum_error_bound_property(seed, size, scale, shards):
    """Property (previously skipped for want of a real mesh): for any
    payload, |compressed_psum - exact_sum| <= shards * (amax / 127) *
    1.5 — every shard rounds within one int8 level of the shared
    scale, and the errors add at worst linearly."""
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device session mesh")
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:shards]), ("data",))
    g = scale * jax.random.normal(
        jax.random.PRNGKey(seed), (shards, size), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), shards)

    f = shard_map(
        lambda s, k: compressed_psum_int8(
            s.reshape(s.shape[1:]), k[0], "data"),
        mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P(),
    )
    out = np.asarray(f(g, keys))
    exact = np.asarray(g, np.float64).sum(0)
    amax = np.abs(np.asarray(g)).max()
    bound = shards * (amax / 127.0) * 1.5
    assert np.abs(out - exact).max() <= bound
