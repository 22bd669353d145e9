"""The ragged decode step's KV path (one new token, a (B,) cache index).

Attention reads the layer's cache through a select view (the new token
at each row's own position, the cache elsewhere) instead of a written
copy, and after the layer scan each slot's token slice goes into the
stacked caches with an in-place dynamic_update_slice. Both must give
exactly what the per-row write they replace gave, for every cache
class: ``KVCache``, ``QuantKVCache`` (int8, ternary), ``MLACache`` and
``QuantMLACache``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention as A
from repro.models import transformer as T
from repro.models.layers import QuantConfig
from repro.models.registry import get_config
from repro.serve.engine import fused_decode_fn

CACHES = {
    "kv-bf16": ("smollm-135m", "bf16"),
    "kv-int8": ("smollm-135m", "int8"),
    "kv-ternary": ("smollm-135m", "ternary"),
    "mla-bf16": ("deepseek-v2-236b", "bf16"),
    "mla-int8": ("deepseek-v2-236b", "int8"),
    "mla-ternary": ("deepseek-v2-236b", "ternary"),
}

B, S_MAX = 4, 16
IDX = jnp.asarray([0, S_MAX - 1, 7, 3], jnp.int32)    # scattered, both ends
START = jnp.asarray([0, 5, 2, 0], jnp.int32)


def _cfg(name):
    arch, cache_dtype = CACHES[name]
    return get_config(arch, smoke=True).replace(
        quant=QuantConfig(mode="off", cache_dtype=cache_dtype))


def _random_like(key, a):
    """Random contents of a cache leaf's dtype: bf16 values, int8 codes,
    valid packed ternary nibbles, positive f32 scales."""
    if a.dtype == jnp.uint8:
        t = jax.random.randint(key, a.shape[:-1] + (2 * a.shape[-1],), -1, 2)
        return A.pack_ternary_kv(t.astype(jnp.int8))
    if a.dtype == jnp.int8:
        return jax.random.randint(key, a.shape, -127, 128).astype(jnp.int8)
    if a.dtype == jnp.float32:
        return jax.random.uniform(key, a.shape, jnp.float32, 0.01, 0.1)
    return jax.random.normal(key, a.shape, a.dtype)


def _random_tree(key, tree):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return treedef.unflatten([_random_like(k, a) for k, a in zip(keys, leaves)])


def _per_row_write(stack, ts, idx):
    """The writer the slot loop replaced: a vmapped per-row
    dynamic_update_slice over the batch axis."""
    return jax.vmap(
        lambda stack_r, ts_r, i: jax.lax.dynamic_update_slice(
            stack_r, ts_r, (0, i) + (0,) * (stack_r.ndim - 2)),
        in_axes=(1, 1, 0), out_axes=1,
    )(stack, ts.astype(stack.dtype), idx)


def _assert_trees_equal(a, b):
    as_np = lambda x: np.asarray(x, np.float32 if x.dtype == jnp.bfloat16 else x.dtype)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(as_np(x), as_np(y))


@pytest.mark.parametrize("name", sorted(CACHES))
def test_slot_loop_write_equals_per_row_write(name):
    """The post-scan writer puts each row's token at its own offset,
    bit for bit as the vmapped per-row write did, and touches nothing
    else (every other position keeps its random contents)."""
    cfg = _cfg(name)
    stacks = tuple(_random_tree(jax.random.PRNGKey(0), T.init_caches(cfg, B, S_MAX)))
    token = tuple(_random_tree(jax.random.PRNGKey(1), tuple(
        s[:, :, :1] for s in stacks)))
    got = jax.jit(T._write_kv)(stacks, token, IDX)
    want = jax.jit(lambda st, ts, i: tuple(
        _per_row_write(s, t, i) for s, t in zip(st, ts)))(stacks, token, IDX)
    _assert_trees_equal(got, want)
    # the scalar-index write is unchanged and agrees with a broadcast index
    at3 = jnp.full((B,), 3, jnp.int32)
    _assert_trees_equal(jax.jit(T._write_kv)(stacks, token, jnp.int32(3)),
                        jax.jit(T._write_kv)(stacks, token, at3))


@pytest.mark.parametrize("name", sorted(CACHES))
def test_select_view_attention_equals_write_then_attend(name, monkeypatch):
    """One layer of ragged decode attends over the select view and
    returns the same output and token slices as attending over the
    cache with the token written into it."""
    cfg = _cfg(name)
    layer = jax.tree.map(lambda a: a[0], T.init_params(jax.random.PRNGKey(2), cfg)["blocks"])
    cache_stack = _random_tree(jax.random.PRNGKey(3), T.init_caches(cfg, B, S_MAX))
    cache = T._wrap_cache(cfg, tuple(c[0] for c in cache_stack))
    x = jax.random.normal(jax.random.PRNGKey(4), (B, 1, cfg.d_model), jnp.bfloat16)
    positions = (IDX - START)[:, None]
    fn = A.mla_attention if cfg.mla else A.gqa_attention

    def run():
        return jax.jit(lambda p, x, c: fn(p, x, cfg, positions, c, IDX, START))(
            layer["attn"], x, cache)

    out, new = run()
    monkeypatch.setattr(A, "attend_rows", A.write_cache_rows)
    out_ref, new_ref = run()
    _assert_trees_equal((out, new), (out_ref, new_ref))


def test_attend_rows_is_a_select_only_for_one_ragged_token():
    """The view is the written cache, and is a write (not a select) for
    a scalar index or several new tokens."""
    buf = jax.random.normal(jax.random.PRNGKey(5), (B, S_MAX, 2, 4))
    new = jax.random.normal(jax.random.PRNGKey(6), (B, 1, 2, 4))
    np.testing.assert_array_equal(np.asarray(A.attend_rows(buf, new, IDX)),
                                  np.asarray(A.write_cache_rows(buf, new, IDX)))
    prims = lambda *a: str(jax.make_jaxpr(A.attend_rows)(*a))
    view = prims(buf, new, IDX)
    assert "select_n" in view
    assert "dynamic_update_slice" not in view and "scatter" not in view
    assert "dynamic_update_slice" in prims(buf, new, jnp.int32(3))
    assert "scatter" in prims(buf, jnp.concatenate([new, new], 1), IDX)


def test_fused_decode_step_has_no_scatter():
    """smollm's fused decode step writes its caches with
    dynamic_update_slice alone: a scatter is what made the TPU compiler
    relay out both whole caches every step."""
    cfg = get_config("smollm-135m", smoke=True).replace(quant=QuantConfig(mode="off"))
    params = jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: T.init_caches(cfg, B, S_MAX))
    i32 = jax.ShapeDtypeStruct((B,), jnp.int32)
    jaxpr = str(jax.make_jaxpr(fused_decode_fn(cfg))(
        params, jax.ShapeDtypeStruct((B, 1), jnp.int32), caches, i32, i32,
        jax.ShapeDtypeStruct((2,), jnp.uint32)))
    assert "dynamic_update_slice" in jaxpr
    assert "scatter" not in jaxpr
