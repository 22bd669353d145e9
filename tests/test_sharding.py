"""Sharding rules + HLO analysis unit tests (logical — no big meshes;
the 512-device meshes are exercised only by launch/dryrun.py)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import sharding as shd
from repro.launch import hlo_analysis as ha
from repro.models import transformer as T
from repro.models.registry import get_config


class TestParamSpecs:
    def test_init_sharded_creates_leaves_under_param_specs(self, tp_mesh):
        """Parameters come out of init_sharded already placed under
        their param_specs on the mesh, with the same values as the
        unsharded init (threefry is partitionable)."""
        from repro.launch.mesh import make_tp_mesh

        cfg = get_config("smollm-135m", smoke=True)
        mesh = make_tp_mesh(2)
        init = functools.partial(T.init_params, cfg=cfg)
        params = shd.init_sharded(init, jax.random.PRNGKey(0), mesh)
        specs = shd.param_specs(params, axis_sizes=shd.mesh_axis_sizes(mesh))
        flat_s = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
        flat_p = jax.tree.leaves(params)
        assert any("model" in s for s in flat_s)
        for leaf, spec in zip(flat_p, flat_s):
            assert leaf.sharding == NamedSharding(mesh, spec), spec
        for a, b in zip(flat_p, jax.tree.leaves(init(jax.random.PRNGKey(0)))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_rules_cover_model(self):
        cfg = get_config("yi-34b", smoke=True)
        params = jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.PRNGKey(0))
        specs = shd.param_specs(params)
        flat_p = shd.tree_paths(params)
        flat_s = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
        assert len(flat_p) == len(flat_s)
        by_path = {p: s for (p, _), s in zip(flat_p, flat_s)}
        # attention projections are tensor-parallel
        assert any("model" in str(s) for p, s in by_path.items() if p.endswith("wq"))
        # stacked blocks keep layer dim unsharded
        wq_spec = next(s for p, s in by_path.items() if "blocks" in p and p.endswith("wq"))
        assert wq_spec[0] is None and wq_spec[2] == "model"
        # norms replicated
        norm_spec = next(s for p, s in by_path.items() if p.endswith("ln1"))
        assert all(a is None for a in norm_spec)

    def test_moe_expert_sharding(self):
        cfg = get_config("grok-1-314b", smoke=True)
        params = jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.PRNGKey(0))
        by_path = dict(shd.tree_paths(params))
        specs = shd.param_specs(params)
        flat_p = shd.tree_paths(params)
        flat_s = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
        for (p, leaf), s in zip(flat_p, flat_s):
            if "moe/w_gate" in p or "moe/w_down" in p:
                assert s[1] == "model", (p, s)  # expert dim (after layer dim)

    def test_rank_always_matches(self):
        for arch in ("deepseek-v2-236b", "zamba2-2.7b", "whisper-large-v3"):
            cfg = get_config(arch, smoke=True)
            params = jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.PRNGKey(0))
            specs = shd.param_specs(params)
            for (path, leaf), s in zip(
                shd.tree_paths(params),
                jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P)),
            ):
                assert len(s) == leaf.ndim, (path, s, leaf.shape)


class TestCacheSpecs:
    def test_kv_cache_sharded_on_seq_and_batch(self):
        cfg = get_config("yi-34b", smoke=True)
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        caches = jax.eval_shape(lambda: T.init_caches(cfg, 16, 64))
        specs = shd.cache_specs(caches, mesh, batch=16)
        for s, leaf in zip(
            jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P)),
            jax.tree.leaves(caches),
        ):
            assert len(s) == leaf.ndim
            assert "model" in tuple(a for a in s if a)  # something sharded


class TestActivationSharding:
    def test_disabled_is_identity(self):
        shd.disable_activation_sharding()
        x = jnp.ones((4, 8, 16))
        assert shd.shard_act(x, "btd") is x

    def test_batch_divisor_guard(self):
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        shd.enable_activation_sharding(multi_pod=False, batch_divisor=16)
        try:
            with jax.set_mesh(mesh):
                x = jnp.ones((1, 8, 16))  # batch 1 not divisible: no crash
                y = shd.shard_act(x, "btd")
                assert y.shape == x.shape
        finally:
            shd.disable_activation_sharding()


class TestHloAnalysis:
    def test_scan_trip_multiplier(self):
        def f(x, w):
            def body(c, wi):
                return jnp.tanh(c @ wi), None
            return jax.lax.scan(body, x, w)[0]

        xs = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        ws = jax.ShapeDtypeStruct((12, 64, 64), jnp.float32)
        txt = jax.jit(f).lower(xs, ws).compile().as_text()
        c = ha.analyze(txt, 1)
        assert c.flops == 12 * 2 * 64**3

    def test_collective_accounting_formulas(self):
        hlo = """
HloModule m
ENTRY %main (p: f32[1024]) -> f32[1024] {
  %p = f32[1024]{0} parameter(0)
  %ar = f32[1024]{0} all-reduce(%p), replica_groups=[1,4]<=[4], to_apply=%add
  ROOT %ag = f32[1024]{0} all-gather(%ar), replica_groups=[2,2]<=[4], dimensions={0}
}
"""
        c = ha.analyze(hlo, 4)
        # all-reduce: 2 * 4096 * 3/4 = 6144 ; all-gather: 4096 * 1/2 = 2048
        assert c.coll["all-reduce"] == 6144
        assert c.coll["all-gather"] == 2048

    def test_dus_counts_update_only(self):
        def f(cache, upd, i):
            return jax.lax.dynamic_update_slice(cache, upd, (i, 0))

        cs = jax.ShapeDtypeStruct((4096, 64), jnp.float32)
        us = jax.ShapeDtypeStruct((1, 64), jnp.float32)
        txt = (
            jax.jit(f, donate_argnums=(0,))  # in-place update (cache pattern)
            .lower(cs, us, jax.ShapeDtypeStruct((), jnp.int32))
            .compile().as_text()
        )
        c = ha.analyze(txt, 1)
        assert c.hbm_bytes < 4096 * 64 * 4  # far less than the full cache


class TestFsdp:
    def test_big_weights_gain_data_axis(self):
        cfg = get_config("yi-34b")  # full config: big weights
        params = jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.PRNGKey(0))
        axis_sizes = {"model": 16, "data": 16}
        plain = shd.param_specs(params, axis_sizes=axis_sizes)
        fsdp = shd.param_specs(params, fsdp=True, axis_sizes=axis_sizes)
        found = 0
        for (path, leaf), sp, sf in zip(
            shd.tree_paths(params),
            jax.tree.leaves(plain, is_leaf=lambda s: isinstance(s, P)),
            jax.tree.leaves(fsdp, is_leaf=lambda s: isinstance(s, P)),
        ):
            axes_p = {a for a in jax.tree_util.tree_leaves(tuple(sp)) if a}
            axes_f = {a for a in jax.tree_util.tree_leaves(tuple(sf)) if a}
            if "data" in axes_f and "data" not in axes_p:
                found += 1
                # every sharded dim still divides
                for dim, ax in zip(leaf.shape, sf):
                    if ax is not None:
                        sz = 1
                        for a in (ax if isinstance(ax, tuple) else (ax,)):
                            sz *= axis_sizes.get(a, 1)
                        assert dim % sz == 0
        assert found > 3  # attention + mlp weights got the data axis

    def test_small_leaves_untouched(self):
        cfg = get_config("smollm-135m", smoke=True)
        params = jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.PRNGKey(0))
        axis_sizes = {"model": 16, "data": 16}
        fsdp = shd.param_specs(params, fsdp=True, axis_sizes=axis_sizes)
        for (path, leaf), sf in zip(
            shd.tree_paths(params),
            jax.tree.leaves(fsdp, is_leaf=lambda s: isinstance(s, P)),
        ):
            if leaf.size < (1 << 20):  # tiny smoke weights: no fsdp churn
                assert "data" not in {a for a in jax.tree_util.tree_leaves(tuple(sf)) if a}
