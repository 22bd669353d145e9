"""Tensor-parallel serving: the multi-device differential harness.

Runs on the 8 virtual host devices the session conftest forces (the
``tp_mesh`` fixture skips when they are absent). The contract pinned
here (DESIGN.md §8):

  * fused TP={1,2,4} greedy decode is **token-identical** to the
    unsharded engine for every serving family (dense / MLA+MoE / SSM /
    hybrid) — in fp mode and in the quantized cim mode (whose ADC event
    counts are integers, so the TP partial-sum all-reduce is exact);
  * ``execute`` / ``execute_packed`` are **bit-equal** under sharded vs
    replicated operands for every registered spec (column/N sharding
    never splits the contraction);
  * ``execute_tp`` (explicit row-parallel shard_map path) is bit-equal
    to ``execute`` — whole ADC blocks per shard — and its
    int8-compressed variant stays inside the quantization error bound;
  * the PR-2 serving invariants survive sharding: jaxpr size of the
    fused step independent of n_slots AND mesh size, and
    host_syncs/decode_steps unchanged by TP;
  * the PR-2 known limit (per-tensor activation scale couples batch
    rows) is **retired** by ``QuantConfig(act_scale="per_row")``
    (DESIGN.md §9): quantized dense rows are bit-identical solo vs
    co-batched, and quantized fused serving is token-identical to
    per-request generate() — the former strict xfail, now passing.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import ternary as tern
from repro.core.execution import (
    CiMExecSpec,
    execute,
    execute_packed,
    execute_tp,
    registered_specs,
)
from repro.dist import sharding as shd
from repro.launch.mesh import make_tp_mesh
from repro.models import transformer as T
from repro.models.layers import QuantConfig, dense
from repro.models.registry import get_config
from repro.serve.engine import ContinuousBatcher, Request

# one smoke arch per serving family (the families the ragged-decode
# contract distinguishes: KV caches, latent MLA caches + MoE, SSM state,
# hybrid ssm+shared-attention)
FAMILY_ARCHS = {
    "dense": "smollm-135m",
    "mla": "deepseek-v2-236b",
    "ssm": "mamba2-780m",
    "hybrid": "zamba2-2.7b",
}

PROMPTS = [[3, 1, 4], [9, 8], [2, 7, 1, 8, 2], [6]]
MAX_NEWS = [4, 5, 3, 4]


def _family_cfg(family, quant=None):
    cfg = get_config(FAMILY_ARCHS[family], smoke=True)
    if family == "mla":
        cfg = cfg.replace(moe_capacity_factor=8.0)  # no smoke-size drops
    if quant is not None:
        cfg = cfg.replace(quant=quant)
    return cfg


def _serve(params, cfg, mesh, **kw):
    b = ContinuousBatcher(params, cfg, n_slots=2, s_max=32, mesh=mesh, **kw)
    reqs = [Request(i, p, max_new=m) for i, (p, m) in
            enumerate(zip(PROMPTS, MAX_NEWS))]
    for r in reqs:
        b.submit(r)
    b.run()
    assert all(r.done for r in reqs)
    return [r.generated for r in reqs], b.stats()


# ---------------------------------------------------------------------------
# Differential decode sweep
# ---------------------------------------------------------------------------


class TestTPTokenIdentity:
    @pytest.mark.parametrize("family", sorted(FAMILY_ARCHS))
    def test_fused_tp_decode_token_identical(self, family, tp_mesh):
        """TP={1,2,4} fused greedy decode == the unsharded engine,
        request by request, token by token (fp mode). The degenerate
        TP=1 mesh (sharding machinery on, nothing actually split) is
        pinned once on the dense family."""
        cfg = _family_cfg(family, QuantConfig(mode="off"))
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        base, base_stats = _serve(params, cfg, None)
        for tp in ((1, 2, 4) if family == "dense" else (2, 4)):
            toks, stats = _serve(params, cfg, make_tp_mesh(tp))
            assert toks == base, (family, tp)
            # host-sync discipline unchanged by TP: still one fetch per
            # fused step / prefill batch, same step count
            assert stats == base_stats, (family, tp)

    def test_quantized_tp_decode_token_identical(self, tp_mesh):
        """cim mode under TP: ADC event counts are integers, the partial
        sums add exactly — quantized TP serving is token-identical too."""
        cfg = _family_cfg("dense")          # registry default: mode="cim"
        assert cfg.quant.mode == "cim"
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        base, base_stats = _serve(params, cfg, None)
        toks, stats = _serve(params, cfg, make_tp_mesh(2))
        assert toks == base and stats == base_stats

    def test_prepared_bitplanes_serve_sharded(self, tp_mesh):
        """prepare_weights under a mesh: the stored 2-bit planes land
        N-sharded on the devices (each device holds only its weight
        shard) and serving from the folded weights stays token-identical
        to the unsharded prepared engine."""
        cfg = _family_cfg("dense")
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        spec = CiMExecSpec(formulation="bitplane", backend="jnp",
                           packing="bitplane_u8")
        kw = dict(exec_spec=spec, prepare_weights=True)
        base, _ = _serve(params, cfg, None, **kw)

        mesh = make_tp_mesh(2)
        b = ContinuousBatcher(params, cfg, n_slots=2, s_max=32, mesh=mesh,
                              **kw)
        assert b.packed
        sharded = 0
        for path, (p1, p2, scale) in b.packed.items():
            ns = p1.sharding
            assert isinstance(ns, NamedSharding), path
            if ns.spec[-1] == "model":
                sharded += 1
                # each device addresses half the plane columns
                shard_shape = ns.shard_shape(p1.shape)
                assert shard_shape[-1] == p1.shape[-1] // 2, path
        assert sharded > 0, "no plane picked up the model axis"
        reqs = [Request(i, p, max_new=m) for i, (p, m) in
                enumerate(zip(PROMPTS, MAX_NEWS))]
        for r in reqs:
            b.submit(r)
        b.run()
        assert [r.generated for r in reqs] == base

    def test_pallas_tp_serving_token_identical(self, tp_mesh):
        """A Pallas spec under a TP mesh: the SPMD partitioner cannot
        split a Mosaic kernel, so dense() runs each MAC per shard
        (execute_tp row/col) — tokens still equal the unsharded engine,
        and the engine's mesh switch does not leak."""
        cfg = _family_cfg("dense")
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        spec = CiMExecSpec(formulation="blocked", backend="pallas")
        base, base_stats = _serve(params, cfg, None, exec_spec=spec)
        toks, stats = _serve(params, cfg, make_tp_mesh(2), exec_spec=spec)
        assert toks == base and stats == base_stats
        assert shd.tp_mesh() is None

    def test_compress_tp_serves_and_differs_in_wire_only(self, tp_mesh):
        """compress_tp=True (int8 TP all-reduce) completes the workload
        with the same serving discipline; tokens may differ from the
        exact engine (documented trade) but stay valid."""
        cfg = _family_cfg("dense")
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        toks, stats = _serve(params, cfg, make_tp_mesh(2), compress_tp=True)
        # the engine scopes the TP-mesh switch to its own calls — nothing
        # leaks into the process after serving
        assert shd.tp_mesh() is None
        _, base_stats = _serve(params, cfg, None)
        assert stats == base_stats
        for t, m in zip(toks, MAX_NEWS):
            assert len(t) == m and all(0 <= x < cfg.vocab for x in t)

    def test_compress_tp_guards(self, tp_mesh):
        cfg = _family_cfg("dense", QuantConfig(mode="off"))
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(ValueError, match="quantized"):
            ContinuousBatcher(params, cfg, n_slots=2, s_max=32,
                              mesh=make_tp_mesh(2), compress_tp=True)
        with pytest.raises(ValueError, match="mesh"):
            ContinuousBatcher(params, cfg, n_slots=2, s_max=32,
                              compress_tp=True)
        bad = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("x",))
        with pytest.raises(ValueError, match="model"):
            ContinuousBatcher(params, cfg, n_slots=2, s_max=32, mesh=bad)
        # a packed spec without prepare_weights can never engage the
        # compressed route (dense() only routes unpacked MACs) — reject
        # instead of silently serving with exact collectives
        packed_spec = CiMExecSpec(formulation="blocked", backend="jnp",
                                  packing="bitplane_u8")
        with pytest.raises(ValueError, match="prepare_weights"):
            with pytest.warns(UserWarning):  # packed-per-forward warning
                ContinuousBatcher(params, cfg, n_slots=2, s_max=32,
                                  mesh=make_tp_mesh(2), compress_tp=True,
                                  exec_spec=packed_spec)


# ---------------------------------------------------------------------------
# execute / execute_packed under sharded operands
# ---------------------------------------------------------------------------


def _ternary_pair(m=8, k=64, n=32):
    kx, kw, mx, mw = jax.random.split(jax.random.PRNGKey(7), 4)
    x = (jnp.sign(jax.random.normal(kx, (m, k)))
         * (jax.random.uniform(mx, (m, k)) > 0.3)).astype(jnp.float32)
    w = (jnp.sign(jax.random.normal(kw, (k, n)))
         * (jax.random.uniform(mw, (k, n)) > 0.3)).astype(jnp.float32)
    return x, w


class TestShardedExecute:
    def test_execute_bit_equal_sharded_vs_replicated(self, tp_mesh):
        """Every registered (formulation, backend, packing): replicated x
        + N-sharded w == the single-device result, bit for bit (column
        sharding never re-associates the contraction)."""
        mesh = make_tp_mesh(2)
        x, w = _ternary_pair()
        for spec in registered_specs():
            base = np.asarray(execute(spec, x, w))
            xs = jax.device_put(x, NamedSharding(mesh, P()))
            ws = jax.device_put(w, NamedSharding(mesh, P(None, "model")))
            out = np.asarray(execute(spec, xs, ws))
            np.testing.assert_array_equal(base, out, err_msg=spec.name)

    def test_execute_packed_bit_equal_sharded_planes(self, tp_mesh):
        """Stored 2-bit planes sharded along N (the packed_specs layout)
        == replicated planes, bit for bit, for both packed kernels."""
        mesh = make_tp_mesh(2)
        x, w = _ternary_pair()
        p1, p2 = tern.pack_ternary(w.astype(jnp.int8), axis=0)
        ns = NamedSharding(mesh, P(None, "model"))
        for form in ("exact", "blocked"):
            for backend in ("jnp", "pallas"):
                spec = CiMExecSpec(formulation=form, backend=backend,
                                   packing="bitplane_u8")
                base = np.asarray(execute_packed(spec, x, p1, p2))
                out = np.asarray(execute_packed(
                    spec, x, jax.device_put(p1, ns), jax.device_put(p2, ns)))
                np.testing.assert_array_equal(base, out,
                                              err_msg=f"{form}/{backend}")

    def test_execute_tp_bit_equal(self, tp_mesh):
        """Explicit row-parallel shard_map MAC: whole ADC blocks per
        shard -> integer partials -> exact psum -> bit equality, for
        every unpacked jnp formulation at TP=2 and TP=4."""
        x, w = _ternary_pair()
        for form in ("exact", "blocked", "corrected", "bitplane", "fused"):
            spec = CiMExecSpec(formulation=form, backend="jnp")
            base = np.asarray(execute(spec, x, w))
            for tp in (2, 4):
                out = np.asarray(execute_tp(spec, x, w, make_tp_mesh(tp)))
                np.testing.assert_array_equal(base, out,
                                              err_msg=f"{form} tp={tp}")

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    @pytest.mark.parametrize("split", ["row", "col"])
    def test_execute_tp_split_bit_equal(self, split, backend, tp_mesh):
        """Both explicit splits equal execute() bit for bit, for the
        jnp formulation and the Pallas kernel, at TP=2 and TP=4 — with
        an N (30) that TP=4 does not divide (the column split then
        computes the replicated weight whole on every device)."""
        spec = CiMExecSpec(formulation="blocked", backend=backend)
        for n in (32, 30):
            x, w = _ternary_pair(n=n)
            base = np.asarray(execute(spec, x, w))
            for tp in (2, 4):
                out = np.asarray(execute_tp(spec, x, w, make_tp_mesh(tp),
                                            split=split))
                np.testing.assert_array_equal(
                    base, out, err_msg=f"{split} n={n} tp={tp}")

    @pytest.mark.parametrize("backend,per_shard", [("pallas", True),
                                                   ("jnp", False)])
    def test_dense_runs_pallas_per_shard(self, backend, per_shard, tp_mesh):
        """Under an installed TP mesh dense() wraps a Pallas MAC in a
        shard_map and leaves jnp MACs to the implicit GSPMD path."""
        x, w = _ternary_pair()
        qc = QuantConfig(mode="cim", exec_spec=CiMExecSpec(
            formulation="blocked", backend=backend))

        def f(a, b):
            shd.set_tp_mesh(make_tp_mesh(2))
            try:
                return dense(a, b, qc, tp="row")
            finally:
                shd.set_tp_mesh(None)

        assert ("shard_map" in str(jax.make_jaxpr(f)(x, w))) == per_shard

    def test_execute_tp_rejects_packed_and_noisy(self, tp_mesh):
        x, w = _ternary_pair()
        mesh = make_tp_mesh(2)
        with pytest.raises(ValueError, match="packed|N-sharded"):
            execute_tp(CiMExecSpec(formulation="blocked", backend="jnp",
                                   packing="bitplane_u8"), x, w, mesh)
        with pytest.raises(ValueError, match="error"):
            execute_tp(CiMExecSpec(formulation="blocked", backend="jnp",
                                   error_prob=0.1), x, w, mesh)
        with pytest.raises(ValueError, match="split"):
            execute_tp(CiMExecSpec(formulation="blocked", backend="jnp"),
                       x, w, mesh, split="diag")

    def test_execute_tp_compressed_error_bound(self, tp_mesh):
        """int8-compressed TP all-reduce: per-shard quantization error is
        bounded by (amax/127) per shard, summed over shards."""
        x, w = _ternary_pair(m=16, k=128, n=64)
        spec = CiMExecSpec(formulation="blocked", backend="jnp")
        base = np.asarray(execute(spec, x, w))
        for tp in (2, 4):
            out = np.asarray(execute_tp(spec, x, w, make_tp_mesh(tp),
                                        compressed=True))
            bound = tp * (np.abs(base).max() / 127.0 + 1e-6) * 1.5
            assert np.abs(out - base).max() <= bound, tp


# ---------------------------------------------------------------------------
# Invariant pins (jaxpr size, host syncs)
# ---------------------------------------------------------------------------


class TestTPInvariants:
    def test_jaxpr_size_independent_of_slots_and_mesh(self, tp_mesh):
        """The traced fused step is one batched program: its equation
        count must not grow with the slot count, and sharding is a
        compile-time property — tracing under different TP meshes yields
        the identical program. Migrated to the registered tracing
        contract, whose axes cover the n_slots × tp cross product and
        which additionally enforces the structural serving rules (zero
        host callbacks, no uint8 pads)."""
        from repro.analysis import run_contract

        findings, meta = run_contract("serve.fused_decode_step")
        assert not findings, findings
        # with 8 virtual devices every combo traces live — none skipped
        assert not meta["skipped"], meta
        assert len(meta["eqn_counts"]) == 6, meta

    def test_jaxpr_size_compressed_tp_mesh_independent(self, tp_mesh):
        """Even the explicit shard_map route (compress_tp) traces to the
        same equation count for every mesh size — the collective is one
        primitive regardless of how many devices sit under the axis.
        Checked both at the execute_tp level (registered contract) and
        through the dense() layer route (inline audit_invariance)."""
        from repro.analysis import TraceContract, audit_invariance, run_contract

        findings, meta = run_contract("execution.execute_tp.compressed")
        assert not findings, findings
        assert not meta["skipped"], meta

        x = jnp.ones((4, 64), jnp.float32)
        w = jnp.ones((64, 32), jnp.float32)
        qc = QuantConfig(mode="cim", tp_reduce="int8")

        def build(tp):
            mesh = make_tp_mesh(tp)

            def f(a, b):
                shd.set_tp_mesh(mesh)
                try:
                    return dense(a, b, qc, tp="row")
                finally:
                    shd.set_tp_mesh(None)

            return f, (x, w)

        findings, meta = audit_invariance(
            build, {"tp": (2, 4)},
            contract=TraceContract(max_host_callbacks=0),
            name="tp_serve.dense_row_compressed")
        assert not findings, findings

    def test_host_syncs_per_token_unchanged_by_tp(self, tp_mesh):
        """TP must not add device->host chatter: same decode_steps, same
        host_syncs, for the same workload (already asserted pairwise in
        the sweep; pinned here explicitly as the per-token ratio)."""
        cfg = _family_cfg("dense", QuantConfig(mode="off"))
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        _, s1 = _serve(params, cfg, None)
        _, s2 = _serve(params, cfg, make_tp_mesh(2))
        tokens = sum(MAX_NEWS)
        assert s1["host_syncs"] / tokens == s2["host_syncs"] / tokens
        assert s1 == s2


# ---------------------------------------------------------------------------
# PR-2 caveat retired: per-row activation scales decouple batch rows
# ---------------------------------------------------------------------------


class TestPerRowActScale:
    """The former strict xfail (per-tensor activation scale couples
    co-batched rows), flipped deliberately by ``act_scale="per_row"``
    (DESIGN.md §9)."""

    def _rows(self):
        kx, kw = jax.random.split(jax.random.PRNGKey(3))
        x1 = jax.random.normal(kx, (1, 64), jnp.float32)
        mate = 5.0 * jax.random.normal(jax.random.PRNGKey(9), (1, 64),
                                       jnp.float32)
        w = jax.random.normal(kw, (64, 32), jnp.float32)
        return x1, jnp.concatenate([x1, mate], axis=0), w

    def test_quantized_dense_row_independent_of_batchmates(self):
        """A row's quantized dense() output is bit-identical whether it
        is computed alone or co-batched: per-row thresholds/scales make
        each (.., K) row's quantization a function of that row only."""
        qc = QuantConfig(mode="cim", act_scale="per_row")
        x1, x2, w = self._rows()
        solo = np.asarray(dense(x1, w, qc))[0]
        cobatched = np.asarray(dense(x2, w, qc))[0]
        np.testing.assert_array_equal(solo, cobatched)

    def test_per_tensor_default_still_couples(self):
        """The default per-tensor scale still couples rows (one amax over
        the batch) — the documented trade the per_row option retires; if
        this ever passes, the default granularity silently changed."""
        qc = QuantConfig(mode="cim")
        assert qc.act_scale == "per_tensor"
        x1, x2, w = self._rows()
        solo = np.asarray(dense(x1, w, qc))[0]
        cobatched = np.asarray(dense(x2, w, qc))[0]
        assert bool(np.any(solo != cobatched))

    def test_quantized_fused_serving_token_identical_to_generate(self):
        """The acceptance pin: under act_scale="per_row" the quantized
        (cim) fused batcher serves every request token-identically to
        per-request generate() — heterogeneous co-batched slots,
        left-padded batched prefill and all."""
        from repro.serve.engine import generate

        qc = QuantConfig(mode="cim", act_scale="per_row")
        cfg = _family_cfg("dense", qc)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        solos = [
            np.asarray(generate(params, jnp.asarray([p], jnp.int32), cfg,
                                max_new=m, s_max=32))[0].tolist()
            for p, m in zip(PROMPTS, MAX_NEWS)
        ]
        toks, _ = _serve(params, cfg, None)
        assert toks == solos
