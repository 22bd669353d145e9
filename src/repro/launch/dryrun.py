import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("REPRO_EXTRA_XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay first — jax locks the device count at first
init. Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both \
        --out results/dryrun

Per cell this:
  1. builds the production mesh (16x16, and 2x16x16 with --multi-pod),
  2. constructs ShapeDtypeStruct stand-ins for every input (weights via
     jax.eval_shape over init — no allocation anywhere),
  3. jit(train_step/serve_step, in_shardings, out_shardings)
       .lower(...).compile(),
  4. prints memory_analysis + cost_analysis and writes the roofline JSON.
"""
import argparse
import dataclasses
import functools
import json
import sys
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.dist import sharding as shd
from repro.launch import hlo_analysis
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh, mesh_batch_divisor
from repro.models import transformer as T
from repro.models.registry import SHAPES, ShapeCell, cell_supported, get_config, input_specs
from repro.optim.adamw import AdamWConfig
import importlib
ts = importlib.import_module('repro.train.train_step')


def _ns(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def state_specs(cfg: ArchConfig):
    """ShapeDtypeStruct tree of the TrainState — zero allocation."""
    return jax.eval_shape(
        functools.partial(ts.init_train_state, cfg=cfg), jax.random.PRNGKey(0)
    )


def train_shardings(cfg: ArchConfig, mesh, state_shapes):
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    pspec = shd.param_specs(state_shapes.params, axis_sizes=axis_sizes)
    opt_spec = ts.TrainState(
        params=pspec,
        opt=type(state_shapes.opt)(
            step=P(), mu=pspec, nu=pspec
        ),
        rng=P(),
        residual=None if state_shapes.residual is None else shd.param_specs(
            state_shapes.residual, axis_sizes=axis_sizes),
    )
    return _ns(mesh, opt_spec)


def batch_shardings(cfg: ArchConfig, mesh, specs: Dict, batch: int):
    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dprod = 1
    for a in daxes:
        dprod *= mesh.shape[a]
    b = daxes if batch % dprod == 0 and dprod > 1 else None
    out = {}
    for k, v in specs.items():
        out[k] = NamedSharding(mesh, P(*((b,) + (None,) * (len(v.shape) - 1))))
    return out


@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh_name: str
    ok: bool
    seconds: float
    error: Optional[str] = None
    roofline: Optional[dict] = None
    memory_analysis: Optional[str] = None
    #: measured-cost score (launch.hillclimb.score_cell) when the cell
    #: was driven with --calibration; None for analytic-only runs
    calibrated: Optional[dict] = None


def lower_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool = False,
    quant_mode: Optional[str] = None,
    remat: Optional[bool] = None,
    verbose: bool = True,
    extra_tag: str = "",
    cfg_overrides: Optional[dict] = None,
    quant_overrides: Optional[dict] = None,
    fsdp: bool = False,
    array_spec=None,
) -> CellResult:
    # resolve the hardware binding first: a typo'd --array-spec dies with
    # the registered sets listed, before any compile work
    from repro import hw

    if isinstance(array_spec, str):
        array_spec = hw.parse_array_spec(array_spec)
    cfg = get_config(arch)
    if quant_mode is not None:
        cfg = cfg.replace(quant=dataclasses.replace(cfg.quant, mode=quant_mode))
    if quant_overrides:
        cfg = cfg.replace(quant=dataclasses.replace(cfg.quant, **quant_overrides))
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    if remat is not None:
        cfg = cfg.replace(remat=remat)
    shape = SHAPES[shape_name]
    mesh_name = ("2x16x16" if multi_pod else "16x16") + extra_tag
    skip = cell_supported(cfg, shape)
    if skip:
        return CellResult(arch, shape_name, mesh_name, ok=True, seconds=0.0,
                          error=f"SKIP: {skip}")
    t0 = time.time()
    from repro.models import layers as _L
    _L.set_native_accum(True)  # TPU-target HLO: bf16 operands, f32 accum
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    shd.enable_activation_sharding(
        multi_pod=multi_pod, batch_divisor=mesh_batch_divisor(mesh),
        model_size=mesh.shape["model"],
    )
    try:
        specs = input_specs(cfg, shape)
        if shape.kind == "train":
            state_shapes = state_specs(cfg)
            state_sh = train_shardings(cfg, mesh, state_shapes)
            batch_sh = batch_shardings(cfg, mesh, specs, shape.batch)
            opt_cfg = AdamWConfig()

            def step(state, batch):
                return ts.train_step(state, batch, cfg, opt_cfg)

            with jax.set_mesh(mesh):
                lowered = jax.jit(
                    step,
                    in_shardings=(state_sh, batch_sh),
                    out_shardings=(state_sh, None),
                    donate_argnums=(0,),
                ).lower(state_shapes, specs)
        elif shape.kind == "prefill":
            params_shapes = jax.eval_shape(
                functools.partial(T.init_params, cfg=cfg), jax.random.PRNGKey(0)
            )
            params_sh = _ns(mesh, shd.param_specs(
                params_shapes, axis_sizes=dict(zip(mesh.axis_names, mesh.devices.shape))))
            batch_sh = batch_shardings(cfg, mesh, specs, shape.batch)

            def step(params, batch):
                return T.forward(params, batch, cfg)

            with jax.set_mesh(mesh):
                lowered = jax.jit(
                    step, in_shardings=(params_sh, batch_sh)
                ).lower(params_shapes, specs)
        else:  # decode
            params_shapes = jax.eval_shape(
                functools.partial(T.init_params, cfg=cfg), jax.random.PRNGKey(0)
            )
            params_sh = _ns(mesh, shd.param_specs(
                params_shapes, fsdp=fsdp,
                axis_sizes=dict(zip(mesh.axis_names, mesh.devices.shape))))
            cache_shapes = jax.eval_shape(
                functools.partial(T.init_caches, cfg, shape.batch, shape.seq)
            )
            cache_sh = _ns(mesh, shd.cache_specs(cache_shapes, mesh, shape.batch))
            batch_sh = batch_shardings(cfg, mesh, specs, shape.batch)
            enc_in_specs = "enc" in specs
            tok_spec = specs["tokens"]

            def step(params, tokens, caches, index, enc=None):
                from repro.serve.engine import serve_step

                return serve_step(params, tokens, caches, index, cfg, enc)

            args = [params_shapes, tok_spec, cache_shapes,
                    jax.ShapeDtypeStruct((), jnp.int32)]
            in_sh = [params_sh, batch_sh["tokens"], cache_sh, None]
            if enc_in_specs:
                args.append(specs["enc"])
                in_sh.append(batch_sh["enc"])
            with jax.set_mesh(mesh):
                lowered = jax.jit(
                    step,
                    in_shardings=tuple(in_sh),
                    out_shardings=(None, cache_sh),
                    donate_argnums=(2,),
                ).lower(*args)

        compiled = lowered.compile()
        mem = None
        try:
            ma = compiled.memory_analysis()
            mem = str(ma)
        except Exception:
            pass
        hlo = compiled.as_text()
        # Whole-program accounting with while-loop trip counts; the SPMD
        # module is per-device, so flops/bytes are per-chip already (see
        # launch/hlo_analysis.py for why compiled.cost_analysis() cannot
        # be used on this backend).
        hc = hlo_analysis.analyze(hlo, chips)
        # execution-spec -> hardware mapping: which array design (NM /
        # CiM-I / CiM-II) this cell's MACs would execute on — bound to
        # the --array-spec hardware when given — with the Figs
        # 9/11-calibrated per-MAC-pass cost attached.
        cim_array = None
        if cfg.quant.mode != "off":
            from repro.core import execution as xapi

            cim_array = xapi.spec_cost_summary(
                cfg.quant.resolved_spec(), array=array_spec)
        roof = rl.Roofline(
            arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
            flops=hc.flops * chips,            # whole-job FLOPs
            bytes_accessed=hc.hbm_bytes * chips,
            coll_bytes=hc.coll_bytes,          # per-device
            coll_breakdown=dict(hc.coll),
            model_flops=rl.model_flops_estimate(cfg, shape, shape.kind),
            cim_array=cim_array,
            array_spec=None if array_spec is None else array_spec.name,
        )
        res = CellResult(
            arch, shape_name, mesh_name, ok=True, seconds=time.time() - t0,
            roofline=roof.to_dict(), memory_analysis=mem,
        )
        if verbose:
            print(f"[dryrun] {arch} {shape_name} {mesh_name}: OK "
                  f"({res.seconds:.1f}s) bottleneck={roof.bottleneck} "
                  f"Tc={roof.t_compute:.3e} Tm={roof.t_memory:.3e} "
                  f"Tx={roof.t_collective:.3e}")
            if mem:
                print(f"  memory: {mem}")
        return res
    except Exception as e:
        if verbose:
            traceback.print_exc()
        return CellResult(arch, shape_name, mesh_name, ok=False,
                          seconds=time.time() - t0, error=f"{type(e).__name__}: {e}")
    finally:
        shd.disable_activation_sharding()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["off", "on", "both"], default="off")
    ap.add_argument("--quant", default=None,
                    choices=[None, "off", "ternary", "cim", "cim_fused"])
    ap.add_argument("--array-spec", default=None,
                    help="hardware binding for cost cells: "
                         "TECH[/DESIGN][/RxC][/aN][/pP], e.g. 3T-FEMFET/CiM-I "
                         "(see repro.hw; design is overridden by the "
                         "cell's execution spec)")
    ap.add_argument("--out", default=None, help="directory for per-cell JSON")
    args = ap.parse_args(argv)

    if args.array_spec is not None:
        from repro import hw

        try:
            hw.parse_array_spec(args.array_spec)
        except ValueError as e:
            ap.error(f"bad --array-spec: {e}")

    from repro.models.registry import ARCH_IDS

    cells = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    pods = {"off": [False], "on": [True], "both": [False, True]}[args.multi_pod]
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                res = lower_cell(arch, shape, multi_pod=mp, quant_mode=args.quant,
                                 array_spec=args.array_spec)
                cells.append(res)
                failures += 0 if res.ok else 1
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    tag = f"{arch}__{shape}__{res.mesh_name}"
                    if args.quant:
                        tag += f"__{args.quant}"
                    with open(os.path.join(args.out, tag + ".json"), "w") as f:
                        json.dump(dataclasses.asdict(res), f, indent=1)
    print(f"\n[dryrun] {len(cells)} cells, {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
