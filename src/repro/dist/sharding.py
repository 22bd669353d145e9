"""Sharding rules for parameters, decode caches, and activations.

Three layers of policy, all mesh-axis-name based ("pod", "data", "model"):

  * ``param_specs``  — PartitionSpec tree for a parameter pytree. Tensor
    parallelism on the weight names the ternary/CiM dense path quantizes
    (attention projections, MLP/expert FFN weights), expert-dim sharding
    for MoE, replication for norms/small leaves, optional FSDP ("data"
    axis added to large weights whose dims divide).
  * ``cache_specs``  — decode caches: batch over the data-like axes, the
    sequence/state dim over "model".
  * ``shard_act``    — activation sharding constraints by *logical* axes
    name ("btd", "logits", "gecd", ...). Module-global switch: the
    dry-run (and tests) call ``enable_activation_sharding`` around the
    lowering; everything is an identity no-op when disabled, so CPU
    smoke tests never pay a constraint.

``tree_paths`` flattens a pytree into ("a/b/c", leaf) pairs — the path
currency used by quant/prepare.py's weight-name regexes and the spec
rules here.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

PyTree = Any


def mesh_axis_sizes(mesh) -> Dict[str, int]:
    """{axis_name: size} for a mesh — the ``axis_sizes`` currency the
    spec rules below take (so specs only name axes the shapes divide)."""
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def named_shardings(mesh, spec_tree: PyTree) -> PyTree:
    """Bind a PartitionSpec tree to a mesh as NamedShardings (the form
    ``jax.device_put`` / ``jit`` shardings consume)."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )

# ---------------------------------------------------------------------------
# Pytree path flattening
# ---------------------------------------------------------------------------


def _key_str(k) -> str:
    if isinstance(k, jax.tree_util.DictKey):
        return str(k.key)
    if isinstance(k, jax.tree_util.SequenceKey):
        return str(k.idx)
    if isinstance(k, jax.tree_util.GetAttrKey):
        return str(k.name)
    if isinstance(k, jax.tree_util.FlattenedIndexKey):
        return str(k.key)
    return str(k)


def tree_paths(tree: PyTree) -> List[Tuple[str, Any]]:
    """Flatten ``tree`` to a list of ("path/like/this", leaf) pairs, in
    ``jax.tree_util.tree_flatten`` leaf order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [("/".join(_key_str(k) for k in path), leaf) for path, leaf in flat]


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

# column-parallel (shard the output-channel / last dim over "model")
_COL_TP = {
    "wq", "wk", "wv", "w_uk", "w_uv", "w_dkv", "w_in",
    "w_gate", "w_up", "unembed", "projector",
}
# row-parallel (shard the contraction dim over "model")
_ROW_TP = {"wo", "w_out", "w_down"}
# MoE expert weights: shard the expert dim over "model"
_EXPERT_TP = {"w_gate", "w_up", "w_down"}

# leaves below this size are never FSDP-sharded (gather overhead > savings)
FSDP_MIN_SIZE = 1 << 20


def _axis_size(axis, axis_sizes: Optional[Dict[str, int]]) -> int:
    if axis_sizes is None:
        return 1
    size = 1
    for a in axis if isinstance(axis, tuple) else (axis,):
        size *= int(axis_sizes.get(a, 1))
    return size


def _divides(dim: int, axis, axis_sizes: Optional[Dict[str, int]]) -> bool:
    """True when sharding ``dim`` over ``axis`` is legal. With no
    ``axis_sizes`` the mesh is unknown — emit the logical axis and let the
    partitioner decide (the unit tests exercise this mode)."""
    if axis_sizes is None:
        return True
    size = _axis_size(axis, axis_sizes)
    return size >= 1 and dim % size == 0


def _is_stacked(segs: List[str]) -> bool:
    """Stacked-layer leaves carry the layer dim first (scan-over-layers);
    unrolled lists ("blocks/0/...") see per-layer leaves."""
    return segs[0] in ("blocks", "enc_blocks") and not (
        len(segs) > 1 and segs[1].isdigit()
    )


def _leaf_spec(path: str, leaf, axis_sizes: Optional[Dict[str, int]]) -> List:
    segs = path.split("/")
    name = segs[-1]
    parent = segs[-2] if len(segs) > 1 else ""
    ndim = len(leaf.shape)
    spec: List = [None] * ndim

    # norms / biases / vectors: replicated
    if ndim < 2 or name.startswith("ln") or name in (
        "final_norm", "enc_norm", "router", "conv_w", "conv_b", "dt_bias",
        "enc_pos",
    ):
        return spec

    if parent == "moe" and name in _EXPERT_TP and ndim >= 3:
        e_dim = ndim - 3
        if _divides(leaf.shape[e_dim], "model", axis_sizes):
            spec[e_dim] = "model"
        return spec

    if name == "embed":
        # shard the vocab dim (embedding lookups all-gather cheaply)
        if _divides(leaf.shape[0], "model", axis_sizes):
            spec[0] = "model"
        return spec

    if name in _COL_TP:
        if _divides(leaf.shape[-1], "model", axis_sizes):
            spec[-1] = "model"
        return spec

    if name in _ROW_TP:
        if _divides(leaf.shape[-2], "model", axis_sizes):
            spec[-2] = "model"
        return spec

    return spec


def param_specs(
    params: PyTree,
    fsdp: bool = False,
    axis_sizes: Optional[Dict[str, int]] = None,
) -> PyTree:
    """PartitionSpec tree matching ``params`` (rank always equals leaf
    rank). ``fsdp=True`` additionally spreads large weights over the
    "data" axis wherever a free dim divides."""

    def f(path_keys, leaf):
        path = "/".join(_key_str(k) for k in path_keys)
        spec = _leaf_spec(path, leaf, axis_sizes)
        if fsdp and axis_sizes and math.prod(leaf.shape) >= FSDP_MIN_SIZE:
            if "data" not in spec:
                start = 1 if _is_stacked(path.split("/")) else 0
                for i in range(start, len(spec)):
                    if spec[i] is None and _divides(leaf.shape[i], "data", axis_sizes):
                        spec[i] = "data"
                        break
        return P(*spec)

    return jax.tree_util.tree_map_with_path(f, params)


def init_sharded(init_fn, key: jax.Array, mesh) -> PyTree:
    """Run ``init_fn(key)`` with every output leaf created directly
    under its :func:`param_specs` sharding on ``mesh`` (jit with
    ``out_shardings``): each device materializes only its own shard, so
    a model too large for one device is never built whole on the first
    one."""
    shapes = jax.eval_shape(init_fn, key)
    shardings = named_shardings(
        mesh, param_specs(shapes, axis_sizes=mesh_axis_sizes(mesh)))
    return jax.jit(init_fn, out_shardings=shardings)(key)


# ---------------------------------------------------------------------------
# Cache specs
# ---------------------------------------------------------------------------


def cache_specs(caches: PyTree, mesh, batch: int) -> PyTree:
    """Decode-cache PartitionSpecs. Stacked cache leaves are
    (L, B, S/state...): batch over the data-like axes, the first trailing
    dim that divides over "model" (KV caches: the sequence dim).

    Quantized caches (DESIGN.md §13) need no special casing: int8/uint8
    code leaves keep the (L, B, S, ...) layout and their per-(row,
    position) scale leaves are (L, B, S) — both split on the sequence
    dim under the same rule, so each device stores its sequence shard
    of the codes together with the matching shard of the scales."""
    axis_sizes = mesh_axis_sizes(mesh)
    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dsize = _axis_size(daxes, axis_sizes)
    msize = int(axis_sizes.get("model", 1))

    def f(leaf):
        shape = leaf.shape
        spec: List = [None] * len(shape)
        if len(shape) >= 2 and daxes and batch % max(dsize, 1) == 0 and shape[1] == batch:
            spec[1] = daxes if len(daxes) > 1 else daxes[0]
        for i in range(2, len(shape)):
            if msize >= 1 and shape[i] % max(msize, 1) == 0:
                spec[i] = "model"
                break
        return P(*spec)

    return jax.tree.map(f, caches)


# ---------------------------------------------------------------------------
# Packed-bitplane specs (quant/prepare.pack_params output)
# ---------------------------------------------------------------------------


def packed_specs(
    packed: Dict[str, Any], axis_sizes: Optional[Dict[str, int]] = None
) -> Dict[str, Any]:
    """PartitionSpecs for a ``quant.prepare`` packed dict — either the
    legacy ``{path: (pos_plane, neg_plane, scale)}`` tuples or the
    canonical ``{path: PackedPlanes}`` layout ``prepare_for_spec`` emits
    (a registered pytree, so one structure-preserving tree map covers
    both; the canonical layout is consumed unchanged — no re-layout
    between prepare and placement). Planes are (..., K/8, N), scales
    (..., 1, N).

    Every entry shards the output-channel dim N over "model" — the planes
    are packed 2-bit *along K*, so splitting K would tear u8 bytes apart,
    while an N split keeps each device streaming only the plane columns
    its TP shard consumes (the "each device streams only its 2-bit weight
    shard" contract). Leaves whose N doesn't divide stay replicated (the
    canonical padded N is a 128 multiple, so typical TP degrees divide)."""

    def leaf_spec(leaf):
        spec: List = [None] * leaf.ndim
        if leaf.ndim >= 2 and _divides(leaf.shape[-1], "model", axis_sizes):
            spec[-1] = "model"
        return P(*spec)

    return jax.tree.map(leaf_spec, packed)


# ---------------------------------------------------------------------------
# Serving tensor-parallel mesh (module-global switch, mirrors the
# activation-sharding pattern: consumers read it at trace time)
# ---------------------------------------------------------------------------

_TP_MESH = None


def set_tp_mesh(mesh) -> None:
    """Install the mesh the explicit TP collectives (shard_map entry
    points — ``execution.execute_tp``) run over. ``None`` disables the
    explicit path; the implicit GSPMD path (params/caches device_put with
    NamedShardings, partitioner inserts collectives) needs no global."""
    global _TP_MESH
    _TP_MESH = mesh


def tp_mesh():
    return _TP_MESH


def replica_device_groups(replicas: int, tp: int) -> List[List[Any]]:
    """Partition the visible devices into ``replicas`` disjoint groups
    of ``tp`` devices — the device plan behind the serving front door's
    multi-replica router (DESIGN.md §12): the groups are the rows of a
    ``(replicas, tp)`` grid, i.e. replication lives on the ``"data"``
    axis of the device plane while each replica's internal TP sharding
    keeps the ``"model"`` axis. Groups are disjoint, so replica engines
    never contend for a device and their collectives never cross."""
    if replicas < 1 or tp < 1:
        raise ValueError(f"need replicas >= 1 and tp >= 1, got "
                         f"replicas={replicas} tp={tp}")
    devs = jax.devices()
    need = replicas * tp
    if len(devs) < need:
        raise ValueError(
            f"{replicas} replicas x tp={tp} needs {need} devices but only "
            f"{len(devs)} are visible (on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} before the "
            "first jax import)"
        )
    return [list(devs[r * tp:(r + 1) * tp]) for r in range(replicas)]


# ---------------------------------------------------------------------------
# Activation sharding (logical axes, module-global switch)
# ---------------------------------------------------------------------------

# None = disabled (identity). When enabled: {"multi_pod", "divisor",
# "model_size", "data"} — consumers (models/moe.py) read "divisor" to pick
# the routing group count.
_ACT_AXES: Optional[Dict[str, Any]] = None


def enable_activation_sharding(
    *, multi_pod: bool = False, batch_divisor: int = 1, model_size: int = 1
) -> None:
    global _ACT_AXES
    _ACT_AXES = {
        "multi_pod": bool(multi_pod),
        "divisor": int(batch_divisor),
        "model_size": int(model_size),
        "data": ("pod", "data") if multi_pod else ("data",),
    }


def disable_activation_sharding() -> None:
    global _ACT_AXES
    _ACT_AXES = None


def model_axis_size() -> int:
    """Size of the "model" mesh axis when activation sharding is on; 1
    otherwise (callers use it to guard divisibility)."""
    return int(_ACT_AXES.get("model_size", 1)) if _ACT_AXES else 1


def batch_axes() -> Tuple[str, ...]:
    """The data-like mesh axes batch dims shard over (() when off)."""
    return _ACT_AXES["data"] if _ACT_AXES else ()


def _act_spec(x, name: str) -> P:
    cfg = _ACT_AXES
    data = cfg["data"]
    d = data if len(data) > 1 else data[0]
    div = cfg["divisor"]
    msize = cfg.get("model_size", 1)
    batch_ok = div > 1 and x.shape[0] % div == 0
    b = d if batch_ok else None
    if name == "btd":
        return P(b, None, None)
    if name == "logits":
        v = "model" if msize >= 1 and x.shape[-1] % max(msize, 1) == 0 else None
        return P(b, None, v)
    if name == "gecd":          # (groups, experts, capacity, d): expert-sharded
        return P(b, "model", None, None)
    if name == "gecd_cap":      # expert count doesn't divide: shard capacity
        return P(b, None, "model", None)
    if name == "bqhgd_sp":      # context parallelism: query rows over "model"
        return P(None, "model", None, None, None)
    return P(*([None] * x.ndim))


def shard_act(x: jax.Array, name: str) -> jax.Array:
    """Apply a named activation sharding constraint; identity when
    activation sharding is disabled or no mesh context is active."""
    if _ACT_AXES is None:
        return x
    try:
        return jax.lax.with_sharding_constraint(x, _act_spec(x, name))
    except (RuntimeError, ValueError):
        # no mesh context (eager smoke path) — constraints are advisory
        return x
