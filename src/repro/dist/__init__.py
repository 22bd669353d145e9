"""Distribution utilities: sharding rules and explicit collectives."""
from repro.dist import collectives, sharding  # noqa: F401
from repro.dist.collectives import (  # noqa: F401
    compressed_psum_int8,
    mean_grads_int8,
    tp_allreduce,
)
from repro.dist.sharding import (  # noqa: F401
    batch_axes,
    cache_specs,
    disable_activation_sharding,
    enable_activation_sharding,
    init_sharded,
    mesh_axis_sizes,
    model_axis_size,
    named_shardings,
    packed_specs,
    param_specs,
    set_tp_mesh,
    shard_act,
    tp_mesh,
    tree_paths,
)
