"""SPMD robust aggregation over a Mesh-sharded machine axis.

``grad_agg.aggregate_machine_axis`` is pure math over a local (m, ...)
array; this module runs the same math when the machine axis is sharded
across devices. The schedule is gather-then-reduce:

    shard_map over the machine axis
      -> lax.all_gather the machine rows (tiled)     # the only collective
        -> aggregate_machine_axis on the full axis   # identical math

Every device then holds the identical aggregate, so the output is
replicated over the machine axis while any *payload* sharding (e.g. a
"model" axis on the parameter dims) is preserved — the robust aggregators
(median / trimmed / DCQ) are coordinate-wise, so payload shards never
need to communicate.

The replicated reference and this path agree to fp32 tolerance (1e-4 in
tests/test_dist.py): the post-gather reduction is the same program, the
only difference is the gather's concatenation order, which is the machine
order by construction (tiled all-gather).
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.agg import get_aggregator
from repro.dist.grad_agg import GradAggConfig, aggregate_machine_axis


def tree_machine_specs(tree, mesh: Mesh, fsdp: bool = False,
                       machine_axis=None):
    """Per-leaf PartitionSpecs for a machine-stacked pytree: the machine
    axis rides the mesh's batch axes while every payload dim keeps the
    PARAM sharding rule from models/sharding.py. (Dropping the payload
    sharding replicates every machine's gradient over the model axis — a
    16x memory/collective blow-up; see EXPERIMENTS.md §Perf HC-train it1.)

    Extracted from train/trainer.py so the sharded tree protocol, the
    trainer and the sweep executor route leaves over the mesh with the
    same rule.
    """
    from repro.models import sharding as shd
    ax = machine_axis if machine_axis is not None else shd.batch_axes(mesh)
    if isinstance(ax, str) and ax not in mesh.axis_names:
        # pure machine mesh (e.g. 1-D ("machines",)): no "data" axis
        ax = mesh.axis_names[0]

    def mspec(kp, leaf):
        path = tuple(str(getattr(k, "key", getattr(k, "idx", "")))
                     for k in kp)
        ps = shd.param_spec(path, tuple(leaf.shape[1:]), mesh, fsdp=fsdp)
        return P(*((ax,) + tuple(ps)))
    return jax.tree_util.tree_map_with_path(mspec, tree)


def sharded_aggregate_leaf(values: jax.Array, cfg: GradAggConfig,
                           mesh: Mesh, spec: P) -> jax.Array:
    """Aggregate one (m, ...) leaf whose machine axis is sharded.

    Args:
      values: array with the machine axis leading; sharded as ``spec``.
      cfg: aggregation config (method/trim/K as in grad_agg).
      mesh: the device mesh carrying ``spec``'s axis names.
      spec: PartitionSpec of ``values``; ``spec[0]`` names the mesh
        axis (or axes) the machine dimension is sharded over, the rest
        describes payload sharding and is preserved on the output.

    Returns: the aggregate, shape ``values.shape[1:]``, replicated over
    the machine axis and sharded as ``spec[1:]`` on the payload dims.
    """
    machine_axis = spec[0] if len(spec) else None
    if machine_axis is None:
        # machine axis replicated: nothing to gather, aggregate in place
        return aggregate_machine_axis(values, cfg)
    rest = P(*spec[1:])
    reg_name = "dcq_mad" if cfg.method == "dcq" else cfg.method
    try:
        coordinatewise = get_aggregator(reg_name).coordinatewise
    except KeyError:
        # match the ValueError contract of aggregate_machine_axis
        raise ValueError(f"unknown aggregation method {cfg.method!r}") \
            from None
    if not coordinatewise and any(s is not None for s in rest):
        # e.g. geomedian: Weiszfeld weights couple all coordinates; a
        # payload shard would compute a different (wrong) aggregate than
        # the replicated path. The registry declares which rules commute
        # with payload sharding.
        raise ValueError(
            f"{cfg.method} is not coordinate-wise: payload dims must be "
            f"replicated in the sharded strategy, got spec {spec}")

    def gather_and_reduce(x):
        full = jax.lax.all_gather(x, machine_axis, axis=0, tiled=True)
        return aggregate_machine_axis(full, cfg)

    return jax.shard_map(gather_and_reduce, mesh=mesh, in_specs=(spec,),
                         out_specs=rest, check_vma=False)(values)
