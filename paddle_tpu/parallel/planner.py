"""The ONE parallel planner: axis assignment for every sharding engine.

Before this module, three planners each picked their own axes: the
sparse-embedding engine sharded tables over the dp axis, the ZeRO
planner sharded optimizer state over the dp axis, and the
auto_parallel search sharded "the last axis of big params" over its
own `mp` axis — an assignment that could collide with all of the
above the moment a model axis existed. :func:`plan_parallel` is now
the single owner: it reads the mesh hierarchy once
(`parallel/env.mesh_hierarchy`) and hands each engine its axis —

* sparse tables  → rows over the REPLICA (intra-pod ici) axis,
* tensor parallel → weight out-dims / vocab rows over the MODEL axis,
  resolved through the logical-axis rules (`parallel/axis_rules.py`),
* ZeRO-1/2 state → flat buffers over the REPLICA axis, with TP'd vars
  sized at their LOCAL block shapes (per-chip bytes ∝ 1/(mp·replica)),

so ZeRO moments, bucket lifetimes and AMP masters shard over
`replica` while params shard over `model` — composing, never
colliding. The GSPMD path (`parallel/auto_parallel.py`) asks the same
owner through :func:`param_tp_dims` instead of guessing "last axis".
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional

from . import env as penv

__all__ = ["ParallelPlan", "plan_parallel", "param_tp_dims",
           "experts_held"]


def experts_held(num_experts: int, ep_size: int, ep_rank: int = 0):
    """(first expert, how many) that rank `ep_rank` of an
    expert-parallel group of `ep_size` holds: contiguous equal shares.
    A routed layer built with this range (`layers.moe_experts`) routes
    over all `num_experts` and computes its own experts' part; the sum
    over the group's ranks is the layer. On one chip of the group the
    layer runs as it is, without the exchange: nothing stands in for
    the other ranks (the all-to-all over several chips is not built
    yet: ROADMAP R2)."""
    if num_experts % ep_size or not 0 <= ep_rank < ep_size:
        raise ValueError("%d experts over %d ranks, rank %d"
                         % (num_experts, ep_size, ep_rank))
    share = num_experts // ep_size
    return ep_rank * share, share


class ParallelPlan(NamedTuple):
    """The planner's verdict for one program on one mesh."""

    sparse_plan: Optional[object]   # embedding.planner.SparseTablePlan
    tp_plan: Optional[object]       # tensor_parallel.TensorParallelPlan
    shard_plan: Optional[object]    # sharded_update.ShardedUpdatePlan
    hier: Optional[object]          # env.MeshHierarchy (None = flat)


def plan_parallel(program, block, mesh, dp_axis, feed_names=(),
                  fetch_names=()) -> ParallelPlan:
    """Run the three sharding planners in their one valid order —
    sparse tables first (their optimizer ops leave the ZeRO planner's
    jurisdiction), tensor parallel second (its local shapes feed the
    ZeRO layout), ZeRO last — with every axis read from the mesh
    hierarchy. The fallback trail (`program._sharded_update_fallback`)
    is reset HERE, once per compile, so the TP planner's structured
    declines survive the ZeRO planner running after it."""
    hier = penv.mesh_hierarchy(mesh)
    program._sharded_update_fallback = []

    ndev = int(mesh.shape[dp_axis]) if mesh is not None \
        and dp_axis in mesh.shape else 1
    dcn_axis = hier[0] if hier is not None else None
    dcn_size = hier[2] if hier is not None else 1

    from ..embedding import planner as _emb_planner

    sparse_plan = _emb_planner.plan_sparse_tables(
        program, block, ndev, dp_axis, dcn_axis=dcn_axis,
        dcn_size=dcn_size, feed_names=feed_names)

    tp_plan = None
    if hier is not None and hier.model_axis is not None \
            and hier.mp_size > 1:
        from . import tensor_parallel as _tp

        tp_plan = _tp.plan_tensor_parallel(
            program, block, hier.mp_size, hier.model_axis,
            feed_names=feed_names, fetch_names=fetch_names,
            sparse_plan=sparse_plan)

    from . import sharded_update as _su

    shard_plan = _su.plan_sharded_update(
        program, block, ndev, dp_axis, dcn_axis=dcn_axis,
        dcn_size=dcn_size, tp_plan=tp_plan, sparse_plan=sparse_plan)

    return ParallelPlan(sparse_plan, tp_plan, shard_plan, hier)


def param_tp_dims(program, block, feed_names=(), fetch_names=(),
                  mp_hint=2) -> Dict[str, int]:
    """{param name: model-shardable dim} for the GSPMD/auto_parallel
    plan search — the SAME feasibility scan (axis rules + consumption
    audit) the manual TP engine runs, so the search's candidate specs
    and the shard_map engine can never disagree about which params may
    shard where. `mp_hint` only gates the divisibility check; the
    search re-checks divisibility against each candidate tp degree
    (`auto_parallel.build_specs`)."""
    from . import tensor_parallel as _tp

    trail = list(getattr(program, "_sharded_update_fallback", []) or [])
    plan = _tp.plan_tensor_parallel(
        program, block, mp_hint, penv.MODEL_AXIS,
        feed_names=feed_names, fetch_names=fetch_names,
        sparse_plan=getattr(program, "_sparse_plan", None))
    # probe only: restore the pre-existing fallback trail — declines at
    # the hint degree would misattribute the search's actual choice
    program._sharded_update_fallback = trail
    if plan is None:
        return {}
    return {n: p.tp_dim for n, p in plan.params.items()}
