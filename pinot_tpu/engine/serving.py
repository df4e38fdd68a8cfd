"""Shared per-table execution: the one rollup-or-plan-then-batch loop.

Both the in-process broker (broker/broker.py) and the HTTP server node
(cluster/server_node.py) serve a query over a list of segments; this is
that loop in one place so fixes (rollup gating, tracing, upsert handling)
cannot drift between the two paths.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from ..query.context import QueryContext
from ..query.planner import CompiledPlan, SegmentPlanner
from ..startree.query import try_rollup_execute
from ..utils import phases as ph
from ..utils.spans import annotate, phase
from .batch import execute_plans_batched


@dataclass
class TableExecution:
    plans: List[Optional[CompiledPlan]]         # None where rollup answered
    real_plans: List[CompiledPlan]
    partials: List[Any] = field(default_factory=list)
    rollup_segments: int = 0

    @property
    def pruned(self) -> int:
        return sum(1 for p in self.real_plans if p.kind == "pruned")

    @property
    def docs_scanned(self) -> int:
        return sum(p.segment.n_docs for p in self.real_plans
                   if p.kind in ("kernel", "host", "topgroups"))


def plan_segments(ctx: QueryContext, segments: List[Any],
                  use_rollups: bool = True) -> TableExecution:
    # one query = one plan-cache generation: the retrace detector flags
    # any kernel compile of a plan structure already warm from an
    # EARLIER query (ops/plan_cache.RetraceDetector). The accountant's
    # query id dedupes multi-table executions of one query (hybrid
    # offline+realtime) into a single warmup generation.
    from ..ops.plan_cache import global_plan_cache
    from .accounting import global_accountant
    global_plan_cache.detector.begin_query(
        global_accountant.current_query_id())
    plans: List[Optional[CompiledPlan]] = []
    precomputed: Dict[int, Any] = {}
    with phase(ph.PLANNING, segments=len(segments)):
        for i, seg in enumerate(segments):
            partial = (try_rollup_execute(ctx, seg)
                       if use_rollups and hasattr(seg, "metadata") else None)
            if partial is not None:
                precomputed[i] = partial
                plans.append(None)
            else:
                plans.append(SegmentPlanner(ctx, seg).plan())
        ex = TableExecution(plans, [p for p in plans if p is not None],
                            rollup_segments=len(precomputed))
        ex._precomputed = precomputed  # type: ignore[attr-defined]
        # segment-heat telemetry (utils/heat): one touch per executed
        # segment — the access signal the fleet rollup ranks hot
        # segments by and the future HBM tier admits on
        from ..utils.heat import global_segment_heat
        for p in ex.real_plans:
            if p.kind in ("kernel", "host", "topgroups"):
                global_segment_heat.touch(p.segment, ctx.table,
                                          p.segment.n_docs)
        if ex.real_plans:
            p0 = ex.real_plans[0]
            annotate(kinds=sorted({p.kind for p in ex.real_plans}),
                     rollup_segments=len(precomputed), pruned=ex.pruned)
            if p0.kind == "kernel":
                annotate(strategy=p0.kernel_plan.strategy,
                         est_sel=p0.est_selectivity,
                         slots_cap=p0.slots_cap,
                         cost_trace=p0.strategy_trace)
    return ex


def execute_planned(ex: TableExecution, trim: bool = False) -> List[Any]:
    """Run the batched device dispatch and interleave rollup partials back
    into input order. ``trim``: these partials are the statement's only
    ones (engine/batch.execute_plans_batched)."""
    precomputed = getattr(ex, "_precomputed", {})
    # the real plan that the first rollup partial with groups precedes:
    # no combine of group-by segments reaches over it
    stop = next((sum(1 for p in ex.plans[:i] if p is not None)
                 for i in sorted(precomputed)
                 if getattr(precomputed[i], "groups", None)), None)
    with phase(ph.EXECUTION, segments=len(ex.real_plans)):
        executed = list(execute_plans_batched(
            ex.real_plans, stop, trim and not precomputed))
    executed = iter(executed)
    ex.partials = [precomputed[i] if p is None else next(executed)
                   for i, p in enumerate(ex.plans)]
    return ex.partials


def execute_segments(ctx: QueryContext, segments: List[Any],
                     use_rollups: bool = True,
                     trim: bool = False) -> TableExecution:
    ex = plan_segments(ctx, segments, use_rollups)
    execute_planned(ex, trim)
    return ex


def execute_on_mesh(ctx: QueryContext, dist,
                    segment_names: Optional[List[str]] = None):
    """The partial of ``ctx`` from ONE mesh program over ``dist``, the
    table's mesh residency (parallel/distributed.DistributedTable; the
    caller reads ``dm.distributed`` once), or None where the caller has
    to take the per-segment path: a selection, a subset of the resident
    segments, or a plan the mesh cannot combine
    (DistributedTable.mesh_plan); each counts ``mesh_fallbacks``. Shared
    by the in-process broker and the server node; it crosses the
    boundaries of the per-segment path (planning, then execution around
    distributed_execute), so the phase counters read alike."""
    from ..ops.plan_cache import global_plan_cache
    from ..utils.metrics import global_metrics
    from .accounting import global_accountant
    plan = None
    if ctx.is_aggregation and (segment_names is None or
                               set(segment_names) == dist.segment_names):
        global_plan_cache.detector.begin_query(
            global_accountant.current_query_id())
        with phase(ph.PLANNING, segments=1):
            plan = dist.mesh_plan(ctx)
    if plan is None:
        global_metrics.count("mesh_fallbacks")
        return None
    with phase(ph.EXECUTION, segments=len(dist.segments)), \
            phase(ph.DISTRIBUTED_EXECUTE):
        return dist.execute(plan)
