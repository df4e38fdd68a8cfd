"""Distributed execution: segments sharded over a device mesh, one
shard_map program per query, XLA collectives for the combine.

Reference parity: the broker scatter-gather data plane —
pinot-core/.../transport/QueryRouter.java:89 (Netty fan-out to servers) +
BrokerReduceService.java:61 (merge DataTables) + per-server combine
(BaseCombineOperator.java:99-117, one task per segment). TPU-native
replacement: segments of one table are stacked into (n_segments, bucket)
arrays laid out over a 1-D Mesh axis; each device vmaps the leaf kernel
over its local segments (intra-server combine), then psum/pmin/pmax over
ICI replace the Netty response hop entirely. The result lands replicated on
every device — the "broker" just reads it.

Requirements for the dense on-device combine:
- all segments share table-level dictionaries (SegmentBuilder shared_dicts
  path), so dict ids and group spaces agree across devices;
- plans whose params are per-segment data (null-mask filters) fall back to
  the per-segment host-merge path.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine.executor import extract_partial, resolve_params
from ..utils.spans import annotate, device_fence, span
from ..ops.kernels import build_kernel
from ..query.context import QueryContext
from ..query.planner import CompiledPlan, SegmentPlanner
from ..segment.immutable import ImmutableSegment, bucket_for
from .mesh import SEG_AXIS, segment_mesh


def _reduce_op(name: str) -> str:
    if name.endswith("_present"):
        return "or"
    if name.endswith("_min"):
        return "min"
    if name.endswith("_max"):
        return "max"
    return "sum"  # matched, counts, sums, avg parts, group_count


class DistributedTable:
    """A table resident across a device mesh as stacked sharded columns."""

    def __init__(self, segments: List[ImmutableSegment],
                 mesh: Optional[Mesh] = None):
        if not segments:
            raise ValueError("no segments")
        self.segments = segments
        self.mesh = mesh or segment_mesh()
        self.n_dev = self.mesh.devices.size
        self.bucket = max(bucket_for(s.n_docs) for s in segments)
        # pad segment count to a multiple of the mesh (empty segments are
        # inert: n_docs=0 -> all-false validity masks)
        self.n_slots = -(-len(segments) // self.n_dev) * self.n_dev
        self._cols: Dict[str, jax.Array] = {}
        self._n_docs = self._shard_1d(np.array(
            [s.n_docs for s in segments] +
            [0] * (self.n_slots - len(segments)), dtype=np.int32))
        self._check_shared_dicts()

    def _check_shared_dicts(self) -> None:
        s0 = self.segments[0]
        for s in self.segments[1:]:
            for name, m in s0.columns.items():
                m2 = s.columns[name]
                if m.has_dict != m2.has_dict:
                    raise ValueError(
                        f"segment {s.name!r} column {name!r} does not share "
                        "the table dictionary (build with shared_dicts=...)")
                if m.has_dict:
                    v0 = np.asarray(s0.dictionary(name).values)
                    v1 = np.asarray(s.dictionary(name).values)
                    if len(v0) != len(v1) or not np.array_equal(v0, v1):
                        raise ValueError(
                            f"segment {s.name!r} column {name!r} dictionary "
                            "differs from the table dictionary")

    def _plan_view(self):
        """A table-wide planning view: segment 0's shape with min/max/nulls
        WIDENED across every mesh-resident segment. Planning against one
        segment's statistics is wrong table-wide: its min/max would
        constant-fold predicates other segments don't satisfy, and
        AggSpec.bits sized from one segment's value range would silently
        truncate other segments' int8-limb group sums."""
        import copy
        s0 = self.segments[0]
        view = copy.copy(s0)
        view.columns = {}
        for name, m0 in s0.columns.items():
            m = copy.copy(m0)
            for s in self.segments[1:]:
                m2 = s.columns[name]
                if m.min is not None:
                    m.min = (None if m2.min is None
                             else min(m.min, m2.min))
                if m.max is not None:
                    m.max = (None if m2.max is None
                             else max(m.max, m2.max))
                m.has_nulls = m.has_nulls or m2.has_nulls
                m.is_sorted = m.is_sorted and m2.is_sorted
            view.columns[name] = m
        # ANY segment with upsert-invalidated docs forces the validdocs
        # param into the plan (-> try_execute falls back to the per-segment
        # path), not just segment 0
        view.valid_docs = next(
            (s.valid_docs for s in self.segments
             if getattr(s, "valid_docs", None) is not None), None)
        return view

    # -- sharded residency -------------------------------------------------
    def _sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def _shard_1d(self, host: np.ndarray) -> jax.Array:
        return jax.device_put(host, self._sharding(P(SEG_AXIS)))

    def device_col(self, name: str) -> jax.Array:
        if name not in self._cols:
            m = self.segments[0].columns[name]
            stack = np.zeros(
                (self.n_slots, self.bucket),
                dtype=np.int32 if m.has_dict else m.fwd_dtype)
            for i, s in enumerate(self.segments):
                arr = np.asarray(s.fwd(name))
                stack[i, : s.n_docs] = arr.astype(stack.dtype, copy=False)
            self._cols[name] = jax.device_put(
                stack, self._sharding(P(SEG_AXIS, None)))
        return self._cols[name]

    # -- execution ---------------------------------------------------------
    def plan(self, ctx: QueryContext) -> CompiledPlan:
        """Plan against the widened table view; shared dictionaries make the
        dict-id params valid table-wide, and widened min/max keep raw-column
        constant folds and limb sizing correct for every segment. Compact-
        strategy group-bys run flattened per device (local segments
        concatenate along the row axis — _distributed_kernel), so the
        planner chooses strategies exactly as the single-chip path does."""
        return SegmentPlanner(ctx, self._plan_view()).plan()

    def try_execute(self, ctx: QueryContext):
        """Distributed partial, or None when the plan needs the per-segment
        path (host fallbacks, per-segment null masks, metadata fast paths
        whose states differ per segment)."""
        plan = self.plan(ctx)
        if plan.kind != "kernel":
            return None
        if any(isinstance(p, tuple)
               and p[0] in ("nullmask", "validdocs", "docmask")
               for p in plan.params):
            return None  # per-segment data params need the per-segment path
        if any(not getattr(self.segments[0].columns[c],
                           "single_value", True)
               for c in plan.col_names):
            # MV columns are (bucket, maxValues) matrices; the sharded
            # column stack is 2-D — per-segment path handles them
            return None
        if plan.kernel_plan is not None and any(
                s.kind in ("distinct_count_theta", "percentile_sketch",
                           "raw_theta", "percentile_raw_sketch")
                for s in plan.kernel_plan.aggs):
            # theta hash lists / percentile centroids are NOT
            # positionally combinable across shards (HLL presence is —
            # it rides the 'or' reduce); per-segment path merges them
            return None
        out = self._run(plan)
        return extract_partial(plan, out)

    def _cost_model_cap(self, plan: CompiledPlan) -> Optional[int]:
        """Scale the planner's cost-model compaction capacity to one
        device's LOCAL shard (local segment count x bucket) — the mesh
        kernels must not run at the heuristic default caps (ROADMAP).
        Shares multistage/costs.scaled_compact_cap with the fused batch
        dispatch so the scaling rule cannot fork."""
        if plan.kernel_plan.strategy != "compact":
            return None
        from ..multistage.costs import scaled_compact_cap
        local = self.n_slots // self.n_dev
        return scaled_compact_cap(plan, local * self.bucket,
                                  self.mesh.devices.flat[0].platform)

    def _run(self, plan: CompiledPlan) -> Dict[str, np.ndarray]:
        cols = tuple(self.device_col(n) for n in plan.col_names)
        # replicated placement on THIS mesh's devices — never the default
        # backend (the driver's dryrun runs a CPU mesh under a TPU default)
        params = resolve_params(plan, sharding=self._sharding(P()))
        cap = self._cost_model_cap(plan)
        local = self.n_slots // self.n_dev
        with span("mesh_dispatch", devices=self.n_dev,
                  local_segments=local, bucket=self.bucket,
                  strategy=plan.kernel_plan.strategy, slots_cap=cap,
                  est_sel=plan.est_selectivity):
            fn = _distributed_kernel(plan.kernel_plan, self.bucket,
                                     self.mesh, len(cols), len(params),
                                     slots_cap=cap)
            with span("device_execute"):
                dev = fn(cols, self._n_docs, params)
                device_fence(dev)
            with span("device_transfer"):
                host = jax.device_get(dev)
            if int(host.pop("overflow", 0)):
                # compact capacity exceeded on some device: rerun at the
                # cannot-overflow capacity of a full local shard
                from ..ops.compact import full_slots_cap
                full = full_slots_cap(local * self.bucket)
                with span("overflow_retry", slots_cap=full):
                    fn = _distributed_kernel(
                        plan.kernel_plan, self.bucket, self.mesh,
                        len(cols), len(params), slots_cap=full)
                    host = jax.device_get(fn(cols, self._n_docs, params))
                host.pop("overflow", None)
                annotate(overflow_retry=True, slots_cap=full)
            if "matched" in host:
                matched = int(np.asarray(host["matched"]).sum())
                annotate(matched=matched,
                         meas_sel=matched / max(
                             sum(s.n_docs for s in self.segments), 1))
            return host


def _distributed_kernel(kernel_plan, bucket: int, mesh: Mesh,
                        n_cols: int, n_params: int,
                        slots_cap: int = None):
    from ..ops.kernels import (_ladder_min_elems, _two_pass_mode,
                               cpu_scatter_default)

    platform = mesh.devices.flat[0].platform
    # the compact-path env knobs resolve HERE so they are part of the
    # cache key (the jitted_kernel convention) — flipping them between
    # calls must never hit a stale cached mesh program
    return _distributed_kernel_cached(kernel_plan, bucket, mesh, n_cols,
                                      n_params, slots_cap,
                                      cpu_scatter_default(platform),
                                      _two_pass_mode(),
                                      _ladder_min_elems())


@functools.lru_cache(maxsize=512)
def _distributed_kernel_cached(kernel_plan, bucket: int, mesh: Mesh,
                               n_cols: int, n_params: int,
                               slots_cap: int, scatter: bool,
                               two_pass_mode: str = "auto",
                               ladder_min: int = 1 << 22):
    """jit(shard_map(kernel + collectives)) cached per plan/mesh."""
    # dense (space,) outputs only: psum/pmin/pmax combine positionally
    # across shards, which device-side transfer compaction would break.
    # platform pins the kernel lowering to the mesh's backend (the
    # driver's dryrun runs a CPU mesh under a TPU process default).
    platform = mesh.devices.flat[0].platform
    compact_gb = (kernel_plan.is_group_by
                  and kernel_plan.strategy == "compact")

    def per_device(cols, n_docs, params):
        # cols: tuple of (L, bucket) local shards; n_docs: (L,)
        local_segs = n_docs.shape[0]
        if compact_gb:
            # flatten local segments into one row axis: shared table
            # dictionaries make params segment-agnostic, so one Pallas
            # compaction + group pass serves the whole local shard
            kern = build_kernel(kernel_plan, bucket, slots_cap, platform,
                                xfer_compact=False,
                                local_segments=local_segs,
                                scatter=scatter,
                                two_pass_mode=two_pass_mode,
                                ladder_min=ladder_min)
            flat = tuple(c.reshape(local_segs * bucket) for c in cols)
            local = kern(flat, n_docs, params)
        else:
            kern = build_kernel(kernel_plan, bucket, slots_cap, platform,
                                xfer_compact=False, scatter=scatter,
                                two_pass_mode=two_pass_mode,
                                ladder_min=ladder_min)
            out = jax.vmap(lambda c, n: kern(c, n, params))(cols, n_docs)
            local = {}
            for k, v in out.items():
                op = _reduce_op(k)
                if op == "sum":
                    local[k] = v.sum(axis=0)
                elif op == "min":
                    local[k] = v.min(axis=0)
                elif op == "max":
                    local[k] = v.max(axis=0)
                else:
                    local[k] = v.max(axis=0)
        red = {}
        for k, v in local.items():
            op = _reduce_op(k)
            if k == "overflow" or op == "sum":
                red[k] = jax.lax.psum(v, SEG_AXIS)
            elif op == "min":
                red[k] = jax.lax.pmin(v, SEG_AXIS)
            elif op == "max":
                red[k] = jax.lax.pmax(v, SEG_AXIS)
            else:  # 'or' on bool presence
                red[k] = jax.lax.pmax(
                    v.astype(jnp.int32), SEG_AXIS).astype(bool)
        return red

    in_specs = (tuple(P(SEG_AXIS, None) for _ in range(n_cols)),
                P(SEG_AXIS),
                tuple(P() for _ in range(n_params)))
    mapped = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                           out_specs=P(), check_vma=False)
    return jax.jit(mapped)
