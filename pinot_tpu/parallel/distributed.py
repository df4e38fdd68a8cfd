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

from ..engine.executor import (count_compact_steps, extract_partial,
                               resolve_params)
from ..ops.kernels import (build_kernel, cpu_scatter_default,
                           launch_forms, over_segments, sort_core_fits,
                           takes_sparse_post)
from ..utils import phases as ph
from ..utils.devmem import global_device_memory
from ..utils.metrics import global_metrics
from ..utils.spans import (annotate, count_dispatch, device_fence, phase,
                           span)
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
                 mesh: Optional[Mesh] = None,
                 sort_row_limit: Optional[int] = None):
        """``sort_row_limit``: the most rows of a local shard the sort
        core takes as one flattened program (None: the one-chip constant,
        ops/kernels.SEGMENTED_SORT_ROW_LIMIT); over it the shard runs per
        local segment inside the same mesh program (_route)."""
        if not segments:
            raise ValueError("no segments")
        self.segments = segments
        self.segment_names = frozenset(s.name for s in segments)
        self.mesh = mesh or segment_mesh()
        self.sort_row_limit = sort_row_limit
        self.n_dev = self.mesh.devices.size
        self.bucket = max(bucket_for(s.n_docs) for s in segments)
        # pad segment count to a multiple of the mesh (empty segments are
        # inert: n_docs=0 -> all-false validity masks)
        self.n_slots = -(-len(segments) // self.n_dev) * self.n_dev
        self._cols: Dict[str, jax.Array] = {}
        self._wide_columns = None    # _plan_view's widened column metadata
        self._overflowed = set()     # (kernel plan, capacity) that overflowed
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
        if self._wide_columns is None:   # segments are immutable: once
            wide = {}
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
                wide[name] = m
            self._wide_columns = wide
        view.columns = self._wide_columns
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
            global_device_memory.add("mesh_cols", (id(self), name),
                                     int(stack.nbytes))
        return self._cols[name]

    def evict_device(self) -> None:
        """Drop the sharded columns (a replaced or stopped residency);
        queries in flight keep their own references."""
        for name in list(self._cols):
            self._cols.pop(name, None)
            global_device_memory.remove("mesh_cols", (id(self), name))

    # -- execution ---------------------------------------------------------
    def plan(self, ctx: QueryContext) -> CompiledPlan:
        """Plan against the widened table view; shared dictionaries make the
        dict-id params valid table-wide, and widened min/max keep raw-column
        constant folds and limb sizing correct for every segment. The
        planner chooses strategies exactly as the single-chip path does;
        how a compact group-by runs on a device's shard is _route's."""
        return SegmentPlanner(ctx, self._plan_view()).plan()

    def mesh_plan(self, ctx: QueryContext) -> Optional[CompiledPlan]:
        """The table-wide plan, or None when the statement needs the
        per-segment path (host fallbacks, per-segment null masks, metadata
        fast paths whose states differ per segment)."""
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
        return plan

    def execute(self, plan: CompiledPlan):
        """One mesh program for ``plan`` (of mesh_plan): its partial."""
        out = self._run(plan)
        with phase(ph.EXTRACT_PARTIAL, segments=len(self.segments)):
            return extract_partial(plan, out)

    def try_execute(self, ctx: QueryContext):
        """Distributed partial, or None when the plan needs the
        per-segment path."""
        plan = self.mesh_plan(ctx)
        return None if plan is None else self.execute(plan)

    @property
    def local_segments(self) -> int:
        return self.n_slots // self.n_dev

    def _route(self, kernel_plan) -> str:
        """The mesh program's family. A compact group-by flattens the
        local shard into one row axis while the one-chip rule allows that
        many rows (ops/kernels.sort_core_fits: any number on the
        factorized core, SEGMENTED_SORT_ROW_LIMIT on the sort core; the
        shared dictionaries leave the group space as it is); over it the
        program maps the kernel over the local segments instead."""
        if not (kernel_plan.is_group_by
                and kernel_plan.strategy == "compact"):
            return ph.MESH_DENSE
        local = self.local_segments
        if local == 1 or sort_core_fits(kernel_plan, local * self.bucket,
                                        row_limit=self.sort_row_limit):
            return ph.MESH_COMPACT
        return ph.MESH_COMPACT_PER_SEGMENT

    def _cost_model_cap(self, plan: CompiledPlan,
                        rows: int) -> Optional[int]:
        """Scale the planner's cost-model compaction capacity to the
        ``rows`` one compaction of the mesh program sees — the local
        shard, or one local segment on the routed sort core; the mesh
        kernels must not run at the heuristic default caps (ROADMAP).
        Shares multistage/costs.scaled_compact_cap with the fused batch
        dispatch so the scaling rule cannot fork."""
        if plan.kernel_plan.strategy != "compact":
            return None
        from ..multistage.costs import _pow2_at_least, scaled_compact_cap
        cap = scaled_compact_cap(plan, rows,
                                 self.mesh.devices.flat[0].platform)
        # the cost model's floor (3 x STAGE = 864 slot rows, q3.4) is its
        # one capacity that is no power of two, and XLA:TPU refuses the
        # sort core at it inside the mesh program, with either post
        # ("vmem ... reduce-window ... u32[7,128]": described v5e:2x2
        # compiles and a chip run of PR 29); at 1,024 it compiles
        return None if cap is None else _pow2_at_least(cap)

    def _launch(self, plan: CompiledPlan, family: str, cap: Optional[int],
                cols, params, xfer_compact: bool = True
                ) -> Dict[str, np.ndarray]:
        """One launch of the mesh program and its copy back."""
        from ..engine.accounting import global_accountant
        global_accountant.sample()   # kill/timeout before the launch
        fn = _distributed_kernel(plan.kernel_plan, self.bucket, self.mesh,
                                 len(cols), len(params), cap, family,
                                 xfer_compact)
        count_dispatch(family, *launch_forms(
            plan.kernel_plan, params,
            platform=self.mesh.devices.flat[0].platform))
        with phase(ph.DEVICE_EXECUTE):
            dev = fn(cols, self._n_docs, params)
            device_fence(dev)
        with phase(ph.DEVICE_TRANSFER):
            return jax.device_get(dev)  # jaxlint: ok host-sync

    def _run(self, plan: CompiledPlan) -> Dict[str, np.ndarray]:
        from ..engine.accounting import global_accountant
        from ..ops.compact import full_slots_cap
        from ..ops.plan_cache import global_plan_cache
        with phase(ph.DISPATCH_PREPARE):
            cols = tuple(self.device_col(n) for n in plan.col_names)
            # replicated placement on THIS mesh's devices — never the
            # default backend (the driver's dryrun runs a CPU mesh under
            # a TPU default)
            params = resolve_params(plan, sharding=self._sharding(P()))
        local = self.local_segments
        family = self._route(plan.kernel_plan)
        # rows under one compaction: it sizes the capacity and the retry
        rows = self.bucket * (1 if family == ph.MESH_COMPACT_PER_SEGMENT
                              else local)
        cap = self._cost_model_cap(plan, rows)
        if (plan.kernel_plan, cap) in self._overflowed:
            # this capacity overflowed for this plan before: straight to
            # the full one, not the doomed launch and its retry again
            cap = full_slots_cap(rows)
        with span("mesh_dispatch", devices=self.n_dev,
                  local_segments=local, bucket=self.bucket,
                  strategy=plan.kernel_plan.strategy, route=family,
                  slots_cap=cap, est_sel=plan.est_selectivity):
            host = self._launch(plan, family, cap, cols, params)
            if int(host.pop("overflow", 0)):
                # compact capacity exceeded on some device: rerun at the
                # cannot-overflow capacity of what one compaction sees
                self._overflowed.add((plan.kernel_plan, cap))
                cap = full_slots_cap(rows)
                global_metrics.count("mesh_overflow_retries")
                with span("overflow_retry", slots_cap=cap), \
                        global_plan_cache.detector.expected():
                    host = self._launch(plan, family, cap, cols, params)
                host.pop("overflow", None)
                annotate(overflow_retry=True, slots_cap=cap)
            # host numpy behind _launch's device_get, like the checks
            # around it — host-sync [jaxlint baseline]
            if int(host.pop("group_overflow", 0)):  # jaxlint: ok host-sync
                # more live groups than the transfer compaction (or one
                # segment's sparse post) holds: dense (space,) all the way
                global_metrics.count("group_xfer_overflow_retries")
                with span("group_overflow_retry"), \
                        global_plan_cache.detector.expected():
                    host = self._launch(plan, family, cap, cols, params,
                                        xfer_compact=False)
                host.pop("overflow", None)
                annotate(group_overflow_retry=True)
            if "group_idx" in host:
                # the result came back compacted to its live groups:
                # count where the program took the list of them from
                global_metrics.count(
                    "mesh_live_list_sparse" if lists_live_groups_sparse(
                        plan.kernel_plan, family, True, cpu_scatter_default(
                            self.mesh.devices.flat[0].platform))
                    else "mesh_live_list_dense")
            if "matched" in host:
                count_compact_steps(host)
                matched = int(np.asarray(host["matched"]).sum())
                annotate(matched=matched,
                         meas_sel=matched / max(
                             sum(s.n_docs for s in self.segments), 1))
            global_accountant.track_result(host)
            return host


def _distributed_kernel(kernel_plan, bucket: int, mesh: Mesh,
                        n_cols: int, n_params: int,
                        slots_cap: Optional[int], family: str,
                        xfer_compact: bool = True):
    from ..ops.kernels import _ladder_min_elems, _two_pass_mode

    platform = mesh.devices.flat[0].platform
    # the compact-path env knobs resolve HERE so they are part of the
    # cache key (the jitted_kernel convention) — flipping them between
    # calls must never hit a stale cached mesh program
    return _distributed_kernel_cached(kernel_plan, bucket, mesh, n_cols,
                                      n_params, slots_cap, family,
                                      xfer_compact,
                                      cpu_scatter_default(platform),
                                      _two_pass_mode(),
                                      _ladder_min_elems())


def _fold(name: str, v: jax.Array) -> jax.Array:
    """Per-local-segment outputs (L, ...) -> this device's partial."""
    op = _reduce_op(name)
    if op == "sum":
        return v.sum(axis=0)
    return v.min(axis=0) if op == "min" else v.max(axis=0)  # max, 'or'


def lists_live_groups_sparse(kernel_plan, family: str, xfer_compact: bool,
                             scatter: bool) -> bool:
    """Whether a mesh program lists the live groups of its combined
    result from the devices' own sparse rows (_gather_live_groups) and
    not by a nonzero over the dense group space (_compact_group_xfer):
    exactly when the per-device kernel hands its groups over sparse. The
    program's rule, and the host's where it counts which of the two a
    transfer-compacted result took (mesh_live_list_sparse / _dense in
    DistributedTable._run), so the counter cannot fork from the branch
    the device took."""
    return (family != ph.MESH_DENSE
            and takes_sparse_post(kernel_plan, xfer_compact, scatter))


def _densify(out: Dict[str, jax.Array], space: int) -> Dict[str, jax.Array]:
    """Sparse group outputs — (group_idx, value) rows as the sorted core's
    sparse post emits them, of one kernel call or stacked over the local
    segments — scattered into this device's dense (space,) partial, which
    the positional collectives can combine. A sentinel row (group_idx ==
    space) falls outside and is dropped. The ids themselves are not lost
    with it: _gather_live_groups lists the combined result's live groups
    from them after the collectives."""
    from ..ops.kernels import _extreme
    idx = out["group_idx"].reshape(-1)
    dense = {"group_overflow": out["group_overflow"].sum()}
    for k, v in out.items():
        if k in ("group_idx", "group_overflow"):
            continue
        if v.shape != out["group_idx"].shape:     # matched, overflow
            dense[k] = v.sum(axis=0) if v.ndim else v
            continue
        op = _reduce_op(k)
        fill = 0 if op == "sum" else _extreme(
            v.dtype, 1 if op == "min" else -1)
        at = jnp.full(space, fill, v.dtype).at[idx]
        combine = {"sum": at.add, "min": at.min}.get(op, at.max)
        dense[k] = combine(v.reshape(-1), mode="drop")
    return dense


@jax.named_scope(ph.SCOPE_XFER_COMPACT)
def _gather_live_groups(space: int, ids: jax.Array,
                        red: Dict[str, jax.Array]) -> None:
    """_compact_group_xfer's contract for a combined result whose
    devices emitted their groups sparse, at a cost that follows the ids
    and not the group space: ``ids`` are this device's rows of dense
    space ids (live groups first, the sentinel ``space`` behind them);
    the live groups of the combined result are exactly the union of every
    device's ids (a sparse row's id is live iff its count is positive),
    so the sorted distinct union IS nonzero(group_count > 0): ascending,
    padded with ``space`` to GROUP_XFER_CAP. Every device computes the
    same replicated list and gathers the dense combined outputs at it,
    sentinel rows zeroed; group_overflow flags more distinct ids than
    the list holds (the executor retries with xfer_compact=False)."""
    from ..ops.kernels import COUNT_OUTPUTS, GROUP_XFER_CAP
    ids = jnp.sort(jax.lax.all_gather(ids.reshape(-1), SEG_AXIS,
                                      tiled=True))
    repeat = jnp.concatenate(
        [jnp.zeros(1, jnp.bool_), ids[1:] == ids[:-1]])
    ids = jnp.where(repeat, jnp.int32(space), ids)
    # distinct ids first, ascending; the sentinel sorts behind them
    idx = jnp.sort(ids)[:GROUP_XFER_CAP]
    dense = [k for k in red if k not in COUNT_OUTPUTS]
    red["group_idx"] = idx
    red["group_overflow"] = (jnp.sum(ids < space, dtype=jnp.int32)
                             > GROUP_XFER_CAP).astype(jnp.int32)
    for k in dense:
        v = red[k]
        red[k] = jnp.where(idx < space, v.at[idx].get(mode="clip"),
                           jnp.zeros((), dtype=v.dtype))


@functools.lru_cache(maxsize=512)
def _distributed_kernel_cached(kernel_plan, bucket: int, mesh: Mesh,
                               n_cols: int, n_params: int,
                               slots_cap: Optional[int], family: str,
                               xfer_compact: bool, scatter: bool,
                               two_pass_mode: str = "auto",
                               ladder_min: int = 1 << 22):
    """jit(shard_map(kernel + collectives)) cached per plan/mesh/route,
    named pinot_<family> and staged like every other kernel program."""
    from ..ops.kernels import _compact_group_xfer
    from ..utils.compileplane import kernel_jit, staged

    # psum/pmin/pmax combine dense (space,) partials positionally across
    # shards. With ``xfer_compact`` a compact kernel may still emit its
    # groups sparse (the sorted core's sparse post: cost by compacted
    # rows, not by the space — q4.3's 1.75M groups); _densify scatters
    # them into the device's dense partial before the collectives, and
    # the combined result is gathered at its live groups for the
    # transfer: listed from the devices' own ids (_gather_live_groups),
    # so no pass over the space stands between the collectives and the
    # copy back. A kernel that emits dense groups over a large space
    # (the dense strategy, the scatter core) has no such ids and keeps
    # the one-chip _compact_group_xfer. platform pins the kernel
    # lowering to the mesh's backend (the driver's dryrun runs a CPU
    # mesh under a TPU process default).
    platform = mesh.devices.flat[0].platform
    compact = family != ph.MESH_DENSE
    sparse_list = lists_live_groups_sparse(kernel_plan, family,
                                           xfer_compact, scatter)

    def per_device(cols, n_docs, params):
        # cols: tuple of (L, bucket) local shards; n_docs: (L,)
        local_segs = n_docs.shape[0]
        kern = build_kernel(
            kernel_plan, bucket, slots_cap, platform,
            xfer_compact=xfer_compact and compact, scatter=scatter,
            local_segments=local_segs if family == ph.MESH_COMPACT else 1,
            two_pass_mode=two_pass_mode, ladder_min=ladder_min)
        if family == ph.MESH_COMPACT:
            # flatten local segments into one row axis: shared table
            # dictionaries make params segment-agnostic, so one Pallas
            # compaction + group pass serves the whole local shard
            flat = tuple(c.reshape(local_segs * bucket) for c in cols)
            local = kern(flat, n_docs, params)
        elif family == ph.MESH_COMPACT_PER_SEGMENT:
            # the routed sort core: one local segment at a time, the
            # body compiled once (a flattened shard over the row limit
            # is what XLA refuses: ops/kernels SEGMENTED_SORT_ROW_LIMIT)
            local = jax.lax.map(lambda cn: kern(cn[0], cn[1], params),
                                (cols, n_docs))
        else:
            local = over_segments(kernel_plan,
                                  lambda c, n: kern(c, n, params),
                                  cols, n_docs)
        # the sparse post's ids: (cap,) on the flattened route, (L, cap)
        # on the routed core
        ids = local.get("group_idx")
        if ids is not None:
            local = _densify(local, kernel_plan.group_space)
        elif family != ph.MESH_COMPACT:
            local = {k: _fold(k, v) for k, v in local.items()}
        red = {}
        with jax.named_scope(ph.SCOPE_COMBINE):
            for k, v in local.items():
                op = _reduce_op(k)
                if k in ("overflow", "group_overflow") or op == "sum":
                    red[k] = jax.lax.psum(v, SEG_AXIS)
                elif op == "min":
                    red[k] = jax.lax.pmin(v, SEG_AXIS)
                elif op == "max":
                    red[k] = jax.lax.pmax(v, SEG_AXIS)
                else:  # 'or' on bool presence
                    red[k] = jax.lax.pmax(
                        v.astype(jnp.int32), SEG_AXIS).astype(bool)
        if xfer_compact and kernel_plan.is_group_by:
            # live groups only over the wire to the host; a segment whose
            # sparse post overflowed counts with a result that does
            spilled = red.pop("group_overflow", 0)
            if sparse_list:
                _gather_live_groups(kernel_plan.group_space, ids, red)
            else:
                _compact_group_xfer(kernel_plan, red)
            if "group_overflow" in red:
                red["group_overflow"] = red["group_overflow"] + spilled
            else:
                red["group_overflow"] = jnp.asarray(spilled, jnp.int32)
        return red

    in_specs = (tuple(P(SEG_AXIS, None) for _ in range(n_cols)),
                P(SEG_AXIS),
                tuple(P() for _ in range(n_params)))
    mapped = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                           out_specs=P(), check_vma=False)
    key = ("mesh", kernel_plan, bucket, mesh, n_cols, n_params, slots_cap,
           family, xfer_compact, scatter, two_pass_mode, ladder_min)
    return staged(kernel_jit(mapped, family), "mesh_kernel", key)
