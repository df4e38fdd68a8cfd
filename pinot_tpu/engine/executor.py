"""Per-segment execution: run the compiled plan, extract mergeable partials.

Reference parity: pinot-core/.../query/executor/ServerQueryExecutorV1Impl
.java:134 + operator/combine/BaseCombineOperator.java:99-117. Pinot runs one
task per segment on a thread pool and merges; here each segment is one XLA
program launch (the device's internal parallelism replaces the thread pool)
and partial states come back as host numpy to merge at reduce. vmap over
same-bucket segment batches and on-device psum combine live in
parallel/distributed.py.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..ops import aggregations
from ..query.context import QueryContext
from ..query.sql import Star
from ..query.planner import AggBinding, CompiledPlan, SegmentPlanner
from ..segment.immutable import ImmutableSegment
from ..utils import phases as ph
from ..utils.metrics import global_metrics
from ..utils.spans import annotate, count_dispatch, phase, span
from . import host_eval

# segments answered by the host path (numpy, no kernel): declared at 0 so
# that a window in which nothing falls back still reads it
global_metrics.count("segments_host", 0)
# kernel group-by segments of a statement that entered a combine of two
# or more (place_group_partials), and those extracted as their own
# partial: declared at 0 as segments_host is
global_metrics.count("segments_combined", 0)
global_metrics.count("segments_extracted", 0)
# the top-groups route (run_topgroups): statements it answered, and the
# groups its launches found before the trim; declared at 0 as above
global_metrics.count("topgroups_statements", 0)
global_metrics.count("topgroups_live_groups", 0)


@dataclass
class AggPartial:
    states: List[Any]  # aligned with ctx.aggregations


@dataclass
class GroupByPartial:
    groups: Dict[Tuple, List[Any]]  # key values -> states per aggregation


@dataclass
class GroupColumns:
    """One segment's live groups in array form, the first stage of a
    group-by extraction: the decoded key values, one array a group
    column, and each aggregation's state as a tuple of arrays, one a
    part (AVG's sum and count are two), int64 where the part is integral
    and float64 where it is not. A state that is no number (a distinct
    set, a sketch) is its list of per-group states already, and leaves
    the segment uncombinable, as a null-aware plan does."""
    keys: List[np.ndarray]
    states: List[Any]
    kinds: List[str]          # base kind a state (ops/aggregations)
    combinable: bool

    @property
    def n(self) -> int:
        return len(self.keys[0])


@dataclass
class SelectionPartial:
    labels: List[str]
    rows: List[tuple]
    order_keys: List[tuple] = field(default_factory=list)


def empty_partial(ctx: QueryContext):
    if ctx.is_group_by:
        return GroupByPartial({})
    if ctx.is_aggregation:
        na = host_eval.null_aware(ctx)
        # with null handling, SUM over zero rows is null, not 0 (the merge
        # is null-absorbing, so any segment with rows still wins)
        return AggPartial([None if na and a.kind == "sum"
                           else aggregations.empty_state(a)
                           for a in ctx.aggregations])
    return SelectionPartial([], [])


class SegmentExecutor:
    """Plans + executes one query over one segment."""

    def __init__(self, segment: ImmutableSegment):
        self.segment = segment

    def execute(self, ctx: QueryContext):
        plan = SegmentPlanner(ctx, self.segment).plan()
        return execute_plan(plan)


def execute_segment(ctx: QueryContext, segment: ImmutableSegment):
    return SegmentExecutor(segment).execute(ctx)


def execute_plan(plan: CompiledPlan, xfer_compact: bool = True,
                 host_params: Optional[Tuple[Any, ...]] = None,
                 columns: bool = False):
    """``xfer_compact=False`` reruns a kernel plan straight to dense
    group outputs, ``host_params`` hands a kernel plan its already
    resolved host params (both: run_kernel); ``columns`` answers a
    kernel group-by with its GroupColumns, for a combine
    (place_group_partials)."""
    ctx, seg = plan.ctx, plan.segment
    if plan.kind == "pruned":
        if not ctx.is_aggregation and plan.select_names:
            return SelectionPartial(list(plan.select_names), [])
        return empty_partial(ctx)
    if plan.kind == "fast":
        return AggPartial(list(plan.fast_states))
    if plan.kind == "host":
        global_metrics.count("segments_host")
        with span("segment_host", segment=seg.name):
            if host_eval.null_aware(ctx):
                mask, _ = host_eval.eval_filter_3vl(ctx.filter, seg)
            else:
                mask = host_eval.eval_filter(ctx.filter, seg)
            vd = getattr(seg, "valid_docs", None)
            if vd is not None:
                from ..query.planner import _truthy
                if not _truthy(ctx.options.get("skipUpsert")):
                    mask = mask & vd[: seg.n_docs]
            if ctx.is_group_by:
                return GroupByPartial(
                    host_eval.host_group_by(ctx, seg, mask))
            if ctx.is_aggregation:
                return AggPartial(host_eval.host_aggregate(ctx, seg, mask))
            labels, rows, okeys = host_eval.host_selection(ctx, seg, mask)
            return SelectionPartial(labels, rows, okeys)
    if plan.kind == "topgroups":
        return run_topgroups([plan], None)
    if plan.kind == "kselect":
        out = run_select_kernel(plan)
        with phase(ph.EXTRACT_PARTIAL, segment=seg.name):
            return extract_select(plan, out)
    assert plan.kind == "kernel"
    out = run_kernel(plan, xfer_compact, host_params)
    with phase(ph.EXTRACT_PARTIAL, segment=seg.name):
        return extract(plan, out, columns)


def run_select_kernel(plan: CompiledPlan) -> Dict[str, np.ndarray]:
    from ..ops.kernels import jitted_select_kernel
    from ..utils.spans import device_fence
    seg = plan.segment
    with span("segment_kselect", segment=seg.name, bucket=seg.bucket):
        with phase(ph.DISPATCH_PREPARE):
            cols = seg.device_cols(plan.col_names)
            params = resolve_params(plan)
        fn = jitted_select_kernel(plan.select_plan, seg.bucket)
        count_dispatch(ph.SELECT_TOPK)
        with phase(ph.DEVICE_EXECUTE):
            out = fn(cols, np.int32(seg.n_docs), params)
            device_fence(out)
        with phase(ph.DEVICE_TRANSFER):
            host = jax.device_get(out)  # jaxlint: ok host-sync
        from .accounting import global_accountant
        global_accountant.track_result(host)
        return host


def run_topgroups(plans: List[CompiledPlan], top: Optional[int]
                  ) -> GroupByPartial:
    """ONE top-groups launch over a statement's segments of one server
    (ops/kernels.build_topgroups_kernel) and its partial: the ``top``
    groups first by the ORDER BY's count, or with ``top`` None every
    group, each key decoded through the server's dictionaries. Every
    plan shares one id space (TopGroupsPlan.space)."""
    from ..ops.kernels import jitted_topgroups, unpack_keys
    from ..utils.spans import device_fence
    from .tier import global_tier
    tgs = [p.topgroups for p in plans]
    segs = [p.segment for p in plans]
    columns = [tgs[0].space.column(k) for k in plans[0].group_cols]
    with span("topgroups_dispatch", segments=len(plans), top=top), \
            global_tier.pinned({s.uid for s in segs}):
        with phase(ph.DISPATCH_PREPARE, segments=len(plans)):
            cols = tuple(s.device_cols(p.col_names)
                         for s, p in zip(segs, plans))
            params = tuple(resolve_params(p) for p in plans)
            remaps = tuple(tuple(c.device_remap(s) for c in columns)
                           for s in segs)
            n_docs = np.asarray(  # jaxlint: ok host-sync — host ints
                [s.n_docs for s in segs], dtype=np.int32)
        fn = jitted_topgroups(tuple(t.pred for t in tgs),
                              tuple(t.key_cols for t in tgs),
                              tuple(s.bucket for s in segs), tgs[0].widths,
                              top, tgs[0].descending)
        count_dispatch(ph.TOPGROUPS)
        with phase(ph.DEVICE_EXECUTE):
            dev = fn(cols, n_docs, params, remaps)
            device_fence(dev)
        with phase(ph.DEVICE_TRANSFER):
            out = jax.device_get(dev)  # jaxlint: ok host-sync
    # host numpy from here on — host-sync [jaxlint baseline]
    live = int(out["live"])  # jaxlint: ok host-sync — host numpy
    global_metrics.count("topgroups_statements")
    global_metrics.count("topgroups_live_groups", live)
    annotate(live_groups=live)
    with phase(ph.EXTRACT_PARTIAL, segments=len(plans)):
        sel = np.nonzero(out["count"] > 0)[0]
        hi = out["hi"][sel] if "hi" in out else np.zeros(len(sel), np.int64)
        ids = unpack_keys(hi, out["lo"][sel], tgs[0].widths)
        keys = zip(*[c.decode(i) for c, i in zip(columns, ids)])
        n_aggs = len(plans[0].ctx.aggregations)
        return GroupByPartial({k: [c] * n_aggs for k, c in zip(
            keys, out["count"][sel].tolist())})


def extract_select(plan: CompiledPlan, out: Dict[str, np.ndarray]
                   ) -> "SelectionPartial":
    """Device top-k winners -> SelectionPartial (values resolved through
    the segment dictionaries; order keys resolved the same way so the
    broker's cross-segment merge compares values, not ids).

    host-sync [jaxlint baseline]: ``out`` is host numpy — the dispatch
    already fenced and device_got it; everything below is extraction."""
    seg, sp = plan.segment, plan.select_plan
    n = min(int(out["matched"]), sp.k)
    cols_vals: List[np.ndarray] = []
    for i, name in enumerate(plan.select_names):
        stored = np.asarray(out[f"sel_{i}"])[:n]
        d = seg.dictionary(name)
        cols_vals.append(d.values_for(stored) if d is not None else stored)
    rows = [tuple(_py(c[r]) for c in cols_vals) for r in range(n)]
    okeys_cols: List[np.ndarray] = []
    for j, (col, _d, card) in enumerate(sp.order):
        stored = np.asarray(out[f"ord_{j}"])[:n]
        name = plan.col_names[col]
        d = seg.dictionary(name)
        okeys_cols.append(d.values_for(stored) if d is not None else stored)
    okeys = [tuple(_py(c[r]) for c in okeys_cols) for r in range(n)]
    ctx = plan.ctx
    if any(isinstance(i, Star) for i in ctx.select_items):
        labels = list(plan.select_names)
    else:
        labels = list(ctx.labels)
    return SelectionPartial(labels, rows, okeys)


# planner param markers whose value is a device array the segment
# already caches: they do not depend on the statement. Every other param
# (a literal, a "docmask", a "hash64" table) is a value the host computes
# for this statement and uploads with the launch.
RESIDENT_PARAMS = ("dictvals", "nullmask", "validdocs")


def _marker(p: Any) -> Optional[str]:
    """The name of a symbolic planner param; None for a literal."""
    if isinstance(p, tuple) and len(p) == 2 and isinstance(p[0], str):
        return p[0]
    return None


def resolve_params_host(plan: CompiledPlan) -> Tuple[Any, ...]:
    """Planner params as far as the host takes them, with no transfer:
    a literal becomes the numpy array the device would hold (the dtype
    canonicalisation ``jax.device_put`` applies, so an int64 literal
    with x64 off still reads int32); a segment-resident marker
    (RESIDENT_PARAMS) stays the marker tuple. ``resolve_params`` and the
    batched dispatch (engine/batch.py) finish the job."""
    seg = plan.segment
    out: List[Any] = []
    for p in plan.params:
        m = _marker(p)
        if m in RESIDENT_PARAMS:
            out.append(p)
            continue
        if m == "hash64":
            # per-dict-id 64-bit hash table for sketch aggregations
            # (host _hash64 — md5 for strings — so device and host
            # sketches agree bit-for-bit)
            from ..ops.aggregations import _hash64
            x = _hash64(np.asarray(seg.dictionary(p[1]).values))
        elif m == "docmask":
            # index-predicate doc mask (TEXT_MATCH/JSON_MATCH/
            # VECTOR_SIMILARITY): pad to the segment bucket
            mask = np.asarray(p[1], dtype=bool)
            x = np.zeros(seg.bucket, dtype=bool)
            x[: len(mask)] = mask
        else:
            x = np.asarray(p)  # jaxlint: ok host-sync — planner literal
        out.append(x.astype(jax.dtypes.canonicalize_dtype(x.dtype),
                            copy=False))
    return tuple(out)


def resident_param(seg: ImmutableSegment, p: Tuple[str, Any],
                   sharding=None) -> jax.Array:
    """The device array behind a RESIDENT_PARAMS marker (a lookup in the
    segment's device cache; the first use uploads)."""
    if p[0] == "dictvals":
        return seg.device_dict_values(p[1], sharding=sharding)
    if p[0] == "nullmask":
        return seg.device_null_mask(p[1], sharding=sharding)
    assert p[0] == "validdocs", p
    return seg.device_valid_mask(sharding=sharding)


def param_sig(plan: CompiledPlan, host: Tuple[Any, ...]) -> Tuple:
    """((shape, dtype), ...) of the params as the device will hold them,
    read from the host form: what groups same-shaped launches
    (engine/batch.py) and keys the fused kernels (engine/ragged.py)
    without an upload."""
    seg = plan.segment
    sig = []
    for p in host:
        if not isinstance(p, tuple):
            sig.append((tuple(p.shape), str(p.dtype)))
        elif p[0] == "dictvals":
            sig.append(((len(seg.dictionary(p[1])),), str(
                jax.dtypes.canonicalize_dtype(
                    seg.columns[p[1]].data_type.np_dtype))))
        else:  # nullmask / validdocs: one flag a padded row
            sig.append(((seg.bucket,), "bool"))
    return tuple(sig)


def upload_params(arrays: List[np.ndarray], sharding=None) -> List[Any]:
    """THE hand-over of a launch's literal params to the device, once a
    launch (counter ``param_uploads``). On the default device the arrays
    go as they are: the compiled call transfers its numpy arguments
    itself, which the chip showed cheaper than one ``jax.device_put`` of
    the list in front of it (1.21 against 1.68 ms a synced launch of
    Q1's shapes on a v5e: PERF.md, PR 30). A ``sharding`` (the mesh path,
    ``resolve_params``) needs the placement said: one ``device_put`` of
    the list."""
    if not arrays:
        return []
    global_metrics.count("param_uploads")
    if sharding is None:
        return arrays
    return jax.device_put(arrays, sharding)


def stack_params(hosts: List[Tuple[Any, ...]], lead: np.ndarray,
                 resident_stack) -> Tuple[Any, Tuple[Any, ...]]:
    """(lead, params) of ONE launch over many plans of one signature,
    every param with a new leading axis over ``hosts`` (one
    ``resolve_params_host`` a plan). Literals are stacked on the host and
    reach the device together with ``lead`` (the launch's own per-plan
    host array: n_docs, a segment index) in ONE hand-over
    (``upload_params``); ``resident_stack(marker)`` supplies a
    segment-resident param stacked the same way. No eager jax operation
    runs here."""
    lead, *stacked = upload_params([lead] + [
        np.stack([h[j] for h in hosts])
        for j, p in enumerate(hosts[0]) if not isinstance(p, tuple)])
    literals = iter(stacked)
    return lead, tuple(
        resident_stack(p) if isinstance(p, tuple) else next(literals)
        for p in hosts[0])


def resolve_params(plan: CompiledPlan, sharding=None,
                   host: Optional[Tuple[Any, ...]] = None
                   ) -> Tuple[Any, ...]:
    """Materialize planner params as a kernel's ``params`` argument:
    symbolic markers hit the segment device cache; literal scalars/arrays
    (tiny) go up in one hand-over (``upload_params``: with the launch
    itself on the default device, so they are numpy until then).

    `sharding` pins placement (e.g. a mesh-replicated NamedSharding for the
    distributed path) so params never land on the default backend — required
    when the process default is a real TPU but the query runs on a CPU mesh.
    `host` is this plan's ``resolve_params_host`` where the caller already
    has it (engine/batch.py hands it down the per-segment route).
    """
    if host is None:
        host = resolve_params_host(plan)
    literals = iter(upload_params(
        [p for p in host if not isinstance(p, tuple)], sharding))
    return tuple(
        resident_param(plan.segment, p, sharding) if isinstance(p, tuple)
        else next(literals) for p in host)


@dataclass
class KernelFlight:
    """One segment's kernel between its launch and its collection
    (``launch_kernel`` -> ``finish_kernel``): what the retry ladder
    needs to run the segment again, and the device outputs owed one
    ``PlanCacheEntry.collect``."""
    plan: CompiledPlan
    xfer_compact: bool
    cols: Tuple[Any, ...]
    n: Any
    params: Tuple[Any, ...]
    entry: Any
    cap: Optional[int]
    out: Dict[str, Any]


def _count_launch(windowed: bool) -> None:
    """A plan-cache launch issued from a window of two or more segments
    (``plan_launch_windowed``), or issued and collected alone
    (``plan_launch_solo``: a one-segment group, every retry)."""
    global_metrics.count("plan_launch_windowed" if windowed
                         else "plan_launch_solo")


def launch_kernel(plan: CompiledPlan, xfer_compact: bool = True,
                  host_params: Optional[Tuple[Any, ...]] = None,
                  windowed: bool = False) -> KernelFlight:
    """The first half of ``run_kernel``: the segment's columns and
    params, its plan-cache entry, and the launch, which does not wait
    for the device."""
    from ..ops.plan_cache import global_plan_cache
    seg = plan.segment
    if host_params is None:
        # the per-segment route's own resolution (a compact plan that
        # engine/batch.py did not group): a leaf of its own, beside the
        # launch's preparation and not inside it
        with phase(ph.PARAMS_HOST):
            host_params = resolve_params_host(plan)
    with phase(ph.DISPATCH_PREPARE):
        cols = seg.device_cols(plan.col_names)
        params = resolve_params(plan, host=host_params)
    n = np.int32(seg.n_docs)
    cap = plan.slots_cap
    # drift_requantized: the compile at the measured-selectivity
    # capacity is a deliberate, counted recompile — never a retrace.
    # The cache brackets only the actual miss, so the warm
    # re-plannings of a drifted shape (hits) stay outside expected()
    # and genuine retraces remain visible.
    entry = global_plan_cache.entry(
        plan.kernel_plan, seg.bucket, cap, xfer_compact=xfer_compact,
        expected_compile=plan.drift_requantized)
    if plan.drift_requantized:
        annotate(drift_requantized=True)
    if entry.overflowed:
        # this capacity already overflowed for this plan: go straight
        # to the (already compiled) full-capacity kernel instead of
        # paying the doomed tight kernel plus the retry on every
        # execution
        from ..ops.compact import full_slots_cap
        cap = full_slots_cap(seg.bucket)
        with global_plan_cache.detector.expected():
            entry = global_plan_cache.entry(
                plan.kernel_plan, seg.bucket, cap,
                xfer_compact=xfer_compact)
        annotate(slots_cap=cap, known_overflow=True)
    _count_launch(windowed)
    return KernelFlight(plan, xfer_compact, cols, n, params, entry, cap,
                        entry.launch(cols, n, params))


def count_compact_steps(host: Dict[str, Any]) -> None:
    """Take the compactor's grid steps by form out of a collected
    result and count them (``compact_steps_narrow`` / ``_wide``), after
    any retry, so each route counts the launch that answered: here,
    engine/batch.py's segmented route and the mesh's collect. Host
    numpy; a result that did not compact carries neither."""
    from ..ops.kernels import COMPACT_STEP_OUTPUTS
    for name in COMPACT_STEP_OUTPUTS:
        n = host.pop(name, None)
        if n is not None:
            global_metrics.count(name, int(np.sum(n)))  # jaxlint: ok host-sync


def finish_kernel(flight: KernelFlight) -> Dict[str, np.ndarray]:
    """The second half of ``run_kernel``: block on this segment's host
    copy, then everything that reads its result — the measured
    selectivity, the retry ladder (each rung a solo blocking run), the
    sparse post's probe counters, the accounting fence."""
    from ..ops.plan_cache import global_plan_cache
    plan, xfer_compact = flight.plan, flight.xfer_compact
    cols, n, params = flight.cols, flight.n, flight.params
    entry, cap, seg = flight.entry, flight.cap, plan.segment

    def rerun(cap: Optional[int], xfer_compact: bool):
        _count_launch(False)
        return global_plan_cache.entry(
            plan.kernel_plan, seg.bucket, cap,
            xfer_compact=xfer_compact).run(cols, n, params)

    # everything below the collect fence is host numpy: the int()s of
    # the ladder read values that are already here
    host = entry.collect(flight.out)
    if "matched" in host:
        matched = int(host["matched"].sum())  # jaxlint: ok host-sync
        global_plan_cache.record_measured(
            plan.kernel_plan, seg.bucket, entry, matched, seg.n_docs,
            segment=seg, params=plan.params)
        annotate(matched=matched,
                 meas_sel=matched / max(seg.n_docs, 1))
    # chaos hook: force the overflow retry ladder on kernels that
    # report overflow (result-identical — the full-capacity rerun
    # recomputes the same answer; exercises the retry path + retrace
    # bracketing under test)
    from ..utils.faults import fault_fires
    forced_overflow = "overflow" in host and \
        fault_fires("device.overflow", key=seg.name)
    overflow = int(host.pop("overflow", 0))  # jaxlint: ok host-sync
    if overflow or forced_overflow:
        # compact-strategy capacity exceeded (the selectivity estimate
        # undershot): rerun with a capacity that cannot overflow
        from ..ops.compact import full_slots_cap
        entry.mark_overflowed()
        cap = full_slots_cap(seg.bucket)
        global_metrics.count("compact_overflow_retries")
        with span("overflow_retry", slots_cap=cap), \
                global_plan_cache.detector.expected():
            host = rerun(cap, xfer_compact)
        host.pop("overflow", None)
        annotate(overflow_retry=True, slots_cap=cap)
    if int(host.pop("group_overflow", 0)):  # jaxlint: ok host-sync
        # more live groups than the transfer-compaction cap: rerun
        # with dense (space,) outputs
        global_metrics.count("group_xfer_overflow_retries")
        with span("group_overflow_retry"), \
                global_plan_cache.detector.expected():
            host = rerun(cap, False)
        host.pop("overflow", None)
        annotate(group_overflow_retry=True)
    from ..ops.kernels import (cpu_scatter_default, sparse_post_probes,
                               takes_sparse_post)
    if "group_idx" in host and takes_sparse_post(
            plan.kernel_plan, xfer_compact, cpu_scatter_default()):
        # which rung of its probe ladder the sparse post's tail took:
        # the host holds group_idx, so it applies the kernel's rule
        n_live = int(np.count_nonzero(  # jaxlint: ok host-sync
            host["group_idx"] < plan.kernel_plan.group_space))
        global_metrics.count("sparse_post_results")
        global_metrics.count(
            f"sparse_post_probes_{sparse_post_probes(n_live)}")
    # the launch that answered, after its retries, as on the other routes
    count_compact_steps(host)
    from .accounting import global_accountant
    global_accountant.track_result(host)
    return host


def run_kernel(plan: CompiledPlan, xfer_compact: bool = True,
               host_params: Optional[Tuple[Any, ...]] = None
               ) -> Dict[str, np.ndarray]:
    """Execute the compiled kernel through the keyed plan cache
    (ops/plan_cache.py): one compiled XLA program per (plan, bucket,
    slots_cap, platform, flags), so repeated iterations of the same
    query never re-trace. No buffer is donated, so nothing has to wait
    for a host copy before the next launch (ops/plan_cache.py says
    why). This is the window of one segment: ``launch_kernel``, then
    ``finish_kernel`` (``execute_kernel_plans`` holds a statement's
    launches ahead of its first collection).

    The compact strategy's compaction capacity comes from the planner's
    cost model (CompiledPlan.slots_cap — selectivity-estimate-derived and
    quantized, hence a stable cache key); an underestimate reports
    overflow and retries once at full_slots_cap. xfer_compact=False goes
    straight to dense (space,) group outputs — used when the caller
    already knows the transfer compaction spilled (engine/batch.py's
    vmapped path). ``host_params`` is the plan's ``resolve_params_host``
    where engine/batch.py made it for its group key; called on its own
    the kernel resolves for itself."""
    from .tier import global_tier
    seg = plan.segment
    with span("segment_kernel", segment=seg.name, bucket=seg.bucket,
              strategy=plan.kernel_plan.strategy,
              est_sel=plan.est_selectivity, slots_cap=plan.slots_cap), \
            global_tier.pinned({seg.uid}):
        # pinned for the WHOLE solo execution: a budget enforcement
        # that a nested admission triggers on this thread must not
        # demote the very segment whose columns this query just
        # uploaded (engine/tier anti-thrash)
        return finish_kernel(launch_kernel(plan, xfer_compact,
                                           host_params))


def execute_kernel_plans(plans: List[CompiledPlan],
                         host_params: List[Optional[Tuple[Any, ...]]],
                         columns: bool = False) -> List[Any]:
    """``execute_plan`` for the kernel plans of one statement that run
    one program a segment (engine/batch.py's per-segment route), as a
    launch window: every segment's kernel is launched before the first
    is collected, and segment k's retry ladder and extraction run on
    the host while the later segments' kernels run on the device. No
    blocking copy sits between two kernels of the statement. Partials
    come back in input order, each what ``execute_plan`` returns (with
    ``columns``, a group-by's GroupColumns).

    The look-ahead is the whole statement: a queued launch holds its
    outputs and nothing else on the device (0.66 MB a segment of q3.2
    at 2^23 rows; a program's 69 MB of temporaries are the running
    program's alone: 128 launches queued read 84 MB more in use than
    none — PERF.md, PR 36), and 4 ahead was within 2 ms of 8 on every
    statement timed. A span tree nests and fences every launch
    (utils/spans.device_fence), so a traced statement runs its
    segments one by one, as one segment does."""
    from ..utils.spans import tracing_active
    from .accounting import global_accountant
    from .tier import global_tier
    if len(plans) < 2 or tracing_active():
        return [execute_plan(p, host_params=h, columns=columns)
                for p, h in zip(plans, host_params)]
    results: List[Any] = []
    flights: deque = deque()
    # pinned for as long as a launch of theirs is uncollected (the
    # reason is run_kernel's)
    with global_tier.pinned({p.segment.uid for p in plans}):
        for p, h in zip(plans, host_params):
            # preemption point between launches, and below between
            # collections: raises on kill/timeout, and the launches not
            # yet collected are dropped with their buffers
            global_accountant.sample()
            flights.append(launch_kernel(p, host_params=h, windowed=True))
        while flights:
            global_accountant.sample()
            flight = flights.popleft()
            host = finish_kernel(flight)
            with phase(ph.EXTRACT_PARTIAL,
                       segment=flight.plan.segment.name):
                results.append(extract(flight.plan, host, columns))
    return results


def extract(plan: CompiledPlan, out: Dict[str, np.ndarray],
            columns: bool = False):
    """``extract_partial``, or with ``columns`` a group-by's first
    stage alone (``group_columns``), which a combine takes."""
    if columns and plan.ctx.is_group_by:
        return group_columns(plan, out)
    return extract_partial(plan, out)


def extract_partial(plan: CompiledPlan, out: Dict[str, np.ndarray]):
    # host-sync [jaxlint baseline]: ``out`` is host numpy (run_kernel /
    # the batched dispatch device_got it behind one fence); extraction
    # and the _scalar_state/_group_state helpers below never touch
    # device values.
    ctx, seg = plan.ctx, plan.segment
    if not ctx.is_group_by:
        matched = int(out["matched"])
        na = host_eval.null_aware(ctx)
        states: List[Any] = []
        for b in plan.agg_bindings:
            states.append(_scalar_state(b, out, matched, seg, na))
        return AggPartial(states)
    return group_partial(group_columns(plan, out))


def group_columns(plan: CompiledPlan, out: Dict[str, np.ndarray]
                  ) -> GroupColumns:
    """The array stage of a group-by extraction: the segment's live
    groups (count > 0) as GroupColumns. Host numpy throughout."""
    seg = plan.segment
    gi = out.get("group_idx")
    gc = out["group_count"]
    if gi is not None:
        # device-compacted outputs: arrays are gathered non-empty rows,
        # gi holds their dense space ids (sentinel rows have count 0)
        sel = np.nonzero(gc > 0)[0]
        idxs = np.asarray(gi)[sel]  # jaxlint: ok host-sync — host numpy
    else:
        idxs = np.nonzero(gc > 0)[0]
        sel = idxs
    # decode dense cartesian keys -> per-key ids -> values
    key_cols: List[np.ndarray] = []
    rem = idxs.copy()
    decoders = plan.group_decoders or [
        ("dict", name, seg.columns[name].cardinality)
        for name in plan.group_cols]
    for dec in reversed(decoders):
        card = dec[-1]
        ids = rem % card
        rem = rem // card
        if dec[0] == "dict":
            key_cols.append(seg.dictionary(dec[1]).values_for(ids))
        else:  # ("int" | "double", lo, stride, card): expression keys
            # (YEAR(ts) an int, ROUND(x) a double, as the host answers)
            vals = dec[1] + ids.astype(np.int64) * dec[2]
            key_cols.append(vals.astype(np.float64) if dec[0] == "double"
                            else vals)
    key_cols.reverse()
    combinable = not host_eval.null_aware(plan.ctx)
    states: List[Any] = []
    for b in plan.agg_bindings:
        arrays = _state_arrays(b, out, sel)
        if arrays is None:
            combinable = False
            states.append(_group_state(b, out, sel, seg))
        else:
            states.append(arrays)
    return GroupColumns(key_cols, states,
                        [_kind(b) for b in plan.agg_bindings], combinable)


def group_partial(cols: GroupColumns) -> GroupByPartial:
    """The build stage: one segment's GroupByPartial from its
    GroupColumns (counter ``segments_extracted``)."""
    global_metrics.count("segments_extracted")
    return _build_groups(cols.keys, cols.states)


def _build_groups(keys: List[np.ndarray], states: List[Any]
                  ) -> GroupByPartial:
    """Key tuples and per-group state lists from whole columns
    (``tolist`` gives the Python scalars ``_py`` gives a cell)."""
    rows = list(zip(*[k.tolist() for k in keys]))
    cols: List[List[Any]] = []
    for s in states:
        if not isinstance(s, tuple):
            cols.append(s)
        elif len(s) == 2:   # AVG: (sum, count) pairs
            cols.append(list(zip(s[0].tolist(), s[1].tolist())))
        else:
            cols.append(s[0].tolist())
    if not cols:
        return GroupByPartial({k: [] for k in rows})
    return GroupByPartial(dict(zip(rows, map(list, zip(*cols)))))


def _state_arrays(b: AggBinding, out: Dict[str, np.ndarray],
                  sel: np.ndarray) -> Optional[Tuple[np.ndarray, ...]]:
    """A numeric state's parts at ``sel``, int64 where the binding is
    integral and float64 where it is not (the values ``int()`` and
    ``float()`` make of them); None for a state that is no number."""
    name = f"agg{b.index}_{_kind(b)}"
    k = _kind(b)
    if k == "count":
        # group COUNT is served by the kernel's shared count row
        parts = ((out["group_count"], True),)
    elif k in ("sum", "min", "max"):
        parts = ((out[name], b.integral),)
    elif k == "avg":
        parts = ((out[name + "_sum"], b.integral),
                 (out[name + "_cnt"], True))
    else:
        return None
    return tuple(
        np.asarray(arr)[sel].astype(  # jaxlint: ok host-sync — host numpy
            np.int64 if integral else np.float64, copy=False)
        for arr, integral in parts)


def combine_group_columns(forms: List[GroupColumns]
                          ) -> Optional[GroupByPartial]:
    """ONE GroupByPartial for several segments' GroupColumns of one
    statement (Pinot's GroupByCombineOperator, on the host in arrays),
    equal to what the broker's merge of their own partials in this
    order makes (engine/reduce.py), groups in the order it first meets
    them, every value alike to the bit: a group takes its first
    segment's states, and a later segment's are added to them in
    segment order, or kept where MIN/MAX's ``min(a, b)`` keeps ``a``.
    None where that cannot be promised: an uncombinable segment, a key
    column of another kind in another segment or a NaN key (the merge
    never unites NaN keys), a part of another dtype, or an integral
    sum that could leave int64 (largest value x segments >= 2^62)."""
    if not all(f.combinable for f in forms):
        return None
    live = [f for f in forms if f.n]
    if not live:
        return GroupByPartial({})
    keys: List[np.ndarray] = []
    for c in range(len(live[0].keys)):
        parts = [f.keys[c] for f in live]
        if len({p.dtype.kind for p in parts}) != 1:
            return None
        keys.append(np.concatenate(parts))
    codes = _key_codes(keys)
    if codes is None:
        return None
    _u, first, inverse = np.unique(codes, return_index=True,
                                   return_inverse=True)
    order = np.argsort(first, kind="stable")       # first-seen order
    rank = np.empty(len(order), dtype=np.int64)
    rank[order] = np.arange(len(order))
    gid = rank[inverse.ravel()]
    first_rows = first[order]
    later = np.ones(len(codes), dtype=bool)
    later[first_rows] = False
    bounds = np.cumsum([0] + [f.n for f in live])
    spans = [(int(lo), int(hi)) for lo, hi in zip(bounds[:-1], bounds[1:])]
    states: List[Any] = []
    for a, kind in enumerate(live[0].kinds):
        merged = []
        for p in range(len(live[0].states[a])):
            parts = [f.states[a][p] for f in live]
            if len({x.dtype for x in parts}) != 1:
                return None
            vals = np.concatenate(parts)
            acc = _merge_part(kind, vals, gid, first_rows, later, spans)
            if acc is None:
                return None
            merged.append(acc)
        states.append(tuple(merged))
    return _build_groups([k[first_rows] for k in keys], states)


def _key_codes(keys: List[np.ndarray]) -> Optional[np.ndarray]:
    """One int64 code a row, equal where every key column is equal as
    the merge's dict sees it: a column's dense codes (np.unique's
    inverse; a dict's for objects) joined mixed-radix, made dense again
    before the radix product could reach 2^62. None on a NaN key."""
    code: Optional[np.ndarray] = None
    space = 1
    for col in keys:
        if col.dtype.kind == "O":
            index: Dict[Any, int] = {}
            inv = np.fromiter((index.setdefault(v, len(index))
                               for v in col.tolist()),
                              dtype=np.int64, count=len(col))
            card = len(index)
        else:
            if col.dtype.kind in "fc" and np.isnan(col).any():
                return None
            uniq, inv = np.unique(col, return_inverse=True)
            inv, card = inv.ravel().astype(np.int64, copy=False), len(uniq)
        if code is None:
            code, space = inv, card
            continue
        if space * card >= 1 << 62:
            uniq, code = np.unique(code, return_inverse=True)
            code, space = code.ravel(), len(uniq)
        code, space = code * card + inv, space * card
    return code


def _merge_part(kind: str, vals: np.ndarray, gid: np.ndarray,
                first_rows: np.ndarray, later: np.ndarray,
                spans: List[Tuple[int, int]]) -> Optional[np.ndarray]:
    """One state part merged by group: ``merge_states`` of each
    segment's value into its group's, segment by segment."""
    acc = vals[first_rows]
    if kind in ("min", "max"):
        # min(a, b) is b where b < a, else a (max: b > a), NaN and
        # signed zeros included; np.minimum would differ on both
        wins = np.less if kind == "min" else np.greater
        for lo, hi in spans:
            m = later[lo:hi]
            g, v = gid[lo:hi][m], vals[lo:hi][m]
            cur = acc[g]
            acc[g] = np.where(wins(v, cur), v, cur)
        return acc
    bound = -(-(1 << 62) // len(spans))   # |value| x segments < 2^62
    if vals.dtype.kind == "i" and (vals.max() >= bound
                                   or vals.min() <= -bound):
        return None
    for lo, hi in spans:
        m = later[lo:hi]
        np.add.at(acc, gid[lo:hi][m], vals[lo:hi][m])
    return acc


def place_group_partials(results: List[Any],
                         stop: Optional[int] = None) -> None:
    """Turn the GroupColumns among a statement's ``results`` (one entry
    a plan, in plan order) into partials, in place. The kernel
    group-by segments ahead of the first partial that stays on its own
    with groups in it (a host-path segment, a spilled rerun, a fused or
    pipelined one, or ``stop``: where the caller puts a rollup's) are
    combined into ONE partial at the first of them, an empty one at
    each other (counter ``segments_combined``): the broker's merge then
    meets every group in the order and with the values it met them
    segment by segment. Every other segment is built on its own."""
    forms = [i for i, p in enumerate(results) if isinstance(p, GroupColumns)]
    if not forms:
        return
    firsts = [i for i, p in enumerate(results)
              if isinstance(p, GroupByPartial) and p.groups]
    if stop is not None:
        firsts.append(stop)
    stop = min(firsts, default=len(results))
    ahead = [i for i in forms if i < stop]
    combined = combine_group_columns([results[i] for i in ahead]) \
        if len(ahead) > 1 else None
    if combined is not None:
        global_metrics.count("segments_combined", len(ahead))
        results[ahead[0]] = combined
        for i in ahead[1:]:
            results[i] = GroupByPartial({})
    for i in forms:
        if isinstance(results[i], GroupColumns):
            results[i] = group_partial(results[i])


def _scalar_state(b: AggBinding, out: Dict[str, np.ndarray], matched: int,
                  seg: ImmutableSegment, na: bool = False) -> Any:
    name = f"agg{b.index}_{_kind(b)}"
    k = _kind(b)
    # null-aware plans emit the aggregation's own non-null row count
    # (AggSpec.null_param); all-null input finalizes SUM/MIN/MAX to null
    nnz = out.get(name + "_nnz")
    eff = int(nnz) if nnz is not None else matched
    if k == "count":
        return int(out[name])
    if k == "sum":
        # COUNTMV rides the sum state but keeps COUNT semantics: empty
        # input is 0, never null (round-4 fuzzer finding — the host path
        # and the SQL standard agree)
        if na and eff == 0 and b.agg.kind != "count_mv":
            return None
        v = out[name]
        return int(v) if b.integral else float(v)
    if k in ("min", "max"):
        if eff == 0:
            return None
        v = out[name]
        return int(v) if b.integral else float(v)
    if k == "avg":
        s = out[name + "_sum"]
        c = int(out[name + "_cnt"])
        return (int(s) if b.integral else float(s), c)
    if k == "distinct_count":
        present = out[name + "_present"]
        ids = np.nonzero(present)[0]
        vals = seg.dictionary(b.dict_col).values_for(ids)
        return set(_py(v) for v in vals)
    # device sketch partials -> host AggImpl state formats (the broker
    # reduce merges them through ops/aggregations like any host partial);
    # RAW forms share their base sketch's state (RawAgg delegates)
    k = {"raw_hll": "distinct_count_hll",
         "raw_theta": "distinct_count_theta",
         "percentile_raw_sketch": "percentile_sketch"}.get(k, k)
    if k == "distinct_count_hll":
        return _hll_registers(out[name + "_present"], b)[0]
    if k == "distinct_count_theta":
        h = np.asarray(out[name + "_hashes"]).astype(np.uint64)
        sent = np.uint64(0xFFFFFFFFFFFFFFFF)
        return [int(x) for x in h if x != sent]
    if k == "percentile_sketch":
        means = np.asarray(out[name + "_pc_mean"])
        ws = np.asarray(out[name + "_pc_w"])
        return [[float(m_), float(w_)]
                for m_, w_ in zip(means, ws) if w_ > 0]
    raise ValueError(k)


def _group_state(b: AggBinding, out: Dict[str, np.ndarray],
                 idxs: np.ndarray, seg: ImmutableSegment) -> List[Any]:
    """The per-group states of an aggregation whose state is no number
    (``_state_arrays`` takes the numeric ones)."""
    name = f"agg{b.index}_{_kind(b)}"
    k = _kind(b)
    if k == "distinct_count":
        present = out[name + "_present"][idxs]  # (n_groups, card)
        d = seg.dictionary(b.dict_col)
        res = []
        for row in present:
            ids = np.nonzero(row)[0]
            res.append(set(_py(v) for v in d.values_for(ids)))
        return res
    if k in ("distinct_count_hll", "raw_hll"):
        return _hll_registers(np.asarray(out[name + "_present"])[idxs], b)
    raise ValueError(k)


def _hll_registers(pm: np.ndarray, b: AggBinding) -> List[List[int]]:
    """(n?, m*R) presence bitmap(s) -> per-row HllAgg register lists,
    vectorized across groups (one reshape + two reductions)."""
    from ..ops.aggregations import HllAgg
    p = HllAgg(b.agg).log2m
    r_levels = 64 - p + 1
    pm = np.asarray(pm)
    if pm.ndim == 1:
        pm = pm[None, :]
    rr = pm.reshape(pm.shape[0], 1 << p, r_levels)
    ranks = np.arange(1, r_levels + 1, dtype=np.int64)
    regs = np.where(rr.any(axis=2), (rr * ranks).max(axis=2), 0)
    return [row.tolist() for row in regs]


def _kind(b: AggBinding) -> str:
    # MV kinds lower to their base kind's device states/names
    # (SUMMV -> agg<i>_sum etc.; ops/aggregations.MV_BASE_KIND)
    from ..ops.aggregations import base_kind
    return base_kind(b.agg.kind)


def _py(v: Any) -> Any:
    return v.item() if isinstance(v, np.generic) else v
