"""Batched segment execution: one device dispatch for many segments.

Reference parity: pinot-core/.../operator/combine/BaseCombineOperator
.java:83,99-117 — Pinot runs one task per segment on a thread pool and
merges. TPU-native: segments sharing a plan structure, bucket, and param
signature jit ONE vmapped kernel and launch ONCE — jax.vmap over the
stacked (n_segments, bucket) columns replaces the thread pool, and the
fixed per-execution dispatch cost is paid once per query instead of once
per segment. Per-segment partials are
sliced out of the stacked outputs host-side, so per-segment dictionaries
stay correct (unlike parallel/distributed.py, which requires shared
dictionaries in exchange for on-device psum combine).
"""
from __future__ import annotations

import functools
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.kernels import build_kernel, launch_forms, over_segments
from ..query.planner import CompiledPlan
from ..utils import phases as ph
from ..utils.devmem import global_device_memory
from ..utils.metrics import global_metrics
from ..utils.spans import (annotate, count_dispatch, device_fence, phase,
                           span)
from .executor import (GroupColumns, count_compact_steps,
                       execute_kernel_plans, execute_plan, extract,
                       param_sig, place_group_partials, resident_param,
                       resolve_params_host, stack_params)

# stack cache: ((segment uid, name) pairs, what, bucket) -> (stamp, tuple
# of stacked device arrays), where `what` is a plan's column names
# (_stacked_cols) or ("param", marker) for ONE segment-resident param
# (_stacked_resident) and `stamp` is what the entry was built from where
# that can change under a live segment (upsert validity versions; else
# None); bounded LRU since segment sets change under
# realtime. Keyed by the segments' process-unique LOAD uid, not the name:
# segment names recur across tables and across reloads at the same bucket,
# and a name-only key served the PREVIOUS table's device data to exact-
# looking queries (round-9 chaos-soak find). The name rides along only for
# evict_stacks_containing.
_STACK_CACHE: "OrderedDict[Tuple, Tuple[Any, Tuple[jax.Array, ...]]]" = \
    OrderedDict()
_STACK_CACHE_MAX = 32
# _cached_stack runs on broker pool / scheduler worker threads and
# evict_stacks_containing on the reload path: OrderedDict LRU mutation
# (move_to_end/popitem) is a multi-step linked-list relink that is NOT
# GIL-atomic (the segdir._CACHE_LOCK lesson; surfaced by concur CC201).
# The device-side stack BUILD stays outside the lock — a rare double
# build is benign (last insert wins), a corrupted LRU is not. The
# eviction epoch closes the build window: a stack built while an
# eviction ran may contain a just-evicted segment, and inserting it
# would resurrect device buffers the eviction claimed to free — such a
# build is returned uncached instead.
_STACK_LOCK = threading.Lock()
_EVICT_EPOCH = 0


def _seg_key(seg) -> Tuple[int, str]:
    # the uid is REQUIRED: an id() fallback would reintroduce the same
    # stale-data class via recycled addresses, because _STACK_CACHE
    # outlives the segment object (only ImmutableSegment reaches the
    # batched kernel path today — give any new segment type a uid)
    return (seg.uid, seg.name)


def _vmap_family(plan_struct) -> str:
    """The batched launch's kernel family: the scan strategy's own, the
    dense one's for every other plan that reaches it."""
    return ph.GROUP_SCAN if plan_struct.strategy == "scan" \
        else ph.DENSE_VMAP


@functools.lru_cache(maxsize=512)
def _vmapped_kernel_cached(plan_struct, bucket: int, scatter: bool):
    """One program over the stacked segments (ops/kernels.over_segments)."""
    from ..utils.compileplane import kernel_jit, staged
    kernel = build_kernel(plan_struct, bucket, scatter=scatter)

    def batched(cols, n_docs, params):
        return over_segments(plan_struct, kernel, cols, n_docs, params)
    return staged(kernel_jit(batched, _vmap_family(plan_struct)),
                  "vmap_kernel", ("vmap", plan_struct, bucket, scatter))


def _vmapped_kernel(plan_struct, bucket: int):
    from ..ops.kernels import cpu_scatter_default

    return _vmapped_kernel_cached(plan_struct, bucket,
                                  cpu_scatter_default())


def _cached_stack(key: Tuple, build, stamp: Any = None,
                  counters: Optional[Tuple[str, str]] = None
                  ) -> Tuple[jax.Array, ...]:
    """The stack under ``key`` (key[0] = the segments' _seg_key pairs),
    built by ``build()`` on a miss or when the cached one carries
    another ``stamp``. ``counters`` = (hits, builds) names to count."""
    with _STACK_LOCK:
        hit = _STACK_CACHE.get(key)
        if hit is not None and hit[0] == stamp:
            _STACK_CACHE.move_to_end(key)
            if counters:
                global_metrics.count(counters[0])
            return hit[1]
        epoch = _EVICT_EPOCH
    stack = build()
    if counters:
        global_metrics.count(counters[1])
    # a reload's superseded entry (same names, older uids) is left to
    # the 32-entry LRU: proactively deleting same-name entries would
    # make two LIVE tables with generic segment names evict each other's
    # stacks on every alternation
    with _STACK_LOCK:
        if _EVICT_EPOCH != epoch:
            # an eviction ran mid-build: this stack may include the
            # evicted segment — serve it to THIS query but never cache
            return stack
        _STACK_CACHE[key] = (stamp, stack)
        _STACK_CACHE.move_to_end(key)  # a re-stamped entry is fresh
        # device-memory telemetry: the stack cache is an HBM resident
        # the tiered store manages (utils/devmem, /debug/memory)
        global_device_memory.add("stack_cache", key,
                                 sum(int(c.nbytes) for c in stack))
        while len(_STACK_CACHE) > _STACK_CACHE_MAX:
            old_key, _old = _STACK_CACHE.popitem(last=False)
            global_device_memory.remove("stack_cache", old_key)
    # shared-budget admission (engine/tier.py), OUTSIDE _STACK_LOCK
    # (the demotion path re-enters evict_stacks_containing): a stack
    # insert can push HBM over budget — demote the coldest segments
    # outside this group's working set
    from .tier import global_tier
    global_tier.enforce(protect={u for u, _n in key[0]})
    return stack


def _stacked_cols(plans: List[CompiledPlan], bucket: int
                  ) -> Tuple[jax.Array, ...]:
    key = (tuple(_seg_key(p.segment) for p in plans),
           tuple(plans[0].col_names), bucket)
    return _cached_stack(key, lambda: tuple(
        jnp.stack([p.segment.device_col(c, bucket) for p in plans])
        for c in plans[0].col_names))


def _stacked_resident(plans: List[CompiledPlan], marker: Tuple[str, Any],
                      bucket: int) -> jax.Array:
    """One segment-resident param (executor.RESIDENT_PARAMS) stacked over
    the group's segments, kept beside the column stacks: it does not
    depend on the statement, so a repeat of the group is a lookup. An
    upsert table's validity masks change under the same segments: their
    versions stamp the entry, and a newer version replaces it."""
    key = (tuple(_seg_key(p.segment) for p in plans),
           ("param",) + tuple(marker), bucket)
    stamp = tuple(p.segment.valid_docs_version for p in plans) \
        if marker[0] == "validdocs" else None
    return _cached_stack(
        key,
        lambda: (jnp.stack([resident_param(p.segment, marker)
                            for p in plans]),),
        stamp, ("param_stack_hits", "param_stack_builds"))[0]


def evict_stacks_containing(segment_name: str) -> None:
    """Drop stacked copies that include a segment (called from
    ImmutableSegment.evict_device so eviction actually frees HBM)."""
    global _EVICT_EPOCH
    with _STACK_LOCK:
        _EVICT_EPOCH += 1
        for key in [k for k in _STACK_CACHE
                    if any(n == segment_name for _, n in k[0])]:
            del _STACK_CACHE[key]
            global_device_memory.remove("stack_cache", key)


def clear_stack_cache() -> None:
    """Drop every stacked entry AND its device-memory accounting in
    one locked step (test isolation; not an eviction — no counters)."""
    global _EVICT_EPOCH
    with _STACK_LOCK:
        _EVICT_EPOCH += 1
        _STACK_CACHE.clear()
        global_device_memory.drop_pool("stack_cache")


def execute_plans_batched(plans: List[CompiledPlan],
                          stop: Optional[int] = None) -> List[Any]:
    """Execute all plans; kernel plans with matching structure run in one
    vmapped dispatch, the ones that run one program a segment in one
    launch window (executor.execute_kernel_plans). Returns partials in
    input order, a statement's kernel group-by segments combined into
    one at the first of them and an empty one at each other
    (executor.place_group_partials; ``stop`` is the index of the first
    plan that a partial the caller puts among them, a rollup's,
    precedes)."""
    results: List[Any] = [None] * len(plans)
    groups: Dict[Tuple, List[int]] = {}
    # plan indexes of the statement's per-segment route
    per_segment: List[int] = []
    # plan index -> its params in host form (executor.resolve_params_host):
    # the group key reads shapes and dtypes from it, a batched group
    # stacks it, the per-segment route takes it along — nothing reaches
    # the device before a launch does
    hosts: Dict[int, Tuple[Any, ...]] = {}
    # plan index -> its segment's slice of a batched launch's host
    # outputs: the statement's extraction below takes them all at once
    outputs: Dict[int, Dict[str, Any]] = {}

    from ..ops.kernels import segmented_compact_fits, segmented_compact_ok
    from .accounting import global_accountant
    kernel_plans: List[int] = []
    for i, plan in enumerate(plans):
        # preemption point between per-segment launches (the hot-loop
        # ThreadAccountantOps.sample analog): raises on kill/timeout
        global_accountant.sample()
        if plan.kind != "kernel":
            results[i] = execute_plan(plan)
        else:
            kernel_plans.append(i)
    # the kernel plans' host params and group keys: one leaf crossing a
    # statement, with nothing metered inside it
    with phase(ph.PARAMS_HOST, segments=len(kernel_plans)):
        for i in kernel_plans:
            plan = plans[i]
            kp = plan.kernel_plan
            # column shapes join the group key: same-plan segments can
            # differ in MV padded width (maxValues), and a stack needs
            # equal shapes
            shape_sig = tuple(
                getattr(plan.segment.columns[c], "max_values", None) or 0
                for c in plan.col_names)
            hosts[i] = resolve_params_host(plan)
            if kp.strategy == "compact":
                sv_only = all(getattr(plan.segment.columns[c],
                                      "single_value", True)
                              for c in plan.col_names)
                if segmented_compact_ok(kp) and sv_only:
                    # compact group-bys batch via the segmented kernel:
                    # the segment index becomes the leading group-key
                    # factor (ops/kernels.build_segmented_compact_kernel),
                    # replacing the per-segment launches the Pallas
                    # compaction forced
                    kind = "segc"
                else:
                    per_segment.append(i)
                    continue
            else:
                # dense and scan: one vmapped launch over the segments
                kind = "dense"
            key = (kind, kp, plan.segment.bucket,
                   param_sig(plan, hosts[i]) + shape_sig)
            groups.setdefault(key, []).append(i)

    from .ragged import global_batcher
    for (kind, plan_struct, bucket, sig), idxs in groups.items():
        global_accountant.sample()
        if global_batcher.enabled:
            # cross-query micro-batching (PR 8): offer this group to the
            # ragged admission queue — concurrent queries sharing the
            # plan structure fuse into one cube-contraction launch.
            # None means dispatch solo (reason counted/annotated).
            fused = global_batcher.submit(
                [plans[i] for i in idxs], [hosts[i] for i in idxs],
                bucket, (kind,) + sig)
            if fused is not None:
                for k, i in enumerate(idxs):
                    results[i] = fused[k]
                continue
        n_seg = len(idxs)
        if n_seg == 1 or (kind == "segc" and not segmented_compact_fits(
                plan_struct, bucket, n_seg)):
            per_segment.extend(idxs)
            continue
        group_plans = [plans[i] for i in idxs]
        if kind == "dense":
            from .pipeline import (execute_kernel_plans_pipelined,
                                   group_stack_bytes, hbm_budget_bytes)
            if group_stack_bytes(group_plans, bucket) > hbm_budget_bytes():
                # working set exceeds the HBM budget: stream segments
                # through the double-buffered pipeline instead of
                # staking everything resident (engine/pipeline.py)
                partials = execute_kernel_plans_pipelined(
                    plans, plan_struct, bucket, hosts, idxs)
                for k, i in enumerate(idxs):
                    results[i] = partials[k]
                continue
        # tier access hook BEFORE the stack build: a warm stack hit
        # never reaches device_col, so this is where the tier.evict
        # chaos point can force a mid-query demotion of a segment this
        # group is using (the build below then re-promotes it)
        from .tier import global_tier
        for p in group_plans:
            global_tier.on_access(p.segment)
        # pin the group's working set for the WHOLE dispatch (stack
        # build through extraction): a budget demotion triggered from
        # THIS thread — the group's own admissions, or a nested plan-
        # cache accumulator registration — must pick victims outside it
        # (engine/tier.py, anti-thrash)
        with global_tier.pinned({p.segment.uid for p in group_plans}):
            with phase(ph.DISPATCH_PREPARE, segments=n_seg):
                cols = _stacked_cols(group_plans, bucket)
                n_docs, params = stack_params(
                    [hosts[i] for i in idxs],
                    np.asarray(  # jaxlint: ok host-sync — host ints
                        [p.segment.n_docs for p in group_plans],
                        dtype=np.int32),
                    lambda m: _stacked_resident(group_plans, m, bucket))
            if kind == "segc":
                _run_segmented_compact(plans, idxs, plan_struct, bucket,
                                       cols, n_docs, params, outputs)
                continue
            with span("vmap_dispatch", segments=n_seg, bucket=bucket,
                      strategy=plan_struct.strategy):
                fn = _vmapped_kernel(plan_struct, bucket)
                count_dispatch(_vmap_family(plan_struct),
                               *launch_forms(plan_struct, params))
                with phase(ph.DEVICE_EXECUTE):
                    dev = fn(cols, n_docs, params)
                    device_fence(dev)
                with phase(ph.DEVICE_TRANSFER):
                    out = jax.device_get(dev)  # jaxlint: ok host-sync
                global_accountant.track_result(out)
                # per-segment slicing below runs on host numpy behind
                # the single fence above — host-sync [jaxlint baseline]
                spilled: List[int] = []
                for k, i in enumerate(idxs):
                    per_seg = {name: v[k] for name, v in out.items()}
                    if int(per_seg.pop("group_overflow", 0)):
                        spilled.append(i)
                    else:
                        outputs[i] = per_seg
                for i in spilled:
                    # this segment alone exceeded the transfer-
                    # compaction cap; rerun it solo, straight to dense
                    # outputs (the rerun crosses the boundaries again)
                    results[i] = execute_plan(plans[i], xfer_compact=False,
                                              host_params=hosts[i])
    # a group-by's segments come back in array form where more than one
    # kernel segment could meet in a combine
    columns = len(kernel_plans) > 1 and plans[kernel_plans[0]].ctx.is_group_by
    if per_segment:
        per_segment.sort()
        for i, partial in zip(per_segment, execute_kernel_plans(
                [plans[i] for i in per_segment],
                [hosts[i] for i in per_segment], columns=columns)):
            results[i] = partial
    if outputs or any(isinstance(r, GroupColumns) for r in results):
        # the statement's extraction: one crossing however many batched
        # launches answered it, and the combine of its group-by segments
        with phase(ph.EXTRACT_PARTIAL, segments=len(kernel_plans)):
            for i, out in outputs.items():
                results[i] = extract(plans[i], out, columns)
            place_group_partials(results, stop)
    return results


def _run_segmented_compact(plans, idxs, plan_struct, bucket, cols, n_docs,
                           params, outputs) -> None:
    """One device program for S same-plan compact group-by segments;
    slices the (S*space,) dense outputs apart, each segment's slice into
    ``outputs``."""
    from ..ops.compact import full_slots_cap
    from ..ops.kernels import jitted_segmented_compact
    from .accounting import global_accountant

    n_seg = len(idxs)
    # cost-model capacity scaled to the combined live rows of the fused
    # dispatch (ROADMAP: no heuristic default caps on segmented paths)
    from ..multistage.costs import scaled_compact_cap
    cap = scaled_compact_cap(plans[idxs[0]],
                             sum(plans[i].segment.n_docs for i in idxs))
    with span("segmented_compact_dispatch", segments=n_seg, bucket=bucket,
              strategy=plan_struct.strategy, slots_cap=cap,
              est_sel=plans[idxs[0]].est_selectivity):
        fn = jitted_segmented_compact(plan_struct, bucket, n_seg, cap)
        forms = launch_forms(plan_struct, params, segmented=True)
        out = _launch_segmented(fn, cols, n_docs, params, forms)
        # retry-ladder checks + slicing below read host numpy behind the
        # fence above — host-sync [jaxlint baseline]
        from ..ops.plan_cache import global_plan_cache
        if int(out.pop("overflow", 0)):
            global_metrics.count("compact_overflow_retries")
            cap = full_slots_cap(n_seg * bucket)
            # expected() bracket: the full-capacity recompile is a
            # deliberate retry, counted overflow_retry in the
            # compile-event taxonomy — never a retrace
            with span("overflow_retry", slots_cap=cap), \
                    global_plan_cache.detector.expected():
                fn = jitted_segmented_compact(plan_struct, bucket, n_seg,
                                              cap)
                out = _launch_segmented(fn, cols, n_docs, params, forms)
            out.pop("overflow", None)
            annotate(overflow_retry=True, slots_cap=cap)
        if int(out.pop("group_overflow", 0)):
            global_metrics.count("group_xfer_overflow_retries")
            with span("group_overflow_retry"), \
                    global_plan_cache.detector.expected():
                fn = jitted_segmented_compact(plan_struct, bucket, n_seg,
                                              cap, xfer_compact=False)
                out = _launch_segmented(fn, cols, n_docs, params, forms)
            out.pop("overflow", None)
            annotate(group_overflow_retry=True)
        global_accountant.track_result(out)
    space = plan_struct.group_space
    count_compact_steps(out)
    matched = out.pop("matched")
    gi = out.pop("group_idx", None)
    for k, i in enumerate(idxs):
        per_seg = {"matched": matched[k]}
        if gi is not None:
            # transfer-compacted: rows are live groups of the combined
            # S*space; this segment owns flat ids [k*space, (k+1)*space)
            rows = np.nonzero((gi >= k * space) & (gi < (k + 1) * space)
                              & (np.asarray(out["group_count"]) > 0))[0]
            per_seg["group_idx"] = np.asarray(gi)[rows] - k * space
            for name, v in out.items():
                per_seg[name] = np.asarray(v)[rows]
        else:
            for name, v in out.items():
                v = np.asarray(v)
                if v.ndim >= 1 and v.shape[0] == n_seg * space:
                    per_seg[name] = v.reshape(
                        (n_seg, space) + v.shape[1:])[k]
                else:
                    per_seg[name] = v
        outputs[i] = per_seg


def _launch_segmented(fn, cols, n_docs, params,
                      forms: Tuple[Tuple[int, int], Tuple[int, int]]
                      ) -> Dict[str, Any]:
    """One launch of the segmented compact program and its copy back;
    ``forms`` is its ops/kernels.launch_forms."""
    count_dispatch(ph.COMPACT_SEGMENTED, *forms)
    with phase(ph.DEVICE_EXECUTE):
        dev = fn(cols, n_docs, params)
        device_fence(dev)
    with phase(ph.DEVICE_TRANSFER):
        return jax.device_get(dev)  # jaxlint: ok host-sync
