"""Phase-level decomposition of the fused group-by kernels.

One implementation of the mask / fuse / compact / sort / aggregate /
transfer timing ladder, shared by tools/profile_compact.py (the CLI that
appends ``phase_profile`` ledger records) and EXPLAIN ANALYZE with
OPTION(profilePhases=true) (engine/executor.py attaches the phases as
child spans of the segment kernel span).

Each phase time is the amortized per-launch device time of a jitted
prefix of the kernel pipeline (bench.kernel_time convention: pipelined
launches amortize the fixed per-dispatch cost), so successive phases are
CUMULATIVE — ``t_compact_ms`` includes mask+fuse — and deltas attribute
the increments. ``t_transfer_ms`` is the full kernel minus the
no-transfer-compaction variant.

Re-running prefixes compiles extra XLA programs; this is a profiling
surface, never part of the untraced query path.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional

import numpy as np


def timeit(fn, *args, iters: int = 5) -> float:
    """Amortized per-launch seconds: warm once, then (t_{k+1}-t_1)/k so
    the fixed dispatch floor cancels (bench.kernel_time convention)."""
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    t_one = time.perf_counter() - t0
    t0 = time.perf_counter()
    outs = [fn(*args) for _ in range(iters + 1)]
    jax.block_until_ready(outs)
    t_k = time.perf_counter() - t0
    return max((t_k - t_one) / iters, 1e-9)


PHASE_KEYS = ("t_mask_ms", "t_fuse_ms", "t_compact_ms", "t_sort_ms",
              "t_aggregate_ms", "t_kernel_ms", "t_transfer_ms")


def profile_plan(plan, iters: int = 5) -> Dict[str, Any]:
    """Decompose a compiled 'kernel' plan's device time into phases.

    -> {strategy, space, est_selectivity, cost_trace, needs_sort,
        scatter_core, t_mask_ms, [compact-path: slots_cap, cap_rows,
        t_fuse_ms, t_compact_ms, [t_sort_ms], t_aggregate_ms, matched,
        measured_selectivity, n_valid_rows, overflow, inflation],
        t_kernel_ms, [t_transfer_ms]}
    """
    import jax
    import jax.numpy as jnp

    from ..engine.executor import resolve_params
    from . import kernels
    from .compact import compact, full_slots_cap
    from .kernels import (_needs_sort, _payload_columns,
                          cpu_scatter_default, jitted_kernel)

    seg = plan.segment
    kp = plan.kernel_plan
    bucket = seg.bucket
    n = np.int32(seg.n_docs)
    cols = seg.device_cols(plan.col_names)
    params = resolve_params(plan)

    res: Dict[str, Any] = {
        "strategy": kp.strategy,
        "space": kp.group_space if kp.is_group_by else 0,
        "n_cols": len(cols),
        "est_selectivity": plan.est_selectivity,
        "cost_trace": plan.strategy_trace,
        "needs_sort": _needs_sort(kp) if kp.is_group_by else None,
        "scatter_core": cpu_scatter_default(),
    }

    # phase 1: predicate mask only
    def mask_fn(cols, n, params):
        valid = jnp.arange(bucket, dtype=jnp.int32) < n
        return valid & kernels._eval_pred(kp.pred, cols, params, bucket)

    res["t_mask_ms"] = round(
        timeit(jax.jit(mask_fn), cols, n, params, iters=iters) * 1e3, 2)

    if kp.strategy == "compact":
        cap = plan.slots_cap or full_slots_cap(bucket)
        res["slots_cap"] = cap
        res["cap_rows"] = cap * 128

        # phase 2: + fused key/payload materialization
        def fuse_fn(cols, n, params):
            m = mask_fn(cols, n, params)
            m, keys = kernels._group_keys_sentinel(kp, m, cols, params)
            payloads, *_meta = _payload_columns(kp, m, cols, params)
            return (m, keys) + payloads

        res["t_fuse_ms"] = round(
            timeit(jax.jit(fuse_fn), cols, n, params, iters=iters) * 1e3,
            2)

        # phase 3: + one compaction of [key] + payloads
        def comp_fn(cols, n, params):
            m = mask_fn(cols, n, params)
            m, keys = kernels._group_keys_sentinel(kp, m, cols, params)
            payloads, *_meta = _payload_columns(kp, m, cols, params)
            return compact(m, (keys,) + payloads, cap)

        jcomp = jax.jit(comp_fn)
        res["t_compact_ms"] = round(
            timeit(jcomp, cols, n, params, iters=iters) * 1e3, 2)
        _v, ccols, n_valid, matched, overflow = jcomp(cols, n, params)
        res["matched"] = int(matched)
        res["measured_selectivity"] = round(
            int(matched) / max(int(seg.n_docs), 1), 8)
        res["n_valid_rows"] = int(n_valid)
        res["overflow"] = int(overflow)
        res["inflation"] = round(int(n_valid) / max(int(matched), 1), 2)

        if res["needs_sort"]:
            # phase 3b: + the sort-once pass over the compacted keys
            # (the sorted post's dominant O(n log n) step)
            def sort_fn(cols, n, params):
                _valid, ccols, *_rest = comp_fn(cols, n, params)
                return jnp.sort(ccols[0])

            res["t_sort_ms"] = round(
                timeit(jax.jit(sort_fn), cols, n, params,
                       iters=iters) * 1e3, 2)

        # phase 4: + post-aggregation (full kernel minus transfer
        # compaction)
        f_noxfer = jitted_kernel(kp, bucket, plan.slots_cap,
                                 xfer_compact=False)
        res["t_aggregate_ms"] = round(
            timeit(f_noxfer, cols, n, params, iters=iters) * 1e3, 2)

    # phase 5: full kernel (as shipped, with transfer compaction)
    ffull = jitted_kernel(kp, bucket, plan.slots_cap)
    res["t_kernel_ms"] = round(
        timeit(ffull, cols, n, params, iters=iters) * 1e3, 2)
    if "t_aggregate_ms" in res:
        res["t_transfer_ms"] = round(
            max(res["t_kernel_ms"] - res["t_aggregate_ms"], 0.0), 2)
    return res


def attach_phase_spans(prof: Dict[str, Any]) -> None:
    """Attach a profile's phase ladder to the current span as child
    event spans (EXPLAIN ANALYZE's OPTION(profilePhases=true) path).
    Cumulative ladder times are converted to per-phase increments."""
    from ..utils.spans import add_event

    if prof.get("t_aggregate_ms") is not None:   # compact decomposition
        ladder = [k for k in ("t_mask_ms", "t_fuse_ms", "t_compact_ms",
                              "t_sort_ms", "t_aggregate_ms")
                  if prof.get(k) is not None]
        prev = 0.0
        for k in ladder:
            cum = float(prof[k])
            add_event("phase_" + k[2:-3], max(cum - prev, 0.0),
                      cumulative_ms=cum)
            prev = cum
        add_event("phase_transfer", float(prof.get("t_transfer_ms", 0.0)))
        return
    # dense/one-hot kernels: mask, then the fused aggregate remainder
    mask_ms = float(prof.get("t_mask_ms", 0.0))
    kernel_ms = float(prof.get("t_kernel_ms", 0.0))
    add_event("phase_mask", mask_ms)
    add_event("phase_aggregate", max(kernel_ms - mask_ms, 0.0),
              cumulative_ms=kernel_ms)
