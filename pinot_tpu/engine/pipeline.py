"""Pipelined (double-buffered) segment scan: overlap host->device
transfer of the next segment with compute on the current one.

Reference parity: SURVEY 2.9 "pipelined streaming" — the reference keeps
servers saturated by streaming blocks through operator chains on thread
pools (BaseCombineOperator workers + Netty streaming responses). On a
TPU the analogous overlap is the DMA/compute pipeline: JAX dispatch is
asynchronous, so enqueueing segment i+1's ``jax.device_put`` before
blocking on segment i's kernel lets the H2D copy ride the transfer
engine while the MXU works. This path exists for COLD scans whose
working set exceeds the HBM budget: the resident-cache path
(engine/batch.py) stacks everything in HBM and launches once, which is
faster but needs the data to fit; this one holds at most TWO segments'
columns in device memory at a time and streams the rest.

The router (execute_plans_batched) sends a same-structure kernel group
here when its stacked footprint exceeds ``hbm_budget_bytes()``.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..query.planner import CompiledPlan
from ..utils.phases import plan_family
from ..utils.spans import count_dispatch
from ..utils.stats import make_bump

# default budget: v5e has 16GB HBM; leave headroom for outputs/compile
_DEFAULT_BUDGET = 8 << 30

# observability: how many pipelined streams ran (tests + trace hooks);
# thread-safe — concurrent broker queries, tests assert exact counts
STATS = {"pipelined_groups": 0, "pipelined_segments": 0}
bump = make_bump(STATS)


def hbm_budget_bytes() -> int:
    """Resident-scan budget (PINOT_HBM_BUDGET_BYTES overrides; the
    reference sizes off-heap buffers from server config the same way)."""
    return int(os.environ.get("PINOT_HBM_BUDGET_BYTES", _DEFAULT_BUDGET))


def group_stack_bytes(plans: List[CompiledPlan], bucket: int) -> int:
    """Footprint of stacking this group's columns in HBM (what
    engine/batch.py would upload)."""
    total = 0
    for p in plans:
        for c in p.col_names:
            m = p.segment.columns[c]
            width = 1 if getattr(m, "single_value", True) else \
                (m.max_values or 1)
            # dict ids upload as int32; raw columns keep their dtype
            item = 4 if m.has_dict else np.dtype(m.fwd_dtype).itemsize
            total += bucket * width * item
    return total


def execute_kernel_plans_pipelined(plans: List[CompiledPlan],
                                   plan_struct, bucket: int,
                                   host_params: Dict[int, Tuple],
                                   idxs: List[int]) -> List[Any]:
    """Run same-structure kernel plans one segment at a time with the
    next segment's transfer in flight; returns partials in plans order.
    ``host_params``: plan index -> ``executor.resolve_params_host``
    (uploaded here: this path does stream).

    Double-buffer discipline: at any moment device memory holds the
    in-flight transfer (i+1) plus the executing segment (i); segment
    i-1's columns are dropped as soon as its kernel output is enqueued
    (jax frees the buffers when the last reference dies after the
    dependent computation completes).
    """
    from ..ops.kernels import jitted_kernel, launch_forms
    from .accounting import global_accountant
    from .executor import extract_partial, resolve_params

    fn = jitted_kernel(plan_struct, bucket)  # lru-cached jit: repeated
    # over-budget queries must not pay a fresh XLA compile per group
    family = plan_family(plan_struct)
    group = [plans[i] for i in idxs]
    params = [resolve_params(p, host=host_params[i])
              for p, i in zip(group, idxs)]
    # one signature group: every segment's dictionaries have one shape
    forms = launch_forms(plan_struct, params[0])

    def stage(k: int):
        seg = group[k].segment
        return tuple(jax.device_put(seg.host_col_padded(c, bucket))
                     for c in group[k].col_names)

    bump("pipelined_groups")
    results: List[Any] = []
    staged = stage(0)
    outs: List[Any] = []
    for k, plan in enumerate(group):
        global_accountant.sample()
        cur = staged
        # enqueue the NEXT transfer before compute: async dispatch lets
        # the H2D copy overlap this kernel on the transfer engine
        staged = stage(k + 1) if k + 1 < len(group) else None
        count_dispatch(family, *forms)
        out = fn(cur, jnp.int32(plan.segment.n_docs), params[k])
        outs.append(out)
        del cur  # last py-reference; freed once the kernel consumes it
        bump("pipelined_segments")
        if k >= 1:
            # bound in-flight work to the double buffer: resolve the
            # previous segment's output before enqueueing more
            # double-buffer resolution point — host-sync [jaxlint baseline]
            outs[k - 1] = jax.device_get(outs[k - 1])
    outs[-1] = jax.device_get(outs[-1])  # jaxlint: ok host-sync
    dense_fn = None
    for k, (plan, out) in enumerate(zip(group, outs)):
        out = {name: np.asarray(v)  # jaxlint: ok host-sync — host already
               for name, v in out.items()}
        global_accountant.track_result(out)
        if int(out.pop("group_overflow", 0)):
            # rerun this segment dense (no transfer compaction) WITHOUT
            # run_kernel: that path populates the persistent device cache,
            # which would make the over-budget working set resident —
            # exactly what this streaming path exists to avoid
            if dense_fn is None:
                dense_fn = jitted_kernel(plan_struct, bucket,
                                         xfer_compact=False)
            seg = plan.segment
            cols = tuple(jax.device_put(seg.host_col_padded(c, bucket))
                         for c in plan.col_names)
            from ..ops.plan_cache import global_plan_cache
            count_dispatch(family, *forms)
            with global_plan_cache.detector.expected():
                # a deliberate dense rerun (compile-event taxonomy:
                # overflow_retry, never a retrace)
                dense = jax.device_get(dense_fn(  # jaxlint: ok host-sync
                    cols, jnp.int32(seg.n_docs), params[k]))
            del cols
            dense.pop("group_overflow", None)
            global_accountant.track_result(dense)
            results.append(extract_partial(plan, dense))
        else:
            results.append(extract_partial(plan, out))
    return results
