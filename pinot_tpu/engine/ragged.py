"""Cross-query micro-batching: one fused device dispatch for N queries.

PR 8 tentpole. Pinot serves thousands of small concurrent queries per
node and the engine paid one device dispatch per query — the plan cache
amortized compiles but not launches. This module sits between the
serving layer (engine/batch.execute_plans_batched) and the kernel
engine: a short-window admission queue (engine/scheduler.MicroBatchQueue
— the scheduler grown beyond FCFS/priority) collects in-flight queries
that share the exact plan structure the plan cache already keys
(ops/plan_cache: KernelPlan + bucket + param signature) plus
segment-stack compatibility from engine/batch, and fuses each group
into ONE ragged launch.

The fusion core borrows the variable-length packing idiom of *Ragged
Paged Attention* and the one-tensor-program-per-plan framing of *Query
Processing on Tensor Computation Runtimes* (PAPERS.md): queries sharing
a KernelPlan differ only in hoisted literal params, so

- ONE unmasked group-by over the union of predicate + group dimensions
  builds a literal-free **cube** per segment (cached device-resident in
  ops/plan_cache.global_cube_cache, keyed by segment uid);
- per-query literal params stack as a leading batch axis and each
  query's predicate is evaluated over the cube's id grid — a few
  thousand cells instead of millions of rows;
- per-query variable-length segment lists pack into a padded
  segment-id layout (items = (query, segment) pairs, pow2 ladder so
  shapes stay jit-cache-stable and zero-retrace after warmup);
- one contraction launch reduces masked cells per item, results unpack
  and extract per query through the ordinary extract_partial path, so
  fused digests are byte-identical to solo (exact integer sums only —
  float sums would reassociate and are never fused).

Fairness and admission: a query near its accountant deadline, or a
plan the cube cost model rejects, dispatches solo immediately — never
queue-blocked. The per-key ``estimate_ms()`` EWMA (the engine-side
analog of the adaptive instance selector's latency estimator) feeds
the deadline check.

**Cold means not ready now** (PR 33). A query never pays for a cube or
for a fused program: ``submit`` looks up what is resident at that moment.
If a (spec, segment) cube of the group is missing from
``global_cube_cache`` or, once the batch has formed (``_lead``), its
stacked cubes or the combine program of its (spec, segment count, padded
items, param signature) have not been made, the submission and every
member of that batch dispatch solo at once, counted
``solo_fallback_cold``, and the missing pieces go to ``_Background``: one
worker thread (builds are device work: they queue behind each other),
single-flight per (spec, segment uid) and per program key, attached to no
query's accountant and under no query's deadline. A program job makes the
shape's whole ladder (every pow2 padded count from two whole-table queries
up to ``max_batch`` of them or the cell budget), so which peers meet later
decides no compile. The background compile
goes through the same ``_KernelRegistry`` (``kernel_jit`` + ``StagedFn``)
as a foreground one would. The gauge ``cube_builds_pending`` counts jobs
queued or running, ``cube_builds_background`` and
``fused_compiles_background`` the jobs done, and ``wait_ready(timeout)``
blocks until the gauge is 0: a node's warm-up, the tests and the
benchmark's entry call it after a burst. A cube evicted with its segment
is rebuilt the same way, in the background. So every fused dispatch on a
query's thread is a warm one, and only those feed ``_record_ms``: one
slow first attempt can no longer send a shape solo for good.

Every query wraps its wait + dispatch in a
``ragged_dispatch`` span on its own thread (queue_wait_ms annotated)
so per-query wall attribution survives the fusion, and the accountant
carries batched/batch_size per query for the query_stats ledger.

ENABLED by default since round 16 (PINOT_MICROBATCH=0,
Broker(micro_batch=False) or configure() turn it off). Batching was
opt-in through rounds 13-15 because fused compositions depend on
arrival timing and the fault registry's process-global per-site hit
counters made chaos decisions composition-sensitive; utils/faults.py
now keys decision streams by (owning query id, site key), so a query's
same-seed fault stream is identical whether its peers fused, ran solo,
or interleaved arbitrarily — chaos soaks run with batching armed.
"""
from __future__ import annotations

import logging
import os
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import phases as ph
from ..utils.metrics import global_metrics
from ..utils.spans import (annotate, count_dispatch, device_fence, phase,
                           span)
from .scheduler import MicroBatchQueue

log = logging.getLogger(__name__)

# cost-model caps: the cube must stay small relative to the data it
# collapses, the per-item masked-cell work must stay bounded, and raw
# (no-dictionary) predicate columns only join as dims over a small
# metadata-bounded value span
CUBE_SPACE_LIMIT = 1 << 20
RAW_DIM_SPAN_CAP = 1 << 12
ITEM_CELL_BUDGET = 1 << 23          # pow2-padded items x cube_space
DEFAULT_WINDOW_MS = 4.0
DEFAULT_MAX_BATCH = 32

# why a submission dispatched solo instead of fusing (counted as
# solo_fallback_<reason>; a globally disabled batcher never reaches the
# admission path, so it is deliberately NOT a reason here)
_SOLO_REASONS = ("incompatible", "no_peers", "deadline",
                 "window_expired", "timeout", "leader_error", "cold")


@dataclass(frozen=True)
class CubeSpec:
    """Literal-free fusion recipe for one plan structure on one
    segment shape. Hashable — it keys the cube cache, the jitted
    builders, and the admission queue."""
    kp: Any                       # ops.ir.KernelPlan
    bucket: int
    n_cols: int
    # (col_idx, card, base, is_dict) in cube-key order: group dims
    # first (the plan's own arithmetic), then predicate-only dims
    dims: Tuple[Tuple[int, int, int, bool], ...]
    group_space: int              # G (1 for scalar aggregations)
    pred_space: int               # P
    cube_space: int               # G * P


def _value_param_indices(ve) -> Tuple[set, set]:
    """(dict-value param indices, other param indices) referenced by an
    aggregation value expression. Literal params inside agg values make
    the cube literal-DEPENDENT and therefore unshareable."""
    from ..ops.ir import Bin, Case, Col, Func, Lit, MvReduce
    dicts: set = set()
    other: set = set()

    def walk(e):
        if isinstance(e, Col):
            if e.dict_param is not None:
                dicts.add(e.dict_param)
        elif isinstance(e, MvReduce):
            if e.dict_param is not None:
                dicts.add(e.dict_param)
        elif isinstance(e, Lit):
            other.add(e.param)
        elif isinstance(e, Bin):
            walk(e.lhs)
            walk(e.rhs)
        elif isinstance(e, Func):
            for a in e.args:
                walk(a)
        elif isinstance(e, Case):
            other.add(-1)  # CASE may hide predicate params: ineligible
    walk(ve)
    return dicts, other


def _pred_fusable(p) -> bool:
    """Allowlist walk of the predicate IR: only node shapes the cube's
    grid evaluator has been vetted for may fuse. Anything else —
    MaskParam (per-row index-predicate masks), MvReduce/Case value
    shapes, or any FUTURE Pred/ValueExpr subclass — fails CLOSED, so
    new IR can never silently evaluate over a zero placeholder grid
    (the fail-open shape the Func/Case column-discovery fix patched)."""
    from ..ops.ir import (And, Cmp, EqId, FalseP, IdRange, InBitmap,
                          InSet, Not, Or, TrueP)

    def value_ok(ve) -> bool:
        from ..ops.ir import Bin, Col, Func, Lit
        if isinstance(ve, (Col, Lit)):
            return True
        if isinstance(ve, Bin):
            return value_ok(ve.lhs) and value_ok(ve.rhs)
        if isinstance(ve, Func):
            return all(value_ok(a) for a in ve.args)
        return False            # MvReduce needs (N, M) cols; Case and
        # unknown shapes are unvetted on the 1-D grid

    if isinstance(p, (TrueP, FalseP, EqId, IdRange, InSet, InBitmap)):
        return True
    if isinstance(p, Cmp):
        return value_ok(p.lhs)
    if isinstance(p, (And, Or)):
        return all(_pred_fusable(c) for c in p.children)
    if isinstance(p, Not):
        return _pred_fusable(p.child)
    return False


# (kernel plan, segment uid, x64 flag) -> derived (spec, reason): the
# derivation walks the plan IR + per-column segment metadata and runs
# on every submission, but both inputs are immutable per load uid (the
# cube cache's own invariant), so peers microseconds apart share it
_SPEC_MEMO: "OrderedDict[Tuple, Tuple[Optional[CubeSpec], str]]" = \
    OrderedDict()
_SPEC_MEMO_MAX = 512
_spec_lock = threading.Lock()


def cube_spec_for(plan) -> Tuple[Optional[CubeSpec], str]:
    """Derive the fusion recipe for a compiled kernel plan, or
    (None, reason) when the plan is ineligible. Eligibility is the
    cube cost model: every predicate column must be a bounded
    single-value dimension, aggregations must be exact under cell
    re-association (COUNT / integral SUM / AVG), and the cube must be
    small relative to the segment. Memoized by (plan, segment uid)."""
    kp = plan.kernel_plan
    uid = getattr(plan.segment, "uid", None)
    key = None
    if kp is not None and uid is not None:
        key = (kp, uid, bool(jax.config.jax_enable_x64))
        with _spec_lock:
            hit = _SPEC_MEMO.get(key)
            if hit is not None:
                _SPEC_MEMO.move_to_end(key)
                return hit
    out = _derive_cube_spec(plan)
    if key is not None:
        with _spec_lock:
            _SPEC_MEMO[key] = out
            _SPEC_MEMO.move_to_end(key)
            while len(_SPEC_MEMO) > _SPEC_MEMO_MAX:
                _SPEC_MEMO.popitem(last=False)
    return out


def _derive_cube_spec(plan) -> Tuple[Optional[CubeSpec], str]:
    from ..ops.kernels import _pred_col_indices
    kp = plan.kernel_plan
    if kp is None:
        return None, "incompatible"
    if kp.key_exprs:
        return None, "incompatible"          # expression group keys
    from ..ops.kernels import int_acc_dtype
    if int_acc_dtype() != jnp.int64:
        # cube cells accumulate int64 subtotals; with jax_enable_x64
        # off they would silently canonicalize to int32 and wrap —
        # the solo compact path errors LOUDLY on the same condition
        # (sum_carrier_dtype), so fusion must never mask it
        return None, "incompatible"
    for spec in kp.aggs:
        if spec.kind not in ("count", "sum", "avg"):
            return None, "incompatible"      # sketches / min-max / distinct
        if spec.kind in ("sum", "avg") and not spec.integral:
            return None, "incompatible"      # float sums reassociate
        if spec.null_param is not None:
            return None, "incompatible"      # null handling masks per agg
        if spec.value is not None:
            _dicts, other = _value_param_indices(spec.value)
            if other:
                return None, "incompatible"  # literal inside agg value
    if not _pred_fusable(kp.pred):
        return None, "incompatible"          # per-row mask semantics or
        # a node shape the grid evaluator was never vetted for — the
        # eligibility walk is allowlist-shaped so new IR fails CLOSED
    for p in plan.params:
        if isinstance(p, tuple) and len(p) == 2 and \
                p[0] in ("nullmask", "validdocs", "docmask", "hash64"):
            return None, "incompatible"      # per-row masks can't cube
    seg = plan.segment
    if getattr(seg, "uid", None) is None:
        return None, "incompatible"          # cache key contract
    group_cols = {ci for ci, _ in kp.group_keys}
    dims: List[Tuple[int, int, int, bool]] = [
        (ci, card, 0, True) for ci, card in kp.group_keys]
    pred_only = sorted(_pred_col_indices(kp.pred) - group_cols)
    pred_space = 1
    for ci in pred_only:
        if ci >= len(plan.col_names):
            return None, "incompatible"
        name = plan.col_names[ci]
        meta = seg.columns.get(name)
        if meta is None or not getattr(meta, "single_value", True):
            return None, "incompatible"      # MV predicate semantics
        if seg.dictionary(name) is not None:
            card, base, is_dict = int(meta.cardinality), 0, True
        else:
            lo, hi = getattr(meta, "min", None), getattr(meta, "max", None)
            if not isinstance(lo, int) or not isinstance(hi, int):
                return None, "incompatible"
            span = hi - lo + 1
            if span <= 0 or span > RAW_DIM_SPAN_CAP:
                return None, "incompatible"
            card, base, is_dict = span, lo, False
        if card <= 0:
            return None, "incompatible"
        dims.append((ci, card, base, is_dict))
        pred_space *= card
    from ..ops.kernels import GROUP_XFER_SPACE
    group_space = kp.group_space if kp.is_group_by else 1
    if group_space >= GROUP_XFER_SPACE:
        # the fused kernel emits dense [items, group_space] outputs;
        # at or past the engine's own sparse-transfer threshold the
        # solo path's (group_idx, value) contract moves orders of
        # magnitude fewer bytes than a fused dense transfer would
        return None, "incompatible"
    cube_space = group_space * pred_space
    if cube_space > CUBE_SPACE_LIMIT or cube_space > seg.bucket:
        return None, "incompatible"          # cube beats the scan only
        # when it is (much) smaller than the data it collapses
    return CubeSpec(kp=kp, bucket=seg.bucket, n_cols=len(plan.col_names),
                    dims=tuple(dims), group_space=group_space,
                    pred_space=pred_space, cube_space=cube_space), ""


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _dim_digits(spec: CubeSpec, cols) -> Tuple[jax.Array, jax.Array]:
    """(cube key [bucket], in-domain mask): the plan's own group-key
    Horner arithmetic extended by the predicate-only dims."""
    key = jnp.zeros((spec.bucket,), dtype=jnp.int32)
    ok = jnp.ones((spec.bucket,), dtype=jnp.bool_)
    for ci, card, base, _is_dict in spec.dims:
        digit = cols[ci].astype(jnp.int32) - jnp.int32(base)
        ok &= (digit >= 0) & (digit < card)
        key = key * jnp.int32(card) + digit
    return key, ok


def _grid_cols(spec: CubeSpec) -> Tuple[jax.Array, ...]:
    """Per-dim id/value arrays over the cube cells — the domain the
    per-query predicate masks evaluate on (pure iota arithmetic, traced
    inside the jitted combine kernel)."""
    idx = jnp.arange(spec.cube_space, dtype=jnp.int32)
    cols: List[Optional[jax.Array]] = [None] * spec.n_cols
    div = spec.cube_space
    for ci, card, base, _is_dict in spec.dims:
        div //= card
        cols[ci] = (idx // jnp.int32(div)) % jnp.int32(card) \
            + jnp.int32(base)
    zero = jnp.zeros((spec.cube_space,), dtype=jnp.int32)
    return tuple(zero if c is None else c for c in cols)


def _cube_jobs(spec: CubeSpec):
    """The deduped integral sum payload slots (ops/kernels
    _payload_columns contract, restricted to the cube-eligible kinds)."""
    jobs = []
    slots: Dict[Tuple, int] = {}
    for i, agg in enumerate(spec.kp.aggs):
        if agg.kind == "count":
            jobs.append((i, agg, None))
            continue
        key = (agg.value, agg.integral)
        slot = slots.setdefault(key, len(slots))
        jobs.append((i, agg, slot))
    return jobs, len(slots)


def build_cube_kernel(spec: CubeSpec):
    """fn(cols, n_docs, params) -> {"cnt": [cube] i64, "s<k>": [cube]
    i64}: the literal-free cube — one unmasked pass over the segment."""
    from ..ops.kernels import _eval_value

    jobs, n_slots = _cube_jobs(spec)
    slot_values = {}
    for _i, agg, slot in jobs:
        if slot is not None and slot not in slot_values:
            slot_values[slot] = agg.value

    @jax.named_scope(ph.SCOPE_CUBE_BUILD)
    def kernel(cols, n_docs, params):
        valid = jnp.arange(spec.bucket, dtype=jnp.int32) < n_docs
        key, ok = _dim_digits(spec, cols)
        keys_s = jnp.where(valid & ok, key, jnp.int32(spec.cube_space))
        nseg = spec.cube_space + 1
        out = {"cnt": jax.ops.segment_sum(
            (valid & ok).astype(jnp.int64), keys_s,
            num_segments=nseg)[: spec.cube_space]}
        for slot, ve in slot_values.items():
            v = _eval_value(ve, cols, params, promote=True)
            v = jnp.where(valid & ok, v.astype(jnp.int64), 0)
            out[f"s{slot}"] = jax.ops.segment_sum(
                v, keys_s, num_segments=nseg)[: spec.cube_space]
        return out

    return kernel


def build_cube_combine_kernel(spec: CubeSpec):
    """fn(cubes, seg_idx [N], params [N-stacked]) -> per-item outputs
    named exactly like the solo kernel's (matched / group_count /
    agg<i>_*), so extract_partial is oblivious to the fusion."""
    from ..ops.kernels import _agg_name, _eval_pred

    jobs, _n_slots = _cube_jobs(spec)
    G, P = spec.group_space, spec.pred_space
    grouped = spec.kp.is_group_by

    @jax.named_scope(ph.SCOPE_CUBE_COMBINE)
    def kernel(cubes, seg_idx, params):
        grid = _grid_cols(spec)

        def mask_one(ps):
            return _eval_pred(spec.kp.pred, grid, ps, spec.cube_space)

        masks = jax.vmap(mask_one)(params)            # [N, cube] bool
        n = masks.shape[0]

        def reduce_cells(cells):
            sel = jnp.where(masks, cells[seg_idx], 0)  # [N, cube] i64
            if grouped:
                return sel.reshape(n, G, P).sum(-1)    # [N, G]
            return sel.sum(-1)                         # [N]

        counts = reduce_cells(cubes["cnt"])
        out: Dict[str, jax.Array] = {}
        if grouped:
            out["group_count"] = counts
            out["matched"] = counts.sum(-1)
        else:
            out["matched"] = counts
        slot_sums: Dict[int, jax.Array] = {}
        for i, agg, slot in jobs:
            name = _agg_name(i, agg)
            if agg.kind == "count":
                if not grouped:
                    out[name] = counts
                continue  # grouped COUNT rides group_count
            s = slot_sums.get(slot)
            if s is None:
                s = reduce_cells(cubes[f"s{slot}"])
                slot_sums[slot] = s
            if agg.kind == "avg":
                out[name + "_sum"] = s
                out[name + "_cnt"] = counts
            else:
                out[name] = s
        return out

    return kernel


class _KernelRegistry:
    """Bounded jit cache for the cube builders/combiners. Every compile
    registers with the plan cache's RetraceDetector under the full
    shape key (spec, segment count, pow2 pad, param shapes): a
    RE-compile of a key already seen in an earlier query generation —
    an LRU eviction rebuild, a flipped knob — is flagged exactly like
    a plan-cache retrace. A key's FIRST-ever compile is warmup by the
    detector's own rule, and since PR 33 it is the background's: a
    query's thread only takes a program that ``ready`` hands out, so a
    run that wants every batch of its window fused visits the pow2 rungs
    during warm-up and waits (``RaggedBatcher.wait_ready``;
    benchmark/entries/served_http_dash.py does)."""

    def __init__(self, maxsize: int = 256):
        self._lock = threading.Lock()
        self._fns: "OrderedDict[Tuple, Any]" = OrderedDict()
        # keys the LRU dropped: their rebuild classifies as
        # lru_evict_rebuild in the compile-event taxonomy
        self._evicted: "OrderedDict[Tuple, bool]" = OrderedDict()
        # keys whose program has run once (compiled, or read back from
        # the persistent cache): the only ones a query's thread calls
        self._ready: set = set()
        self._maxsize = maxsize

    def get(self, key: Tuple, make, family: str = ph.RAGGED_FUSED):
        # the whole miss path stays under the lock so concurrent
        # leaders of one key can't double-build the wrapper; the
        # compile itself classifies + lands its compile_event at first
        # call (utils/compileplane.StagedFn, single-flight under the
        # wrapper's own lock). Cheap to hold: jax.jit() is lazy.
        from ..utils.compileplane import kernel_jit, staged
        with self._lock:
            fn = self._fns.get(key)
            if fn is not None:
                self._fns.move_to_end(key)
                return fn
            hints = None
            if key in self._evicted:
                del self._evicted[key]
                hints = {"evicted": True}
            fn = staged(kernel_jit(make(), family), "ragged", key,
                        hints=hints)
            self._fns[key] = fn
            while len(self._fns) > self._maxsize:
                old_key, _old = self._fns.popitem(last=False)
                self._evicted[old_key] = True
                self._ready.discard(old_key)
                while len(self._evicted) > 4 * self._maxsize:
                    self._evicted.popitem(last=False)
            return fn

    def ready(self, key: Tuple):
        """The program of ``key`` if it has been made (``mark_ready``),
        else None: a query's thread never compiles one."""
        with self._lock:
            if key not in self._ready:
                return None
            self._fns.move_to_end(key)
            return self._fns[key]

    def mark_ready(self, key: Tuple) -> None:
        with self._lock:
            if key in self._fns:
                self._ready.add(key)

    def clear(self):
        with self._lock:
            self._fns.clear()
            self._evicted.clear()
            self._ready.clear()


_kernels = _KernelRegistry()


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


# ---------------------------------------------------------------------------
# the batcher
# ---------------------------------------------------------------------------

def default_enabled() -> bool:
    """The process-default batching switch: ON unless PINOT_MICROBATCH=0
    (flipped from opt-in in round 16 — module docstring)."""
    return os.environ.get("PINOT_MICROBATCH") != "0"


class _Submission:
    __slots__ = ("plans", "hosts", "future", "query_id", "t0",
                 "n_items", "abandoned")

    def __init__(self, plans, hosts, query_id):
        self.plans = plans
        # per plan, executor.resolve_params_host: nothing is uploaded
        # for a query that ends up dispatching solo
        self.hosts = hosts
        self.future: "Future[Any]" = Future()
        self.query_id = query_id
        self.t0 = time.perf_counter()
        self.n_items = len(plans)
        # set by a follower that gave up waiting (deadline margin) and
        # re-dispatched solo: the leader must not report this query as
        # batched — its fused results were discarded
        self.abandoned = False


class _Packed:
    """One batch laid out for the combine program: items = (submission,
    plan, host params) in submission order, the unique segments' plans
    in first-seen order, the pow2-padded item count, and the registry
    key and launch arguments of the program at a padded count."""
    __slots__ = ("spec", "items", "seg_plans", "seg_order", "npad", "sig")

    def __init__(self, spec: CubeSpec, batch: List[_Submission]):
        from .executor import param_sig
        self.spec = spec
        self.items = [(sub, plan, params) for sub in batch
                      for plan, params in zip(sub.plans, sub.hosts)]
        self.seg_order: Dict[int, int] = {}
        self.seg_plans: List[Any] = []
        for _sub, plan, _p in self.items:
            if plan.segment.uid not in self.seg_order:
                self.seg_order[plan.segment.uid] = len(self.seg_plans)
                self.seg_plans.append(plan)
        self.npad = _pow2(len(self.items))
        self.sig = param_sig(self.items[0][1], self.items[0][2])

    def program_key(self, npad: Optional[int] = None) -> Tuple:
        return ("combine", self.spec, len(self.seg_plans),
                npad or self.npad, self.sig)

    def launch_args(self, stacked, npad: Optional[int] = None) -> Tuple:
        """(stacked cubes, seg_idx, params) of the program at ``npad``
        padded items (the batch's own count, or another rung of the
        ladder for the background): literals stacked on the host
        (executor.stack_params); pads repeat item 0 and are sliced off
        at unpack, so shapes stay cache-stable."""
        from .executor import resident_param, stack_params
        npad = npad or self.npad
        n = min(len(self.items), npad)
        padded = [self.items[k if k < n else 0] for k in range(npad)]
        seg_idx = np.asarray(  # jaxlint: ok host-sync — host ints
            [self.seg_order[plan.segment.uid] for _s, plan, _h in padded],
            dtype=np.int32)
        dev_seg_idx, params = stack_params(
            [hosts for _s, _plan, hosts in padded], seg_idx,
            lambda m: jnp.stack([resident_param(plan.segment, m)
                                 for _s, plan, _h in padded]))
        return stacked, dev_seg_idx, params


class _Background:
    """The builder of what a cold submission found missing: a FIFO and
    one worker thread (started at need, gone when the queue is empty),
    single-flight by job key. A job that raised is logged, counted
    ``cube_build_errors`` and not asked for again until ``clear``: its
    shape keeps dispatching solo."""

    def __init__(self):
        self._cond = threading.Condition()
        self._jobs: deque = deque()
        self._keys: set = set()          # queued or running
        self._failed: set = set()
        self._thread: Optional[threading.Thread] = None

    def request(self, key: Tuple, job) -> None:
        with self._cond:
            if key in self._keys or key in self._failed:
                return
            self._keys.add(key)
            self._jobs.append((key, job))
            global_metrics.gauge("cube_builds_pending", len(self._keys))
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, name="ragged-background", daemon=True)
                self._thread.start()

    def _run(self) -> None:
        while True:
            with self._cond:
                if not self._jobs:
                    self._thread = None
                    return
                key, job = self._jobs.popleft()
            failed = True
            try:
                job()
                failed = False
            except Exception:  # noqa: BLE001 — no query waits on this
                global_metrics.count("cube_build_errors")
                log.exception("background build %r failed", key[0])
            finally:
                with self._cond:
                    self._keys.discard(key)
                    if failed:
                        self._failed.add(key)
                    global_metrics.gauge("cube_builds_pending",
                                         len(self._keys))
                    self._cond.notify_all()

    def wait_idle(self, timeout: Optional[float]) -> bool:
        with self._cond:
            return self._cond.wait_for(lambda: not self._keys, timeout)

    def clear(self) -> None:
        with self._cond:
            self._failed.clear()


class RaggedBatcher:
    """The cross-query micro-batching dispatcher (module docstring)."""

    def __init__(self, window_ms: float = DEFAULT_WINDOW_MS,
                 max_batch: int = DEFAULT_MAX_BATCH,
                 enabled: Optional[bool] = None):
        self.window_ms = window_ms
        # overload degradation (broker/workload.OverloadGovernor): at
        # rung >= 1 the governor widens the admission window by this
        # factor — fewer, fuller fused launches while the cluster sheds
        # speculative work (reset to 1.0 when pressure clears)
        self.window_scale = 1.0
        self.max_batch = max_batch
        self.enabled = (default_enabled()
                        if enabled is None else bool(enabled))
        self.queue = MicroBatchQueue()
        self._lock = threading.Lock()
        self._est_ms: Dict[Any, float] = {}
        self._background = _Background()

    def configure(self, enabled: Optional[bool] = None,
                  window_ms: Optional[float] = None,
                  max_batch: Optional[int] = None) -> "RaggedBatcher":
        if enabled is not None:
            self.enabled = bool(enabled)
        if window_ms is not None:
            self.window_ms = float(window_ms)
        if max_batch is not None:
            self.max_batch = int(max_batch)
        return self

    def wait_ready(self, timeout: Optional[float] = None) -> bool:
        """Block until no background build or compile is queued or
        running (the gauge ``cube_builds_pending`` is 0); False if
        ``timeout`` seconds passed first."""
        return self._background.wait_idle(timeout)

    # -- admission ---------------------------------------------------------
    def estimate_ms(self, key: Any) -> Optional[float]:
        """EWMA of fused-dispatch wall ms for a compatibility key (the
        adaptive selector's estimate_ms analog, keyed by plan shape)."""
        with self._lock:
            return self._est_ms.get(key)

    def _record_ms(self, key: Any, ms: float) -> None:
        # fed by _lead after a fused dispatch, which is a warm one by
        # construction: a cold batch never reaches _execute_fused
        with self._lock:
            prev = self._est_ms.get(key)
            self._est_ms[key] = ms if prev is None \
                else 0.7 * prev + 0.3 * ms
            if len(self._est_ms) > 512:
                self._est_ms.pop(next(iter(self._est_ms)))

    @staticmethod
    def _solo(reason: str) -> None:
        global_metrics.count(f"solo_fallback_{reason}")
        annotate(batched=False, solo_reason=reason)
        return None

    def submit(self, plans: List[Any], hosts: List[Tuple],
               bucket: int, group_sig: Tuple) -> Optional[List[Any]]:
        """Try to fuse one query's compatible kernel-plan group (its
        params in host form, one tuple a plan) with
        concurrent peers. Returns per-plan partials, or None — the
        caller then runs the ordinary solo dispatch (reason counted in
        solo_fallback_* and annotated on the span). Never queue-blocks
        a query that should dispatch solo: ineligible, peer-less, cold
        and deadline-pressured queries bail before enqueueing."""
        if not self.enabled:
            return None
        from .accounting import global_accountant
        # a lone query never waits the window: admission only batches
        # when there is concurrent demand — checked FIRST because it is
        # the common low-concurrency hot path and costs one lock, while
        # spec derivation below walks the plan IR and segment metadata
        if len(global_accountant.running()) < 2:
            return self._solo("no_peers")
        spec, _why = cube_spec_for(plans[0])
        if spec is None:
            return self._solo("incompatible")
        # the budget bounds what the kernel EXECUTES — the pow2-padded
        # item count, not the raw one (pad rows do real work) — and it
        # has to hold this group twice, or no peer like it could ever
        # join: such a query neither waits the window nor has cubes built
        if _pow2(2 * len(plans)) * spec.cube_space > ITEM_CELL_BUDGET:
            return self._solo("incompatible")
        # dim cardinalities are segment state (dictionaries differ per
        # segment): every segment in this group must derive the same
        # spec or the shared grid would mis-decode its ids
        by_uid = {plans[0].segment.uid: plans[0]}
        for plan in plans[1:]:
            if plan.segment.uid in by_uid:
                continue
            by_uid[plan.segment.uid] = plan
            other, _w = cube_spec_for(plan)
            if other != spec:
                return self._solo("incompatible")
        # what is not resident NOW is built behind the query, never by it
        from ..ops.plan_cache import global_cube_cache
        missing = global_cube_cache.missing(
            spec, [p.segment for p in by_uid.values()])
        if missing:
            for uid in missing:
                self._background.request(
                    ("cube", spec, uid),
                    lambda p=by_uid[uid]: self._cube_job(spec, p))
            return self._solo("cold")
        qid = global_accountant.current_query_id()
        key = (spec, bucket, group_sig)
        window_ms = self.window_ms * self.window_scale
        usage = global_accountant.usage(qid) if qid else None
        if usage is not None and usage.deadline is not None:
            rem_ms = (usage.deadline - time.perf_counter()) * 1e3
            est = self.estimate_ms(key) or window_ms
            if rem_ms < window_ms + 2.0 * est:
                return self._solo("deadline")
        sub = _Submission(plans, hosts, qid)
        # weight cap = largest pow2 <= the budgeted item count, so the
        # PADDED batch still fits ITEM_CELL_BUDGET on device
        budget_items = max(ITEM_CELL_BUDGET // max(spec.cube_space, 1), 1)
        max_weight = 1 << max(budget_items.bit_length() - 1, 0)
        with span(ph.RAGGED_DISPATCH, bucket=bucket,
                  strategy=spec.kp.strategy):
            global_metrics.gauge("batch_queue_depth", self.queue.depth())
            with phase(ph.RAGGED_WAIT):
                batch = self.queue.offer(
                    key, sub, window_ms / 1e3, self.max_batch,
                    max_weight=max_weight, weight=sub.n_items)
            # re-read after the offer resolves so a drained queue
            # reports 0 instead of freezing at the last pre-offer value
            global_metrics.gauge("batch_queue_depth", self.queue.depth())
            if batch is None:
                return self._await_follower(sub, usage)
            if len(batch) == 1:
                # the window expired with no peers for this key
                annotate(queue_wait_ms=round(
                    (time.perf_counter() - sub.t0) * 1e3, 3))
                return self._solo("window_expired")
            return self._lead(key, spec, batch, sub)

    def _await_follower(self, sub: _Submission, usage) -> Optional[List]:
        from concurrent.futures import TimeoutError as FutTimeout
        timeout = 60.0
        if usage is not None and usage.deadline is not None:
            # reserve half the remaining budget for the solo fallback:
            # a stalled leader must not convert a servable query into
            # a guaranteed deadline kill after the wait
            rem = usage.deadline - time.perf_counter()
            timeout = max(min(rem * 0.5, 60.0), 0.05)
        try:
            with phase(ph.RAGGED_WAIT):
                result = sub.future.result(timeout=timeout)
        except FutTimeout:
            # abandon BEFORE the last-chance re-check: either the
            # leader already set the result (use it — nothing was
            # wasted) or it sees the flag and skips this query's
            # batched accounting. A leader reading the flag in the same
            # instant may still count one abandoned query as batched —
            # an accepted, annotated-in-review race, not a hang.
            sub.abandoned = True
            result = sub.future.result(0) if sub.future.done() else "timeout"
        except Exception:
            result = "leader_error"
        wait_ms = (time.perf_counter() - sub.t0) * 1e3
        if isinstance(result, str):
            # the leader's word for why its batch answers solo: "cold"
            # (nothing was ready) or "leader_error"
            return self._solo(result)
        partials, batch_size, exec_ms = result
        annotate(batched=True, batch_size=batch_size,
                 queue_wait_ms=round(wait_ms - exec_ms, 3),
                 fused_share_ms=round(
                     exec_ms * sub.n_items / max(batch_size, 1), 3))
        return partials

    # -- fused execution (leader thread) -----------------------------------
    def _lead(self, key, spec: CubeSpec, batch: List[_Submission],
              own: _Submission) -> Optional[List]:
        from ..ops.plan_cache import global_cube_cache

        def send_solo(reason: str) -> None:
            for sub in batch:
                if sub is not own and not sub.future.done():
                    sub.future.set_result(reason)
            return self._solo(reason)

        t_exec = time.perf_counter()
        try:
            packed = _Packed(spec, batch)
            stacked = global_cube_cache.stacked_if_ready(
                spec, [p.segment for p in packed.seg_plans])
            fn = _kernels.ready(packed.program_key())
            if stacked is None or fn is None:
                # the cubes were evicted since submit looked, or this
                # batch's (segments, padded items) program has not been
                # made: the whole batch answers solo, now
                self._background.request(
                    packed.program_key(),
                    lambda: self._program_job(spec, packed))
                return send_solo("cold")
            results = self._execute_fused(spec, batch, packed, stacked, fn)
        except BaseException as e:  # noqa: BLE001 — followers must not hang
            global_metrics.count("fused_dispatch_errors")
            send_solo("leader_error")
            if isinstance(e, (KeyboardInterrupt, SystemExit)):
                raise
            return None
        exec_ms = (time.perf_counter() - t_exec) * 1e3
        self._record_ms(key, exec_ms)
        n_queries = len(batch)
        global_metrics.count("batched_dispatches")
        global_metrics.count("batched_queries", n_queries)
        global_metrics.count(_size_bucket(n_queries))
        from .accounting import global_accountant
        for sub in batch:
            # a follower that abandoned the wait (deadline margin) is
            # answering solo: its fused results are discarded, so it
            # must not be reported as batched
            if sub.query_id and not sub.abandoned:
                global_accountant.note_batched(sub.query_id, n_queries)
            if sub is not own:
                sub.future.set_result(
                    (results[id(sub)], n_queries, exec_ms))
        annotate(batched=True, batch_size=n_queries, leader=True,
                 fused_items=sum(s.n_items for s in batch),
                 queue_wait_ms=round(
                     (t_exec - own.t0) * 1e3, 3),
                 fused_ms=round(exec_ms, 3))
        return results[id(own)]

    def _execute_fused(self, spec: CubeSpec, batch: List[_Submission],
                       packed: _Packed, stacked, fn) -> Dict[int, List]:
        from .executor import extract_partial

        items, npad = packed.items, packed.npad
        n_items = len(items)
        with phase(ph.FUSED_EXECUTE, queries=len(batch), items=n_items,
                   padded=npad, segments=len(packed.seg_plans),
                   cube_space=spec.cube_space):
            with phase(ph.DISPATCH_PREPARE):
                args = packed.launch_args(stacked)
            count_dispatch(ph.RAGGED_FUSED)
            with phase(ph.DEVICE_EXECUTE):
                dev = fn(*args)
                device_fence(dev)
            with phase(ph.DEVICE_TRANSFER):
                host = jax.device_get(dev)  # jaxlint: ok host-sync
        from .accounting import global_accountant
        # memory accounting is apportioned per participant (outputs are
        # [npad, ...] so every item owns an equal slice): piling the
        # whole batch onto the leader's query would make the heap
        # watcher kill it for the followers' footprint
        total_bytes = sum(np.asarray(v).nbytes  # jaxlint: ok host-sync
                          for v in host.values())
        per_item = total_bytes // max(npad, 1)
        for sub in batch:
            if sub.query_id:
                global_accountant.track_memory_for(
                    sub.query_id, per_item * sub.n_items)
        # unpack + extract per item on host numpy behind the single
        # fence above — host-sync [jaxlint baseline]
        results: Dict[int, List[Any]] = {id(s): [] for s in batch}
        with phase(ph.EXTRACT_PARTIAL, items=n_items):
            for k, (sub, plan, _p) in enumerate(items):
                per_item = {name: v[k] for name, v in host.items()}
                results[id(sub)].append(extract_partial(plan, per_item))
        return results

    # -- the background's jobs (no query's thread runs these) --------------
    def _cube_job(self, spec: CubeSpec, plan) -> Dict[str, jax.Array]:
        from ..ops.plan_cache import global_cube_cache
        return global_cube_cache.entry(
            spec, plan.segment, lambda: self._build_cube(spec, plan))

    def _program_job(self, spec: CubeSpec, packed: _Packed) -> None:
        """Make a batch's stacked cubes and the combine programs of its
        ladder by running the batch once at each rung, the answers
        dropped: every pow2 padded count that whole-table queries over
        these segments can reach, from two queries up to ``max_batch``
        of them or the cell budget, so who meets whom later decides no
        compile. The first call of a rung compiles
        (utils/compileplane.StagedFn) or reads the persistent cache."""
        from ..ops.plan_cache import global_cube_cache
        cubes = [self._cube_job(spec, p) for p in packed.seg_plans]
        stacked = global_cube_cache.stacked(
            spec, [p.segment for p in packed.seg_plans], cubes)
        n = len(cubes)
        rungs = {packed.npad}
        npad = _pow2(2 * n)
        while npad <= _pow2(self.max_batch * n) \
                and npad * spec.cube_space <= ITEM_CELL_BUDGET:
            rungs.add(npad)
            npad *= 2
        for npad in sorted(rungs):
            key = packed.program_key(npad)
            if _kernels.ready(key) is not None:
                continue
            fn = _kernels.get(key, lambda: build_cube_combine_kernel(spec),
                              ph.RAGGED_FUSED)
            with span(ph.FUSED_EXECUTE, background=True, padded=npad,
                      segments=n, cube_space=spec.cube_space):
                count_dispatch(ph.RAGGED_FUSED)
                jax.block_until_ready(
                    fn(*packed.launch_args(stacked, npad)))
            _kernels.mark_ready(key)
            global_metrics.count("fused_compiles_background")

    def _build_cube(self, spec: CubeSpec, plan) -> Dict[str, jax.Array]:
        from ..ops.kernels import dict_decode_forms
        from .executor import resolve_params
        seg = plan.segment
        fn = _kernels.get(("cube", spec),
                          lambda: build_cube_kernel(spec),
                          ph.CUBE_BUILD_KERNEL)
        with span(ph.CUBE_BUILD, segment=seg.name, bucket=seg.bucket,
                  cube_space=spec.cube_space):
            cols = seg.device_cols(plan.col_names)
            params = resolve_params(plan)
            count_dispatch(ph.CUBE_BUILD_KERNEL,
                           dict_decode_forms(spec.kp, params))
            out = fn(cols, jnp.int32(seg.n_docs), params)
            jax.block_until_ready(out)
        global_metrics.count("cube_builds_background")
        return out

    def clear(self) -> None:
        """Test hook: drop kernel caches, estimates and the memory of
        failed background jobs (the cube cache is cleared through
        ops/plan_cache.global_cube_cache)."""
        _kernels.clear()
        self._background.clear()
        with self._lock:
            self._est_ms.clear()


def _size_bucket(n: int) -> str:
    for b in (2, 4, 8, 16, 32):
        if n <= b:
            return f"fused_batch_size_le_{b}"
    return "fused_batch_size_gt_32"


global_batcher = RaggedBatcher()


def batching_health(snapshot: Dict[str, Any]) -> Dict[str, Any]:
    """The micro-batching block the broker /metrics endpoint and /ui
    console render next to the scatter counters."""
    c = snapshot["counters"]
    out = {k: c.get(k, 0) for k in (
        "batched_dispatches", "batched_queries", "fused_dispatch_errors",
        "cube_cache_hits", "cube_cache_misses")}
    out["solo_fallbacks"] = {r: c.get(f"solo_fallback_{r}", 0)
                             for r in _SOLO_REASONS}
    out["batch_size_histogram"] = {
        f"le_{b}": c.get(f"fused_batch_size_le_{b}", 0)
        for b in (2, 4, 8, 16, 32)}
    out["batch_size_histogram"]["gt_32"] = c.get(
        "fused_batch_size_gt_32", 0)
    out["batch_queue_depth"] = snapshot["gauges"].get(
        "batch_queue_depth", 0)
    # "cold" among the solo fallbacks: a cube or a fused program was not
    # ready when the query asked; these say what the background builder
    # (module docstring) has queued or running, and what it has made
    out["cube_builds_pending"] = snapshot["gauges"].get(
        "cube_builds_pending", 0)
    out["cube_builds_background"] = c.get("cube_builds_background", 0)
    out["fused_compiles_background"] = c.get(
        "fused_compiles_background", 0)
    # live device bytes the fusion plane holds resident (utils/devmem
    # gauges mirrored by the cube cache) — rendered on /ui next to the
    # hit counters so cache pressure is visible where batching is tuned
    g = snapshot["gauges"]
    out["cube_cache_bytes"] = int(g.get("device_bytes_cube_cache", 0)
                                  + g.get("device_bytes_cube_stacked", 0))
    out["enabled"] = global_batcher.enabled
    return out
