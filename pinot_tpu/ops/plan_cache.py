"""Keyed kernel-plan cache: one compiled program an entry, launched
without waiting and collected apart.

Round-6 tentpole: repeated SSB iterations must never re-trace. jax.jit
already caches traces, but nothing (a) surfaced a hit/miss counter a test
can assert zero-retrace against, or (b) recorded the measured selectivity
a plan actually saw (the observability input for the cost model in
multistage/costs.py).

The cache key is the full kernel identity — (plan structure, bucket,
slots_cap, platform, xfer_compact, scatter core, compact-path env knobs)
— exactly the signature the jitted-kernel lru caches use, so one entry
maps to one compiled XLA program.

Launch and collection are two steps: launch() dispatches the program and
returns its device outputs with their copy to the host started,
collect() blocks on that one launch's copy. The entry lock guards
bookkeeping alone (the run count, the measured selectivity), never a
device_get, so many launches of one entry can be in flight: a
statement's segments (engine/executor.py's window), or two workers on
literal variants of one statement. No buffer is donated: a donated
output cannot go to the next launch before its own host copy was
collected, which is what would put the lock back over the copy, and on
the chip a pool of accumulators (one a launch in flight) timed the same
as no donation at all: an output is at most 32,768 rows of a few int32
columns (PERF.md, PR 36).

Round-7 observability: every hit/miss also counts into
utils.metrics.global_metrics (one snapshot covers the whole engine), a
RetraceDetector flags any compile of an already-warm plan structure
after its first query (a retrace: shape change, evicted entry, flipped
env knob) as a span annotation + counter, and launch()/collect() split
compile-vs-execute-vs-transfer into utils/spans spans when a trace is
being taken.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..utils import phases as ph
from ..utils.devmem import global_device_memory, nbytes_of
from ..utils.metrics import global_metrics
from ..utils.spans import (count_dispatch, device_fence, phase, span,
                           span_tracer)
from .ir import KernelPlan


class RetraceDetector:
    """Flags kernel compiles that happen AFTER a plan structure's first
    (warmup) query — the silent perf killers: a bucket/shape change, an
    evicted entry, a flipped env knob in the cache key.

    Semantics: ``begin_query()`` (engine/serving.py, once per query)
    advances a generation. A cache miss whose plan structure was already
    compiled in an EARLIER generation is a retrace; misses within one
    generation (a table with mixed segment buckets compiles the same
    plan at several shapes on its first query) are warmup, not
    retraces. ``expected()`` brackets deliberate recompiles (the
    capacity-overflow retry ladder) so they count separately.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._gen = 0
        self._last_token: Any = object()       # never equals a real token
        self._first_gen: Dict[int, int] = {}   # hash(plan) -> generation
        self._expected = threading.local()
        self.retraces = 0
        self.expected_recompiles = 0
        # compile-plane forensics (utils/compileplane): first-ever vs
        # same-generation compiles, so the compile_event trigger
        # taxonomy reconciles EXACTLY against this snapshot
        self.cold_compiles = 0
        self.warmup_compiles = 0

    def begin_query(self, token: Any = None) -> None:
        """Advance the generation. ``token`` (the accountant's query id)
        dedupes multi-table executions of ONE query — a hybrid
        offline+realtime query plans two segment lists but must stay a
        single warmup generation, or its second half's cold compiles
        would read as retraces."""
        with self._lock:
            if token is not None and token == self._last_token:
                return
            self._last_token = token
            self._gen += 1

    @contextmanager
    def expected(self):
        """Bracket a deliberate recompile (overflow retry ladder)."""
        prev = getattr(self._expected, "on", False)
        self._expected.on = True
        try:
            yield
        finally:
            self._expected.on = prev

    def expected_active(self) -> bool:
        """Whether this thread is inside an expected() bracket (the
        plan cache pins the bracket into stage hints at miss time so
        the classification at the ACTUAL compile — which may run after
        the bracket closed — still counts as deliberate)."""
        return getattr(self._expected, "on", False)

    def classify_compile(self, token: Any) -> str:
        """Classify one compile of ``token`` (the forensics primitive):
        'cold' (first ever), 'warmup' (another compile inside the
        structure's first query generation), 'expected' (inside an
        expected() bracket — the overflow ladder / drift re-quantize),
        or 'retrace'. Counts the matching counter; called by
        utils/compileplane.StagedFn at the moment the XLA compile
        actually stages, so the compile_event stream and this
        detector's totals reconcile one-to-one."""
        h = hash(token)
        expected = getattr(self._expected, "on", False)
        with self._lock:
            last = self._first_gen.get(h)
            gen = self._gen
            self._first_gen[h] = gen
            # counters mutate under the lock: concurrent server threads
            # (cluster scatter pool) must not lose increments
            if last is None:
                self.cold_compiles += 1
                return "cold"
            if last >= gen:
                self.warmup_compiles += 1
                return "warmup"
            if expected:
                self.expected_recompiles += 1
            else:
                self.retraces += 1
        if expected:
            global_metrics.count("plan_cache_expected_recompiles")
            return "expected"
        global_metrics.count("plan_cache_retraces")
        span_tracer.annotate(retrace=True)
        return "retrace"

    def observe_compile(self, plan: Any) -> bool:
        """Count one compile; -> True when the retrace flag fired."""
        return self.classify_compile(plan) == "retrace"

    def snapshot(self) -> Dict[str, int]:
        return {"retraces": self.retraces,
                "expected_recompiles": self.expected_recompiles}

    def trigger_snapshot(self) -> Dict[str, int]:
        """The four raw classification counters (the compile-forensics
        reconciliation oracle; snapshot() keeps its historical shape)."""
        return {"cold": self.cold_compiles,
                "warmup": self.warmup_compiles,
                "retraces": self.retraces,
                "expected_recompiles": self.expected_recompiles}

    def clear(self) -> None:
        with self._lock:
            self._first_gen.clear()
            self._gen = 0
            self._last_token = object()
            self.retraces = 0
            self.expected_recompiles = 0
            self.cold_compiles = 0
            self.warmup_compiles = 0


class PlanCacheEntry:
    """One compiled kernel + its run statistics."""

    def __init__(self, base_fn, plan: Any = None, key: Any = None,
                 stage_hints: Optional[Dict[str, Any]] = None):
        from ..utils.compileplane import (kernel_jit, key_fingerprint,
                                          staged)
        # compile-plane forensics: the jit is wrapped in explicit AOT
        # staging (utils/compileplane.StagedFn) so the first run's
        # lower/compile split, executable memory bytes and trigger
        # classification land a compile_event. The detector token stays
        # the PLAN STRUCTURE (the retrace detector's historical key);
        # stage_hints carry the miss context (drift re-quantize /
        # LRU-eviction rebuild) the trigger taxonomy refines through.
        if plan is None:
            # direct constructions (tests) get a never-reused token —
            # an id() here could alias a GC'd entry's address in the
            # detector's generation map (the round-19 memo rule)
            import uuid
            plan = ("plan_cache", uuid.uuid4().hex)
        self.family = ph.plan_family(plan)
        # the structure _forms reads at each launch (direct
        # constructions pass a bare token: nothing to count)
        self._kernel_plan = plan if isinstance(plan, KernelPlan) else None
        self.fn = staged(kernel_jit(base_fn, self.family),
                         "plan_cache", plan, hints=stage_hints)
        if key is not None:
            self.fn.key_fp = key_fingerprint(key)
        self.lock = threading.Lock()
        self.runs = 0
        # measured selectivity feedback: what the kernel actually matched.
        # Mutated through record_measured/mark_overflowed ONLY — the
        # entry lock guards them, and analysis/jaxlint's
        # unlocked-mutation rule holds every other mutation site to that.
        self.last_matched: Optional[int] = None
        self.last_rows: Optional[int] = None
        # set once this entry's capacity has overflowed: the executor
        # then goes STRAIGHT to the full-capacity entry on later runs
        # instead of paying the overflowing tight kernel forever
        self.overflowed = False

    def _forms(self, params) -> Tuple[Tuple[int, int], Tuple[int, int]]:
        if self._kernel_plan is None:
            return (0, 0), (0, 0)
        from .kernels import launch_forms
        return launch_forms(self._kernel_plan, params)

    def launch(self, cols, n_docs, params) -> Dict[str, Any]:
        """Dispatch the compiled program and return its DEVICE outputs
        without waiting for them; their copy to the host is started.
        ``collect`` waits for it; a launch nobody collects (a query
        killed between two collections) is dropped with its buffers.

        Under an active span trace the first-run (compile) vs execute
        split is fenced with block_until_ready; untraced launches keep
        async dispatch."""
        with self.lock:
            self.runs += 1
            first = self.runs == 1
        count_dispatch(self.family, *self._forms(params))
        with phase(ph.DEVICE_EXECUTE, compiled=first):
            out = self.fn(cols, n_docs, params)
            device_fence(out)
        for leaf in jax.tree_util.tree_leaves(out):
            leaf.copy_to_host_async()
        return out

    @staticmethod
    def collect(out: Dict[str, Any]) -> Dict[str, Any]:
        """Block on ONE launch's host copy and return host numpy."""
        with phase(ph.DEVICE_TRANSFER):
            # THE transfer fence of a plan-cache launch
            return jax.device_get(out)  # jaxlint: ok host-sync

    def run(self, cols, n_docs, params) -> Dict[str, Any]:
        """One launch, collected at once: HOST numpy outputs."""
        return self.collect(self.launch(cols, n_docs, params))

    def record_measured(self, matched: int, rows: int) -> None:
        with self.lock:
            self.last_matched = int(matched)
            self.last_rows = int(rows)

    def mark_overflowed(self) -> None:
        """Capacity overflow observed (engine/executor.py retry ladder);
        taken under the entry lock so concurrent same-plan queries can't
        lose the flag."""
        with self.lock:
            self.overflowed = True

    @property
    def measured_selectivity(self) -> Optional[float]:
        if self.last_matched is None or not self.last_rows:
            return None
        return self.last_matched / self.last_rows


class KernelPlanCache:
    """(plan, bucket, slots_cap, platform, flags) -> PlanCacheEntry with
    hit/miss counters (tests/test_plan_cache.py's zero-retrace assertion
    reads these)."""

    def __init__(self, maxsize: int = 512):
        self._entries: "OrderedDict[Tuple, PlanCacheEntry]" = OrderedDict()
        # (plan, bucket) -> last measured selectivity: the O(1) index
        # measured_for reads on the planning hot path (a lock-held scan
        # of every entry per planned segment would serialize planners)
        self._measured: "OrderedDict[Tuple, float]" = OrderedDict()
        # (plan, bucket, cap) combinations whose drift-requantize
        # expected-compile bracket has been consumed (_note_requantize)
        self._requantized: "OrderedDict[Tuple, bool]" = OrderedDict()
        # keys the LRU evicted (bounded memory of them): a re-miss of
        # one is an lru_evict_rebuild in the compile-event taxonomy
        self._evicted_keys: "OrderedDict[Tuple, bool]" = OrderedDict()
        self._maxsize = maxsize
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.detector = RetraceDetector()

    def entry(self, plan, bucket: int,
              slots_cap: Optional[int] = None,
              platform: Optional[str] = None,
              xfer_compact: bool = True,
              scatter: Optional[bool] = None,
              expected_compile: bool = False) -> PlanCacheEntry:
        from .kernels import (_ladder_min_elems, _two_pass_mode,
                              build_kernel, cpu_scatter_default)

        if scatter is None:
            scatter = cpu_scatter_default(platform)
        key = (plan, bucket, slots_cap, platform, xfer_compact, scatter,
               _two_pass_mode(), _ladder_min_elems())
        with self._lock:
            ent = self._entries.get(key)
            if ent is not None:
                self.hits += 1
                self._entries.move_to_end(key)
                hit = True
            else:
                self.misses += 1
                hit = False
        global_metrics.count("plan_cache_hits" if hit
                             else "plan_cache_misses")
        if hit:
            span_tracer.annotate(cache="hit")
            return ent
        span_tracer.annotate(cache="miss")
        # compile-plane forensics: the trigger CONTEXT is known here, at
        # the miss, but classification + the compile_event land at the
        # entry's first run — where the XLA compile actually stages
        # (utils/compileplane.StagedFn), so concurrent same-key misses
        # (only one entry survives the setdefault below) can never
        # double-count an event. The drift re-quantize hint is consumed
        # ONCE per (plan, bucket, cap): a LATER miss of the same
        # combination (LRU eviction churn, a mode flip) is a genuine
        # recompile and must stay visible to the retrace detector.
        stage_hints: Dict[str, Any] = {}
        if expected_compile and self._note_requantize(plan, bucket,
                                                      slots_cap):
            global_metrics.count("selectivity_drift_recompiles")
            stage_hints["expected_kind"] = "drift_requantize"
        elif self.detector.expected_active():
            # inside an executor expected() bracket (the overflow retry
            # ladder): pin the kind now — the bracket may have closed
            # by the time the entry first runs
            stage_hints["expected_kind"] = "overflow_retry"
        if __debug__:
            # debug assertion (analysis/plan_verify): every structure
            # entering the cache must honor the hashable-frozen key
            # contract and the strategy gates — a violation here means a
            # caller synthesized a plan behind the planner's back.
            # Stripped under python -O; PINOT_PLAN_VERIFY=0 disables.
            from ..analysis.plan_verify import debug_check_cache_plan
            debug_check_cache_plan(plan, bucket)
        with span("trace_kernel", bucket=bucket, slots_cap=slots_cap):
            base = build_kernel(plan, bucket, slots_cap, platform,
                                xfer_compact, scatter=scatter,
                                two_pass_mode=key[6], ladder_min=key[7])
            ent = PlanCacheEntry(base, plan=plan, key=key,
                                 stage_hints=stage_hints)
        with self._lock:
            # a concurrent miss may have built the same entry; keep the
            # first one registered so its run stats survive
            ent = self._entries.setdefault(key, ent)
            if key in self._evicted_keys:
                # eviction-rebuild attribution attaches to the
                # SURVIVING entry at publish time (consumed exactly
                # once, by the first publisher): a loser of the
                # setdefault race above must not walk off with the
                # hint while the winner's compile reads as a plain
                # retrace. set_hints is a no-op once the first compile
                # consumed the hints — by then the marker was already
                # attached by whoever published first.
                del self._evicted_keys[key]
                ent.fn.set_hints(evicted=True)
            self._entries.move_to_end(key)
            while len(self._entries) > self._maxsize:
                old_key, _old = self._entries.popitem(last=False)
                # remember the evicted key (bounded): its next miss is
                # an lru_evict_rebuild, not an unexplained retrace
                self._evicted_keys[old_key] = True
                while len(self._evicted_keys) > 4 * self._maxsize:
                    self._evicted_keys.popitem(last=False)
            global_metrics.gauge("plan_cache_entries", len(self._entries))
        return ent

    def stats(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses,
                "entries": len(self._entries),
                **self.detector.snapshot()}

    def snapshot_misses(self) -> int:
        return self.misses

    def _note_requantize(self, plan, bucket: int,
                         slots_cap: Optional[int]) -> bool:
        """True exactly once per (plan, bucket, cap): whether this miss
        is the drift re-quantize's own compile (bracket it) or a
        rebuild of a combination already compiled before (don't)."""
        key = (plan, bucket, slots_cap)
        with self._lock:
            if key in self._requantized:
                return False
            self._requantized[key] = True
            self._requantized.move_to_end(key)
            while len(self._requantized) > self._maxsize:
                self._requantized.popitem(last=False)
            return True

    @staticmethod
    def _measured_key(plan, bucket: int, segment, params) -> Tuple:
        """KernelPlan hoists literals into params, so two queries
        differing only in a literal value (WHERE f<=1 vs f<=99) — or
        structurally identical plans on different tables — share the
        plan object. The measurement key therefore carries segment
        identity and a params fingerprint: one query's measured
        selectivity must never set another query's capacity."""
        import numpy as np
        seg_id = getattr(segment, "uid", None) \
            or getattr(segment, "name", None)
        fp = []
        for p in params or ():
            if isinstance(p, np.ndarray):
                fp.append((str(p.dtype), p.shape, p.tobytes()))
            else:
                fp.append(repr(p))  # scalars + ("dictvals", col) markers
        return (plan, bucket, seg_id, tuple(fp))

    def record_measured(self, plan, bucket: int, entry: PlanCacheEntry,
                        matched: int, rows: int,
                        segment=None, params=None) -> None:
        """Record a run's measured selectivity on the entry AND the
        index measured_for reads — the engine executor's post-run
        feedback write."""
        entry.record_measured(matched, rows)
        sel = entry.measured_selectivity
        if sel is None:
            return
        key = self._measured_key(plan, bucket, segment, params)
        with self._lock:
            self._measured[key] = sel
            self._measured.move_to_end(key)
            while len(self._measured) > self._maxsize:
                self._measured.popitem(last=False)

    def measured_for(self, plan, bucket: int,
                     segment=None, params=None) -> Optional[float]:
        """Most recently measured selectivity for this exact
        (plan, bucket, segment, literal-params) combination — the
        feedback value query/planner.py's selectivity-drift re-quantize
        consumes (round 12): when it disagrees with the IR estimate past
        multistage/costs.SELECTIVITY_DRIFT_RATIO, the planner re-derives
        the compact capacity from this measurement and the resulting
        compile runs as an expected_compile (counted, never a retrace).
        Measurements only exist after a run of the same query on the
        same segment, so a hit here implies that shape has been warm."""
        key = self._measured_key(plan, bucket, segment, params)
        with self._lock:
            return self._measured.get(key)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._measured.clear()
            self._requantized.clear()
            self._evicted_keys.clear()
            self.hits = 0
            self.misses = 0
        self.detector.clear()


class CubeCache:
    """(cube spec, segment uid) -> device-resident literal-free cube
    (engine/ragged.py) — the piece that turns the plan cache from a
    compile-amortizer into a throughput engine (PR 8): queries sharing
    a plan STRUCTURE differ only in hoisted literal params, so one
    unmasked group-by over the union of predicate + group dimensions
    answers every one of them by contraction. The cube is keyed by the
    segment's process-unique load uid (the round-9 _STACK_CACHE rule:
    names recur across tables and reloads; uids never do) so a reload
    can never serve stale cells, and the name rides along only for
    evict_cubes_containing."""

    MAX_STACKED = 16

    def __init__(self, maxsize: int = 128):
        # one entry a (spec, segment): a served table of 8 segments and
        # a dashboard's seven fusable statement shapes hold 56 at once
        # (PR 33; 16 evicted them by turns). A stack is one a (spec,
        # segment set). Bytes are the tier budget's to bound.
        self._entries: "OrderedDict[Tuple, Dict[str, Any]]" = OrderedDict()
        # (spec, uid tuple) -> {name: [S, ...]} stacked device arrays:
        # the warm fused path would otherwise re-copy every per-segment
        # cube through jnp.stack on every dispatch
        self._stacked: "OrderedDict[Tuple, Dict[str, Any]]" = OrderedDict()
        # key -> Event while a build is in flight: concurrent fused
        # leaders missing the same key must not each run the full
        # unmasked segment scan (cold-path dedup)
        self._building: Dict[Tuple, threading.Event] = {}
        self._maxsize = maxsize
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def entry(self, spec, segment, build_fn) -> Dict[str, Any]:
        key = (spec, segment.uid, segment.name)
        while True:
            with self._lock:
                hit = self._entries.get(key)
                if hit is not None:
                    self._entries.move_to_end(key)
                    self.hits += 1
                    waiting = None
                else:
                    waiting = self._building.get(key)
                    if waiting is None:
                        self._building[key] = threading.Event()
                        self.misses += 1
            if hit is not None:
                global_metrics.count("cube_cache_hits")
                return hit
            if waiting is None:
                break               # this thread builds
            # another leader is scanning this segment right now: wait
            # for its result instead of duplicating the scan (on its
            # failure the loop re-enters and this thread builds)
            waiting.wait(timeout=600)
        global_metrics.count("cube_cache_misses")
        try:
            built = build_fn()
        except BaseException:
            # failed build: release waiters (they re-enter and build)
            with self._lock:
                ev = self._building.pop(key, None)
            if ev is not None:
                ev.set()
            raise
        with self._lock:
            # publish BEFORE signaling: a waiter woken by the event
            # must find the entry, or it would re-run the very scan
            # the event deduplicates
            built = self._entries.setdefault(key, built)
            global_device_memory.add("cube_cache", key, nbytes_of(built))
            self._entries.move_to_end(key)
            while len(self._entries) > self._maxsize:
                old_key, _old = self._entries.popitem(last=False)
                global_device_memory.remove("cube_cache", old_key)
            global_metrics.gauge("cube_cache_entries", len(self._entries))
            ev = self._building.pop(key, None)
        if ev is not None:
            ev.set()
        # shared-budget admission (engine/tier.py), outside self._lock:
        # the cube is a new HBM resident charged to the one budget
        from ..engine.tier import global_tier
        global_tier.enforce(protect={segment.uid})
        return built

    @staticmethod
    def _stack_key(spec, segments) -> Tuple:
        return (spec, tuple(s.uid for s in segments),
                tuple(s.name for s in segments))

    def missing(self, spec, segments) -> List[int]:
        """Uids of ``segments`` with no resident cube for ``spec``. A
        look, not a use: nothing is counted or reordered."""
        with self._lock:
            return [s.uid for s in segments
                    if (spec, s.uid, s.name) not in self._entries]

    def stacked_if_ready(self, spec, segments) -> Optional[Dict[str, Any]]:
        """The cached ``stacked`` of these segments, or None: the warm
        fused path reads it and never stacks on a query's thread."""
        key = self._stack_key(spec, segments)
        with self._lock:
            hit = self._stacked.get(key)
            if hit is not None:
                self._stacked.move_to_end(key)
                self.hits += 1
        if hit is not None:
            global_metrics.count("cube_cache_hits")
        return hit

    def stacked(self, spec, segments, per_segment: List[Dict[str, Any]]
                ) -> Dict[str, Any]:
        """{name: [S, ...]} stack of the given segments' cubes, cached
        by (spec, uid tuple) so a warm fused dispatch pays zero device
        copies. ``per_segment`` must be the entry() results for the
        same segments, in order."""
        key = self._stack_key(spec, segments)
        with self._lock:
            hit = self._stacked.get(key)
            if hit is not None:
                self._stacked.move_to_end(key)
                return hit
        stacked = {name: jnp.stack([c[name] for c in per_segment])
                   for name in per_segment[0]}
        with self._lock:
            stacked = self._stacked.setdefault(key, stacked)
            global_device_memory.add("cube_stacked", key,
                                     nbytes_of(stacked))
            self._stacked.move_to_end(key)
            while len(self._stacked) > self.MAX_STACKED:
                old_key, _old = self._stacked.popitem(last=False)
                global_device_memory.remove("cube_stacked", old_key)
        # shared-budget admission (engine/tier.py), outside self._lock
        from ..engine.tier import global_tier
        global_tier.enforce(protect={s.uid for s in segments})
        return stacked

    def resident_uids(self) -> set:
        """Segment uids with a resident per-segment cube — the 'warm
        ragged cube' placement signal the residency heartbeats report
        (a replica holding the cube answers plan-key-sharing queries
        without re-scanning the columns)."""
        with self._lock:
            return {k[1] for k in self._entries}

    def evict_containing(self, segment_name: str) -> None:
        with self._lock:
            for key in [k for k in self._entries if k[2] == segment_name]:
                del self._entries[key]
                global_device_memory.remove("cube_cache", key)
            for key in [k for k in self._stacked
                        if segment_name in k[2]]:
                del self._stacked[key]
                global_device_memory.remove("cube_stacked", key)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "entries": len(self._entries),
                    "stacked": len(self._stacked)}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._stacked.clear()
            self.hits = 0
            self.misses = 0
        global_device_memory.drop_pool("cube_cache")
        global_device_memory.drop_pool("cube_stacked")


global_plan_cache = KernelPlanCache()
global_cube_cache = CubeCache()
