"""HBM-tiered segment store: hot / warm / cold under ONE device budget.

Pinot's entire performance layer is off-heap mmap (PAPER.md §2.9); the
TPU analog is HBM residency. Before this module every device cache —
segment columns (segment/immutable), the stack cache (engine/batch),
the cube caches (ops/plan_cache.CubeCache) — grew unboundedly and independently, so a node serving
more table-bytes than fit in HBM either OOMed or re-uploaded per query.
This is the managed memory hierarchy ROADMAP direction 1 called for:

- **hot**: a segment's padded columns resident in HBM (uid-keyed, the
  ``ImmutableSegment._device`` cache);
- **warm**: the padded host arrays kept after a demotion, ready to
  ``jax.device_put`` without re-reading/re-padding the mmap;
- **cold**: mmap on disk only (the load state every segment starts in).

Admission is driven by use: any ``device_col`` upload promotes the
segment hot and charges the shared budget. The budget is ONE number —
``PINOT_HBM_BUDGET_BYTES`` (also the resident-vs-streamed group router
knob in engine/pipeline.py) or ``configure(budget_bytes=...)`` — summed
across ALL devmem pools (utils/devmem.POOLS), and an over-budget
admission demotes the **coldest** hot segments first, ranked by
``utils/heat.SegmentHeat``'s time-decayed scores with the uid as the
deterministic tiebreak: the same heat sequence always produces the
same promote/demote decisions (``decisions`` is the replayable log the
state-machine test pins). Demoting a segment drops its device columns
AND every stacked/cube copy that contains it (the round-9 eviction
discipline), so the accounting in utils/devmem reconciles exactly
across demotions. A query touching a demoted segment transparently
re-promotes through the normal ``device_col`` path — warm arrays skip
the host-side re-pad — with digests byte-identical regardless of tier
placement (same arrays, same kernels; the plan cache keeps the
compiled executables, so re-promotion never retraces).

Enforcement is edge-triggered and slightly soft: the budget is checked
at every admission, with the admitting working set protected (demoting
the segment a query is mid-upload on would thrash), so one admission
whose group IS the whole hot set can overshoot transiently and is
reconciled at the next admission. The default budget is **unbounded**
(env var absent): tier-1 and the env-pinned baselines run exactly the
round-14 behavior, and warm host copies are only kept while a budget
is armed.

Chaos: the ``tier.evict`` fault point (utils/faults.py, per-(query id,
site key) stream discipline) fires in ``on_access`` and force-demotes
the touched segment MID-QUERY; the query must re-promote and finish
byte-exact (tools/chaos_smoke.py ``--tier``).

Counters/gauges: ``tier_promotions`` / ``tier_demotions`` (+ broker-
side ``tier_affinity_hits`` / ``tier_affinity_misses``) in
global_metrics, per-query in ``query_stats``, fleet-aggregated by
cluster/rollup.py; occupancy gauges (``tier_hot_bytes`` etc.) feed
/debug/memory, broker /metrics + /ui and the controller Fleet view.
"""
from __future__ import annotations

import os
import threading
import weakref
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Set, Tuple

from ..utils.devmem import POOLS, global_device_memory
from ..utils.heat import global_segment_heat
from ..utils.metrics import global_metrics

TIER_HOT, TIER_WARM, TIER_COLD = "hot", "warm", "cold"
MAX_DECISIONS = 4096
_UNSET = object()


def env_budget_bytes() -> Optional[int]:
    """The tier budget from PINOT_HBM_BUDGET_BYTES — only when the
    operator set it explicitly (None = unbounded, the tier-1 default;
    engine/pipeline.py's group router keeps its own 8 GB default for
    the resident-vs-streamed decision)."""
    raw = os.environ.get("PINOT_HBM_BUDGET_BYTES")
    if not raw:
        return None
    try:
        return max(int(raw), 1)
    except ValueError:
        return None


def env_warm_budget_bytes() -> Optional[int]:
    """Optional host-side warm-tier bound (PINOT_WARM_BUDGET_BYTES):
    over it, the coldest warm segments drop to cold (mmap only)."""
    raw = os.environ.get("PINOT_WARM_BUDGET_BYTES")
    if not raw:
        return None
    try:
        return max(int(raw), 1)
    except ValueError:
        return None


class TierManager:
    """The hot/warm/cold segment state machine (module docstring).

    Thread discipline: ``_lock`` is a LEAF lock — it guards only the
    registry/state/log dicts and is NEVER held while calling into a
    segment's demotion path (which takes the stack/cube cache locks);
    victims are selected under the lock, the demotion executes outside
    it. A concurrent re-admission between selection and execution is
    benign: the state heals at the next transition and the data path
    re-promotes through device_col either way."""

    def __init__(self, devmem=None, heat=None,
                 budget_bytes: Optional[int] = None,
                 warm_budget_bytes: Optional[int] = None):
        self._devmem = devmem if devmem is not None else \
            global_device_memory
        self._heat = heat if heat is not None else global_segment_heat
        self._lock = threading.Lock()
        self._refs: Dict[int, Any] = {}            # uid -> weakref
        self._state: Dict[int, str] = {}           # uid -> tier
        self._names: Dict[int, str] = {}           # uid -> segment name
        self._warm_bytes: Dict[int, int] = {}      # uid -> host bytes
        # GC'd uids pending removal: fed by the weakref callbacks
        # WITHOUT the lock (GC can run the callback on a thread
        # already holding _lock), drained by _reap_locked
        self._dead: List[int] = []
        self._budget = budget_bytes                # None -> env
        self._warm_budget = warm_budget_bytes      # None -> env
        # thread-local pin set: the segments of the group THIS thread
        # is currently staking resident (engine/batch wraps the stack
        # build + dispatch) — never demotion victims, or an admission
        # mid-stack would evict its own working set (thrash)
        self._pins = threading.local()
        self.promotions = 0
        self.demotions = 0
        # replayable decision log: (action, segment, from, to, reason)
        # — the state-machine determinism contract (same heat sequence
        # => same decisions)
        self.decisions: List[Tuple[str, str, str, str, str]] = []

    # -- configuration -----------------------------------------------------
    @property
    def budget_bytes(self) -> Optional[int]:
        return self._budget if self._budget is not None \
            else env_budget_bytes()

    @property
    def warm_budget_bytes(self) -> Optional[int]:
        return self._warm_budget if self._warm_budget is not None \
            else env_warm_budget_bytes()

    @property
    def armed(self) -> bool:
        """True when an HBM budget is in force (warm host copies are
        only stashed while armed — unbounded runs stay byte-for-byte
        the pre-tier behavior)."""
        return self.budget_bytes is not None

    def configure(self, budget_bytes: Any = _UNSET,
                  warm_budget_bytes: Any = _UNSET) -> None:
        """Set/clear the budgets from code (None reverts to the env)."""
        if budget_bytes is not _UNSET:
            self._budget = budget_bytes
        if warm_budget_bytes is not _UNSET:
            self._warm_budget = warm_budget_bytes
        self.enforce()

    # -- bookkeeping ---------------------------------------------------------
    def _reap_locked(self) -> None:  # holds-lock: _lock
        # drain the GC'd-segment queue (the weakref callbacks feed
        # ``_dead`` lock-free — a callback can fire during GC on a
        # thread that ALREADY holds _lock, so taking the lock there
        # would self-deadlock)
        while self._dead:
            uid = self._dead.pop()  # jaxlint: ok unlocked-mutation
            self._refs.pop(uid, None)  # jaxlint: ok unlocked-mutation
            self._state.pop(uid, None)  # jaxlint: ok unlocked-mutation
            self._names.pop(uid, None)  # jaxlint: ok unlocked-mutation
            self._warm_bytes.pop(uid, None)  # jaxlint: ok unlocked-mutation

    def _register_locked(self, segment) -> None:  # holds-lock: _lock
        self._reap_locked()
        uid = segment.uid
        if uid not in self._refs:
            # the GC-time callback feeds _dead DELIBERATELY without
            # the lock: list.append is GIL-atomic, and GC can fire the
            # callback on a thread already holding _lock — taking it
            # there would self-deadlock (the CC203 this replaces)
            self._refs[uid] = weakref.ref(  # jaxlint: ok unlocked-mutation
                segment,
                lambda _r, u=uid: self._dead.append(u))  # jaxlint: ok unlocked-mutation # concur: ok CC201
            self._names[uid] = segment.name  # jaxlint: ok unlocked-mutation
            self._state[uid] = TIER_COLD  # jaxlint: ok unlocked-mutation

    def _log_locked(self, action: str, name: str, frm: str, to: str,
                    reason: str) -> None:  # holds-lock: _lock
        self.decisions.append((action, name, frm, to, reason))  # jaxlint: ok unlocked-mutation
        if len(self.decisions) > MAX_DECISIONS:
            del self.decisions[: MAX_DECISIONS // 2]  # jaxlint: ok unlocked-mutation

    def note_warm(self, uid: int, delta: int) -> None:
        """Warm host-array accounting (segment/immutable stashes/drops
        padded host copies through here)."""
        with self._lock:
            n = self._warm_bytes.get(uid, 0) + int(delta)
            if n > 0:
                self._warm_bytes[uid] = n
            else:
                self._warm_bytes.pop(uid, None)

    def _hbm_bytes(self) -> int:
        """Live HBM bytes across ALL accounted pools — the one number
        the shared budget compares against."""
        return sum(self._devmem.pool_bytes(p) for p in POOLS)

    # -- transitions ---------------------------------------------------------
    def admitted(self, segment) -> None:
        """A device-cache insert landed for ``segment`` (the ONE
        admission edge: segment/immutable._cache_device). Registers the
        segment, counts the cold/warm->hot promotion, then enforces the
        shared budget with this segment protected."""
        uid = segment.uid
        promoted = prev = None
        with self._lock:
            self._register_locked(segment)
            prev = self._state.get(uid, TIER_COLD)
            if prev != TIER_HOT:
                self._state[uid] = TIER_HOT
                self.promotions += 1
                self._log_locked("promote", segment.name, prev,
                                 TIER_HOT, "access")
                promoted = True
        if promoted:
            global_metrics.count("tier_promotions")
        self.enforce(protect={uid})

    def on_access(self, segment) -> None:
        """Per-column-read hook on the device_col path: one attribute
        read when no chaos plan is armed; under a plan the ``tier.evict``
        point can force a MID-QUERY demotion (the query then re-promotes
        and must finish byte-exact)."""
        from ..utils.faults import fault_fires
        if fault_fires("tier.evict", key=segment.name):
            self.demote(segment, TIER_WARM, reason="fault")

    def demote(self, segment, to: str = TIER_WARM,
               reason: str = "") -> bool:
        """HBM -> host: drop the segment's device residents (and every
        stacked/cube copy containing it); the padded host arrays stay
        warm unless ``to=TIER_COLD`` (host -> disk, mmap only).
        Returns True when a transition actually happened."""
        uid = segment.uid
        drop_warm = to == TIER_COLD
        with self._lock:
            self._register_locked(segment)
            prev = self._state.get(uid, TIER_COLD)
            if prev == TIER_COLD or (prev == TIER_WARM and not drop_warm):
                return False
            self._state[uid] = to
            self.demotions += 1
            self._log_locked("demote", segment.name, prev, to,
                             reason or "explicit")
        # the demotion body runs OUTSIDE _lock (it takes the stack and
        # cube cache locks; _lock stays a leaf)
        segment.demote_device(drop_warm=drop_warm)
        global_metrics.count("tier_demotions")
        self._export()
        return True

    def drain(self, name: str, reason: str = "drain",
              table: Optional[str] = None) -> int:
        """Rebalance drain entry point (cluster/rebalancer.py): warm-
        demote every live HOT copy of the named segment — device
        residents drop, the padded host arrays stay warm, so there is
        NO cold re-pad if the copy is touched again and in-flight
        queries finish on references they already acquired. In-process
        replicas register distinct segment objects under the same name;
        a drain demotes them all (a receiver that just pre-warmed
        re-promotes from its warm arrays on first touch — cheap
        device_put, digests unaffected). Segment names recur ACROSS
        tables too, so pass ``table`` to demote only copies whose
        schema carries that table — an unrelated table sharing the
        name must not pay a re-promotion. Returns demotions
        performed."""
        with self._lock:
            self._reap_locked()
            uids = sorted(uid for uid, n in self._names.items()
                          if n == name
                          and self._state.get(uid) == TIER_HOT)
            segs = []
            for uid in uids:
                ref = self._refs.get(uid)
                seg = ref() if ref is not None else None
                if seg is None:
                    continue
                if table is not None and \
                        getattr(getattr(seg, "schema", None),
                                "name", None) != table:
                    continue
                segs.append(seg)
        n = 0
        for seg in segs:  # demote takes _lock itself (leaf) — call outside
            if self.demote(seg, TIER_WARM, reason=reason):
                n += 1
        return n

    def on_evicted(self, segment) -> None:
        """ImmutableSegment.evict_device (unload/reload path): the
        segment left the hierarchy entirely — mark cold, no demotion
        counters (an unload is not a budget decision)."""
        with self._lock:
            if segment.uid in self._state:
                self._state[segment.uid] = TIER_COLD
            self._warm_bytes.pop(segment.uid, None)
        self._export()

    # -- budget enforcement --------------------------------------------------
    @contextmanager
    def pinned(self, uids):
        """Pin a working set for the enclosed dispatch on THIS thread
        (engine/batch group execution): pinned segments are never
        budget-demotion victims. Stacks nest; chaos demotions
        (tier.evict) ignore pins on purpose — they test correctness,
        not placement policy."""
        prev = getattr(self._pins, "uids", frozenset())
        self._pins.uids = prev | set(uids)
        try:
            yield
        finally:
            self._pins.uids = prev

    def enforce(self, protect: Optional[Set[int]] = None) -> int:
        """Demote coldest-first until HBM is back under budget; the
        ``protect`` uids plus this thread's pinned working set are
        never victims. Returns the number of demotions performed."""
        budget = self.budget_bytes
        n = 0
        if budget is not None:
            protect = (protect or frozenset()) \
                | getattr(self._pins, "uids", frozenset())
            total = self._hbm_bytes()
            if total > budget:
                scores = self._heat.scores()
                for _score, uid, seg in self._victims(scores, TIER_HOT,
                                                      protect):
                    if total <= budget:
                        break
                    if self.demote(seg, TIER_WARM, reason="budget"):
                        n += 1
                        total = self._hbm_bytes()
        n += self._enforce_warm()
        if n:
            self._export()
        return n

    def _victims(self, scores: Dict[Any, float], state: str,
                 protect: Set[int]) -> List[Tuple[float, int, Any]]:
        """Live candidate segments in ``state``, coldest-first with the
        uid as the deterministic tiebreak."""
        with self._lock:
            cands = sorted(
                (scores.get(uid, 0.0), uid)
                for uid, st in self._state.items()
                if st == state and uid not in protect
                and uid in self._refs)
        out = []
        for score, uid in cands:
            with self._lock:
                ref = self._refs.get(uid)
            seg = ref() if ref is not None else None
            if seg is not None:
                out.append((score, uid, seg))
        return out

    def _enforce_warm(self) -> int:
        budget = self.warm_budget_bytes
        if budget is None:
            return 0
        with self._lock:
            total = sum(self._warm_bytes.values())
        if total <= budget:
            return 0
        n = 0
        scores = self._heat.scores()
        for _score, uid, seg in self._victims(scores, TIER_WARM,
                                              frozenset()):
            if total <= budget:
                break
            if self.demote(seg, TIER_COLD, reason="warm_budget"):
                n += 1
            with self._lock:
                total = sum(self._warm_bytes.values())
        # HOT segments stash warm copies too (for their eventual
        # demotion) — when warm-state victims alone can't reach the
        # budget, trim the coldest hot segments' stashes WITHOUT
        # touching their device residents (the next demotion re-pads
        # from mmap instead)
        if total > budget:
            for _score, uid, seg in self._victims(scores, TIER_HOT,
                                                  frozenset()):
                if total <= budget:
                    break
                drop = getattr(seg, "drop_warm", None)
                if drop is not None and drop():
                    # logged only when a stash actually dropped — the
                    # decision log stays a faithful replay, not a visit
                    # trace
                    self._log_warm_trim(seg)
                with self._lock:
                    total = sum(self._warm_bytes.values())
        return n

    def _log_warm_trim(self, segment) -> None:
        with self._lock:
            self._log_locked("trim_warm", segment.name, TIER_HOT,
                             TIER_HOT, "warm_budget")

    # -- serving -------------------------------------------------------------
    def occupancy(self) -> Dict[str, Any]:
        """{tier: {segments, bytes}} occupancy. Hot bytes are the
        accounted segment-column pool (stack/cube copies are charged to
        their own pools); warm bytes are the stashed host arrays."""
        with self._lock:
            counts = {TIER_HOT: 0, TIER_WARM: 0, TIER_COLD: 0}
            for st in self._state.values():
                counts[st] = counts.get(st, 0) + 1
            warm_b = sum(self._warm_bytes.values())
        return {
            "hot": {"segments": counts[TIER_HOT],
                    "bytes": self._devmem.pool_bytes("segment_cols")},
            "warm": {"segments": counts[TIER_WARM], "bytes": warm_b},
            "cold": {"segments": counts[TIER_COLD]},
        }

    def snapshot(self) -> Dict[str, Any]:
        """The tier block /debug/memory, broker /metrics and the fleet
        rollup carry."""
        budget = self.budget_bytes
        out = {
            "armed": budget is not None,
            "budget_bytes": budget or 0,
            "hbm_used_bytes": self._hbm_bytes(),
            "promotions": self.promotions,
            "demotions": self.demotions,
            **self.occupancy(),
        }
        self._export(out)
        return out

    def _export(self, snap: Optional[Dict[str, Any]] = None) -> None:
        """Mirror occupancy into global_metrics gauges (consoles +
        Prometheus)."""
        s = snap if snap is not None else {
            "budget_bytes": self.budget_bytes or 0,
            "hbm_used_bytes": self._hbm_bytes(),
            **self.occupancy()}
        global_metrics.gauge("tier_budget_bytes", s["budget_bytes"])
        global_metrics.gauge("tier_hbm_used_bytes", s["hbm_used_bytes"])
        for t in (TIER_HOT, TIER_WARM):
            global_metrics.gauge(f"tier_{t}_bytes", s[t]["bytes"])
            global_metrics.gauge(f"tier_{t}_segments", s[t]["segments"])
        global_metrics.gauge("tier_cold_segments",
                             s["cold"]["segments"])

    def clear(self) -> None:
        """Test isolation: forget every registration and counter (the
        segments' own caches are untouched — the conftest fixture
        clears those through their devmem-synced paths)."""
        with self._lock:
            self._refs.clear()
            self._state.clear()
            self._names.clear()
            self._warm_bytes.clear()
            del self._dead[:]
            self.promotions = 0
            self.demotions = 0
            self.decisions = []
        self._budget = None
        self._warm_budget = None


def segment_tier(segment) -> str:
    """Observed tier of one segment object (the residency heartbeats
    report): hot = device residents, warm = stashed padded host arrays,
    else cold."""
    if getattr(segment, "_device", None):
        return TIER_HOT
    if getattr(segment, "_warm", None):
        return TIER_WARM
    return TIER_COLD


def tier_health(snapshot: Dict[str, Any]) -> Dict[str, Any]:
    """The tier block broker /metrics + /ui render: live occupancy plus
    the affinity-routing hit ratio derived from the counters."""
    c = snapshot.get("counters", {})
    hits = c.get("tier_affinity_hits", 0)
    misses = c.get("tier_affinity_misses", 0)
    out = dict(global_tier.snapshot())
    out["affinity_hits"] = hits
    out["affinity_misses"] = misses
    out["affinity_hit_ratio"] = round(hits / (hits + misses), 4) \
        if hits + misses else None
    return out


def reconcile_devmem(segments, pools=None) -> Dict[str, Dict[str, int]]:
    """tracked-vs-actual bytes per HBM pool — the bench/test check that
    NO promote/demote/evict path leaks accounting ("zero unaccounted
    devmem bytes"). ``segments`` is the full live segment set whose
    device caches back the segment_cols pool. Reads the caches'
    internals; verification-only, never on a serving path. Callers in
    long-lived/shared processes must start from devmem-synced caches
    (the pytest fixture resets accounting but keeps warm cube
    entries — clear those first, or pass ``pools`` to restrict the
    check to the pools that ARE synced)."""
    from ..engine import batch as eb
    from ..index import vector as vix
    from ..ops.plan_cache import global_cube_cache
    from ..utils.devmem import nbytes_of
    actual = {
        "segment_cols": sum(
            int(a.nbytes) for s in segments
            for a in list(getattr(s, "_device", {}).values())),
        "vector": sum(r.device_bytes() for r in vix.live_readers()),
        "stack_cache": sum(nbytes_of(v)
                           for v in list(eb._STACK_CACHE.values())),
        "cube_cache": sum(
            nbytes_of(v)
            for v in list(global_cube_cache._entries.values())),
        "cube_stacked": sum(
            nbytes_of(v)
            for v in list(global_cube_cache._stacked.values())),
    }
    snap = global_device_memory.snapshot()
    return {p: {"tracked": snap.get(p, {}).get("bytes", 0),
                "actual": actual[p]}
            for p in (pools if pools is not None else actual)}


global_tier = TierManager()
