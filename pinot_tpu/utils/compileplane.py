"""Compile-plane forensics: staged XLA compiles, the warmup-debt ledger
and compile-storm alerting (ISSUE 15 tentpole).

Every observability layer before this round watched *execution*; the
compile plane — the dominant cold-start cost per *Automatic Full
Compilation of Julia Programs and ML Models to Cloud TPUs*, and the
price *Query Processing on Tensor Computation Runtimes* pays to map
relational plans onto a tensor runtime — was visible only as a retrace
counter. This module makes every engine compile a first-class event:

- ``StagedFn`` wraps a ``jax.jit`` callable with explicit AOT staging
  (``.lower()`` then ``.compile()``) keyed by the concrete argument
  signature, so the first call of every XLA program yields a measured
  ``lower_ms``/``compile_ms`` split plus the executable's
  ``memory_analysis()`` bytes and ``cost_analysis()`` FLOP estimate
  (``None`` where the backend doesn't report them — never fabricated).
  Warm calls are one signature lookup and the compiled executable —
  semantically identical to the implicit jit they replace.
  ``PINOT_COMPILE_FORENSICS=0`` disables staging (pure jit fallback).
- every staged compile classifies its **trigger** through the plan
  cache's RetraceDetector (ops/plan_cache.py) into the taxonomy
  {cold, warmup, overflow_retry, drift_requantize, lru_evict_rebuild,
  retrace} and lands ONE validated ``compile_event`` ledger record
  (utils/ledger.py) in the global ``CompileLog``: normalized plan-shape
  hash (utils/shapehash — the SAME function span_diff keys on, so the
  compile plane joins the span plane), plan-cache key fingerprint,
  backend, donated flag, owning qid/sql when the compiling thread is
  executing a query.
- the log feeds per-node warmup-debt counters (``compiles_total``,
  ``compile_ms_total``, ``compiles_<trigger>``) into
  utils.metrics.global_metrics, and a rate-windowed **compile-storm**
  detector: when post-warmup compiles (retrace + lru_evict_rebuild)
  per minute cross the watermark (``PINOT_COMPILE_STORM_PER_MIN``), a
  validated ``alert`` ledger record fires — deterministically, once
  per crossing — into the ledger, the bounded alert ring (consoles +
  /debug/compile) and the ``compile_storm_alerts`` counter.

Zero-cost contract: with no ledger configured the hot path pays only
the warm-signature lookup; record construction, validation and I/O
happen exclusively at compile time (already an XLA-compile-sized
event), and tests pin <1% wall overhead on the SSB corpus
(tests/test_compile_forensics.py, r15-style paired estimator).
"""
from __future__ import annotations

import logging
import os
import threading
import time
import uuid
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from . import phases as ph
from .metrics import global_metrics
from .shapehash import shape_key
from .spans import span, span_tracer

_log = logging.getLogger(__name__)

TRIGGERS = ("cold", "warmup", "overflow_retry", "drift_requantize",
            "lru_evict_rebuild", "retrace")
# the storm signal: compiles a warmed node should NOT be paying
POST_WARMUP_TRIGGERS = ("retrace", "lru_evict_rebuild")
DEFAULT_STORM_PER_MIN = 30
STORM_WINDOW_S = 60.0
RING_CAPACITY = 512
ALERT_RING_CAPACITY = 64

# process identity for fleet dedup (cluster/rollup.py plan_shapes): two
# in-process node roles shipping one shared compile ledger must not
# double-count an event — (proc, seq) is the event's unique id
PROC_TOKEN = f"{os.getpid()}-{uuid.uuid4().hex[:6]}"

_STAGING = [os.environ.get("PINOT_COMPILE_FORENSICS") != "0"]


def staging_enabled() -> bool:
    return _STAGING[0]


def set_staging_enabled(on: bool) -> None:
    """Test/ops hatch: flip explicit AOT staging off (pure jax.jit
    fallback — no events, no lower/compile split)."""
    _STAGING[0] = bool(on)


def _backend() -> str:
    try:
        import jax

        return jax.default_backend()
    except Exception:
        return "unknown"


def key_fingerprint(token: Any) -> str:
    """Stable-in-process 12-hex fingerprint of a cache key/token (keys
    embed plan structures whose repr is deterministic)."""
    import hashlib

    return hashlib.sha1(repr(token).encode()).hexdigest()[:12]


def _current_sql_qid() -> Tuple[Optional[str], Optional[str]]:
    """The sql/qid of the query the compiling thread is executing on
    behalf of (engine/accounting registration), when any."""
    try:
        from ..engine.accounting import global_accountant

        qid = global_accountant.current_query_id()
        if qid is None:
            return None, None
        u = global_accountant.usage(qid)
        return (getattr(u, "sql", None) if u is not None else None), qid
    except Exception:
        return None, None


class CompileLog:
    """The process-global compile-event sink (module docstring)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.path: Optional[str] = \
            os.environ.get("PINOT_COMPILE_LEDGER") or None
        try:
            self.storm_per_min = int(
                os.environ.get("PINOT_COMPILE_STORM_PER_MIN",
                               DEFAULT_STORM_PER_MIN))
        except ValueError:
            self.storm_per_min = DEFAULT_STORM_PER_MIN
        self._seq = 0
        self._events: deque = deque(maxlen=RING_CAPACITY)
        self._alerts: deque = deque(maxlen=ALERT_RING_CAPACITY)
        # storm detection delegates to the ONE latch/window
        # implementation (utils/alerts.RateWindowRule, ISSUE 17):
        # registered on the generic manager so /debug surfaces and the
        # SLO plane share a single alerting plane — the rule owns the
        # (ts, trigger) window deque and the fire-once latch verbatim
        from .alerts import global_alerts
        self._storm_rule = global_alerts.rate_rule(
            "compile_storm", self.storm_per_min, STORM_WINDOW_S)
        self.events_written = 0
        self.alerts_fired = 0

    # -- config ------------------------------------------------------------
    def configure(self, path: Optional[str] = None,
                  storm_per_min: Optional[int] = None) -> "CompileLog":
        with self._lock:
            if path is not None:
                self.path = path or None
            if storm_per_min is not None:
                self.storm_per_min = int(storm_per_min)
        return self

    def configure_path_if_unset(self, path: str) -> bool:
        """Atomic first-wins path adoption (brokers auto-point the log
        at their stats/trace ledger): the check-and-set runs under the
        lock so two concurrently constructed brokers cannot both
        observe 'unset' and split the event stream across two files."""
        with self._lock:
            if self.path:
                return False
            self.path = path or None
            return self.path is not None

    def reset(self) -> None:
        """Clear rings/stream/storm state (tests, chaos gates); the
        configured path and watermark survive — and so does the seq
        counter: (proc, seq) is an event's IDENTITY for fleet dedup
        (rank_plan_shapes / warmup_report), and restarting it would
        make post-reset events alias pre-reset ones in a ledger that
        spans the reset."""
        with self._lock:
            self._events.clear()
            self._alerts.clear()
            self.events_written = 0
            self.alerts_fired = 0
        self._storm_rule.reset()

    # -- recording (compile-time only: never on the warm hot path) --------
    def record(self, site: str, trigger: str, lower_ms: float,
               compile_ms: float, key_fp: str, donated: bool,
               memory_bytes: Optional[int] = None,
               flops: Optional[float] = None) -> Dict[str, Any]:
        from . import ledger as uledger

        sql, qid = _current_sql_qid()
        global_metrics.count("compiles_total")
        global_metrics.count(f"compiles_{trigger}")
        global_metrics.count("compile_ms_total",
                             round(lower_ms + compile_ms, 3))
        with self._lock:
            self._seq += 1
            seq = self._seq
        fields: Dict[str, Any] = {
            "site": site, "trigger": trigger,
            "plan_shape": shape_key(sql) if sql else None,
            "key_fp": key_fp, "backend": _backend(),
            "lower_ms": round(lower_ms, 3),
            "compile_ms": round(compile_ms, 3),
            "donated": bool(donated), "proc": PROC_TOKEN, "seq": seq,
            "memory_bytes": memory_bytes, "flops": flops,
        }
        if sql:
            fields["sql"] = sql[:160]
        if qid:
            fields["qid"] = qid
        rec = uledger.make_record("compile_event", **fields)
        path = self.path
        if path:
            try:
                uledger.append_record(rec, path)
                with self._lock:
                    self.events_written += 1
            except OSError:
                # observability must never fail the data path
                global_metrics.count("compile_event_write_errors")
        with self._lock:
            self._events.append(rec)
        self._note_storm(rec)
        return rec

    def _note_storm(self, rec: Dict[str, Any]) -> None:
        """Rate-windowed compile-storm detection: deterministic in the
        event stream (one alert per watermark crossing). The window +
        latch live in the shared RateWindowRule (utils/alerts) — the
        watermark is passed per call so ``configure()`` keeps working;
        non-storm triggers still prune/evaluate (count=False) so the
        rate decays and the latch re-arms on quiet streams."""
        now = time.monotonic()
        watermark = self.storm_per_min
        fire, rate = self._storm_rule.note(
            now, tag=rec["trigger"],
            count=rec["trigger"] in POST_WARMUP_TRIGGERS,
            watermark=watermark)
        global_metrics.gauge("compile_storm_per_min", rate)
        global_metrics.gauge("compile_storm_watermark", watermark)
        if fire is not None:
            self._fire_alert(fire["rate"], int(fire["watermark"]),
                             fire["tags"])

    def _fire_alert(self, rate: int, watermark: int,
                    counts: Dict[str, int]) -> Dict[str, Any]:
        from .alerts import global_alerts

        rec = global_alerts.fire(
            "compile_storm", "warn", rate, watermark, STORM_WINDOW_S,
            triggers=counts, backend=_backend(), proc=PROC_TOKEN,
            path=self.path, counter="compile_storm_alerts",
            detail=f"{rate} post-warmup compiles/min >= watermark "
                   f"{watermark} (retrace churn / eviction rebuild "
                   "thrash)")
        span_tracer.annotate(compile_storm=True)
        with self._lock:
            self._alerts.append(rec)
            self.alerts_fired += 1
        return rec

    # -- serving -----------------------------------------------------------
    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def alerts(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._alerts)

    def trigger_stream(self) -> List[Tuple[str, str, Optional[str]]]:
        """(site, trigger, plan_shape) triples of the ring — the chaos
        gate's compile-attribution comparison stream."""
        with self._lock:
            return [(r["site"], r["trigger"], r.get("plan_shape"))
                    for r in self._events]

    def snapshot(self, alerts_top: int = 5) -> Dict[str, Any]:
        """GET /debug/compile payload: warmup-debt counters + the event
        and alert rings (newest first)."""
        snap = global_metrics.snapshot()
        out = compile_health(snap)
        with self._lock:
            out["events"] = list(self._events)[::-1]
            out["alerts"] = list(self._alerts)[::-1][:alerts_top]
            out["ledger"] = self.path
            out["events_written"] = self.events_written
        return out


global_compile_log = CompileLog()


def compile_health(snapshot: Dict[str, Any]) -> Dict[str, Any]:
    """The compile-plane block the broker /metrics endpoint and both
    consoles render beside the batching block: warmup-debt totals,
    per-trigger counters, and the compile-storm watermark gauge +
    recent alerts."""
    c = snapshot.get("counters", {})
    g = snapshot.get("gauges", {})
    by_trigger = {t: c[f"compiles_{t}"] for t in TRIGGERS
                  if f"compiles_{t}" in c}
    return {
        "compiles": c.get("compiles_total", 0),
        "compile_ms_total": round(float(c.get("compile_ms_total", 0)), 3),
        "by_trigger": by_trigger,
        "post_warmup": sum(by_trigger.get(t, 0)
                           for t in POST_WARMUP_TRIGGERS),
        "storm_per_min": g.get("compile_storm_per_min", 0),
        "storm_watermark": g.get("compile_storm_watermark",
                                 global_compile_log.storm_per_min),
        "storm_alerts": c.get("compile_storm_alerts", 0),
        "recent_alerts": [
            {"ts": a.get("ts"), "rate_per_min": a.get("rate_per_min"),
             "detail": a.get("detail")}
            for a in global_compile_log.alerts()[-3:]],
    }


# ---------------------------------------------------------------------------
# staged AOT dispatch
# ---------------------------------------------------------------------------

def _sig(args: Tuple[Any, ...]) -> Tuple:
    """Hashable abstract signature of concrete call args: pytree
    structure + per-leaf (dtype, shape), with bare Python scalars keyed
    by type so weak-typed literals can't alias committed arrays."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    out = []
    for x in leaves:
        dt = getattr(x, "dtype", None)
        if dt is not None:
            out.append((str(dt), tuple(getattr(x, "shape", ()))))
        else:
            out.append((type(x).__name__,))
    return (treedef, tuple(out))


def _analyses(compiled) -> Tuple[Optional[int], Optional[float]]:
    """(executable memory bytes, FLOP estimate) where the backend
    reports them; (None, None) otherwise — never fabricated."""
    mem = None
    flops = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = int(getattr(ma, "temp_size_in_bytes", 0)
                      + getattr(ma, "output_size_in_bytes", 0))
    except Exception:
        mem = None
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)) and ca:
            ca = ca[0]
        if isinstance(ca, dict) and ca.get("flops") is not None:
            flops = float(ca["flops"])
    except Exception:
        flops = None
    return mem, flops


def resolve_trigger(raw: str, hints: Dict[str, Any]) -> str:
    """RetraceDetector classification -> the event taxonomy. ``raw``
    'expected' refines through the caller's bracket context (the drift
    re-quantize pins its kind; every other expected() bracket is the
    overflow retry ladder); a 'retrace' of a key the caller knows it
    LRU-evicted is an eviction rebuild, not an unexplained retrace.

    Eviction memory exists where the cache owner can observe its own
    evictions (KernelPlanCache._evicted_keys, ragged
    _KernelRegistry._evicted). The functools.lru_cache-backed sites
    (select/segmented/kernel/vmapped/vector/multistage) expose no
    eviction hook, so a capacity rebuild there reports 'retrace' —
    accepted: their maxsizes (256-1024) sit far above real working
    sets, and a workload that genuinely churns them IS paying
    unexplained recompiles worth alerting on."""
    if raw == "expected":
        return hints.get("expected_kind") or "overflow_retry"
    if raw == "retrace" and hints.get("evicted"):
        return "lru_evict_rebuild"
    return raw


class StagedFn:
    """Explicit-AOT wrapper around one ``jax.jit`` callable: per
    concrete-signature lower/compile staging with single-flight
    compilation, trigger classification through the RetraceDetector,
    and one compile_event per XLA compile. Falls back to the wrapped
    jit on any staging failure (or PINOT_COMPILE_FORENSICS=0) — the
    instrumentation must never become the data path's failure mode."""

    def __init__(self, fn, site: str, token: Any,
                 donated: bool = False,
                 hints: Optional[Dict[str, Any]] = None,
                 key_fp: Optional[str] = None):
        self._fn = fn
        self.site = site
        self.token = token
        self.donated = donated
        # consumed by the FIRST staging only (the classification the
        # cache-miss context prepared); extra-signature compiles
        # classify fresh against (token, signature)
        self._hints: Optional[Dict[str, Any]] = dict(hints or {})
        self.key_fp = key_fp or key_fingerprint(token)
        self._compiled: Dict[Tuple, Any] = {}
        # signatures whose compile was CLASSIFIED on the fallback path
        # (staging off/broken): the retrace-detection plane predates
        # staging and must never be disabled with it
        self._observed: Dict[Tuple, bool] = {}
        # sig -> the compiler's own error for a program it rejected
        self._rejected: Dict[Tuple, Exception] = {}
        # sig -> Event while that signature's compile is in flight:
        # single-flight is per SIGNATURE (the CubeCache idiom), so
        # concurrent DIFFERENT shapes keep compiling in parallel
        # exactly as implicit jit did — _lock is only ever held for
        # dict bookkeeping, never across an XLA compile
        self._building: Dict[Tuple, threading.Event] = {}
        self._lock = threading.Lock()
        self._broken = False

    def set_hints(self, **hints: Any) -> None:
        """Refine the pending first-staging hints (no-op once the
        first compile consumed them) — the plan cache attaches the
        eviction-rebuild hint to the SURVIVING entry at publish time,
        where concurrent same-key misses have already been resolved."""
        with self._lock:
            if self._hints is not None:
                self._hints.update(hints)

    def __call__(self, *args):
        if self._broken or not _STAGING[0]:
            return self._fallback(args)
        try:
            sig = _sig(args)
        except Exception:
            return self._fn(*args)
        compiled = self._compiled.get(sig)  # GIL-atomic dict read
        if compiled is None:
            compiled = self._stage(sig, args)
            if compiled is None:
                return self._fn(*args)
        return compiled(*args)

    def _fallback(self, args):
        """Implicit-jit path (PINOT_COMPILE_FORENSICS=0 or a staging
        failure). The detector classification STILL fires once per
        signature — the pre-round-20 retrace plane (counters, span
        annotation, storm input via triggers) must not silently vanish
        with the staging machinery; only the lower/compile split and
        the compile_event record (unmeasurable here — timings are
        never fabricated) are lost."""
        try:
            sig = _sig(args)
        except Exception:
            return self._fn(*args)
        # unlocked membership probe only gates the locked observe (the
        # _compiled.get fast-path idiom __call__ uses): the
        # authoritative check-and-insert re-runs under the lock
        if sig not in self._compiled and sig not in self._observed:
            self._observe_fallback(sig)
        return self._fn(*args)

    def _observe_fallback(self, sig: Tuple) -> None:
        with self._lock:
            if sig in self._compiled or sig in self._observed:
                return
            self._observed[sig] = True
            hints = self._hints if self._hints is not None else {}
            first = self._hints is not None
            self._hints = None
        try:
            self._classify(
                self.token if first else (self.token, sig), hints)
        except Exception:
            pass

    def _classify(self, token: Any, hints: Dict[str, Any]) -> str:
        from ..ops.plan_cache import global_plan_cache

        det = global_plan_cache.detector
        if hints.get("expected_kind") and not det.expected_active():
            # the miss context pinned a deliberate-recompile kind
            # (drift re-quantize / known-overflow entry) but its
            # expected() bracket closed before this first run —
            # re-raise the bracket so the detector still counts it as
            # expected, never a retrace
            with det.expected():
                raw = det.classify_compile(token)
        else:
            raw = det.classify_compile(token)
        return resolve_trigger(raw, hints)

    def _stage(self, sig: Tuple, args: Tuple):
        while True:
            with self._lock:
                compiled = self._compiled.get(sig)
                if compiled is not None:
                    return compiled
                if sig in self._rejected:
                    raise self._rejected[sig]
                if self._broken:
                    return None
                waiting = self._building.get(sig)
                if waiting is None:
                    self._building[sig] = threading.Event()
                    hints = self._hints if self._hints is not None \
                        else {}
                    first = self._hints is not None
                    self._hints = None
                    break        # this thread builds this signature
            # another thread is compiling this exact signature: wait
            # for its publication instead of duplicating the compile
            # (on its failure the loop re-enters and observes _broken)
            waiting.wait(timeout=600)
        # first signature: the token itself (the detector key the miss
        # context classified against); an EXTRA signature of a warm
        # wrapper is a new XLA program of its own — keyed per
        # signature so a naturally shape-polymorphic kernel's second
        # shape reads cold/warmup, never a phantom retrace
        token = self.token if first else (self.token, sig)
        import jax
        try:
            trigger = self._classify(token, hints)
            with span("build_kernel", staged=True, site=self.site,
                      trigger=trigger) as sp:
                t0 = time.perf_counter()
                with span("lower"):
                    lowered = self._fn.lower(*args)
                t1 = time.perf_counter()
                with span("compile"):
                    compiled = lowered.compile()
                t2 = time.perf_counter()
                mem, flops = _analyses(compiled)
                if sp is not None:
                    sp.annotate(memory_bytes=mem, flops=flops)
            global_compile_log.record(
                self.site, trigger, (t1 - t0) * 1e3,
                (t2 - t1) * 1e3, self.key_fp, self.donated,
                memory_bytes=mem, flops=flops)
        except jax.errors.JaxRuntimeError as e:
            # the COMPILER rejected the program (XLA / Mosaic
            # RESOURCE_EXHAUSTED and the like): the implicit jit would
            # run the same compile to the same verdict, so falling back
            # only pays it twice — minutes, at SSB sizes on the chip.
            # The query fails with the compiler's message, and so does
            # every later call with this signature, without compiling
            # again.
            with self._lock:
                self._rejected[sig] = e
                ev = self._building.pop(sig, None)
            if ev is not None:
                ev.set()
            global_metrics.count("compile_rejections")
            raise
        except Exception:
            # staging infrastructure failure: permanent per-fn
            # fallback to the implicit jit (which re-raises any REAL
            # kernel error on the normal path), logged with its
            # traceback. The signature was
            # already CLASSIFIED above — mark it observed so the
            # fallback path never classifies the same compile twice
            # (the detector/compile_event reconciliation invariant).
            with self._lock:
                self._broken = True
                self._observed[sig] = True
                ev = self._building.pop(sig, None)
            if ev is not None:
                ev.set()
            global_metrics.count("compile_staging_fallbacks")
            _log.warning("staged compile failed at site %r; falling back "
                         "to implicit jit", self.site, exc_info=True)
            return None
        with self._lock:
            self._compiled[sig] = compiled
            ev = self._building.pop(sig, None)
        if ev is not None:
            ev.set()
        return compiled


def kernel_jit(fn, family: str, **jit_kwargs):
    """``jax.jit`` of a kernel family's function under its stable name:
    the profiler's ``XLA Modules`` line then reads
    ``jit_pinot_<family>(...)`` whatever the builder's closure is called
    (the module name is part of the persistent compile cache's key, so a
    rename compiles every program of the family once more)."""
    import jax
    if family not in ph.KERNEL_FAMILIES:
        raise KeyError(f"{family!r} is not in phases.KERNEL_FAMILIES")
    fn.__name__ = fn.__qualname__ = ph.MODULE_PREFIX + family
    return jax.jit(fn, **jit_kwargs)


def staged(fn, site: str, token: Any, donated: bool = False,
           hints: Optional[Dict[str, Any]] = None) -> StagedFn:
    """Wrap a jax.jit callable for staged-compile forensics (the one
    spelling every compile site uses)."""
    return StagedFn(fn, site, token, donated=donated, hints=hints)


def clear_staged_caches() -> None:
    """Drop every staged-kernel cache in the engine (plan cache +
    detector included) so a fresh pass re-pays — and re-attributes —
    its compiles. Chaos/test tooling only; never on a serving path."""
    from ..engine import batch, ragged
    from ..ops import kernels, plan_cache

    plan_cache.global_plan_cache.clear()
    plan_cache.global_cube_cache.clear()
    ragged._kernels.clear()
    batch._vmapped_kernel_cached.cache_clear()
    kernels.jitted_select_kernel.cache_clear()
    kernels.jitted_segmented_compact.cache_clear()
    kernels.jitted_kernel.cache_clear()
    kernels.jitted_topgroups.cache_clear()
    try:
        from ..index import vector

        vector._batched_flat_kernel.cache_clear()
        vector._batched_ivf_kernel.cache_clear()
    except Exception:
        pass
    try:
        from ..multistage import device_join, window

        device_join._jitted_equi_join.cache_clear()
        window._seg_scan_jit.cache_clear()
        window._segment_agg_jit.cache_clear()
    except Exception:
        pass
    try:
        from ..multistage import fused

        fused._fused_program.cache_clear()
    except Exception:
        pass
