"""Span tracer: one tree of timed, annotated spans per query.

Reference parity: Pinot's per-request ``Tracing``/``ServerQueryPhase``
timers (pinot-spi trace SPI), generalized the way "Query Processing on
Tensor Computation Runtimes" attributes tensor-runtime query time —
plan -> compile -> phase -> transfer — so the engine is tunable from
the tree a query brings back (EXPLAIN ANALYZE, traceRatio).

Unlike utils/trace.py (flat phase wall-ms for the response envelope,
kept for API parity), spans form a TREE: each span has a name, wall-ms
duration, free-form attributes, and children. The planner annotates the
plan span with its cost-model decision trace; the plan cache annotates
hit/miss and compile-vs-execute; the executor fences device execution
vs host transfer with block_until_ready and records estimated vs
measured selectivity; batch/mesh paths record per-dispatch fan-out and
the compaction capacity they actually ran with.

Zero cost when inactive: ``span()`` yields immediately unless a root
was started on this thread, so the instrumentation can live on hot
paths (per-segment launches) permanently. EXPLAIN ANALYZE
(query/explain.py) renders the tree; utils/ledger.py emits it as a
versioned ``query_trace`` ledger record so CPU-smoke and TPU hardware
rounds diff span-for-span.

``phase()`` is the same thing for a LAYER BOUNDARY (utils/phases.py
METERED_PHASES): one call, three outputs. It always adds its elapsed
microseconds, a count and, in a sampled share of nests, its self CPU
time to ``global_metrics`` (``phase_us_<name>``, ``phase_n_<name>``,
``phase_cpu_us_<name>`` and ``phase_cpu_wall_us_<name>``: the /metrics
scrape and the benchmark's per-layer readers); while a
``jax.profiler`` session runs it is also a
``TraceAnnotation("pinot.<name>", qid=...)``, so the program's spans sit
on the clock of the device's ``XLA Ops`` (tools/trace_phases.py); and
when the query is sampled it is the tree node ``span()`` would have
been, and feeds the flat ``OPTION(trace=true)`` envelope.
"""
from __future__ import annotations

import random
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

from . import phases as ph
from .metrics import global_metrics
from .trace import Tracing


class Span:
    """One timed node: name, wall duration, attributes, children."""

    __slots__ = ("name", "attrs", "children", "_t0", "duration_ms")

    def __init__(self, name: str, **attrs: Any):
        self.name = name
        self.attrs: Dict[str, Any] = dict(attrs)
        self.children: List["Span"] = []
        self._t0 = time.perf_counter()
        self.duration_ms = 0.0

    def finish(self) -> "Span":
        self.duration_ms = (time.perf_counter() - self._t0) * 1e3
        return self

    def annotate(self, **kv: Any) -> None:
        self.attrs.update(kv)

    def child(self, name: str) -> Optional["Span"]:
        """First child with this name (depth 1), or None."""
        for c in self.children:
            if c.name == name:
                return c
        return None

    def find(self, name: str) -> List["Span"]:
        """All descendants (including self) with this name, pre-order."""
        out = [self] if self.name == name else []
        for c in self.children:
            out.extend(c.find(name))
        return out

    def children_ms(self) -> float:
        return sum(c.duration_ms for c in self.children)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "ms": round(self.duration_ms, 3),
            "attrs": dict(self.attrs),
            "children": [c.to_dict() for c in self.children],
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Span":
        """Rebuild a tree serialized by to_dict() — the cluster broker
        stitches each server's remote-rooted tree (shipped in the
        response envelope) back under the scatter call span that
        dispatched it. Durations are trusted as measured by the remote
        process; only the gap to the enclosing call span (network +
        serde) is attributed broker-side."""
        s = cls(d.get("name", "?"), **dict(d.get("attrs") or {}))
        s.duration_ms = float(d.get("ms", 0.0))
        s.children = [cls.from_dict(c) for c in d.get("children") or []]
        return s


class _Stack(threading.local):
    # a thread that never started a tree reads the class's None, where a
    # getattr with a default raised and caught an AttributeError: half a
    # microsecond, twice a phase crossing
    stack: Optional[List[Span]] = None


class SpanTracer:
    """Thread-local span stack. start()/stop() bracket one traced query;
    span()/annotate() are permanent no-ops outside that bracket."""

    def __init__(self):
        self._local = _Stack()

    # -- lifecycle ---------------------------------------------------------
    def start(self, name: str, **attrs: Any) -> Span:
        root = Span(name, **attrs)
        self._local.stack = [root]
        return root

    def stop(self) -> Optional[Span]:
        stack = self._local.stack
        self._local.stack = None
        if not stack:
            return None
        root = stack[0]
        # close anything left open (an exception mid-query must still
        # yield a renderable tree)
        for s in reversed(stack):
            if s.duration_ms == 0.0:
                s.finish()
        return root

    def active(self) -> bool:
        return bool(self._local.stack)

    def current(self) -> Optional[Span]:
        stack = self._local.stack
        return stack[-1] if stack else None

    # -- recording ---------------------------------------------------------
    @contextmanager
    def span(self, name: str, **attrs: Any):
        stack = self._local.stack
        if not stack:
            yield None
            return
        s = Span(name, **attrs)
        stack[-1].children.append(s)
        stack.append(s)
        try:
            yield s
        finally:
            s.finish()
            if stack and stack[-1] is s:
                stack.pop()

    def annotate(self, **kv: Any) -> None:
        cur = self.current()
        if cur is not None:
            cur.annotate(**kv)

    def add_event(self, name: str, duration_ms: float,
                  **attrs: Any) -> None:
        """Attach a pre-measured child span (an injected fault's delay,
        utils/faults.py) under the current span."""
        cur = self.current()
        if cur is not None:
            s = Span(name, **attrs)
            s.duration_ms = float(duration_ms)
            cur.children.append(s)


span_tracer = SpanTracer()


def sample_decision(query_id: str, ratio: float) -> bool:
    """traceRatio production-sampling decision, deterministic in the
    query id: md5(queryId) maps to a uniform fraction in [0, 1) and the
    query is sampled when that fraction is below ``ratio``. Pure in the
    qid so broker replicas and retried dispatches of the SAME query
    agree on the decision without coordination (the round-10
    traceContext then carries the flag to every server the scatter
    touches). ratio<=0 never samples, ratio>=1 always samples."""
    if ratio <= 0.0:
        return False
    if ratio >= 1.0:
        return True
    import hashlib

    h = int(hashlib.md5(str(query_id).encode()).hexdigest()[:8], 16)
    return (h / float(1 << 32)) < ratio


# module-level conveniences (the form hot paths import)
def span(name: str, **attrs: Any):
    return span_tracer.span(name, **attrs)


def annotate(**kv: Any) -> None:
    span_tracer.annotate(**kv)


def add_event(name: str, duration_ms: float, **attrs: Any) -> None:
    span_tracer.add_event(name, duration_ms, **attrs)


def tracing_active() -> bool:
    return span_tracer.active()


# ---------------------------------------------------------------------------
# layer boundaries: counters always, profiler events and tree nodes on demand
# ---------------------------------------------------------------------------

# per metered phase: its counters and its profiler event's name (no "#"
# in one: TraceMe cuts a name there)
_KEYS = {n: ("phase_us_" + n, "phase_n_" + n, "pinot." + n,
             "phase_cpu_us_" + n, "phase_cpu_wall_us_" + n)
         for n in ph.METERED_PHASES}
_DISPATCH = {f: "kernel_dispatches_" + f for f in ph.KERNEL_FAMILIES}
_query = threading.local()      # .qid: the broker's id of the query in hand
_annotation: Any = None         # jax.profiler.TraceAnnotation, on first use
_thread_ns = time.thread_time_ns
_rand = random.random
# The share of a thread's outermost crossings whose nest reads the
# thread's CPU clock. On the chip hosts that read is a system call of
# 6 µs (PERF.md, PR 37: 2.4 -> 15.4 µs a crossing when every crossing
# read it, and the clock moves in 10 ms ticks there), so one nest in
# eight is read: a Q1 request pays 40 µs, not 250.
CPU_SHARE = 0.125


class _Open(threading.local):
    """This thread's open crossings, innermost last."""

    def __init__(self):
        self.stack: List["phase"] = []


_open = _Open()


def _annotation_cls():
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


def set_query_id(qid: Optional[str]) -> None:
    """Name the query this thread works for from here on: the broker's
    query id, which rides every scatter call in ``traceContext.queryId``
    sampled or not, so one request's profiler events share ``qid`` across
    the two nodes. A phase still open picks it up when it closes."""
    _query.qid = qid


class phase:
    """Context manager for one crossing of a metered layer boundary.
    ``.ms`` holds the elapsed wall-ms after exit and ``.t0`` the start
    (``time.perf_counter``), for callers that report the same
    measurement elsewhere (``ScatterResult.serde_ms``, the wire header's
    ``serdeEncodeMs``); ``.span`` is the tree node, or None.

    A crossing that reads the CPU clock adds its self CPU to
    ``phase_cpu_us_<name>`` and its wall time to
    ``phase_cpu_wall_us_<name>``; one that does not adds 0 to both. Self
    CPU is this thread's CPU time (``time.thread_time_ns``) inside the
    crossing less that of the metered crossings nested inside it on the
    same thread (a stack of open crossings a thread), so summed over the
    phases it counts each CPU microsecond of a thread once, and a child
    on another thread (``scatter``'s pool) takes nothing from its
    parent. Whether the clock is read is drawn once a nest, at its
    outermost crossing on the thread (``CPU_SHARE``), so a parent and
    its children are read together or not at all. A phase's CPU time is
    then ``phase_us`` × ``phase_cpu_us`` ÷ ``phase_cpu_wall_us``; wall
    time less CPU time is the thread off its CPU: blocked on the device
    or a peer, or waiting for the interpreter lock or for a core."""

    __slots__ = ("name", "attrs", "qid", "span", "t0", "ms", "_keys",
                 "_event", "_c0", "_child_ns", "_stack")

    def __init__(self, name: str, qid: Optional[str] = None, **attrs: Any):
        self.name = name
        self.attrs = attrs
        self.qid = qid
        self.ms = 0.0

    def __enter__(self) -> "phase":
        # KeyError: the name is not in phases.METERED_PHASES
        self._keys = _KEYS[self.name]
        self.span = self._event = None
        stack = span_tracer._local.stack
        if stack:
            s = self.span = Span(self.name, **self.attrs)
            stack[-1].children.append(s)
            stack.append(s)
        if (_annotation or _annotation_cls()).is_enabled():
            qid = self.qid = self.qid or getattr(_query, "qid", None)
            kw = self.attrs if qid is None else {"qid": qid, **self.attrs}
            self._event = _annotation(self._keys[2], **kw)
            self._event.__enter__()
        self._stack = stack = _open.stack
        read = (stack[-1]._c0 is not None) if stack else _rand() < CPU_SHARE
        stack.append(self)
        self.t0 = time.perf_counter()
        if read:
            self._child_ns = 0
            self._c0 = _thread_ns()
        else:
            self._c0 = None
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self.t0
        c0 = self._c0
        if c0 is not None:
            cpu_ns = _thread_ns() - c0
        stack = self._stack
        stack.pop()
        name = self.name
        self.ms = ms = dt * 1e3
        if self._event is not None:
            if self.qid is None:
                qid = getattr(_query, "qid", None)
                if qid is not None:
                    self._event.set_metadata(qid=qid)
            self._event.__exit__(*exc)
        s = self.span
        if s is not None:
            s._t0, s.duration_ms = self.t0, ms
            tree = span_tracer._local.stack
            if tree and tree[-1] is s:
                tree.pop()
        if name in ph.TRACED_PHASES:
            scope = Tracing.active()
            if scope is not None:
                scope.add_phase(name, ms)
        us_key, n_key, _event, cpu_key, cpu_wall_key = self._keys
        us = round(dt * 1e6)
        if c0 is None:
            # zeros, so that a phase crossed in a window always has its
            # CPU counters beside it, read or not
            global_metrics.count_four(us_key, us, n_key, 1,
                                      cpu_key, 0, cpu_wall_key, 0)
            return
        if stack:
            stack[-1]._child_ns += cpu_ns
        global_metrics.count_four(us_key, us, n_key, 1,
                                  cpu_key, (cpu_ns - self._child_ns + 500)
                                  // 1000, cpu_wall_key, us)


def record_phase(name: str, seconds: float) -> None:
    """Counters of a boundary crossed on two threads (``server_queue``:
    arrival on the handler's thread to the scheduler worker's start),
    which no ``with`` block can bracket. It has no CPU counter: the wait
    belongs to no thread."""
    us_key, n_key = _KEYS[name][:2]
    global_metrics.count_pair(us_key, round(seconds * 1e6), n_key, 1)


def queue_event(qid: Optional[str]) -> Any:
    """The ``pinot.server_queue`` profiler event of a query that waits
    for a scheduler worker, open from now, or None while no profiler
    session runs (the untraced path makes nothing). The handler's thread
    opens it at arrival and the worker that starts the query closes it,
    so it lands on the worker's thread and the handler is never woken for
    it: the wait spans two threads, so its counters come from
    ``record_phase``."""
    if not (_annotation or _annotation_cls()).is_enabled():
        return None
    event = _annotation(_KEYS[ph.SERVER_QUEUE][2],
                        **({} if qid is None else {"qid": qid}))
    event.__enter__()
    return event


def count_dispatch(family: str, dict_forms: Tuple[int, int] = (0, 0),
                   float_forms: Tuple[int, int] = (0, 0)) -> None:
    """One kernel program launched: ``kernel_dispatches`` and
    ``kernel_dispatches_<family>`` (phases.KERNEL_FAMILIES). Where the
    launched plan decodes dictionary-encoded value columns,
    ``dict_forms`` (ops/kernels.dict_decode_forms) says how many by a
    select chain and how many by a gather: ``dict_decode_select`` and
    ``dict_decode_gather``. Where it has float aggregates,
    ``float_forms`` (ops/kernels.float_acc_forms) says how many it keeps
    at float64 with blocked sums and how many pass through float32:
    ``float_acc_wide`` and ``float_acc_narrow``."""
    global_metrics.count_pair("kernel_dispatches", 1, _DISPATCH[family], 1)
    if dict_forms != (0, 0):
        global_metrics.count_pair("dict_decode_select", dict_forms[0],
                                  "dict_decode_gather", dict_forms[1])
    if float_forms != (0, 0):
        global_metrics.count_pair("float_acc_wide", float_forms[0],
                                  "float_acc_narrow", float_forms[1])


def device_fence(out: Any) -> None:
    """block_until_ready fence separating device execution from host
    transfer in the span tree — only when a trace is being taken, so the
    untraced path keeps XLA's async dispatch pipelining."""
    if span_tracer.active():
        import jax

        jax.block_until_ready(out)
