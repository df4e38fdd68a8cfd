"""The program's phases and the device's operations on one clock.

    python3 tools/trace_phases.py --workload <cell> --seed <n> --seconds <s> \
        [--keep chiprun_out/<name>.xplane.pb]
    python3 tools/trace_phases.py --xplane <file.xplane.pb> [--sample 20]
    python3 tools/trace_phases.py --phase-cost 100000

From one ``.xplane.pb`` of a traced run of a benchmark cell it prints

(a) per metered phase (``pinot.<name>`` host events, written by
    ``pinot_tpu/utils/spans.phase`` while a profiler session runs): count,
    total, self time (its duration less the part its children cover) and
    milliseconds a query; and, from the window's ``phase_cpu_us_<name>``
    and ``phase_cpu_wall_us_<name>`` counters (a run of a cell; not from a
    bare ``--xplane``), its self CPU a query and the share of its self
    time its thread was off a CPU;
(b) the device's idle gaps inside the window, each attributed to the
    innermost ``pinot.*`` event open on the host at that time (of the open
    events, the one that started last: a request walks client -> broker
    handler -> scatter pool thread -> server handler -> scheduler worker,
    so the chain nests in time across threads), longest total first,
    beside the benchmark's own ``in_request:<shape>`` attribution;
(c) device self time by program (``XLA Modules`` names: the kernel
    families ``jit_pinot_<family>`` against the eager one-operation
    programs) and by named scope (the innermost ``pinot.<stage>`` in an
    operation's ``tf_op`` stat, which holds its ``op_name``).

``benchmark/run.py`` deletes its trace directory when a run ends, so the
first form keeps the file itself: it calls ``benchmark.run.run_cell`` with
``benchmark.trace.xplane.load`` wrapped to copy the file to ``--keep``
first (default ``chiprun_out/<cell>.<seed>.xplane.pb``), prints the run's
result line, then reads the kept file. Nothing under ``benchmark/``
changes for it. After the result line it prints how far the work counters
``kernel_dispatches``, ``dict_decode_select`` / ``dict_decode_gather`` and
``float_acc_wide`` / ``float_acc_narrow``
(``pinot_tpu/utils/spans.count_dispatch``), ``sparse_post_results`` /
``sparse_post_probes_<P>`` (``engine/executor.finish_kernel``: the per-segment
route's sparse posts, by the probe count their tail took),
``plan_launch_windowed`` / ``_solo`` (``engine/executor``: the route's
launches issued from a launch window, and alone),
``mesh_live_list_sparse`` / ``_dense`` (``parallel/distributed``: where a
mesh query's transfer compaction took its live list from) and the
micro-batcher's (``engine/ragged.py``: ``batched_queries``,
``solo_fallback_<reason>``, the background's builds and compiles, the
crossings of ``ragged_wait`` and ``fused_execute``) moved a request of the
window. The batcher's two programs show in table (c) as
``jit_pinot_cube_build`` / ``jit_pinot_ragged_fused`` and under the scopes
``pinot.cube_build`` / ``pinot.cube_combine``.
Then two readings of the interpreter lock that do not rest on the thread
CPU clock: a probe thread's lateness over the window (it sleeps
``LOCK_PROBE_S`` in a loop, and waking needs the lock, so past the host's
timer slack, which a one-client cell reads, the lateness is the wait any
thread that wants the lock meets), and the host-work leaves' wall time a
second of the window (``phases.HOST_WORK_PHASES``: above 1, more of them
are under way than the one thread the lock lets run Python at a time).
Interval arithmetic (``merge``, ``clip``, ``self_times``)
is ``benchmark/trace/reduce.py``'s, by import.

Self times group events by their ``qid`` stat, so they are right for any
number of clients as long as one query's events nest: one server call at
a time. With several servers answering one query in parallel the
``scatter_call`` events of a query overlap and their children's time is
taken from the wrong parent; read the enclosing ``scatter`` then.
"""
from __future__ import annotations

import argparse
import bisect
import heapq
import json
import os
import re
import shutil
import sys
import threading
import time
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import xplane_raw  # noqa: E402  (tools/, beside this file)
from benchmark.readers import phase_cpu  # noqa: E402
from benchmark.trace import reduce as red  # noqa: E402
from benchmark.trace import xplane  # noqa: E402

PHASE_PREFIX = "pinot."
SCOPE = re.compile(r"pinot\.[a-z_]+")
RUN_ID = re.compile(r"\(\d+\)$")          # jit_pinot_dense_vmap(1234567)
WORK_COUNTERS = ("kernel_dispatches", "dict_decode_select",
                 "dict_decode_gather", "sparse_post_results",
                 # the micro-batcher (engine/ragged.py): queries answered
                 # by a fused launch, the launches, the cube builds and
                 # combine compiles its background made, and how often the
                 # two phases it owns were crossed
                 "batched_queries", "batched_dispatches",
                 "kernel_dispatches_ragged_fused",
                 "kernel_dispatches_cube_build", "cube_builds_background",
                 "fused_compiles_background", "phase_n_ragged_wait",
                 "phase_n_fused_execute")
# and every counter of these prefixes the program has counted: one a
# probe count of the kernel's ladder (ops/kernels._sparse_post_sizes),
# one a reason a submission to the micro-batcher went solo, one where a
# mesh program took the live list of its transfer compaction from
# (parallel/distributed.lists_live_groups_sparse), one whether a launched
# plan's float aggregates stayed float64 (ops/kernels.float_acc_forms),
# one whether a plan-cache launch was issued from a launch window or
# alone (engine/executor.execute_kernel_plans)
PROBE_COUNTERS = ("sparse_post_probes_", "solo_fallback_",
                  "mesh_live_list_", "float_acc_", "plan_launch_")
LOCK_PROBE_S = 0.002
UNATTRIBUTED = "(in request, no program phase open)"
NO_REQUEST = "(no request open)"
Event = Tuple[str, float, float, dict]    # name, start s, end s, stats


def read_planes(path: str):
    """(host events named pinot.* or bench_*, {device: (ops, modules)}).
    Read from the raw proto: a device operation's scope path is a stat of
    its metadata, which ``jax.profiler.ProfileData`` does not hand out."""
    host: List[Event] = []
    devices: Dict[str, Tuple[List[Event], List[Event]]] = {}
    for plane in xplane_raw.read(path):
        is_device = bool(xplane.DEVICE_PLANE.match(plane["name"]))
        if not is_device and plane["name"] != xplane.HOST_PLANE:
            continue
        for line in plane["lines"]:
            if is_device and line["name"] not in (red.OPS_LINE,
                                                  red.MODULES_LINE):
                continue
            events = [(name, start / 1e9, (start + dur) / 1e9, stats)
                      for name, start, dur, stats in line["events"]
                      if is_device or name.startswith(
                          (PHASE_PREFIX, xplane.SPAN_PREFIX))]
            if not is_device:
                host.extend(events)
            else:
                ops, mods = devices.setdefault(plane["name"], ([], []))
                (ops if line["name"] == red.OPS_LINE
                 else mods).extend(events)
    return host, devices


def phase_table(phases: List[Event]) -> Dict[str, List[float]]:
    """{phase: [count, total s, self s]}; self times within one qid."""
    out: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0, 0.0])
    by_qid: Dict[object, List[Tuple[str, float, float]]] = defaultdict(list)
    for name, s, e, stats in phases:
        out[name][0] += 1
        out[name][1] += e - s
        by_qid[stats.get("qid")].append((name, s, e))
    for events in by_qid.values():
        for name, secs in red.self_times(events).items():
            out[name][2] += secs
    return out


def innermost_timeline(spans: List[Event]) -> List[Tuple[float, float, str]]:
    """Disjoint [(t0, t1, name)] labelling every instant some span is open
    with the open span that started last."""
    cuts = sorted({t for _n, s, e, _st in spans for t in (s, e)})
    order = sorted(spans, key=lambda ev: ev[1])
    heap: List[Tuple[float, float, str]] = []     # (-start, end, name)
    out: List[Tuple[float, float, str]] = []
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(order) and order[i][1] <= a:
            heapq.heappush(heap, (-order[i][1], order[i][2], order[i][0]))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if heap:
            out.append((a, b, heap[0][2]))
    return out


def attribute_gaps(gaps: List[red.Interval],
                   timeline: List[Tuple[float, float, str]]
                   ) -> Dict[str, float]:
    """Idle seconds by the timeline's label (what no label covers is in
    no entry)."""
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for g0, g1 in gaps:
        while j < len(timeline) and timeline[j][1] <= g0:
            j += 1
        k = j
        while k < len(timeline) and timeline[k][0] < g1:
            a, b, name = timeline[k]
            if min(b, g1) > max(a, g0):
                out[name] += min(b, g1) - max(a, g0)
            k += 1
    return out


def device_tables(ops: List[Event], mods: List[Event], lo: float, hi: float):
    """(self s by program, self s by scope, {stat key: ops it named a
    scope in}) of one device inside [lo, hi]."""
    mods = sorted((m for m in mods if m[2] > lo and m[1] < hi),
                  key=lambda m: m[1])
    starts = [m[1] for m in mods]

    def program(t: float) -> str:
        k = bisect.bisect_right(starts, t) - 1
        if k >= 0 and t < mods[k][2]:
            return RUN_ID.sub("", mods[k][0])
        return "(outside any program)"

    clipped, label = [], {}
    scope_keys: Dict[str, int] = defaultdict(int)
    for n, (name, s, e, stats) in enumerate(ops):
        s2, e2 = max(s, lo), min(e, hi)
        if e2 <= s2:
            continue
        scope = "(no pinot scope)"
        for stat, val in stats.items():
            found = SCOPE.findall(val) if isinstance(val, str) else None
            if found:
                scope = found[-1]
                scope_keys[stat] += 1
                break
        # self_times sums by name: name each operation by its number and
        # keep what it is grouped by beside it
        label[str(n)] = (program(s), scope)
        clipped.append((str(n), s2, e2))
    by_prog: Dict[str, float] = defaultdict(float)
    by_scope: Dict[str, float] = defaultdict(float)
    for op, secs in red.self_times(clipped).items():
        prog, scope = label[op]
        by_prog[prog] += secs
        by_scope[scope] += secs
    return by_prog, by_scope, dict(scope_keys)


def analyse(path: str, top: int = 25,
            counters: Optional[Dict[str, float]] = None) -> Dict[str, object]:
    return {"file": path, **tables(*read_planes(path), top=top,
                                   counters=counters)}


def cpu_columns(name: str, own_s: float, n_req: int,
                counters: Optional[Dict[str, float]]):
    """(self CPU ms a query, off-CPU share of the self time) of the phase
    event ``name`` from the window's counters (the estimate of
    ``host_cpu_ms_per_query``'s reader); (None, None) without them, or
    for a phase with no CPU counter (``server_queue``)."""
    c, p = counters or {}, name[len(PHASE_PREFIX):]
    if phase_cpu.CPU + p not in c:
        return None, None
    cpu_s = phase_cpu.cpu_us(c, p) / 1e6
    off = 1.0 - cpu_s / own_s if own_s > 0 else None
    return 1e3 * cpu_s / n_req, off


def tables(host: List[Event], devices, top: int = 25,
           counters: Optional[Dict[str, float]] = None) -> Dict[str, object]:
    """The three tables from the host's events and each device's
    (operations, programs); ``counters``, the window's delta of the
    program's counters, adds the CPU columns of table (a)."""
    window = [ev for ev in host if ev[0] == red.WINDOW_SPAN]
    if len(window) != 1:
        raise RuntimeError(f"the trace holds {len(window)} "
                           f"{red.WINDOW_SPAN} events, expected 1")
    lo, hi = window[0][1], window[0][2]
    requests = sorted((ev for ev in host if ev[0] == red.REQUEST_SPAN
                       and lo <= ev[1] < hi), key=lambda ev: ev[1])
    phases = [ev for ev in host if ev[0].startswith(PHASE_PREFIX)
              and lo <= ev[1] < hi]
    if not devices:
        raise RuntimeError("the trace holds no device plane")
    first = sorted(devices)[0]
    ops, mods = devices[first]
    busy = red.merge(red.clip([(s, e) for _n, s, e, _st in ops], lo, hi))
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    n_req = max(len(requests), 1)

    table = phase_table(phases)
    by_phase = attribute_gaps(gaps, innermost_timeline(phases))
    by_shape = attribute_gaps(gaps, innermost_timeline(
        [("in_request:" + str(st.get("shape", "?")), s, e, st)
         for _n, s, e, st in requests]))
    by_prog, by_scope, scope_keys = device_tables(ops, mods, lo, hi)
    idle = red.length(gaps)
    open_requests = red.merge([(s, e) for _n, s, e, _st in requests])
    in_request = sum(red.overlap(open_requests, g0, g1) for g0, g1 in gaps)
    named = sum(by_phase.values())
    # every pinot.* event lies inside a request, so what is left of the
    # in-request idle time is the client's side of the hop: listed, not
    # dropped
    by_phase[UNATTRIBUTED] = max(in_request - named, 0.0)
    by_phase[NO_REQUEST] = max(idle - in_request, 0.0)

    def rank(d):
        return sorted(d.items(), key=lambda kv: -kv[1])[:top]

    return {
        "device": first, "window_s": hi - lo,
        "requests": len(requests), "busy_s": red.length(busy),
        "idle_s": idle, "idle_in_request_s": in_request,
        "idle_named_share": named / in_request if in_request else None,
        "qids": len({ev[3].get("qid") for ev in phases}),
        "phases": [[n, int(c), tot, own, 1e3 * tot / n_req, 1e3 * own / n_req,
                    *cpu_columns(n, own, n_req, counters)]
                   for n, (c, tot, own) in sorted(
                       table.items(), key=lambda kv: -kv[1][1])],
        "idle_by_phase": rank(by_phase), "idle_by_request": rank(by_shape),
        "device_by_program": rank(by_prog), "device_by_scope": rank(by_scope),
        "scope_stat_keys": scope_keys,
    }


def render(a: Dict[str, object]) -> str:
    out = [f"trace {a['file']} ({a['device']}): window {a['window_s']:.2f} s,"
           f" {a['requests']} requests, {a['qids']} query ids, device busy "
           f"{a['busy_s']:.2f} s, idle {a['idle_s']:.2f} s "
           f"({a['idle_in_request_s']:.2f} s inside requests)", "",
           "(a) phases: count, total s, self s, ms/query, self ms/query, "
           "self CPU ms/query, off-CPU % of self"]
    for n, c, tot, own, ms, own_ms, cpu_ms, off in a["phases"]:
        out.append(f"  {n:<24}{c:>7}{tot:>10.3f}{own:>10.3f}{ms:>10.3f}"
                   f"{own_ms:>10.3f}"
                   + ("         -" if cpu_ms is None else f"{cpu_ms:>10.3f}")
                   + ("         -" if off is None else f"{100 * off:>10.1f}"))
    share = a["idle_named_share"]
    out += ["", "(b) device idle seconds by the innermost program phase open "
            f"(named share of in-request idle: "
            f"{'n/a' if share is None else f'{100 * share:.1f} %'})"]
    out += [f"  {n:<44}{s:>9.3f}" for n, s in a["idle_by_phase"]]
    out += ["    and by the benchmark's request spans:"]
    out += [f"  {n:<44}{s:>9.3f}" for n, s in a["idle_by_request"]]
    out += ["", "(c) device self seconds by program (XLA Modules name)"]
    out += [f"  {n:<44}{s:>9.3f}" for n, s in a["device_by_program"]]
    out += [f"    and by named scope (found in stat {a['scope_stat_keys']})"]
    out += [f"  {n:<44}{s:>9.3f}" for n, s in a["device_by_scope"]]
    return "\n".join(out)


def traced_run(cell: str, seed: int, seconds: float,
               keep: str) -> Dict[str, float]:
    """One traced run of ``cell`` through the benchmark's own
    ``run_cell``, its ``.xplane.pb`` copied to ``keep`` before the run
    removes it. Prints the run's result line; returns the window's delta
    of the program's counters."""
    from benchmark import run

    os.makedirs(os.path.dirname(os.path.abspath(keep)), exist_ok=True)
    load = xplane.load

    def keep_then_load(path: str):
        shutil.copyfile(path, keep)
        return load(path)

    # the window's own delta of the program's work counters: run_cell
    # keeps it for its metric readers and prints none of it
    from pinot_tpu.utils.metrics import global_metrics
    drive = run.tr.drive
    work: Dict[str, float] = {}
    window: Dict[str, float] = {}
    lates: List[float] = []
    spent: List[float] = []

    def counted_drive(*a, **kw):
        before = global_metrics.snapshot()["counters"]
        stop = threading.Event()
        del lates[:], spent[:]
        probe = threading.Thread(target=lock_probe, args=(stop, lates),
                                 daemon=True)
        probe.start()
        t0, requests = drive(*a, **kw)
        spent.append(time.perf_counter() - t0)
        stop.set()
        probe.join()
        after = global_metrics.snapshot()["counters"]
        window.update({k: v - before.get(k, 0) for k, v in after.items()})
        for name in WORK_COUNTERS + tuple(sorted(
                k for k in after if k.startswith(PROBE_COUNTERS))):
            work[name] = window.get(name, 0) / max(len(requests), 1)
        return t0, requests

    xplane.load, run.tr.drive = keep_then_load, counted_drive
    try:
        result = run.run_cell(cell, seed, seconds, True)
    finally:
        xplane.load, run.tr.drive = load, drive
    print(json.dumps(result), flush=True)
    print("work counters, a request of the window: " + ", ".join(
        f"{name} {value:.4f}" for name, value in work.items()), flush=True)
    print("interpreter-lock probe: " + json.dumps(lock_wait(lates)),
          flush=True)
    print(f"host-work leaves' wall a second of the window: "
          f"{host_work_per_s(window, spent[0]):.4f}", flush=True)
    return window


def lock_probe(stop: threading.Event, lates: List[float]) -> None:
    """Until ``stop`` is set: sleep ``LOCK_PROBE_S``, then add how late
    this thread ran again to ``lates`` (seconds)."""
    while not stop.is_set():
        t = time.perf_counter()
        time.sleep(LOCK_PROBE_S)
        lates.append(time.perf_counter() - t - LOCK_PROBE_S)


def lock_wait(lates: List[float]) -> Dict[str, float]:
    """The probe's wakes: how many, their mean lateness and quantiles in
    ms, and the share of them later than 1 ms."""
    if not lates:
        return {"wakes": 0}
    s = sorted(lates)

    def q(f):
        return 1e3 * s[min(len(s) - 1, int(f * len(s)))]

    return {"wakes": len(s), "mean_ms": 1e3 * sum(s) / len(s),
            "p50_ms": q(0.5), "p90_ms": q(0.9), "p99_ms": q(0.99),
            "late_1ms_share": sum(x > 1e-3 for x in s) / len(s)}


def host_work_per_s(window: Dict[str, float], seconds: float) -> float:
    """Wall seconds of the host-work leaves, over every thread, a second
    of the window."""
    from pinot_tpu.utils import phases as ph
    return sum(window.get("phase_us_" + p, 0)
               for p in ph.HOST_WORK_PHASES) / 1e6 / seconds


def sample_device_events(path: str, n: int) -> List[dict]:
    """The first ``n`` distinct operations of the first device with every
    stat they carry: for reading one trace by hand."""
    _host, devices = read_planes(path)
    seen, out = set(), []
    for name, _s, _e, stats in devices[sorted(devices)[0]][0]:
        if name not in seen:
            seen.add(name)
            out.append({"name": name[:120],
                        "stats": {k: str(v)[:300] for k, v in stats.items()}})
            if len(out) >= n:
                break
    return out


def phase_cost(calls: int) -> Dict[str, float]:
    """Microseconds one ``phase()`` crossing costs on this host: while no
    profiler session runs and no query is sampled (counters alone, the
    thread's CPU clock read in ``spans.CPU_SHARE`` of the nests), beside
    the plain ``span()`` it replaced at some sites; a crossing that reads
    the clock (``phase_us_per_call_cpu_read``: two reads, whose own cost
    is ``thread_time_us_per_call``); then inside a profiler session set
    up as the benchmark's (an event with a ``qid`` a crossing)."""
    import tempfile
    import time

    import jax

    from pinot_tpu.utils import phases as ph
    from pinot_tpu.utils import spans
    from pinot_tpu.utils.spans import phase, set_query_id, span

    def timed(make) -> float:
        t = time.perf_counter()
        for _ in range(calls):
            with make():
                pass
        return (time.perf_counter() - t) / calls * 1e6

    out = {"phase_us_per_call": timed(lambda: phase(ph.DISPATCH_PREPARE)),
           "span_us_per_call": timed(lambda: span("device_execute"))}
    share, spans.CPU_SHARE = spans.CPU_SHARE, 1.0
    try:
        out["phase_us_per_call_cpu_read"] = timed(
            lambda: phase(ph.DISPATCH_PREPARE))
    finally:
        spans.CPU_SHARE = share
    t = time.perf_counter()
    for _ in range(calls):
        time.thread_time_ns()
    out["thread_time_us_per_call"] = (time.perf_counter() - t) / calls * 1e6
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 1
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            set_query_id("0123456789ab")
            out["phase_us_per_call_in_session"] = timed(
                lambda: phase(ph.DISPATCH_PREPARE))
        finally:
            set_query_id(None)
            jax.profiler.stop_trace()
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--xplane", help="read this file; run nothing")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--keep", help="where the run's .xplane.pb is copied")
    ap.add_argument("--json", action="store_true",
                    help="print the tables as one JSON line too")
    ap.add_argument("--sample", type=int, default=0,
                    help="also print this many device operations with "
                         "all their stats")
    ap.add_argument("--phase-cost", type=int, default=0, metavar="CALLS",
                    help="time this many phase() crossings and exit")
    args = ap.parse_args(argv)
    if args.phase_cost:
        print(json.dumps(phase_cost(args.phase_cost)))
        return 0
    path = args.xplane
    counters = None
    if path is None:
        if not args.workload:
            ap.error("give --xplane or --workload")
        path = args.keep or os.path.join(
            REPO, "chiprun_out", f"{args.workload}.{args.seed}.xplane.pb")
        counters = traced_run(args.workload, args.seed, args.seconds, path)
    a = analyse(path, counters=counters)
    print(render(a))
    if args.json:
        print(json.dumps(a))
    for ev in sample_device_events(path, args.sample) if args.sample else ():
        print(json.dumps(ev))
    return 0


if __name__ == "__main__":
    sys.exit(main())
