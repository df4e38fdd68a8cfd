"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In ONE process (a chip belongs to one process) it refuses anything but a
TPU with the chips the cell asks for, makes the table from ``--seed`` on
host threads, starts the system through the configuration's entry, warms
every statement of the cell until a whole pass compiles nothing, measures
for ``--seconds``, frees the system, computes the plain reference and
compares every answer of the window with it. The last line of standard
output is the result; the numbers compared, each beside its limit, are
the last lines of standard error.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()      # set-up counts from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import catalog as cat  # noqa: E402
from benchmark import traffic as tr  # noqa: E402

EXIT_NO_CHIP = 3
MAX_WARM_PASSES = 10


def say(msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


@dataclass
class Records:
    """What a run hands to the readers."""
    cell: Dict[str, Any]
    config: Dict[str, Any]
    statements: Dict[str, tr.Statement]       # by key
    requests: List[tr.Request]
    wrong: set                                # ids of requests answered wrongly
    t0: float
    seconds: float
    setup_s: float
    counters: Dict[str, float]                # deltas over the window
    device_kind: str
    logical_bytes: Callable[[dict], int]      # of a shape, resident widths
    trace: Any = None                         # trace.reduce.Reduced

    def answered(self) -> List[tr.Request]:
        """Requests of the window that got the right answer."""
        return [r for r in self.requests
                if r.error is None and id(r) not in self.wrong]


class CompileWatch:
    """The benchmark's own count of programs XLA made ready in this
    process (``/jax/core/compile/backend_compile_duration`` fires for a
    compile and for a read from the persistent cache alike), so that a
    path the program's ``compiles_total`` does not cover is seen too."""
    _installed: Optional["CompileWatch"] = None

    def __init__(self):
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    @classmethod
    def install(cls) -> "CompileWatch":
        if cls._installed is None:
            from jax import monitoring
            w = cls._installed = cls()

            def on_duration(name, secs, **_kw):
                if name == "/jax/core/compile/backend_compile_duration":
                    w.programs += 1
                    w.seconds += secs

            def on_event(name, **_kw):
                if name == "/jax/compilation_cache/cache_hits":
                    w.cache_hits += 1
                elif name == "/jax/compilation_cache/cache_misses":
                    w.cache_misses += 1

            monitoring.register_event_duration_secs_listener(on_duration)
            monitoring.register_event_listener(on_event)
        return cls._installed


def device_info() -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def make_table(config, ds, entry, work: str, seed: int, keep_cols):
    """Generate and build every segment on host threads (numpy releases
    the GIL). Returns (segment directories, host columns kept for the
    reference)."""
    n_seg = int(config["segments"])
    rows_per_seg = int(config["rows"]) // n_seg
    out_dir = os.path.join(work, "segments")

    def one(k: int):
        cols = ds["data"].gen_segment(rows_per_seg, seed, k)
        d = entry.build_segment(cols, ds["data"].MEASURES, out_dir,
                                f"seg_{k}")
        return d, {c: cols[c] for c in keep_cols}

    with ThreadPoolExecutor(max_workers=min(n_seg, os.cpu_count() or 1)) \
            as pool:
        built = list(pool.map(one, range(n_seg)))
    return [d for d, _ in built], [c for _, c in built]


def warm_up(system, statements, watch: CompileWatch) -> Dict[str, float]:
    """Every statement of the cell alone, through a request that outlasts
    a cold compile, pass after pass until a whole pass compiles nothing.
    Returns the seconds it took."""
    first: Dict[str, float] = {}      # each statement's first, cold, answer

    def compiles() -> float:
        return system.counters().get("compiles_total", 0) + watch.programs

    t0 = time.perf_counter()
    for n in range(1, MAX_WARM_PASSES + 1):
        before = compiles()
        for st in statements.values():
            t = time.perf_counter()
            system.execute_warm(st.sql)
            first.setdefault(st.key, time.perf_counter() - t)
        added = compiles() - before
        say(f"warm-up pass {n}: {added:.0f} compiles (the program's count "
            f"+ XLA's), {time.perf_counter() - t0:.1f}s so far")
        if added == 0:
            break
    else:
        raise RuntimeError("warm-up still compiled after "
                           f"{MAX_WARM_PASSES} passes")
    slowest = sorted(first.items(), key=lambda kv: -kv[1])[:5]
    say("slowest first answers: " + ", ".join(
        f"{k} {v:.1f}s" for k, v in slowest))
    return {"warm_s": time.perf_counter() - t0}


def cache_footprint(path: Optional[str]) -> str:
    """Entries and bytes of the persistent compile cache, for the log."""
    if not path or not os.path.isdir(path):
        return "none"
    sizes = [os.path.getsize(os.path.join(path, f)) for f in os.listdir(path)
             if f.endswith("-cache")]
    return f"{len(sizes)} programs, {sum(sizes)} bytes"


def reference_answers(ds, segments, statements):
    """{statement key: rows} from the plain reference, the statements
    spread over host threads."""
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        rows = pool.map(lambda st: ds["oracle"].answer(segments, st.shape),
                        statements.values())
        return dict(zip(statements, rows))


def compare(ds, requests, statements, expected) -> Dict[str, Any]:
    """Every answer of the window against the reference answer of the
    statement that was sent: the same rows, in an order its ORDER BY
    allows. Exact: the limits are 0."""
    wrong, unanswered, first = set(), 0, None
    for r in requests:
        if r.error is not None:
            unanswered += 1
            first = first or (r, "error: " + r.error)
        elif not ds["oracle"].same(r.rows, expected[r.key],
                                   statements[r.key].shape):
            wrong.add(id(r))
            first = first or (r, "rows differ")
    return {"wrong": wrong, "unanswered": unanswered, "first": first}


def run_cell(cell_name: str, seed: int, seconds: float, traced: bool,
             catalog: Optional[cat.Catalog] = None, check_chip: bool = True,
             config_override: Optional[dict] = None,
             wrap_system: Optional[Callable] = None) -> Dict[str, Any]:
    """The whole run; returns the result object. ``check_chip=False``,
    ``config_override`` (a smaller table) and ``wrap_system(system, host
    segments)`` (a fault, or the control, put under the timed path) are for
    the tests under ``benchmark/tests``: such a run is never a result of
    the benchmark."""
    catalog = catalog or cat.Catalog()
    cell = catalog.cell(cell_name)
    config = {**catalog.config(cell["config"]), **(config_override or {})}
    traffic = catalog.traffic(cell["traffic"])
    ds = cat.dataset(config["dataset"])
    entry = cat.entry(config["entry"])

    import jax
    watch = CompileWatch.install()
    device = device_info()
    if check_chip and (device["platform"] != "tpu"
                       or device["count"] < int(cell["chips"])):
        say(f"no chip: JAX found {device}, the cell needs "
            f"{cell['chips']} TPU chip(s)")
        raise SystemExit(EXIT_NO_CHIP)
    if check_chip:
        cat.peak(device["kind"])       # an unknown device is an error
    import pinot_tpu  # noqa: F401 — places the compile cache
    say(f"cell {cell_name} seed {seed} seconds {seconds} trace {int(traced)} "
        f"device {device} compile cache "
        f"{jax.config.jax_compilation_cache_dir}")

    shapes = ds["statements"].load_shapes(
        os.path.join(HERE, traffic["statements"]))
    statements = tr.build_statements(traffic, shapes,
                                     ds["statements"].to_sql)
    keep_cols = sorted({c for st in statements.values()
                        for c in ds["bytes"].columns_read(st.shape)})

    work = tempfile.mkdtemp(prefix="bench_")
    old_tmp, tempfile.tempdir = tempfile.tempdir, work
    system = None
    trace_dir = os.path.join(work, "trace")
    try:
        t = time.perf_counter()
        seg_dirs, host_segments = make_table(config, ds, entry, work, seed,
                                             keep_cols)
        parts = {"data_s": time.perf_counter() - t}
        t = time.perf_counter()
        system = entry.start(config, seg_dirs, work)
        if wrap_system is not None:
            system = wrap_system(system, host_segments)
        parts["start_s"] = time.perf_counter() - t
        parts.update(warm_up(system, statements, watch))
        say("set-up parts: " + json.dumps(
            {k: round(v, 2) for k, v in parts.items()})
            + f"; XLA programs {watch.programs} in {watch.seconds:.1f}s, "
            f"persistent cache {watch.cache_hits} hits "
            f"{watch.cache_misses} misses, holds "
            + cache_footprint(jax.config.jax_compilation_cache_dir)
            + f" (size limit {jax.config.jax_compilation_cache_max_size})")
        itemsize = {c: system.resident_itemsize(c) for c in keep_cols}

        annotate = None
        if traced:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)

            def annotate(st, n):
                return jax.profiler.TraceAnnotation(
                    "bench_request", shape=st.shape["id"], key=st.key, n=n)
        before = {**system.counters(), "xla_programs": watch.programs}
        window = (jax.profiler.TraceAnnotation("bench_window") if traced
                  else contextlib.nullcontext())
        with window:
            t0, requests = tr.drive(system.execute, traffic, statements,
                                    seconds, annotate)
        if traced:
            jax.profiler.stop_trace()
        after = {**system.counters(), "xla_programs": watch.programs}
        setup_s = t0 - T_START
        peak = memory_peak_bytes()
        slow = max(requests, key=lambda r: r.latency_ms, default=None)
        say(f"window closed: {len(requests)} requests, set-up {setup_s:.1f}s"
            + (f", slowest {slow.key} {slow.latency_ms:.0f} ms" if slow
               else ""))

        t = time.perf_counter()
        expected = reference_answers(ds, host_segments, statements)
        verdict = compare(ds, requests, statements, expected)
        say(f"reference and comparison: {time.perf_counter() - t:.1f}s")
        if verdict["first"] is not None:
            r, what = verdict["first"]
            say(f"MISMATCH seed {seed} statement {r.key}: {what}\n  sql: "
                f"{statements[r.key].sql}\n  server ran: "
                + _describe(system, statements[r.key].sql))
        system.stop()
        system = None
        del host_segments
        reduced = None
        if traced:
            from benchmark.trace import reduce as red
            from benchmark.trace import xplane
            reduced = red.reduce_trace(xplane.load(xplane.find(trace_dir)))
    finally:
        tempfile.tempdir = old_tmp
        if system is not None:
            system.stop()
        shutil.rmtree(work, ignore_errors=True)

    rec = Records(
        cell=cell, config=config, statements=statements,
        requests=requests, wrong=verdict["wrong"], t0=t0, seconds=seconds,
        setup_s=setup_s,
        counters={k: after.get(k, 0) - before.get(k, 0) for k in after},
        device_kind=device["kind"], trace=reduced,
        logical_bytes=lambda shape: ds["bytes"].logical_bytes(
            shape, int(config["rows"]), itemsize.__getitem__))
    device_out = {**device, "memory_peak_bytes": peak}
    breakdown = None
    if reduced is not None:
        device_out["busy_s"] = reduced.busy_s
        device_out["window_s"] = reduced.window_s
        breakdown = red.breakdown(reduced)

    metrics = {}
    for m in catalog.metrics_for(cell_name, traced):
        value = catalog.reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    n_wrong = len(verdict["wrong"])
    compared = {
        "wrong_answers": {"value": n_wrong, "limit": 0},
        "unanswered": {"value": verdict["unanswered"], "limit": 0},
        "answers_compared": {"value": len(requests) - verdict["unanswered"],
                             "limit_at_least": 1},
    }
    correct = (n_wrong == 0 and verdict["unanswered"] == 0
               and len(requests) > verdict["unanswered"])
    result = {"correct": correct, "attempted": len(requests),
              "failed": n_wrong + verdict["unanswered"], "metrics": metrics,
              "device": device_out}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    for name, c in compared.items():
        lim = c.get("limit", c.get("limit_at_least"))
        kind = "limit" if "limit" in c else "at least"
        print(f"compared {name}: {c['value']} ({kind} {lim})",
              file=sys.stderr, flush=True)
    return result


def _describe(system, sql: str) -> str:
    try:
        return system.describe(sql)
    except Exception as e:  # noqa: BLE001 — a report must not hide the mismatch
        return f"(describe failed: {type(e).__name__}: {e})"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
