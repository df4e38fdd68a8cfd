"""chip_smoke.py — the served query path, once, on the chip.

The quickest proof that the system still starts on a TPU. In ONE process
(a chip belongs to one process; nothing here starts a JAX child) it

1. refuses anything but a TPU (exit 1, naming what JAX found);
2. builds an SSB table from ``--seed`` with the test corpus's generator
   (pinot_tpu/tools/corpus.py; 18 columns, shapes unchanged): 2^26
   lineorder rows as 8 segments of 2^23 — cut from one chip's share of
   2^27 because the time limit forces it (printed at run time; see
   CUT_REASON);
3. starts Controller + ServerNode + BrokerNode as StartController /
   StartServer / StartBroker construct them (tools/admin.py), registers
   the table and the segments by location over the controller's REST
   API, and waits for the server to load them;
4. runs one SQL query per kernel family through the broker's HTTP
   endpoint (clients.connect_url), once cold and twice warm, asserting
   that the answer equals the corpus's numpy oracle and — from the span
   tree ``EXPLAIN ANALYZE`` brings back from the server — that every
   segment was answered by the expected device dispatch, none by a host
   plan;
5. runs the per-lowering hardware checks of tests/tpu_hw_script.py;
6. fails on any staging fallback or compiler rejection, interpreted or
   XLA compaction, post-warm-up retrace, digest mismatch or phase that
   raised.

On a machine with more than one device the same command runs the mesh
phase instead of 3-5: the layout of the benchmark's four-chip cell (2^27
rows as 16 segments of 2^23, four a device on four chips) behind the same
served trio, the ServerNode constructed over the devices as one mesh
(cluster/server_node.py), the same queries over HTTP, each held to the
oracle and to ONE mesh program of the expected route in the server's span
tree (no fallback to the per-segment path); then the multistage mesh join
/ device window / set-op, and a check that every device holds a shard.

The last stdout line of a pass is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
It claims no speed: the seconds it prints are observations.

    python chip_smoke.py                 # on the chip(s), via the chip tool
    python chip_smoke.py --rehearse-cpu  # tiny CPU walk-through; never a pass
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "tests"))   # tpu_hw_script library

# One chip's share of the deployment is 2^27 rows as 8 segments of 2^24
# (ROADMAP R2). THE CUT, printed at run time: 2^26 rows as 8 x 2^23.
FULL_LOG2_ROWS = 27
CUT_REASON = (
    "the contract gives the smoke 1200 s with nothing compiled beforehand, "
    "and at 2^27 rows on a v5e (chip run of PR 21) the segmented compact "
    "kernel alone took 388 s to compile for q4.1 (187-195 s at 2^26; the "
    "dense kernels 2 s) beside 472 s of hardware checks. Data, upload and "
    "HBM were not the limit: 2^27 rows built in 42 s and sat resident")
LOG2_ROWS = 26
N_SEGMENTS = 8
OPTION = " OPTION(timeoutMs=1000000)"   # outlasts the smoke itself
EXIT_REHEARSAL = 2      # a CPU rehearsal never exits 0

# HBM capacity by device_kind (Google Cloud documentation, "TPU v5e":
# 16 GB of HBM per chip). A device that is not here is an error, not a
# default.
HBM_BYTES = {"TPU v5 lite": 16 << 30}

# (qid, strategy, dispatch span, launches): one query per kernel family
# the planner has, with what the server is expected to be SEEN doing for 8
# same-bucket segments — the span engine/batch.py or engine/executor.py
# opens around each device launch, and how many of them. q1.1, q2.1,
# q4.1 and q4.3 are the corpus's SSB specs. ``dgb`` is an SSB-shaped dense
# group-by: at these segment sizes the planner's one-hot budget (segment
# rows x group space) makes every SSB Q2-Q4 a compact plan, q4.1's 175
# groups included, so the dense small-space family needs a 7-group key.
# The compact families: q4.1 (175 groups a segment) stays on the
# factorized core as one segmented program; q2.1 (7,000 groups a
# segment) is factorized on a segment but would land on the sort core
# as a batch, and q4.3 (1.75M groups) is the sort core outright — both
# go per segment (ops/kernels.segmented_compact_fits).
SMOKE_QUERIES = [
    ("q1.1", "dense", "vmap_dispatch", 1),
    ("dgb", "dense", "vmap_dispatch", 1),
    ("q4.1", "compact", "segmented_compact_dispatch", 1),
    ("q2.1", "compact", "segment_kernel", N_SEGMENTS),
    ("q4.3", "compact", "segment_kernel", N_SEGMENTS),
]
# every span a segment's execution can open on the server, with the
# site its first launch compiles at (utils/compileplane compile_event);
# anything but the expected one in a query's tree fails the smoke
DISPATCH_SPANS = {
    "vmap_dispatch": "vmap_kernel",
    "segmented_compact_dispatch": "segmented_kernel",
    "segment_kernel": "plan_cache",
    "segment_kselect": None, "segment_host": None, "ragged_dispatch": None,
    "overflow_retry": None, "group_overflow_retry": None,
}
DGB_SPEC = ("dgb", [("lo_quantity", "lt", 25)], ("lo_revenue",),
            ["d_year"])
# several devices: the table of the benchmark's four-chip cell, and the
# route of the one mesh program that answers each query
# (parallel/distributed.DistributedTable._route): dense plans vmap the
# local segments; a compact plan on the factorized core (q4.1, q2.1: the
# shared dictionaries leave the group space unmultiplied) flattens the
# local shard; q4.3's sort core over a local shard past
# SEGMENTED_SORT_ROW_LIMIT runs per local segment inside the program
MESH_LOG2_ROWS = 27
MESH_SEGMENTS = 16
MESH_QUERIES = [
    ("q1.1", "dense", "mesh_dense"),
    ("dgb", "dense", "mesh_dense"),
    ("q4.1", "compact", "mesh_compact"),
    ("q2.1", "compact", "mesh_compact"),
    ("q4.3", "compact", "mesh_compact_per_segment"),
]

T0 = time.perf_counter()


def say(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:7.1f}s] {msg}", flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL — {msg}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def check_device(rehearse: bool):
    """Exit unless JAX's first device is a TPU (or this is an explicit
    CPU rehearsal). Returns the device as JAX reports it."""
    import jax

    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    if dev.platform != "tpu" and not rehearse:
        # with JAX_PLATFORMS unset and libtpu failing to initialise, JAX
        # itself drops to the CPU with a warning: that must not pass
        fail(f"no TPU: jax.devices()[0].platform is {dev.platform!r} "
             f"({dev.device_kind}); this smoke only runs on the chip")
    import importlib.metadata as md
    import jaxlib
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    say(f"platform: {info['platform']}  device_kind: {info['kind']}  "
        f"devices: {info['count']}")
    say(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
        f"libtpu {libtpu}  python {sys.version.split()[0]}")
    if dev.platform == "tpu":
        from pinot_tpu.engine import pipeline
        if dev.device_kind not in HBM_BYTES:
            fail(f"unknown device_kind {dev.device_kind!r}: add its HBM "
                 "capacity (with the source) to HBM_BYTES")
        limit = dev.memory_stats()["bytes_limit"]
        say(f"HBM: documented {HBM_BYTES[dev.device_kind]} B, "
            f"memory_stats bytes_limit {limit} B, engine/pipeline "
            f"resident-scan budget {pipeline.hbm_budget_bytes()} B")
        if not pipeline.hbm_budget_bytes() < limit <= \
                HBM_BYTES[dev.device_kind]:
            fail("engine/pipeline's budget, the device's bytes_limit and "
                 "the documented capacity are out of order")
    return info


def cache_dir() -> str:
    import jax
    import pinot_tpu  # noqa: F401 — places the cache (pinot_tpu/__init__.py)
    return jax.config.jax_compilation_cache_dir


def cache_entries() -> set:
    d = cache_dir()
    return set(os.listdir(d)) if os.path.isdir(d) else set()


def check_native() -> None:
    from pinot_tpu import native
    ok = native.available()
    say(f"native.available(): {ok}")
    if not ok and os.path.exists(native._SRC):
        fail("native source present but the library did not build:\n"
             + str(native.build_error()))


def build_table(work: str, log2_rows: int, seed: int,
                n_seg: int = N_SEGMENTS):
    """Generate and build the SSB segments (host work, threads: numpy
    releases the GIL); returns the segment directories."""
    from pinot_tpu.segment import SegmentBuilder
    from pinot_tpu.spi import Schema, TableConfig
    from pinot_tpu.tools import corpus

    rows_per_seg = (1 << log2_rows) // n_seg
    out_dir = os.path.join(work, "segments")

    def one(k: int) -> str:
        cols = corpus.ssb_columns(rows_per_seg, seed=(seed, k))
        schema = Schema("lineorder", corpus.ssb_fields(cols))
        return SegmentBuilder(schema, TableConfig("lineorder")).build(
            cols, out_dir, f"seg_{k}")

    t = time.perf_counter()
    with ThreadPoolExecutor(max_workers=min(n_seg, os.cpu_count() or 1)) \
            as pool:
        dirs = list(pool.map(one, range(n_seg)))
    disk = sum(os.path.getsize(os.path.join(d, f))
               for d in dirs for f in os.listdir(d))
    say(f"data: {n_seg} segments x {rows_per_seg} rows = "
        f"{n_seg * rows_per_seg} lineorder rows (seed {seed}), "
        f"{disk / 1e9:.2f} GB on disk, built in "
        f"{time.perf_counter() - t:.1f}s")
    return dirs


def start_cluster(work: str, mesh=None):
    """Controller + ServerNode + BrokerNode in this process, as
    StartController / StartServer / StartBroker construct them; with
    ``mesh`` (devices) the server keeps its table across them."""
    from pinot_tpu.cluster import BrokerNode, Controller, ServerNode

    controller = Controller(os.path.join(work, "controller"))
    server = ServerNode("smoke_server", controller.url, mesh=mesh)
    return BrokerNode(controller.url), server, controller


def load_table(nodes, seg_dirs, schema) -> None:
    """Register the table and its segments by location over the
    controller's REST API; wait until the server holds every segment."""
    from pinot_tpu.cluster.http_util import http_json

    broker, server, controller = nodes
    t = time.perf_counter()
    http_json("POST", f"{controller.url}/tables",
              {"name": "lineorder", "schema": schema.to_dict(),
               "replication": 1})
    for d in seg_dirs:
        http_json("POST", f"{controller.url}/segments",
                  {"table": "lineorder", "segment": os.path.basename(d),
                   "location": d})
    version = controller.routing_snapshot()["version"]
    if not (server.wait_for_version(version, timeout=120.0)
            and broker.wait_for_version(version, timeout=120.0)):
        fail("server/broker did not reach the controller's routing "
             f"version {version}")
    dm = server._tables.get("lineorder")
    held = len(dm.acquire_segments()) if dm is not None else 0
    if held != len(seg_dirs):
        fail(f"server holds {held} of {len(seg_dirs)} segments")
    say(f"serving: controller {controller.url}, server {server.url}, "
        f"broker {broker.url}; {held} segments loaded in "
        f"{time.perf_counter() - t:.1f}s")


def stop_nodes(nodes) -> None:
    for node in nodes:
        node.stop()


def smoke_specs():
    from pinot_tpu.tools import corpus
    by_id = {q[0]: q for q in corpus.SSB_QUERIES + [DGB_SPEC]}
    return [by_id[qid] + (strategy, span_name, launches)
            for qid, strategy, span_name, launches in SMOKE_QUERIES]


def oracle_digest(host_segs, preds, vexpr, gcols):
    """The corpus's numpy oracle per segment, group sums merged."""
    from pinot_tpu.tools import corpus
    acc: dict = {}
    for seg in host_segs:
        for r in corpus.ssb_oracle(seg, preds, vexpr, gcols):
            acc[r[:-1]] = acc.get(r[:-1], 0) + r[-1]
    return corpus.digest([k + (v,) for k, v in acc.items()])


def observed_dispatch(conn, sql: str) -> dict:
    """What the server RAN for ``sql``, from the span tree EXPLAIN
    ANALYZE brings back over HTTP: {dispatch span: [launches, segments
    covered, strategy]}. A host plan shows up as ``segment_host``, the
    ragged batcher, a retry or a solo rerun as their own spans — none of
    it is re-derived here from the routing rules."""
    seen: dict = {}
    for node, _id, _parent, _ms, detail in conn.execute(
            "EXPLAIN ANALYZE " + sql + OPTION).rows:
        if node not in DISPATCH_SPANS:
            continue
        attrs = dict(kv.split("=", 1) for kv in detail.split())
        entry = seen.setdefault(node, [0, 0, attrs.get("strategy")])
        entry[0] += 1
        entry[1] += int(attrs.get("segments", 1))
    return seen


def compile_sites(since_seq: int) -> list:
    """Sites of the compile events recorded after ``since_seq``."""
    from pinot_tpu.utils.compileplane import global_compile_log
    return sorted({e["site"] for e in global_compile_log.events()
                   if e["seq"] > since_seq})


def resident_bytes() -> str:
    import jax
    from pinot_tpu.utils.devmem import global_device_memory
    tracked = global_device_memory.snapshot()["total"]["bytes"]
    stats = jax.devices()[0].memory_stats() or {}
    return (f"resident: devmem registry {tracked / 1e9:.2f} GB, "
            f"device bytes_in_use {stats.get('bytes_in_use', 0) / 1e9:.2f}"
            f" GB (peak {stats.get('peak_bytes_in_use', 0) / 1e9:.2f} GB)")


def counter(name: str) -> float:
    from pinot_tpu.utils.metrics import global_metrics
    return global_metrics.snapshot()["counters"].get(name, 0)


def overflow_retries() -> float:
    return (counter("compact_overflow_retries")
            + counter("group_xfer_overflow_retries"))


def run_served_query(conn, host_segs, spec):
    """One smoke query over HTTP: cold once, warm twice, vs the oracle,
    then once more under EXPLAIN ANALYZE for what the server ran."""
    from pinot_tpu.ops.plan_cache import global_plan_cache
    from pinot_tpu.tools import corpus
    from pinot_tpu.utils.compileplane import global_compile_log

    qid, preds, vexpr, gcols, strategy, span_name, launches = spec
    sql = corpus.spec_to_sql(preds, vexpr, gcols)
    retries0, compile0 = overflow_retries(), counter("compile_ms_total")
    seq0 = max([e["seq"] for e in global_compile_log.events()], default=0)
    t = time.perf_counter()
    res = conn.execute(sql + OPTION)
    cold_s = time.perf_counter() - t
    compile_s = (counter("compile_ms_total") - compile0) / 1e3
    sites = compile_sites(seq0)
    det0 = global_plan_cache.detector.retraces
    warm_ms = []
    for _ in range(2):
        t = time.perf_counter()
        res = conn.execute(sql + OPTION)
        warm_ms.append((time.perf_counter() - t) * 1e3)
    seen = observed_dispatch(conn, sql)
    retraced = global_plan_cache.detector.retraces - det0
    digest = corpus.digest(res.rows)
    ok = digest == oracle_digest(host_segs, preds, vexpr, gcols)
    on_device = "segment_host" not in seen and \
        sum(v[1] for v in seen.values()) == len(host_segs)
    say(f"query {qid}: plan {'kernel' if on_device else 'NOT kernel'}  "
        f"server ran {seen}  compiled at {sites}  "
        f"cold {cold_s:.2f}s (lower+compile {compile_s:.2f}s)  warm "
        f"{warm_ms[0]:.1f} / {warm_ms[1]:.1f} ms  overflow_retries "
        f"{overflow_retries() - retries0:.0f}  retraces_post_warmup "
        f"{retraced}  segments {res.num_segments}  rows {len(res.rows)}  "
        f"digest_ok {ok}")
    say("  " + resident_bytes())
    if seen != {span_name: [launches, len(host_segs), strategy]}:
        fail(f"{qid}: the server ran {seen}, expected {launches} x "
             f"{span_name} ({strategy}) over {len(host_segs)} segments")
    if sites != [DISPATCH_SPANS[span_name]]:
        fail(f"{qid}: compiled at {sites}, expected "
             f"{DISPATCH_SPANS[span_name]!r}")
    if res.num_segments != len(host_segs):
        fail(f"{qid} answered from {res.num_segments} of "
             f"{len(host_segs)} segments")
    if not ok:
        fail(f"{qid} digest differs from the numpy oracle")
    if retraced:
        fail(f"{qid} retraced {retraced}x after warm-up")


def run_served_queries(broker_url: str, host_segs):
    """Every smoke query through the broker's HTTP endpoint."""
    from pinot_tpu.clients import connect_url

    conn = connect_url(broker_url, timeout=1100.0)
    for spec in smoke_specs():
        run_served_query(conn, host_segs, spec)


def run_mesh_query(conn, host_segs, spec, strategy, route) -> None:
    """One smoke query over HTTP against the mesh-holding server: cold
    once, warm twice, vs the oracle, then under EXPLAIN ANALYZE for the
    one mesh program the server ran."""
    from pinot_tpu.tools import corpus

    qid, preds, vexpr, gcols = spec
    sql = corpus.spec_to_sql(preds, vexpr, gcols)
    fallbacks0 = counter("mesh_fallbacks")
    t = time.perf_counter()
    res = conn.execute(sql + OPTION)
    cold_s = time.perf_counter() - t
    warm_ms = []
    for _ in range(2):
        t = time.perf_counter()
        res = conn.execute(sql + OPTION)
        warm_ms.append((time.perf_counter() - t) * 1e3)
    seen = []
    for node, _id, _parent, _ms, detail in conn.execute(
            "EXPLAIN ANALYZE " + sql + OPTION).rows:
        if node == "mesh_dispatch" or node in DISPATCH_SPANS:
            attrs = dict(kv.split("=", 1) for kv in detail.split())
            seen.append((node, attrs.get("route"), attrs.get("strategy")))
    ok = corpus.digest(res.rows) == oracle_digest(
        host_segs, preds, vexpr, gcols)
    fell_back = counter("mesh_fallbacks") - fallbacks0
    say(f"mesh query {qid}: server ran {seen}  cold {cold_s:.2f}s  warm "
        f"{warm_ms[0]:.1f} / {warm_ms[1]:.1f} ms  mesh_fallbacks "
        f"{fell_back:.0f}  segments {res.num_segments}  rows "
        f"{len(res.rows)}  digest_ok {ok}")
    if seen != [("mesh_dispatch", route, strategy)] or fell_back:
        fail(f"mesh: {qid}: the server ran {seen} with {fell_back:.0f} "
             f"fallbacks, expected one mesh_dispatch by {route} "
             f"({strategy})")
    if res.num_segments != len(host_segs):
        fail(f"mesh: {qid} answered from {res.num_segments} of "
             f"{len(host_segs)} segments")
    if not ok:
        fail(f"mesh: {qid} digest differs from the numpy oracle")


def run_mesh_phase(nodes, host_segs, devices, before) -> None:
    """More than one device: the smoke queries through the served trio
    whose server holds the mesh, then that every device holds a shard,
    then the multistage mesh join / device window / set-op."""
    from pinot_tpu.clients import connect_url
    from pinot_tpu.multistage import device_join
    from pinot_tpu.tools import corpus

    def in_use(d):     # None where the backend reports no memory stats
        return (d.memory_stats() or {}).get("bytes_in_use")

    by_id = {q[0]: q for q in corpus.SSB_QUERIES + [DGB_SPEC]}
    conn = connect_url(nodes[0].url, timeout=1100.0)
    for qid, strategy, route in MESH_QUERIES:
        run_mesh_query(conn, host_segs, by_id[qid], strategy, route)
    dist = nodes[1]._tables["lineorder"].distributed
    shard_devs = sorted({s.device.id for col in dist._cols.values()
                         for s in col.addressable_shards})
    grew = [None if b is None else in_use(d) - b
            for d, b in zip(devices, before)]
    say(f"mesh: {len(dist.segments)} segments, {dist.local_segments} a "
        f"device; column shards on device ids {shard_devs}; bytes_in_use "
        f"grew per device by {grew} B")
    if shard_devs != sorted(d.id for d in devices) \
            or any(g is not None and g <= 0 for g in grew):
        fail("mesh: not every device holds a shard")

    import __graft_entry__ as graft
    joins = device_join.STATS["mesh_joins"]
    graft._dryrun_multistage(len(devices))
    if device_join.STATS["mesh_joins"] <= joins:
        fail("mesh: the join did not take the all_to_all backend")
    say(f"mesh: multistage all_to_all join (mesh_joins "
        f"{device_join.STATS['mesh_joins']}), device window and set-op "
        "equal the host answers")


def final_gates(rehearse: bool, rows_per_seg: int) -> None:
    from pinot_tpu.ops import compact

    fallbacks = counter("compile_staging_fallbacks")
    rejections = counter("compile_rejections")
    say(f"compile_staging_fallbacks: {fallbacks}  compile_rejections: "
        f"{rejections}  Pallas interpret: {compact._interpret()}  "
        f"_use_pallas({rows_per_seg}): {compact._use_pallas(rows_per_seg)}")
    if fallbacks or rejections:
        fail(f"{fallbacks} staged compile(s) fell back to implicit jit "
             f"(traceback logged above), {rejections} program(s) were "
             "rejected by the compiler")
    if rehearse:
        return
    if compact._interpret() or not compact._use_pallas(rows_per_seg):
        fail("the Pallas compactor is interpreted or not selected at the "
             "smoke's sizes")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=1992,
                    help="data seed (default %(default)s)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny walk-through on the CPU; prints no result "
                         f"and exits {EXIT_REHEARSAL}")
    args = ap.parse_args(argv)
    rehearse = args.rehearse_cpu
    device = check_device(rehearse)
    several = device["count"] > 1
    full_log2, n_seg = ((MESH_LOG2_ROWS, MESH_SEGMENTS) if several
                        else (LOG2_ROWS, N_SEGMENTS))
    log2_rows = full_log2
    if rehearse:
        # 2^16 rows a segment, and the sort core's row limit scaled down
        # with them so the server routes each query as it does at full
        # size
        from pinot_tpu.ops import kernels
        log2_rows = full_log2 - 7
        kernels.SEGMENTED_SORT_ROW_LIMIT >>= full_log2 - log2_rows
    from pinot_tpu.segment import ImmutableSegment
    entries0 = cache_entries()
    say(f"compile cache: {cache_dir()} ({len(entries0)} entries before)")
    check_native()

    work = tempfile.mkdtemp(prefix="chip_smoke_")
    tempfile.tempdir = work     # the checks' scratch tables land inside
    nodes = ()
    try:
        if not several and LOG2_ROWS < FULL_LOG2_ROWS and not rehearse:
            say(f"CUT: 2^{LOG2_ROWS} rows, not one chip's share of "
                f"2^{FULL_LOG2_ROWS}: {CUT_REASON}")
        seg_dirs = build_table(work, log2_rows, args.seed, n_seg)
        host_segs = [ImmutableSegment.load(d) for d in seg_dirs]  # oracle
        if several:
            import jax
            devices = jax.devices()
            say(f"{len(devices)} devices: this invocation runs the mesh "
                "phase, the served trio with the server's table across "
                "the devices; the hardware checks are the one-chip "
                "invocation's")
            before = [(d.memory_stats() or {}).get("bytes_in_use")
                      for d in devices]
            nodes = start_cluster(work, mesh=devices)
            load_table(nodes, seg_dirs, host_segs[0].schema)
            run_mesh_phase(nodes, host_segs, devices, before)
            stop_nodes(nodes)
            nodes = ()
        else:
            nodes = start_cluster(work)
            load_table(nodes, seg_dirs, host_segs[0].schema)
            run_served_queries(nodes[0].url, host_segs)
            # free the served table's device residency for what follows
            for seg in nodes[1]._tables["lineorder"].acquire_segments():
                seg.evict_device()
            stop_nodes(nodes)
            nodes = ()
            if not rehearse:
                import tpu_hw_script
                checks: list = []
                t = time.perf_counter()
                tpu_hw_script.run_hardware_checks(checks)
                say(f"hardware checks: {len(checks)} passed in "
                    f"{time.perf_counter() - t:.1f}s: {', '.join(checks)}")
        final_gates(rehearse, (1 << log2_rows) // n_seg)
    finally:
        stop_nodes(nodes)
        tempfile.tempdir = None
        shutil.rmtree(work, ignore_errors=True)
    entries = cache_entries()
    say(f"compile cache: {len(entries)} entries after "
        f"({len(entries0)} before)")
    if entries0 and entries - entries0:
        # JAX persists only compiles that took over 1 s, so a program near
        # that line can be written on a later run: name what was added
        say(f"  added to a warm cache: {sorted(entries - entries0)}")
    if rehearse:
        say("CPU rehearsal finished — NOT a pass; no result is printed")
        return EXIT_REHEARSAL
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
