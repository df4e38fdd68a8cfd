"""The layer-boundary primitive on the served path (utils/spans.phase).

One call at every boundary a query crosses, three outputs: counters that
are always on (``phase_us_<name>``, ``phase_n_<name>``), ``pinot.<name>``
events in a running ``jax.profiler`` session, tree nodes when the query is
sampled. The trio (controller, one server, broker) runs in this process
over a small table; plain statements go over HTTP as a client's would.
No test here compares a time with a threshold of its own: the only
inequalities are structural (a child inside its parent).
"""
import glob
import os
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from pinot_tpu.cluster import (BrokerNode, Controller,  # noqa: E402
                               ServerNode)
from pinot_tpu.cluster.http_util import http_json  # noqa: E402
from pinot_tpu.segment import SegmentBuilder  # noqa: E402
from pinot_tpu.spi import (DataType, FieldSpec, FieldType,  # noqa: E402
                           Schema, TableConfig)
from pinot_tpu.utils import phases as ph  # noqa: E402
from pinot_tpu.utils import spans  # noqa: E402
from pinot_tpu.utils.metrics import global_metrics  # noqa: E402

N_SEGMENTS = 4
ROWS = 1 << 18
N_QUERIES = 5

DENSE = ("SELECT region, SUM(amount), COUNT(*) FROM ptab "
         "GROUP BY region ORDER BY region")
COMPACT = DENSE + " OPTION(groupByStrategy=compact)"
SELECT = "SELECT region, amount FROM ptab ORDER BY amount DESC LIMIT 5"
# Q1-shaped: a dictionary-encoded numeric dimension summed and filtered.
# tier's dictionary (5 entries) decodes by the select chain, code's (300,
# over ops/kernels.DICT_SELECT_MAX) by the gather
SMALL_DICT = "SELECT SUM(amount * tier) FROM ptab WHERE tier BETWEEN 1 AND 3"
LONG_DICT = "SELECT SUM(amount * code) FROM ptab WHERE code < 200"

# what one plain DENSE statement crosses: every phase of PERF.md's table,
# once — one vmapped launch answers the four segments, and the host work
# before it is two crossings: the plans' host params and group keys
# (params_host), then the stacks and the one params upload
# (dispatch_prepare; the per-segment resolve_params passes went with
# PR 30)
CROSSINGS = {
    ph.BROKER_QUERY: 1, ph.BROKER_PARSE: 1, ph.BROKER_ROUTE: 1,
    ph.BROKER_SELECT: 1, ph.SCATTER: 1, ph.SCATTER_CALL: 1,
    ph.WIRE_DECODE: 1, ph.REDUCE: 1, ph.BROKER_RESPOND: 1,
    ph.SERVER_HTTP: 1, ph.SERVER_QUEUE: 1, ph.SERVER_PARSE: 1,
    ph.PLANNING: 1, ph.EXECUTION: 1, ph.PARAMS_HOST: 1,
    ph.DISPATCH_PREPARE: 1,
    ph.DEVICE_EXECUTE: 1, ph.DEVICE_TRANSFER: 1, ph.EXTRACT_PARTIAL: 1,
    ph.SERVER_ENCODE: 1,
}
# parent -> the children that account for it (table B of the issue)
CHILDREN = {
    ph.BROKER_QUERY: [ph.BROKER_PARSE, ph.BROKER_ROUTE, ph.BROKER_SELECT,
                      ph.SCATTER, ph.REDUCE, ph.BROKER_RESPOND],
    ph.SCATTER: [ph.SCATTER_CALL, ph.WIRE_DECODE],
    ph.SCATTER_CALL: [ph.SERVER_HTTP],
    ph.SERVER_HTTP: [ph.SERVER_QUEUE, ph.SERVER_PARSE, ph.PLANNING,
                     ph.EXECUTION, ph.SERVER_ENCODE],
    ph.EXECUTION: [ph.PARAMS_HOST, ph.DISPATCH_PREPARE, ph.DEVICE_EXECUTE,
                   ph.DEVICE_TRANSFER, ph.EXTRACT_PARTIAL],
}


def counters():
    return {k: v for k, v in global_metrics.snapshot()["counters"].items()
            if k.startswith(("phase_", "kernel_dispatches", "wire_bytes",
                             "dict_decode_"))}


def settled(quiet_s=0.05, tries=200):
    """The counters once two snapshots ``quiet_s`` apart are equal."""
    last = counters()
    for _ in range(tries):
        time.sleep(quiet_s)
        now = counters()
        if now == last:
            break
        last = now
    return last


def moved(before, after):
    return {k: after[k] - before.get(k, 0) for k in after}


@pytest.fixture(scope="module")
def trio(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("phases")
    ctrl = Controller(str(tmp / "ctrl"), heartbeat_timeout=30.0,
                      reconcile_interval=0.2)
    server = ServerNode("server_0", ctrl.url, poll_interval=0.1)
    broker = BrokerNode(ctrl.url, routing_refresh=0.1)
    schema = Schema("ptab", [
        FieldSpec("region", DataType.STRING),
        FieldSpec("tier", DataType.INT), FieldSpec("code", DataType.INT),
        FieldSpec("amount", DataType.INT, FieldType.METRIC)])
    builder = SegmentBuilder(schema, TableConfig("ptab"))
    ctrl.add_table("ptab", schema.to_dict(), replication=1)
    rng = np.random.default_rng(25)
    for i in range(N_SEGMENTS):
        cols = {"region": rng.choice(["east", "west", "north"], ROWS),
                "tier": rng.integers(0, 5, ROWS).astype(np.int32),
                "code": rng.integers(0, 300, ROWS).astype(np.int32),
                "amount": rng.integers(0, 1000, ROWS).astype(np.int32)}
        d = builder.build(cols, str(tmp / "segments"), f"ptab_seg_{i}")
        ctrl.add_segment("ptab", f"ptab_seg_{i}", d)
    version = ctrl.routing_snapshot()["version"]
    assert server.wait_for_version(version)
    assert broker.wait_for_version(version)

    def query(sql):
        # the two handlers' phases close after their client has its
        # answer, each on its own thread: the broker's after this
        # client's, the server's after the broker's (under six workers
        # that can be after the broker's closed). A statement is over
        # when both have counted it, so a snapshot taken then is a window
        # of this statement and nothing else's
        keys = ["phase_n_" + ph.BROKER_QUERY, "phase_n_" + ph.SERVER_HTTP]
        seen = [counters().get(k, 0) for k in keys]
        out = http_json("POST", f"{broker.url}/query/sql", {"sql": sql},
                        timeout=300.0)
        for _ in range(5000):
            now = counters()
            if all(now.get(k, 0) > n for k, n in zip(keys, seen)):
                break
            time.sleep(0.002)
        return out

    for sql in (DENSE, COMPACT, SELECT):        # compile outside the tests
        assert "resultTable" in query(sql + (
            " OPTION(timeoutMs=280000)" if "OPTION" not in sql else ""))
    query.segment_dir = str(tmp / "segments" / "ptab_seg_0")
    query.broker_url = broker.url
    yield query
    broker.stop()
    server.stop()
    ctrl.stop()


@pytest.fixture(scope="module")
def plain_run(trio):
    """N plain DENSE statements; (counters before, between, after). The
    counters are the process's: the first snapshot waits until nothing
    that ran before this module (under ``--dist loadfile`` a worker runs
    file after file in one process) is still closing a phase."""
    snaps = [settled()]
    for _ in range(N_QUERIES):
        trio(DENSE)
        snaps.append(counters())
    return snaps


@pytest.mark.parametrize("phase", sorted(CROSSINGS))
def test_every_boundary_counts_its_crossings(plain_run, phase):
    d = moved(plain_run[0], plain_run[-1])
    assert d["phase_n_" + phase] == CROSSINGS[phase] * N_QUERIES
    assert isinstance(d["phase_us_" + phase], int)
    assert d["phase_us_" + phase] >= 0


def test_table_b_is_the_metered_vocabulary():
    """Every metered name is crossed by the served path or by the
    in-process broker (its mesh phase); nothing else is metered."""
    # ... or, for the two the micro-batcher owns, by queries that
    # overlap (tests/test_ragged_batch.py counts their crossings)
    assert set(CROSSINGS) | {ph.DISTRIBUTED_EXECUTE, ph.RAGGED_WAIT,
                             ph.FUSED_EXECUTE} == ph.METERED_PHASES
    with pytest.raises(KeyError):
        with spans.phase("not_a_boundary"):
            pass


@pytest.mark.parametrize("parent", sorted(CHILDREN))
def test_children_never_exceed_their_parent(plain_run, parent):
    d = moved(plain_run[0], plain_run[-1])
    inside = sum(d["phase_us_" + c] for c in CHILDREN[parent])
    crossings = sum(d["phase_n_" + c] for c in CHILDREN[parent])
    # each crossing rounds to a whole microsecond
    assert inside <= d["phase_us_" + parent] + crossings + N_QUERIES


@pytest.mark.parametrize("parent", [ph.BROKER_QUERY, ph.SERVER_HTTP])
def test_the_leaves_cover_a_node(plain_run, parent):
    """What no child covers (a node's self time) is under a tenth of the
    node: at the broker the server call is a leaf, at the server the
    execution phase is taken apart into its own leaves. Self time is
    glue and thread hand-offs, which a loaded host stretches for this
    query or that, so the best covered of the statements is held to it:
    a missing boundary would leave every one of them uncovered."""
    leaves = {
        ph.BROKER_QUERY: [ph.BROKER_PARSE, ph.BROKER_ROUTE,
                          ph.BROKER_SELECT, ph.SCATTER_CALL, ph.WIRE_DECODE,
                          ph.REDUCE, ph.BROKER_RESPOND],
        ph.SERVER_HTTP: [ph.SERVER_QUEUE, ph.SERVER_PARSE, ph.PLANNING,
                         ph.SERVER_ENCODE] + CHILDREN[ph.EXECUTION],
    }[parent]
    shares = []
    for before, after in zip(plain_run, plain_run[1:]):
        d = moved(before, after)
        shares.append(sum(d["phase_us_" + c] for c in leaves)
                      / d["phase_us_" + parent])
    assert max(shares) >= 0.9, shares


def test_counters_only_grow(plain_run):
    for a, b in zip(plain_run, plain_run[1:]):
        assert set(a) <= set(b)
        assert all(b[k] >= a[k] for k in a)
    d = moved(plain_run[0], plain_run[-1])
    assert d["wire_bytes_in"] > 0


@pytest.mark.parametrize("sql,family,launches", [
    (DENSE, ph.DENSE_VMAP, 1),
    (COMPACT, ph.COMPACT_SEGMENTED, 1),
    (SELECT, ph.SELECT_TOPK, N_SEGMENTS),
], ids=["dense", "compact", "select"])
def test_kernel_dispatches_by_family(trio, sql, family, launches):
    before = counters()
    trio(sql)
    d = moved(before, counters())
    assert d["kernel_dispatches_" + family] == launches
    assert d["kernel_dispatches"] == launches
    assert d["phase_n_" + ph.DEVICE_EXECUTE] == launches
    others = {k: v for k, v in d.items()
              if k.startswith("kernel_dispatches_")
              and k != "kernel_dispatches_" + family}
    assert not any(others.values()), others


@pytest.mark.parametrize("sql,form,other", [
    (SMALL_DICT, "select", "gather"), (LONG_DICT, "gather", "select"),
], ids=["small_dictionary", "long_dictionary"])
def test_dict_decode_forms_count_per_launch(trio, sql, form, other):
    """One vmapped launch decodes one dictionary-encoded value column: the
    counter of the form its dictionary's length selects moves by one, the
    other by none, and an operator reads both off the broker."""
    import urllib.request

    from pinot_tpu.ops.kernels import DICT_SELECT_MAX
    assert 5 <= DICT_SELECT_MAX < 300
    trio(sql + " OPTION(timeoutMs=280000)")          # compile
    before = counters()
    trio(sql)
    d = moved(before, counters())
    assert d["kernel_dispatches_" + ph.DENSE_VMAP] == 1
    assert d["kernel_dispatches"] == 1
    assert d["dict_decode_" + form] == 1
    assert d["dict_decode_" + other] == 0
    with urllib.request.urlopen(
            f"{trio.broker_url}/metrics/prometheus") as r:
        text = r.read().decode()
    assert "dict_decode_select" in text and "dict_decode_gather" in text


def test_statements_without_a_dictionary_value_leave_the_forms(trio):
    before = counters()
    trio(DENSE)
    d = moved(before, counters())
    assert not any(v for k, v in d.items() if k.startswith("dict_decode_"))


class _Counted:
    """Count constructions of a class for the length of a test."""

    def __init__(self, monkeypatch, cls):
        self.n = 0
        init = cls.__init__

        def counting(obj, *a, **kw):
            self.n += 1
            init(obj, *a, **kw)

        monkeypatch.setattr(cls, "__init__", counting)


def test_unsampled_and_unprofiled_builds_nothing(trio, monkeypatch):
    """Counters alone: no TraceAnnotation and no Span is constructed for
    a plain statement while no profiler session runs."""
    import jax

    class Annotation(jax.profiler.TraceAnnotation):
        made = 0

        def __init__(self, *a, **kw):
            Annotation.made += 1
            super().__init__(*a, **kw)

    monkeypatch.setattr(spans, "_annotation", Annotation)
    built = _Counted(monkeypatch, spans.Span)
    before = counters()
    trio(DENSE)
    assert moved(before, counters())["phase_n_" + ph.BROKER_QUERY] == 1
    assert Annotation.made == 0 and built.n == 0


def _host_events(trace_dir):
    import jax
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for n, line in enumerate(plane.lines):      # a line is a thread
            for ev in line.events:
                out.append(((plane.name, n), ev.name,
                            {k: v for k, v in ev.stats}))
    return out


def test_a_profiler_session_sees_the_phases_under_one_qid(trio, tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        trio(DENSE)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    ours = [(line, name, st) for line, name, st in events
            if name.startswith("pinot.")]
    # server_queue too: the handler's thread holds its event while the
    # statement waits for a scheduler worker
    assert {name for _l, name, _s in ours} == {
        "pinot." + p for p in CROSSINGS}
    assert not any("#" in name for _l, name, _s in ours)
    # one query id on every event, broker's and server's threads alike:
    # the handlers' threads, the scatter pool's and the scheduler worker's
    qids = {st.get("qid") for _l, _n, st in ours}
    assert len(qids) == 1 and None not in qids
    assert len({line for line, _n, _s in ours}) >= 4
    # the queue's event is closed by the worker that starts the query, so
    # the profiler files it on that worker's thread
    lines = {name: line for line, name, _s in ours}
    assert lines["pinot." + ph.SERVER_QUEUE] == \
        lines["pinot." + ph.SERVER_PARSE]
    # the compiled programs carry the family's name
    assert any("pinot_" + ph.DENSE_VMAP in name or
               any("pinot_" + ph.DENSE_VMAP in str(v) for v in st.values())
               for _l, name, st in events)


def test_sampled_tree_and_envelope_keep_their_names(trio):
    """The fold of Tracing.phase: EXPLAIN ANALYZE over the trio still
    renders the shared vocabulary, now with the broker's boundaries as
    nodes; the in-process broker's OPTION(trace=true) envelope keeps its
    three phases (tests/test_span_tracer.py pins the rest)."""
    rows = trio("EXPLAIN ANALYZE " + DENSE)["resultTable"]["rows"]
    names = [r[0] for r in rows]
    for node in (ph.QUERY, ph.BROKER_ROUTE, ph.BROKER_SELECT, ph.SCATTER,
                 ph.SCATTER_CALL, ph.SERVER_QUERY, ph.SERVER_PARSE,
                 ph.PLANNING, ph.EXECUTION, ph.DISPATCH_PREPARE,
                 "vmap_dispatch", ph.DEVICE_EXECUTE, ph.DEVICE_TRANSFER,
                 ph.EXTRACT_PARTIAL, ph.REDUCE):
        assert node in names, (node, names)
    from pinot_tpu.utils.trace import Tracing
    assert not hasattr(Tracing, "phase")
    scope = Tracing.register("q", True)
    try:
        with spans.phase(ph.PLANNING):
            pass
        with spans.phase(ph.BROKER_ROUTE):      # metered, not traced
            pass
    finally:
        Tracing.unregister()
    assert set(scope.to_dict()["phases"]) == {ph.PLANNING}


@pytest.mark.parametrize("family", sorted(ph.KERNEL_FAMILIES))
def test_module_names_follow_the_vocabulary(family):
    import jax.numpy as jnp

    from pinot_tpu.utils.compileplane import kernel_jit
    fn = kernel_jit(lambda x: x + 1, family)
    text = fn.lower(jnp.zeros(4, jnp.int32)).as_text()
    assert f"@jit_pinot_{family}" in text


def test_an_unknown_family_is_refused():
    from pinot_tpu.utils.compileplane import kernel_jit
    with pytest.raises(KeyError):
        kernel_jit(lambda x: x, "not_a_family")
    with pytest.raises(KeyError):
        spans.count_dispatch("not_a_family")


@pytest.mark.parametrize("sql,scopes", [
    (DENSE, {ph.SCOPE_MASK, ph.SCOPE_GROUP_KEY, ph.SCOPE_AGGREGATE}),
    (COMPACT, {ph.SCOPE_MASK, ph.SCOPE_GROUP_KEY, ph.SCOPE_PAYLOAD,
               ph.SCOPE_COMPACT, ph.SCOPE_AGGREGATE}),
    (SELECT, {ph.SCOPE_MASK, ph.SCOPE_TOPK}),
], ids=["dense", "compact", "select"])
def test_named_scopes_reach_the_lowered_kernel(trio, sql, scopes):
    """The stages' scope names ride the operations' metadata of the real
    kernels (and nothing else of the lowering: they are locations)."""
    import re

    import jax

    from pinot_tpu.engine.executor import resolve_params
    from pinot_tpu.ops import kernels
    from pinot_tpu.query.context import build_query_context
    from pinot_tpu.query.planner import SegmentPlanner
    from pinot_tpu.query.sql import parse_sql
    from pinot_tpu.segment import ImmutableSegment
    seg = ImmutableSegment.load(trio.segment_dir)
    plan = SegmentPlanner(build_query_context(parse_sql(sql)), seg).plan()
    if plan.kind == "kselect":
        kernel = kernels.build_select_kernel(plan.select_plan, seg.bucket)
    else:
        assert plan.kind == "kernel"
        kernel = kernels.build_kernel(plan.kernel_plan, seg.bucket,
                                      plan.slots_cap)
    lowered = jax.jit(kernel).lower(
        seg.device_cols(plan.col_names), np.int32(seg.n_docs),
        resolve_params(plan))
    found = set(re.findall(r"pinot\.[a-z_]+",
                           lowered.as_text(debug_info=True)))
    assert scopes <= found <= ph.KERNEL_SCOPES, found
    assert not re.findall(r"pinot\.[a-z_]+", lowered.as_text())
