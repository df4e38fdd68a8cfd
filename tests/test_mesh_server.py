"""A server node that holds a mesh: the table's segments resident across
the mesh's devices (parallel/distributed.DistributedTable), an aggregation
answered by ONE shard_map program, on the served path (controller, server,
broker, SQL over HTTP).

The table is the benchmark's seeded SSB table at a small size and the
answers are held to the benchmark's plain reference (numpy on the host
columns; it imports nothing of the program). Four of conftest's eight
virtual CPU devices make the mesh. Nothing here measures a speed.
"""
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.entries import served_http  # noqa: E402
from benchmark.ssb import data, oracle, statements  # noqa: E402
from pinot_tpu.clients import connect_url  # noqa: E402
from pinot_tpu.cluster import (BrokerNode, Controller,  # noqa: E402
                               ServerNode)
from pinot_tpu.ops import kernels  # noqa: E402
from pinot_tpu.parallel import DistributedTable, segment_mesh  # noqa: E402
from pinot_tpu.query.context import build_query_context  # noqa: E402
from pinot_tpu.query.sql import parse_sql  # noqa: E402
from pinot_tpu.segment import ImmutableSegment  # noqa: E402
from pinot_tpu.utils import phases as ph  # noqa: E402
from pinot_tpu.utils import spans  # noqa: E402
from pinot_tpu.utils.metrics import global_metrics  # noqa: E402

TABLE = served_http.TABLE
N_DEV = 4
N_SEGMENTS = 8              # two a device
ROWS = 1 << 15              # a segment: every dictionary comes out whole
SEEDS = [3, 11, 2_147_483_659]
SHAPES = statements.load_shapes()
WARM = " OPTION(timeoutMs=600000)"
# the statements whose group space puts them on the sort core
SORT_CORE = ["q3.2", "q3.3", "q3.4", "q4.3"]
# 250 x 250 city pairs: a group space over GROUP_XFER_SPACE on the sparse
# sorted post, whose live groups a segment follow the quantity's bound (a
# literal: one program), so its three tails (``probes``, what
# ops/kernels.sparse_post_probes gives a segment's live groups: under 512,
# under 4,096, more) each hand their rows to the mesh's _densify
CITY_PAIRS = {
    f"cities.qty_lt_{bound}": {
        "id": f"cities.qty_lt_{bound}", "probes": probes,
        "preds": [["d_year", "eq", 1993], ["lo_quantity", "lt", bound]],
        "value": ["lo_revenue"], "group": ["c_city", "s_city"],
        "order": [["c_city", "asc"], ["s_city", "asc"]]}
    for bound, probes in ((3, 512), (9, 4096),
                          (51, kernels.GROUP_XFER_CAP))}
ALL_SHAPES = {**SHAPES, **CITY_PAIRS}
MESH_FAMILIES = [ph.MESH_DENSE, ph.MESH_COMPACT,
                 ph.MESH_COMPACT_PER_SEGMENT]


def counters():
    return dict(global_metrics.snapshot()["counters"])


def moved(before, after=None):
    after = after or counters()
    return {k: after[k] - before.get(k, 0) for k in after}


def mesh_launches(d):
    return {f: d.get("kernel_dispatches_" + f, 0) for f in MESH_FAMILIES
            if d.get("kernel_dispatches_" + f, 0)}


class Trio:
    """Controller + one server + broker over a seeded table."""

    def __init__(self, root, seed, mesh, n_segments=N_SEGMENTS):
        self.seed = seed
        self.root = str(root)
        self.host = [data.gen_segment(ROWS, seed, k)
                     for k in range(n_segments)]
        self.dirs = [self.build(k) for k in range(n_segments)]
        self.ctrl = Controller(os.path.join(self.root, "ctrl"),
                               heartbeat_timeout=60.0)
        self.server = ServerNode("server_0", self.ctrl.url,
                                 poll_interval=0.1, mesh=mesh)
        self.broker = BrokerNode(self.ctrl.url, routing_refresh=0.1)
        schema = ImmutableSegment.load(self.dirs[0]).schema
        self.ctrl.add_table(TABLE, schema.to_dict(), replication=1)
        for d in self.dirs:
            self.ctrl.add_segment(TABLE, os.path.basename(d), d)
        self.settle()
        self.conn = connect_url(self.broker.url, timeout=600.0)

    def build(self, k):
        return served_http.build_segment(
            self.host[k], data.MEASURES,
            os.path.join(self.root, "segments"), f"seg_{k}")

    def settle(self):
        version = self.ctrl.routing_snapshot()["version"]
        assert self.server.wait_for_version(version, timeout=60.0)
        assert self.broker.wait_for_version(version, timeout=60.0)

    @property
    def dm(self):
        return self.server._tables[TABLE]

    def rows(self, key, option=""):
        return self.conn.execute(statements.to_sql(SHAPES[key])
                                 + option).rows

    def expected(self, key, segments=None):
        return oracle.answer(self.host if segments is None else segments,
                             ALL_SHAPES[key])

    def stop(self):
        for node in (self.broker, self.server, self.ctrl):
            node.stop()


@pytest.fixture(scope="module", params=SEEDS)
def mesh_trio(request, tmp_path_factory):
    trio = Trio(tmp_path_factory.mktemp(f"mesh_{request.param}"),
                request.param, jax.devices()[:N_DEV])
    dist = trio.dm.distributed
    assert dist is not None, "the seed's segments share no dictionaries"
    assert dist.n_dev == N_DEV and dist.local_segments == 2
    for key in SHAPES:                    # compile outside the tests
        trio.rows(key, WARM)
    yield trio
    trio.stop()


@pytest.fixture(scope="module")
def plain_trio(tmp_path_factory):
    trio = Trio(tmp_path_factory.mktemp("plain"), SEEDS[0], None)
    assert trio.server.mesh is None and trio.dm.distributed is None
    yield trio
    trio.stop()


# -- the served path over the mesh -----------------------------------------

@pytest.mark.parametrize("key", sorted(SHAPES))
def test_a_mesh_server_answers_as_the_reference(mesh_trio, key):
    """Every seed, every statement: the reference's rows in the
    statement's order, from one mesh program and no fallback."""
    before = counters()
    rows = mesh_trio.rows(key)
    d = moved(before)
    assert oracle.same(rows, mesh_trio.expected(key), SHAPES[key])
    assert d.get("mesh_fallbacks", 0) == 0
    assert sum(mesh_launches(d).values()) == 1
    assert d["kernel_dispatches"] == 1
    assert d.get("mesh_overflow_retries", 0) == 0


@pytest.mark.parametrize("key", sorted(SHAPES))
def test_a_meshless_server_gives_the_same_rows(plain_trio, key):
    """The two paths agree with the reference (the mesh-less node is
    today's server: per-segment launches, no mesh counter moves)."""
    before = counters()
    rows = plain_trio.rows(key, WARM)
    d = moved(before)
    assert oracle.same(rows, plain_trio.expected(key), SHAPES[key])
    assert not mesh_launches(d) and d.get("mesh_fallbacks", 0) == 0


def test_the_response_stands_for_every_segment(mesh_trio):
    res = mesh_trio.conn.execute(statements.to_sql(SHAPES["q2.1"]))
    assert res.num_segments == N_SEGMENTS
    raw = mesh_trio.server.execute(
        statements.to_sql(SHAPES["q2.1"]),
        [os.path.basename(d) for d in mesh_trio.dirs])
    assert raw["segmentsQueried"] == N_SEGMENTS
    assert len(raw["partials_raw"]) == 1


# -- the sort core routed inside the mesh program --------------------------

def _ctx(key):
    return build_query_context(parse_sql(statements.to_sql(ALL_SHAPES[key])))


@pytest.fixture(scope="module")
def segments(mesh_trio):
    return mesh_trio.dm.acquire_segments()


@pytest.mark.parametrize("key", SORT_CORE + sorted(CITY_PAIRS))
def test_the_routed_sort_core_equals_the_flattened_route(mesh_trio,
                                                         segments, key):
    """With the row limit under a local shard's rows (the table's own
    argument), a sort-core statement runs per local segment inside the
    mesh program and gives what the flattened shard and the reference
    give; the city pairs from each tail of the sparse post."""
    from pinot_tpu.engine.reduce import reduce_partials
    mesh = segment_mesh(devices=jax.devices()[:N_DEV])
    if key in CITY_PAIRS:
        live = [len(oracle.segment_sums(seg, CITY_PAIRS[key]))
                for seg in mesh_trio.host]
        assert {kernels.sparse_post_probes(n) for n in live} \
            == {CITY_PAIRS[key]["probes"]}, live
    answers = {}
    for name, limit in (("flattened", None), ("routed", ROWS)):
        dist = DistributedTable(segments, mesh, sort_row_limit=limit)
        plan = dist.mesh_plan(_ctx(key))
        assert plan.kernel_plan.strategy == "compact"
        assert kernels.takes_sparse_post(plan.kernel_plan)
        before = counters()
        partial = dist.execute(plan)
        assert mesh_launches(moved(before)) == {
            ph.MESH_COMPACT if limit is None
            else ph.MESH_COMPACT_PER_SEGMENT: 1}
        answers[name] = reduce_partials(_ctx(key), [partial]).rows
    assert answers["routed"] == answers["flattened"]
    assert oracle.same(answers["routed"], mesh_trio.expected(key),
                       ALL_SHAPES[key])


def test_the_factorized_core_stays_flattened_under_any_limit(segments):
    """The rule is the one-chip one: only the sort core has a row limit;
    the shared dictionaries leave the group space unmultiplied."""
    dist = DistributedTable(segments,
                            segment_mesh(devices=jax.devices()[:N_DEV]),
                            sort_row_limit=1)
    for key in ("q2.1", "q3.1", "q4.2"):
        plan = dist.mesh_plan(_ctx(key))
        if plan.kernel_plan.strategy == "compact":
            assert dist._route(plan.kernel_plan) == ph.MESH_COMPACT
    assert dist._route(dist.mesh_plan(_ctx("q1.1")).kernel_plan) \
        == ph.MESH_DENSE


def test_one_rule_one_constant(segments):
    """sort_core_fits is what engine/batch.py's predicate and the mesh's
    route both read."""
    dist = DistributedTable(segments,
                            segment_mesh(devices=jax.devices()[:N_DEV]))
    kp = dist.mesh_plan(_ctx("q3.2")).kernel_plan
    limit = kernels.SEGMENTED_SORT_ROW_LIMIT
    assert kernels.sort_core_fits(kp, limit)
    assert not kernels.sort_core_fits(kp, limit + 1)
    assert kernels.sort_core_fits(kp, 10, row_limit=10)
    assert not kernels.sort_core_fits(kp, 11, row_limit=10)
    assert kernels.segmented_compact_fits(kp, limit // 2, 2)
    assert not kernels.segmented_compact_fits(kp, limit, 2)
    small = dist.mesh_plan(_ctx("q3.1")).kernel_plan
    assert kernels.sort_core_fits(small, 1 << 40)        # factorized
    # the segmented batch multiplies the space: 4 x 4,375 is the sort core
    assert not kernels.sort_core_fits(small, limit + 1, space_factor=4)


def test_the_mesh_capacity_is_a_power_of_two(segments, monkeypatch):
    """The cost model's floor (864 slot rows on the chip) is refused by
    XLA:TPU in the sort core's dense post; the mesh runs at 1,024."""
    from pinot_tpu.multistage import costs
    dist = DistributedTable(segments,
                            segment_mesh(devices=jax.devices()[:N_DEV]))
    plan = dist.mesh_plan(_ctx("q3.4"))
    for given, want in ((864, 1024), (1024, 1024), (8, 8), (None, None)):
        monkeypatch.setattr(costs, "scaled_compact_cap",
                            lambda *_a, given=given: given)
        assert dist._cost_model_cap(plan, ROWS) == want
    assert dist._cost_model_cap(dist.mesh_plan(_ctx("q1.1")), ROWS) is None


# q3.2's shape with a loose filter: a sort-core group-by (437,500 groups)
# that a third of the rows match, so a small capacity overflows
LOOSE = {**SHAPES["q3.2"], "id": "loose",
         "preds": [["lo_quantity", "lt", 18]]}


@pytest.mark.parametrize("limit", [None, ROWS],
                         ids=["flattened", "per_segment"])
def test_an_overflow_retries_at_full_capacity_once(mesh_trio, segments,
                                                   monkeypatch, limit):
    """A capacity the matches overflow: the program reruns at the
    capacity that cannot overflow (of the shard, or of one segment on the
    routed core), the answer is right, and the next execution of the plan
    goes straight there. The group outputs are sparse (the sorted core's
    sparse post, densified on the device for the collectives, gathered
    for the transfer at the live list the devices' own ids give) until
    they spill."""
    from pinot_tpu.engine.reduce import reduce_partials
    dist = DistributedTable(segments,
                            segment_mesh(devices=jax.devices()[:N_DEV]),
                            sort_row_limit=limit)
    monkeypatch.setattr(dist, "_cost_model_cap", lambda plan, rows: 8)
    ctx = build_query_context(parse_sql(statements.to_sql(LOOSE)))
    plan = dist.mesh_plan(ctx)
    assert dist._route(plan.kernel_plan) == (
        ph.MESH_COMPACT if limit is None else ph.MESH_COMPACT_PER_SEGMENT)
    before = counters()
    rows = reduce_partials(ctx, [dist.execute(plan)]).rows
    d = moved(before)
    # so many groups are live that the transfer compaction spills too:
    # the third launch sends the dense (space,) outputs
    assert d["mesh_overflow_retries"] == 1
    assert d["group_xfer_overflow_retries"] == 1
    assert sum(mesh_launches(d).values()) == 3
    assert oracle.same(rows, oracle.answer(mesh_trio.host, LOOSE), LOOSE)
    before = counters()
    again = reduce_partials(ctx, [dist.execute(plan)]).rows
    d = moved(before)
    assert again == rows and d.get("mesh_overflow_retries", 0) == 0
    assert sum(mesh_launches(d).values()) == 2


def test_the_served_path_routes_by_the_constant(mesh_trio, monkeypatch):
    """The node routes by ops/kernels.SEGMENTED_SORT_ROW_LIMIT itself:
    lowered under a local shard's rows, a sort-core statement over HTTP
    takes the per-segment family and is still right."""
    monkeypatch.setattr(kernels, "SEGMENTED_SORT_ROW_LIMIT", ROWS)
    before = counters()
    rows = mesh_trio.rows("q4.3", WARM)
    d = moved(before)
    assert mesh_launches(d) == {ph.MESH_COMPACT_PER_SEGMENT: 1}
    assert oracle.same(rows, mesh_trio.expected("q4.3"), SHAPES["q4.3"])
    tree = mesh_trio.conn.execute(
        "EXPLAIN ANALYZE " + statements.to_sql(SHAPES["q4.3"]) + WARM).rows
    (detail,) = [r[4] for r in tree if r[0] == "mesh_dispatch"]
    assert "route=mesh_compact_per_segment" in detail
    assert f"devices={N_DEV}" in detail and "local_segments=2" in detail


# -- what has to fall back ---------------------------------------------------

def test_a_selection_falls_back_and_is_counted(mesh_trio):
    sql = (f"SELECT lo_quantity, lo_revenue FROM {TABLE} "
           "WHERE lo_discount = 3 ORDER BY lo_revenue DESC, lo_quantity "
           "LIMIT 5")
    before = counters()
    rows = mesh_trio.conn.execute(sql + WARM).rows
    d = moved(before)
    assert d["mesh_fallbacks"] == 1 and not mesh_launches(d)
    want = sorted(((int(q), int(r)) for seg in mesh_trio.host
                   for q, r, disc in zip(seg["lo_quantity"],
                                         seg["lo_revenue"],
                                         seg["lo_discount"]) if disc == 3),
                  key=lambda t: (-t[1], t[0]))[:5]
    assert [tuple(r) for r in rows] == want


def test_a_subset_of_the_segments_falls_back_and_is_counted(mesh_trio):
    """The server asked for some of the mesh's segments answers from the
    per-segment path: the mesh program covers all of them or none."""
    from pinot_tpu.engine.reduce import reduce_partials
    names = [os.path.basename(d) for d in mesh_trio.dirs[:3]]
    sql = statements.to_sql(SHAPES["q2.2"]) + WARM
    before = counters()
    resp = mesh_trio.server.execute(sql, names)
    d = moved(before)
    assert d["mesh_fallbacks"] == 1 and not mesh_launches(d)
    assert resp["segmentsQueried"] == 3
    rows = reduce_partials(_ctx("q2.2"), resp["partials_raw"]).rows
    assert oracle.same(rows, mesh_trio.expected("q2.2", mesh_trio.host[:3]),
                       SHAPES["q2.2"])


def test_explain_plans_and_launches_nothing(mesh_trio):
    before = counters()
    rows = mesh_trio.conn.execute(
        "EXPLAIN " + statements.to_sql(SHAPES["q1.1"])).rows
    d = moved(before)
    assert rows and d["kernel_dispatches"] == 0
    assert d.get("mesh_fallbacks", 0) == 0


# -- tracing ------------------------------------------------------------------

def test_a_mesh_query_moves_the_phase_counters(mesh_trio):
    """One crossing each, as the per-layer readers expect: planning, then
    execution around distributed_execute, whose leaves are the prepare,
    the one launch, the one copy back and the extraction."""
    before = counters()
    mesh_trio.rows("q2.1")
    # the handlers' phases close after the client has its answer
    import time
    for _ in range(5000):
        if moved(before).get("phase_n_" + ph.SERVER_HTTP, 0):
            break
        time.sleep(0.002)
    d = moved(before)
    for name in (ph.PLANNING, ph.EXECUTION, ph.DISTRIBUTED_EXECUTE,
                 ph.DISPATCH_PREPARE, ph.DEVICE_EXECUTE, ph.DEVICE_TRANSFER,
                 ph.EXTRACT_PARTIAL, ph.SERVER_ENCODE, ph.SERVER_HTTP):
        assert d["phase_n_" + name] == 1, name
    inside = sum(d["phase_us_" + c] for c in (
        ph.DISPATCH_PREPARE, ph.DEVICE_EXECUTE, ph.DEVICE_TRANSFER,
        ph.EXTRACT_PARTIAL))
    assert inside <= d["phase_us_" + ph.DISTRIBUTED_EXECUTE] + 4
    assert d["phase_us_" + ph.DISTRIBUTED_EXECUTE] \
        <= d["phase_us_" + ph.EXECUTION] + 1
    assert d["dict_decode_select"] + d["dict_decode_gather"] == 0


def test_a_q1_statement_counts_its_decode_form(mesh_trio):
    """lo_discount's 11-entry dictionary decodes by the select chain on
    the mesh as on one chip."""
    before = counters()
    mesh_trio.rows("q1.1")
    d = moved(before)
    assert mesh_launches(d) == {ph.MESH_DENSE: 1}
    assert d["dict_decode_select"] == 1 and d["dict_decode_gather"] == 0


def test_an_unsampled_mesh_query_builds_no_span(mesh_trio, monkeypatch):
    made = []
    init = spans.Span.__init__

    def counting(obj, *a, **kw):
        made.append(a[0] if a else kw.get("name"))
        init(obj, *a, **kw)

    monkeypatch.setattr(spans.Span, "__init__", counting)
    before = counters()
    mesh_trio.rows("q3.1")
    assert sum(mesh_launches(moved(before)).values()) == 1
    assert made == []


def test_a_sampled_mesh_query_names_its_route(mesh_trio):
    tree = mesh_trio.conn.execute(
        "EXPLAIN ANALYZE " + statements.to_sql(SHAPES["q2.1"]) + WARM).rows
    names = [r[0] for r in tree]
    for name in (ph.PLANNING, ph.EXECUTION, ph.DISTRIBUTED_EXECUTE,
                 "mesh_dispatch", ph.DEVICE_EXECUTE, ph.DEVICE_TRANSFER,
                 ph.EXTRACT_PARTIAL):
        assert names.count(name) == 1, (name, names)
    (detail,) = [r[4] for r in tree if r[0] == "mesh_dispatch"]
    assert "route=mesh_" in detail and "slots_cap=" in detail


def _lowered(dist, key, xfer_compact=True):
    """(kernel plan, route, lowered program) of a statement on ``dist``."""
    from pinot_tpu.engine.executor import resolve_params
    from pinot_tpu.parallel import distributed
    from jax.sharding import PartitionSpec as P
    plan = dist.mesh_plan(_ctx(key))
    family = dist._route(plan.kernel_plan)
    cols = tuple(dist.device_col(n) for n in plan.col_names)
    params = resolve_params(plan, sharding=dist._sharding(P()))
    rows = dist.bucket * (1 if family == ph.MESH_COMPACT_PER_SEGMENT
                          else dist.local_segments)
    fn = distributed._distributed_kernel(
        plan.kernel_plan, dist.bucket, dist.mesh, len(cols), len(params),
        dist._cost_model_cap(plan, rows), family, xfer_compact)
    return (plan.kernel_plan, family,
            fn._fn.lower(cols, dist._n_docs, params))


def test_the_mesh_programs_carry_their_names(segments):
    """XLA Modules reads jit_pinot_mesh_<route>; the collectives sit
    under pinot.combine."""
    mesh = segment_mesh(devices=jax.devices()[:N_DEV])
    dist = DistributedTable(segments, mesh, sort_row_limit=ROWS)
    for key in ("q1.1", "q2.1", "q3.2"):
        _kp, family, lowered = _lowered(dist, key)
        assert f"@jit_pinot_{family}" in lowered.as_text()
        assert ph.SCOPE_COMBINE in lowered.as_text(debug_info=True)


def _after_the_collectives(text):
    """The lines of the mesh program's body behind its last collective,
    and the signatures of the private functions they call."""
    import re
    lines = text.splitlines()
    last = max(i for i, line in enumerate(lines) if "stablehlo.all_" in line)
    end = next(i for i in range(last, len(lines))
               if lines[i].strip().startswith(("return", "func.return",
                                               "sdy.return")))
    tail = lines[last + 1:end]
    called = set(re.findall(r"call @([\w.]+)", "\n".join(tail)))
    heads = [line for line in lines
             if any(f"@{name}(" in line for name in called)
             and "func.func" in line]
    assert len(heads) == len(called), (called, heads)
    return lines[last], tail, heads


@pytest.mark.parametrize("key,limit", [("q4.3", ROWS), ("q3.2", ROWS),
                                       ("q3.3", None), ("q3.4", None)])
def test_no_pass_over_the_space_stands_behind_the_collectives(segments,
                                                              key, limit):
    """Between the collectives and the outputs of a program whose devices
    emitted sparse rows, nothing has an operand or a result of
    group_space elements but the gathers' operands: the live list comes
    from an all_gather of the ids and two sorts of them, and a nonzero
    over the space cannot come back unnoticed. The dense retry's program
    (xfer_compact=False) and a scatter-core program keep what they had."""
    dist = DistributedTable(segments,
                            segment_mesh(devices=jax.devices()[:N_DEV]),
                            sort_row_limit=limit)
    kp, family, lowered = _lowered(dist, key)
    text = lowered.as_text()
    assert family == (ph.MESH_COMPACT if limit is None
                      else ph.MESH_COMPACT_PER_SEGMENT)
    wide = f"tensor<{kp.group_space}x"
    assert wide in text                  # the dense partials, the psums
    last, tail, heads = _after_the_collectives(text)
    assert "stablehlo.all_gather" in last and "xi32>" in last
    assert text.count("stablehlo.all_gather") == 1
    assert sum("call @sort" in line or "stablehlo.sort" in line
               for line in tail) == 2
    gathers = [line for line in tail if wide in line]
    assert gathers and not any(wide in h for h in heads)
    for line in gathers:
        assert '"stablehlo.gather"' in line, line
        operands, result = line.rsplit("->", 1)
        assert wide in operands and wide not in result, line
    assert len(gathers) >= 2             # the counts and an aggregate
    # the dense retry's program has no list at all
    dense = _lowered(dist, key, xfer_compact=False)[2].as_text()
    assert "stablehlo.all_gather" not in dense


def test_a_small_space_program_is_lowered_as_before(segments, monkeypatch):
    """The nine programs whose kernels hand over dense groups (three
    dense, six flattened compact over 175-7,000 groups) never reach the
    live list: no all_gather, no sort behind the collectives, and the
    same text with the branch taken out of the module."""
    from pinot_tpu.parallel import distributed
    dist = DistributedTable(segments,
                            segment_mesh(devices=jax.devices()[:N_DEV]))
    texts = {}
    for key in ("q1.1", "q2.1", "q3.1", "q4.2"):
        kp, family, lowered = _lowered(dist, key)
        texts[key] = lowered.as_text()
        assert kp.group_space < kernels.GROUP_XFER_SPACE or not kp.is_group_by
        assert not distributed.lists_live_groups_sparse(kp, family, True,
                                                        False)
        assert "stablehlo.all_gather" not in texts[key]
        _last, tail, heads = _after_the_collectives(texts[key])
        assert not any("sort" in line for line in tail + heads)

    def gone(*_a, **_k):
        raise AssertionError("a small-space program asked for the list")
    monkeypatch.setattr(distributed, "_gather_live_groups", gone)
    monkeypatch.setattr(distributed, "lists_live_groups_sparse",
                        lambda *_a: False)
    distributed._distributed_kernel_cached.cache_clear()
    try:
        for key, text in texts.items():
            assert _lowered(dist, key)[2].as_text() == text, key
    finally:
        distributed._distributed_kernel_cached.cache_clear()


@pytest.mark.parametrize("key,sparse", [("q3.2", 1), ("q3.3", 1),
                                        ("q3.4", 1), ("q4.3", 1),
                                        ("q2.1", 0), ("q1.1", 0)])
def test_a_transfer_compacted_query_counts_where_its_list_came_from(
        mesh_trio, key, sparse):
    """mesh_live_list_sparse moves once for each statement whose result
    comes back compacted to its live groups (the four over
    GROUP_XFER_SPACE, whose devices emit sparse rows) and not at all for
    a small-space statement; both names reach Prometheus."""
    import urllib.request
    before = counters()
    rows = mesh_trio.rows(key)
    d = moved(before)
    assert d.get("mesh_live_list_sparse", 0) == sparse
    assert d.get("mesh_live_list_dense", 0) == 0
    assert oracle.same(rows, mesh_trio.expected(key), SHAPES[key])
    if sparse:
        with urllib.request.urlopen(
                f"{mesh_trio.broker.url}/metrics/prometheus") as r:
            assert b"mesh_live_list_sparse" in r.read()


def test_a_dense_kernel_keeps_the_nonzero_and_is_counted(mesh_trio, segments,
                                                         monkeypatch):
    """The scatter core hands over dense (space,) groups: nothing lists
    them but the nonzero over the space (_compact_group_xfer), the answer
    is the same, and the query counts mesh_live_list_dense."""
    import urllib.request
    from pinot_tpu.engine.reduce import reduce_partials
    from pinot_tpu.parallel import distributed
    monkeypatch.setenv("PINOT_CPU_FAST_GROUPBY", "1")
    dist = DistributedTable(segments,
                            segment_mesh(devices=jax.devices()[:N_DEV]))
    plan = dist.mesh_plan(_ctx("q3.2"))
    kp = plan.kernel_plan
    assert kp.group_space >= kernels.GROUP_XFER_SPACE
    assert not distributed.lists_live_groups_sparse(
        kp, dist._route(kp), True, kernels.cpu_scatter_default("cpu"))
    before = counters()
    rows = reduce_partials(_ctx("q3.2"), [dist.execute(plan)]).rows
    d = moved(before)
    assert d.get("mesh_live_list_dense", 0) == 1
    assert d.get("mesh_live_list_sparse", 0) == 0
    assert oracle.same(rows, mesh_trio.expected("q3.2"), SHAPES["q3.2"])
    assert "stablehlo.all_gather" not in _lowered(dist, "q3.2")[2].as_text()
    with urllib.request.urlopen(
            f"{mesh_trio.broker.url}/metrics/prometheus") as r:
        assert b"mesh_live_list_dense" in r.read()


# -- the segment set changes ---------------------------------------------------

@pytest.fixture()
def small_trio(tmp_path):
    trio = Trio(tmp_path, 5, jax.devices()[:N_DEV], n_segments=5)
    yield trio
    trio.stop()


def test_a_changed_segment_set_rebuilds_the_residency(small_trio):
    """Five segments (padded to eight slots); one removed, then put back:
    each time the residency is a new one over the loaded set and the next
    answer is right."""
    trio = small_trio
    first = trio.dm.distributed
    assert first is not None and first.n_slots == 8
    assert len(first.segments) == 5
    assert oracle.same(trio.rows("q2.1", WARM), trio.expected("q2.1"),
                       SHAPES["q2.1"])
    assert first._cols                      # its columns went up

    trio.ctrl.delete_segment(TABLE, "seg_4")
    trio.settle()
    second = trio.dm.distributed
    assert second is not first and len(second.segments) == 4
    assert not first._cols                  # the old residency let go
    before = counters()
    rows = trio.rows("q2.1", WARM)
    assert oracle.same(rows, trio.expected("q2.1", trio.host[:4]),
                       SHAPES["q2.1"])
    assert sum(mesh_launches(moved(before)).values()) == 1

    # the controller's drop took the segment's directory with it
    trio.ctrl.add_segment(TABLE, "seg_4", trio.build(4))
    trio.settle()
    third = trio.dm.distributed
    assert third is not second and len(third.segments) == 5
    before = counters()
    rows = trio.rows("q3.2", WARM)
    d = moved(before)
    assert oracle.same(rows, trio.expected("q3.2"), SHAPES["q3.2"])
    assert sum(mesh_launches(d).values()) == 1
    assert d.get("mesh_fallbacks", 0) == 0


def test_a_stopped_node_lets_its_residency_go(tmp_path):
    trio = Trio(tmp_path, 5, jax.devices()[:N_DEV], n_segments=4)
    trio.rows("q1.1", WARM)
    dist = trio.dm.distributed
    assert dist._cols
    trio.stop()
    assert not dist._cols and trio.dm.distributed is None


def test_a_node_takes_a_mesh_or_a_device_list(tmp_path):
    from jax.sharding import Mesh
    ctrl = Controller(str(tmp_path / "ctrl"))
    try:
        for given in (jax.devices()[:2],
                      segment_mesh(devices=jax.devices()[:2])):
            node = ServerNode("n", ctrl.url, mesh=given)
            try:
                assert isinstance(node.mesh, Mesh)
                assert node.mesh.devices.size == 2
            finally:
                node.stop()
    finally:
        ctrl.stop()
