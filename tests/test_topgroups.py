"""The top-groups route (query/planner._plan_topgroups, engine/batch.
execute_topgroups, ops/kernels.build_topgroups_kernel): COUNT(*) by
dictionary keys ordered by that count, over a server's id space, held to
a collections.Counter over the same rows on the served path (HTTP ->
broker -> server), for ClickBench's four statement shapes over small
seeded tables. The route needs a key space over
ops/kernels.COMPACT_GROUP_LIMIT; here the limit is lowered to 64 for
the whole module, so that a few thousand rows reach it, and the
statements of the accepted cells are planned under both limits. The
server trim's floor (engine/reduce.SERVER_TRIM_MIN, 5,000) is lowered
to 20 the same way, so that a one-server partial of these tables is
trimmed to max(5 x LIMIT, 20) groups, and a two-server one would be if
it were trimmed at all."""
import os
import sys
from collections import Counter

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from pinot_tpu.clients import connect_url  # noqa: E402
from pinot_tpu.cluster import BrokerNode, Controller, ServerNode  # noqa
from pinot_tpu.engine import reduce as R  # noqa: E402
from pinot_tpu.ops import kernels as K  # noqa: E402
from pinot_tpu.query.context import build_query_context  # noqa: E402
from pinot_tpu.query.planner import SegmentPlanner  # noqa: E402
from pinot_tpu.query.sql import parse_sql  # noqa: E402
from pinot_tpu.segment import ImmutableSegment, SegmentBuilder  # noqa
from pinot_tpu.server.data_manager import TableDataManager  # noqa: E402
from pinot_tpu.spi import (DataType, FieldSpec, IndexingConfig,  # noqa
                           Schema, TableConfig)
from pinot_tpu.utils.metrics import global_metrics  # noqa: E402

TABLE = "hits"
ROWS = 1500
SEEDS = (3, 2_147_483_659)
COLS = ("UserID", "SearchPhrase", "SearchEngineID")
# ClickBench's statements 12, 14, 15 and 16: keys, and the <> '' filter
SHAPES = {
    "q12": (("SearchPhrase",), True),
    "q14": (("SearchEngineID", "SearchPhrase"), True),
    "q15": (("UserID",), False),
    "q16": (("UserID", "SearchPhrase"), False),
}
SCHEMA = Schema(TABLE, [FieldSpec("UserID", DataType.LONG),
                        FieldSpec("SearchPhrase", DataType.STRING),
                        FieldSpec("SearchEngineID", DataType.INT)])


def sql_of(key, limit=10, asc=False):
    keys, nonempty = SHAPES[key]
    where = " WHERE SearchPhrase <> ''" if nonempty else ""
    return (f"SELECT {', '.join(keys)}, COUNT(*) AS c FROM {TABLE}{where} "
            f"GROUP BY {', '.join(keys)} ORDER BY c {'ASC' if asc else 'DESC'}"
            f" LIMIT {limit}")


def gen_rows(seed, k, n=ROWS):
    """One segment's rows: users uniform over 700, phrases skewed (half
    of the hits without one), and twelve users planted with 10 phrase-
    less hits each in every segment, so that groups of the user
    statements tie at the 10th count."""
    rng = np.random.default_rng((seed, k))
    users = rng.integers(0, 700, n).astype(np.int64) * 1_000_003 + seed
    phrase_ids = rng.zipf(1.3, n) % 3000
    phrases = np.where(rng.random(n) < 0.5, "",
                       np.char.add("phrase ", phrase_ids.astype(str)))
    users[:120] = np.repeat(np.arange(12, dtype=np.int64) * 7 + 10**12, 10)
    phrases[:120] = ""
    engines = np.where(phrases == "", 0, rng.integers(1, 6, n))
    # a segment's dictionaries differ from its neighbours': a phrase of
    # its own moves the local ids of the phrases after it
    own = rng.random(n) < 0.05
    own[:120] = False
    phrases[own] = f"only in segment {k}"
    return {"UserID": users, "SearchPhrase": phrases,
            "SearchEngineID": engines.astype(np.int32)}


def build(cols, out_dir, name):
    config = TableConfig(TABLE, indexing=IndexingConfig(
        dictionary_columns=["UserID"]))
    return SegmentBuilder(SCHEMA, config).build(cols, out_dir, name)


def reference(tables, key):
    """Counter of the statement's key tuples over every row."""
    keys, nonempty = SHAPES[key]
    out = Counter()
    for t in tables:
        cols = [t[c].tolist() for c in keys]
        for i, row in enumerate(zip(*cols)):
            if nonempty and t["SearchPhrase"][i] == "":
                continue
            out[row] += 1
    return out


def assert_top(rows, counter, limit, asc=False):
    """``rows`` is an answer ORDER BY count (DESC, or ASC) LIMIT allows:
    min(limit, groups) distinct groups with their exact counts, ordered,
    every group strictly beyond the limit-th count present."""
    rows = [tuple(r) for r in rows]
    assert len(rows) == min(limit, len(counter))
    counts = [r[-1] for r in rows]
    assert counts == sorted(counts, reverse=not asc)
    assert len({r[:-1] for r in rows}) == len(rows)
    for r in rows:
        assert counter[r[:-1]] == r[-1], r
    ranked = sorted(counter.values(), reverse=not asc)
    cut = ranked[min(limit, len(ranked)) - 1]
    beyond = {k for k, c in counter.items()
              if (c < cut if asc else c > cut)}
    assert beyond <= {r[:-1] for r in rows}


def counters():
    return dict(global_metrics.snapshot()["counters"])


def moved(before):
    after = counters()
    return {k: after[k] - before.get(k, 0) for k in after}


TRIM_MIN = 20


@pytest.fixture(scope="module", autouse=True)
def small_limit():
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(K, "COMPACT_GROUP_LIMIT", 64)
        mp.setattr(R, "SERVER_TRIM_MIN", TRIM_MIN)
        yield


class Cluster:
    """Controller, ``servers`` server nodes and a broker over a table of
    ``segments`` seeded segments."""

    def __init__(self, root, seed, servers=1, segments=3, mesh=None,
                 rows=ROWS):
        self.root = str(root)
        self.seed = seed
        self.n = rows
        self.tables = []
        self.ctrl = Controller(os.path.join(self.root, "ctrl"),
                               heartbeat_timeout=60.0,
                               reconcile_interval=0.2)
        self.servers = [ServerNode(f"server_{i}", self.ctrl.url,
                                   poll_interval=0.1, mesh=mesh)
                        for i in range(servers)]
        self.broker = BrokerNode(self.ctrl.url, routing_refresh=0.1)
        self.ctrl.add_table(TABLE, SCHEMA.to_dict(), replication=1)
        for _ in range(segments):
            self.add_segment()
        self.conn = connect_url(self.broker.url, timeout=600.0)

    def add_segment(self):
        k = len(self.tables)
        self.tables.append(gen_rows(self.seed, k, self.n))
        name = f"seg_{k}"
        d = build(self.tables[-1], os.path.join(self.root, "segments"), name)
        self.ctrl.add_segment(TABLE, name, d)
        version = self.ctrl.routing_snapshot()["version"]
        for s in self.servers:
            assert s.wait_for_version(version, timeout=60.0)
        assert self.broker.wait_for_version(version, timeout=60.0)

    def held(self):
        return [seg for s in self.servers
                for seg in s._tables[TABLE].acquire_segments()]

    def rows(self, sql):
        return self.conn.execute(sql).rows

    def stop(self):
        for node in [self.broker] + self.servers + [self.ctrl]:
            node.stop()


@pytest.fixture(scope="module", params=SEEDS)
def one_server(request, tmp_path_factory):
    c = Cluster(tmp_path_factory.mktemp(f"one_{request.param}"),
                request.param)
    yield c
    c.stop()


@pytest.fixture(scope="module")
def two_servers(tmp_path_factory):
    c = Cluster(tmp_path_factory.mktemp("two"), SEEDS[0], servers=2,
                segments=4)
    yield c
    c.stop()


@pytest.fixture(scope="module")
def mesh_server(tmp_path_factory):
    c = Cluster(tmp_path_factory.mktemp("mesh"), SEEDS[0],
                mesh=jax.devices()[:2], segments=2)
    yield c
    c.stop()


@pytest.fixture(scope="module")
def growing(tmp_path_factory):
    """A cluster that gains a segment after its first answers."""
    c = Cluster(tmp_path_factory.mktemp("grow"), SEEDS[1], segments=2)
    for key in SHAPES:
        assert_top(c.rows(sql_of(key)), reference(c.tables, key), 10)
    c.add_segment()
    yield c
    c.stop()


# -- the served answers ----------------------------------------------------

@pytest.mark.parametrize("key", sorted(SHAPES))
def test_top10_matches_counter(one_server, key):
    before = counters()
    rows = one_server.rows(sql_of(key))
    d = moved(before)
    assert_top(rows, reference(one_server.tables, key), 10)
    assert d["topgroups_statements"] == 1
    assert d["kernel_dispatches_topgroups"] == 1
    assert d["segments_host"] == 0
    assert d["topgroups_live_groups"] == len(reference(one_server.tables,
                                                       key))
    assert d["partial_groups_sent"] == min(d["topgroups_live_groups"], 50)


@pytest.mark.parametrize("key", sorted(SHAPES))
def test_ties_at_the_limit(one_server, key):
    """The planted users tie: each table has more groups at or above the
    10th count than the LIMIT, and any of them may answer."""
    counter = reference(one_server.tables, key)
    if key in ("q15", "q16"):
        ranked = sorted(counter.values(), reverse=True)
        assert sum(c >= ranked[9] for c in ranked) > 10
    assert_top(one_server.rows(sql_of(key)), counter, 10)


@pytest.mark.parametrize("key", sorted(SHAPES))
def test_ascending_order(one_server, key):
    assert_top(one_server.rows(sql_of(key, 7, asc=True)),
               reference(one_server.tables, key), 7, asc=True)


@pytest.mark.parametrize("key", sorted(SHAPES))
def test_limit_above_the_trim(one_server, key):
    """LIMIT 6000: the partial keeps max(5 x 6000, 5000) groups, every
    group of these tables."""
    counter = reference(one_server.tables, key)
    assert_top(one_server.rows(sql_of(key, 6000)), counter, 6000)


def test_the_partial_is_trimmed_to_5000(tmp_path, monkeypatch):
    """One server, more live groups than Pinot's floor of the trim: the
    partial carries 5,000 of them, and the answer is still exact."""
    monkeypatch.setattr(R, "SERVER_TRIM_MIN", 5000)
    c = Cluster(tmp_path, SEEDS[0], segments=2, rows=8000)
    try:
        counter = reference(c.tables, "q16")
        assert len(counter) > 5000
        before = counters()
        assert_top(c.rows(sql_of("q16")), counter, 10)
        d = moved(before)
        assert d["topgroups_live_groups"] == len(counter)
        assert d["partial_groups_sent"] == 5000
    finally:
        c.stop()


@pytest.mark.parametrize("key", sorted(SHAPES))
def test_two_servers_send_every_group(two_servers, key):
    """Two servers each hold some segments: neither partial is the
    statement's only one, so neither is trimmed (each server's live
    groups are over the trim of 50), and the answer is exact."""
    counter = reference(two_servers.tables, key)
    before = counters()
    assert_top(two_servers.rows(sql_of(key)), counter, 10)
    d = moved(before)
    assert len({s for s in two_servers.servers
                if s._tables[TABLE].acquire_segments()}) == 2
    assert d["topgroups_statements"] == 2
    assert d["partial_groups_sent"] == d["topgroups_live_groups"] > 2 * 50


@pytest.mark.parametrize("key", sorted(SHAPES))
def test_a_segment_added_after_load(growing, key):
    """The id space is made anew when a segment arrives: the new
    segment's values, some of them in no other segment, are counted."""
    counter = reference(growing.tables, key)
    assert_top(growing.rows(sql_of(key)), counter, 10)
    spaces = {id(seg.server_ids) for seg in growing.held()}
    assert len(spaces) == 1 and len(growing.held()) == 3


@pytest.mark.parametrize("key", sorted(SHAPES))
def test_mesh_server_keeps_its_route(mesh_server, key):
    """A mesh node keeps no server id space: the statement takes the
    route it had, and answers."""
    before = counters()
    assert_top(mesh_server.rows(sql_of(key)),
               reference(mesh_server.tables, key), 10)
    d = moved(before)
    assert d["topgroups_statements"] == 0
    assert d["kernel_dispatches_topgroups"] == 0
    assert all(seg.server_ids is None for seg in mesh_server.held())


def test_explain_names_the_route(one_server):
    rows = one_server.rows("EXPLAIN PLAN FOR " + sql_of("q16"))
    assert any(str(r[0]).startswith("TPU_TOPGROUPS(segments:3")
               for r in rows)


# -- the server id space -----------------------------------------------------

@pytest.mark.parametrize("col", COLS)
def test_local_ids_disagree_and_remap(one_server, col):
    """Each segment's remap sends its local id to the server id of the
    same value; the planted per-segment phrases move the local ids of
    the phrases after them."""
    segs = one_server.held()
    column = segs[0].server_ids.column(col)
    for seg in segs:
        local = seg.dictionary(col).values
        got = column.values[column.remaps[seg.uid]].tolist()
        assert got == (list(local) if isinstance(local, list)
                       else local.tolist())
    if col == "SearchPhrase":
        assert any(len(column.remaps[s.uid]) != column.cardinality
                   or (column.remaps[s.uid] != np.arange(
                       column.cardinality)).any() for s in segs)


# -- statements that keep their route ----------------------------------------

INELIGIBLE = {
    "having": f"SELECT UserID, COUNT(*) FROM {TABLE} GROUP BY UserID "
              "HAVING COUNT(*) > 1 ORDER BY COUNT(*) DESC LIMIT 10",
    "two_orders": f"SELECT UserID, COUNT(*) FROM {TABLE} GROUP BY UserID "
                  "ORDER BY COUNT(*) DESC, UserID LIMIT 10",
    "key_order": f"SELECT UserID, COUNT(*) FROM {TABLE} GROUP BY UserID "
                 "ORDER BY UserID LIMIT 10",
    "sum": f"SELECT UserID, SUM(SearchEngineID) AS s FROM {TABLE} "
           "GROUP BY UserID ORDER BY s DESC LIMIT 10",
    "no_order": f"SELECT UserID, COUNT(*) FROM {TABLE} GROUP BY UserID "
                "LIMIT 10",
    "forced": f"SELECT UserID, COUNT(*) FROM {TABLE} GROUP BY UserID "
              "ORDER BY COUNT(*) DESC LIMIT 10 "
              "OPTION(groupByStrategy=compact)",
}


@pytest.mark.parametrize("name", sorted(INELIGIBLE))
def test_other_shapes_keep_their_route(one_server, name):
    ctx = build_query_context(parse_sql(INELIGIBLE[name]))
    for seg in one_server.held():
        assert SegmentPlanner(ctx, seg).plan().kind != "topgroups"


def _accepted_statements():
    from benchmark.ssb import statements as ssb
    from benchmark.taxi import statements as taxi
    from benchmark.tpch import statements as tpch
    out = [("ssb", k, ssb.to_sql(s)) for k, s in ssb.load_shapes().items()]
    out += [("taxi", k, taxi.to_sql(s))
            for k, s in taxi.load_shapes().items()]
    out += [("tpch", k, tpch.to_sql(s))
            for k, s in tpch.load_shapes().items() if k in ("q1.d90",
                                                           "q6.y1994")]
    return out


ACCEPTED = _accepted_statements()


@pytest.fixture(scope="module")
def accepted_segments(tmp_path_factory):
    """{dataset: two segments of its generator, held by a server's data
    manager (with a server id space) and loaded bare (without)}."""
    from benchmark.entries import served_http as ssb_entry
    from benchmark.entries import served_http_taxi as taxi_entry
    from benchmark.entries import served_http_tpch as tpch_entry
    from benchmark.ssb import data as ssb_data
    from benchmark.taxi import data as taxi_data
    from benchmark.tpch import data as tpch_data
    out = {}
    for name, gen, entry in (("ssb", ssb_data, ssb_entry),
                             ("taxi", taxi_data, taxi_entry),
                             ("tpch", tpch_data, tpch_entry)):
        tmp = str(tmp_path_factory.mktemp(name))
        dirs = [entry.build_segment(gen.gen_segment(1 << 12, SEEDS[1], k),
                                    gen.MEASURES, tmp, f"seg_{k}")
                for k in range(2)]
        dm = TableDataManager(name, server_ids=True)
        for d in dirs:
            dm.add_segment_dir(d)
        out[name] = (dm.acquire_segments(),
                     [ImmutableSegment.load(d) for d in dirs])
    return out


def _route(plan):
    return (plan.kind, getattr(plan.kernel_plan, "strategy", None))


@pytest.mark.parametrize("limit", ["chip", "lowered"])
@pytest.mark.parametrize("dataset,key,sql", ACCEPTED,
                         ids=[f"{d}-{k}" for d, k, _ in ACCEPTED])
def test_accepted_cells_keep_their_route(accepted_segments, dataset, key,
                                         sql, limit, monkeypatch):
    """Every statement of the six accepted cells plans as it did without
    a server id space: at the chip's COMPACT_GROUP_LIMIT, and at the
    lowered one, under which only their shape keeps them off the
    top-groups route."""
    if limit == "chip":
        monkeypatch.setattr(K, "COMPACT_GROUP_LIMIT", 1 << 22)
    held, bare = accepted_segments[dataset]
    ctx = build_query_context(parse_sql(sql))
    assert all(s.server_ids is not None for s in held)
    for with_ids, without in zip(held, bare):
        assert _route(SegmentPlanner(ctx, with_ids).plan()) == \
            _route(SegmentPlanner(ctx, without).plan())


# -- the kernel's key packing ------------------------------------------------

@pytest.mark.parametrize("widths", [(5,), (30,), (6, 23), (25, 23),
                                    (20, 20, 21), (31, 30), (1, 30, 30)])
def test_keys_pack_and_unpack(widths):
    rng = np.random.default_rng(sum(widths))
    ids = [rng.integers(0, 1 << w, 257) for w in widths]
    ids[0][:2] = (0, (1 << widths[0]) - 1)
    hi, lo = K._pack_keys([jax.numpy.asarray(i, jax.numpy.int32)
                           for i in ids], widths)
    hi, lo = np.asarray(hi), np.asarray(lo)
    assert (lo >= 0).all() and (hi >= 0).all()
    if K.topgroups_lanes(widths) == 1:
        assert (hi == 0).all() and (lo < (1 << 30)).all()
    else:
        assert (hi < (1 << 30)).all()
    for got, want in zip(K.unpack_keys(hi, lo, widths), ids):
        np.testing.assert_array_equal(got, want)
