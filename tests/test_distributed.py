"""Distributed execution tests over the 8-virtual-device CPU mesh.

Reference analog: scatter-gather integration tests (ClusterTest with N
servers) — here the 'servers' are mesh devices and the combine is psum.
Asserts the shard_map path and the per-segment path produce identical
results (and match a numpy oracle).
"""
import numpy as np
import pytest

import jax

from pinot_tpu.broker import Broker
from pinot_tpu.parallel import DistributedTable, segment_mesh
from pinot_tpu.query.context import build_query_context
from pinot_tpu.query.sql import parse_sql
from pinot_tpu.segment import SegmentBuilder
from pinot_tpu.segment.builder import build_table_dictionaries
from pinot_tpu.server import TableDataManager
from pinot_tpu.spi import (DataType, FieldSpec, FieldType, Schema,
                           TableConfig)

N_SEGMENTS = 16
ROWS_PER_SEG = 500


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    rng = np.random.default_rng(11)
    schema = Schema("orders", [
        FieldSpec("region", DataType.STRING, FieldType.DIMENSION),
        FieldSpec("year", DataType.INT, FieldType.DIMENSION),
        FieldSpec("qty", DataType.INT, FieldType.METRIC),
        FieldSpec("price", DataType.DOUBLE, FieldType.METRIC),
    ])
    cfg = TableConfig("orders")
    chunks = []
    for _ in range(N_SEGMENTS):
        n = ROWS_PER_SEG
        chunks.append({
            "region": rng.choice(["apac", "emea", "latam", "na"], n),
            "year": rng.integers(2018, 2024, n).astype(np.int32),
            "qty": rng.integers(1, 50, n).astype(np.int32),
            "price": np.round(rng.uniform(1, 1000, n), 2),
        })
    shared = build_table_dictionaries(schema, cfg, chunks)
    builder = SegmentBuilder(schema, cfg)
    out = tmp_path_factory.mktemp("orders_table")
    dm = TableDataManager("orders")
    for i, chunk in enumerate(chunks):
        d = builder.build(chunk, str(out), f"seg_{i}", shared_dicts=shared)
        dm.add_segment_dir(d)
    data = {k: np.concatenate([c[k] for c in chunks])
            for k in chunks[0]}
    return dm, data


@pytest.fixture(scope="module")
def dist(table):
    dm, _ = table
    mesh = segment_mesh(8)
    assert mesh.devices.size == 8
    return DistributedTable(dm.acquire_segments(), mesh)


def _ctx(sql):
    return build_query_context(parse_sql(sql))


def test_mesh_has_8_devices():
    assert len(jax.devices()) == 8


def test_distributed_matches_local_sum(table, dist):
    dm, data = table
    b = Broker()
    b.register_table(dm)
    sql = ("SELECT region, SUM(qty), COUNT(*) FROM orders "
           "WHERE year >= 2020 GROUP BY region ORDER BY region LIMIT 10")
    local = b.query(sql)

    dm.set_distributed(dist)
    distributed = b.query(sql)
    assert distributed.rows == local.rows

    mask = data["year"] >= 2020
    expected = sorted(
        (r, int(data["qty"][mask & (data["region"] == r)].sum()),
         int((mask & (data["region"] == r)).sum()))
        for r in np.unique(data["region"]))
    assert [tuple(r) for r in distributed.rows] == expected
    dm.set_distributed(None)


def test_distributed_scalar_aggs(table, dist):
    dm, data = table
    b = Broker()
    b.register_table(dm)
    dm.set_distributed(dist)
    res = b.query("SELECT SUM(qty), MIN(price), MAX(price), AVG(qty) "
                  "FROM orders WHERE region = 'apac'")
    mask = data["region"] == "apac"
    (s, mn, mx, avg), = [tuple(r) for r in res.rows]
    assert s == int(data["qty"][mask].sum())
    assert mn == pytest.approx(float(data["price"][mask].min()))
    assert mx == pytest.approx(float(data["price"][mask].max()))
    assert avg == pytest.approx(float(data["qty"][mask].mean()))
    dm.set_distributed(None)


def test_distributed_empty_filter(table, dist):
    dm, _ = table
    ctx = _ctx("SELECT COUNT(*) FROM orders WHERE region = 'nowhere'")
    # dict fold -> FalseP -> pruned plan, falls back (returns None)
    assert dist.try_execute(ctx) is None


def test_distributed_two_key_group_by(table, dist):
    dm, data = table
    ctx = _ctx("SELECT region, year, SUM(price) FROM orders "
               "GROUP BY region, year ORDER BY region, year LIMIT 100")
    partial = dist.try_execute(ctx)
    assert partial is not None
    from pinot_tpu.engine.reduce import reduce_partials
    res = reduce_partials(ctx, [partial])
    keys = sorted({(r, int(y)) for r, y in
                   zip(data["region"], data["year"])})
    expected = []
    for r, y in keys:
        m = (data["region"] == r) & (data["year"] == y)
        expected.append((r, y, pytest.approx(float(data["price"][m].sum()),
                                             rel=1e-9)))
    got = [tuple(r) for r in res.rows]
    assert len(got) == len(expected)
    for g, e in zip(got, expected):
        assert g[0] == e[0] and g[1] == e[1]
        assert g[2] == e[2]


def test_distributed_distinct_count(table, dist):
    dm, data = table
    ctx = _ctx("SELECT DISTINCTCOUNT(region) FROM orders WHERE year = 2019")
    partial = dist.try_execute(ctx)
    assert partial is not None
    from pinot_tpu.engine.reduce import reduce_partials
    res = reduce_partials(ctx, [partial])
    expected = len(np.unique(data["region"][data["year"] == 2019]))
    assert [tuple(r) for r in res.rows] == [(expected,)]


def test_distributed_heterogeneous_raw_ranges(tmp_path_factory):
    """Regression: planning against segment 0's min/max must not
    constant-fold predicates or size limb sums wrongly for other segments."""
    schema = Schema("hetero", [
        FieldSpec("d", DataType.INT, FieldType.DIMENSION),
        FieldSpec("price", DataType.LONG, FieldType.METRIC),
    ])
    cfg = TableConfig("hetero")
    chunks = [
        {"d": np.array([1, 2, 1, 2], dtype=np.int32),
         "price": np.array([1, 5, 3, 7], dtype=np.int64)},
        {"d": np.array([1, 2, 2, 1], dtype=np.int32),
         "price": np.array([1000000, 9, 2000000, 10], dtype=np.int64)},
    ]
    shared = build_table_dictionaries(schema, cfg, chunks)
    builder = SegmentBuilder(schema, cfg)
    out = tmp_path_factory.mktemp("hetero_table")
    dm = TableDataManager("hetero")
    for i, c in enumerate(chunks):
        dm.add_segment_dir(builder.build(c, str(out), f"s{i}",
                                         shared_dicts=shared))
    dist = DistributedTable(dm.acquire_segments(), segment_mesh(2))

    # raw-range fold: segment 0 max is 7, but segment 1 has rows <= 10 too
    ctx = _ctx("SELECT SUM(price), COUNT(*) FROM hetero WHERE price <= 10")
    partial = dist.try_execute(ctx)
    assert partial is not None
    from pinot_tpu.engine.reduce import reduce_partials
    res = reduce_partials(ctx, [partial])
    assert [tuple(r) for r in res.rows] == [(1 + 5 + 3 + 7 + 9 + 10, 6)]

    # limb sizing: segment 0 range needs 3 bits; segment 1 needs 21
    ctx = _ctx("SELECT d, SUM(price) FROM hetero GROUP BY d ORDER BY d")
    res = reduce_partials(ctx, [dist.try_execute(ctx)])
    assert [tuple(r) for r in res.rows] == [
        (1, 1 + 3 + 1000000 + 10), (2, 5 + 7 + 9 + 2000000)]


def test_between_column_bound_falls_back_cleanly(tmp_path):
    """Regression: BETWEEN with a column bound must plan (generic cmp),
    not crash with a non-SqlError."""
    schema = Schema("bt", [
        FieldSpec("a", DataType.INT, FieldType.METRIC),
        FieldSpec("b", DataType.INT, FieldType.METRIC),
    ])
    builder = SegmentBuilder(schema, TableConfig("bt"))
    d = builder.build({"a": np.array([1, 5, 9], dtype=np.int32),
                       "b": np.array([2, 4, 8], dtype=np.int32)},
                      str(tmp_path), "s0")
    dm = TableDataManager("bt")
    dm.add_segment_dir(d)
    b = Broker()
    b.register_table(dm)
    res = b.query("SELECT COUNT(*) FROM bt WHERE a BETWEEN b AND 9")
    # rows where b <= a <= 9: (1,2) no, (5,4) yes, (9,8) yes
    assert [tuple(r) for r in res.rows] == [(2,)]


# ---------------------------------------------------------------------------
# compact strategy on the mesh (flattened local segments; round-3 item 4)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def big_table(tmp_path_factory):
    """Group space 40*60=2400 > DENSE_SMALL_GROUPS so plans take the
    compact strategy; shared dicts so the mesh path applies."""
    rng = np.random.default_rng(23)
    schema = Schema("events", [
        FieldSpec("ka", DataType.INT, FieldType.DIMENSION),
        FieldSpec("kb", DataType.INT, FieldType.DIMENSION),
        FieldSpec("sel", DataType.INT, FieldType.DIMENSION),
        FieldSpec("v", DataType.LONG, FieldType.METRIC),
        FieldSpec("f", DataType.DOUBLE, FieldType.METRIC),
    ])
    cfg = TableConfig("events")
    chunks = []
    for _ in range(8):
        n = 700
        chunks.append({
            "ka": rng.integers(0, 40, n).astype(np.int32),
            "kb": rng.integers(0, 60, n).astype(np.int32),
            "sel": rng.integers(0, 100, n).astype(np.int32),
            "v": rng.integers(-1000, 1000, n).astype(np.int64),
            "f": np.round(rng.normal(0, 50, n), 3),
        })
    shared = build_table_dictionaries(schema, cfg, chunks)
    builder = SegmentBuilder(schema, cfg)
    out = tmp_path_factory.mktemp("events_table")
    dm = TableDataManager("events")
    for i, chunk in enumerate(chunks):
        d = builder.build(chunk, str(out), f"seg_{i}", shared_dicts=shared)
        dm.add_segment_dir(d)
    data = {k: np.concatenate([c[k] for c in chunks]) for k in chunks[0]}
    return dm, data


def test_distributed_compact_group_by(big_table):
    dm, data = big_table
    dist = DistributedTable(dm.acquire_segments(), segment_mesh(8))

    sql = ("SELECT ka, kb, SUM(v), COUNT(*), MIN(f), MAX(f) FROM events "
           "WHERE sel < 35 GROUP BY ka, kb LIMIT 100000 "
           "OPTION(timeoutMs=300000)")
    plan = dist.plan(_ctx(sql))
    assert plan.kind == "kernel"
    assert plan.kernel_plan.strategy == "compact", \
        "mesh path must no longer force the dense strategy"

    b = Broker()
    b.register_table(dm)
    local = b.query(sql)
    dm.set_distributed(dist)
    distributed = b.query(sql)
    dm.set_distributed(None)

    mask = data["sel"] < 35
    oracle = {}
    for i in np.nonzero(mask)[0]:
        k = (int(data["ka"][i]), int(data["kb"][i]))
        s, c, mn, mx = oracle.get(k, (0, 0, np.inf, -np.inf))
        oracle[k] = (s + int(data["v"][i]), c + 1,
                     min(mn, data["f"][i]), max(mx, data["f"][i]))
    got = {(r[0], r[1]): r[2:] for r in distributed.rows}
    assert set(got) == set(oracle)
    for k, (s, c, mn, mx) in oracle.items():
        gs, gc, gmn, gmx = got[k]
        assert (gs, gc) == (s, c)
        assert gmn == pytest.approx(mn, abs=1e-6)
        assert gmx == pytest.approx(mx, abs=1e-6)
    assert sorted(map(tuple, local.rows)) == sorted(map(tuple,
                                                        distributed.rows))


def test_distributed_expression_group_key(tmp_path_factory):
    """GROUP BY YEAR(ts) on the mesh: the widened table view derives a
    TABLE-WIDE key range, so per-device partials land in the same key
    space and psum-combine correctly."""
    rng = np.random.default_rng(29)
    schema = Schema("ev", [
        FieldSpec("ts", DataType.LONG, FieldType.DIMENSION),
        FieldSpec("amt", DataType.LONG, FieldType.METRIC)])
    cfg = TableConfig("ev")
    chunks = []
    for i in range(8):
        # segments cover DIFFERENT year windows: a per-segment offset
        # would mis-bucket under the shared-plan mesh path
        lo = 1_500_000_000_000 + i * 40_000_000_000
        chunks.append({
            "ts": rng.integers(lo, lo + 60_000_000_000, 400)
            .astype(np.int64),
            "amt": rng.integers(1, 100, 400).astype(np.int64)})
    shared = build_table_dictionaries(schema, cfg, chunks)
    builder = SegmentBuilder(schema, cfg)
    out = tmp_path_factory.mktemp("ev_expr")
    dm = TableDataManager("ev")
    for i, c in enumerate(chunks):
        dm.add_segment_dir(builder.build(c, str(out), f"seg_{i}",
                                         shared_dicts=shared))
    mesh = segment_mesh(8)
    dist = DistributedTable(dm.acquire_segments(), mesh)
    sql = ("SELECT YEAR(ts), COUNT(*), SUM(amt) FROM ev "
           "GROUP BY 1 ORDER BY 1 LIMIT 100")
    plan = dist.plan(_ctx(sql))
    assert plan.kind == "kernel" and plan.kernel_plan.key_exprs
    partial = dist.try_execute(_ctx(sql))
    assert partial is not None
    from pinot_tpu.engine.reduce import reduce_partials
    rows = [tuple(r) for r in reduce_partials(_ctx(sql), [partial]).rows]
    ts = np.concatenate([c["ts"] for c in chunks])
    amt = np.concatenate([c["amt"] for c in chunks])
    years = ts.astype("datetime64[ms]").astype("datetime64[Y]") \
        .astype(np.int64) + 1970
    expected = [(int(y), int((years == y).sum()),
                 int(amt[years == y].sum()))
                for y in np.unique(years)]
    assert rows == expected


# ---------------------------------------------------------------------------
# the transfer compaction's live list, from the devices' own sparse rows
# ---------------------------------------------------------------------------

PAIR_SEGMENTS = 8           # two a device of a four-device mesh
PAIR_ROWS = 2048
SMALL_CAP = 512             # GROUP_XFER_CAP shrunk, for the overflow cases


def _pair(g):
    """A group of the 256 x 256 pair space from one number."""
    return g // 256, g % 256


def _pair_rows():
    """Rows (ka, kb, tag, v) a segment; a tag is one case's filter."""
    rng = np.random.default_rng(34)
    rows = [[] for _ in range(PAIR_SEGMENTS)]

    def put(seg, tag, g, n=1):
        ka, kb = _pair(g)
        rows[seg].extend((ka, kb, tag, int(v))
                         for v in rng.integers(-10**6, 10**6, n))

    for j in range(256):                  # every dictionary comes out whole
        put(0, 0, 257 * j)
    for g in (772, 773, 51217, 65535, 0):  # tag 1: one segment only
        put(1, 1, g, 3)
    for seg in range(PAIR_SEGMENTS):      # tag 2: the same ids everywhere
        for g in (257, 762, 25700, 65027, 300, 301, 40000):
            put(seg, 2, g, 2)
        put(seg, 3, 1000 + seg)           # tag 3 AND ka = 9: no row at all
    for k in range(SMALL_CAP + 1):        # tags 4, 5: SMALL_CAP ids, and one
        for seg in (k % PAIR_SEGMENTS, (k + 1) % PAIR_SEGMENTS):
            if k < SMALL_CAP:
                put(seg, 4, 7 * k + 3)
            put(seg, 5, 7 * k + 3)
    for k in range(SMALL_CAP + 5):        # tag 6: one segment's post spills
        put(2, 6, 11 * k + 1)
    assert max(len(r) for r in rows) <= PAIR_ROWS
    return rows


@pytest.fixture(scope="module")
def pair_table(tmp_path_factory):
    """Two keys of 256 values: a group space of 65,536, over
    GROUP_XFER_SPACE and on the sort core's sparse post."""
    schema = Schema("pairs", [
        FieldSpec("ka", DataType.INT, FieldType.DIMENSION),
        FieldSpec("kb", DataType.INT, FieldType.DIMENSION),
        FieldSpec("tag", DataType.INT, FieldType.DIMENSION),
        FieldSpec("v", DataType.LONG, FieldType.METRIC)])
    cfg = TableConfig("pairs")
    chunks = []
    for seg in _pair_rows():
        ka, kb, tag, v = (np.array(c) for c in zip(*seg))
        chunks.append({"ka": ka.astype(np.int32), "kb": kb.astype(np.int32),
                       "tag": tag.astype(np.int32),
                       "v": v.astype(np.int64)})
    shared = build_table_dictionaries(schema, cfg, chunks)
    builder = SegmentBuilder(schema, cfg)
    out = tmp_path_factory.mktemp("pairs_table")
    dm = TableDataManager("pairs")
    for i, chunk in enumerate(chunks):
        dm.add_segment_dir(builder.build(chunk, str(out), f"seg_{i}",
                                         shared_dicts=shared))
    data = {k: np.concatenate([c[k] for c in chunks]) for k in chunks[0]}
    return dm, data


@pytest.fixture()
def fresh_mesh_programs():
    """A mesh program reads GROUP_XFER_CAP as it is traced: none traced
    under one capacity may answer under another."""
    from pinot_tpu.parallel import distributed
    distributed._distributed_kernel_cached.cache_clear()
    yield
    distributed._distributed_kernel_cached.cache_clear()


# case: (WHERE, the same filter over the host columns, GROUP_XFER_CAP or
#        None for the real one, live groups, whether the dense retry has
#        to answer)
LIVE_LIST_CASES = {
    "one_device_only": ("tag = 1", lambda d: d["tag"] == 1, None, 5, False),
    "repeats_collapse": ("tag = 2", lambda d: d["tag"] == 2, None, 7, False),
    "no_live_group": ("tag = 3 AND ka = 9",
                      lambda d: (d["tag"] == 3) & (d["ka"] == 9),
                      None, 0, False),
    "exactly_the_cap": ("tag = 4", lambda d: d["tag"] == 4,
                        SMALL_CAP, SMALL_CAP, False),
    "one_over_the_cap": ("tag = 5", lambda d: d["tag"] == 5,
                         SMALL_CAP, SMALL_CAP + 1, True),
    "a_segment_spilled": ("tag = 6", lambda d: d["tag"] == 6,
                          SMALL_CAP, SMALL_CAP + 5, True),
}


def _same_bytes(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and a.tobytes() == b.tobytes())


@pytest.mark.parametrize("route", ["flattened", "routed"])
@pytest.mark.parametrize("case", sorted(LIVE_LIST_CASES))
def test_the_live_list_is_the_nonzero_of_the_combined_counts(
        pair_table, fresh_mesh_programs, monkeypatch, case, route):
    """The mesh program lists its combined result's live groups from the
    devices' own sparse rows, (cap,) ids on the flattened route and
    (L, cap) on the routed core: the list is jnp.nonzero(group_count > 0,
    size=cap, fill_value=space) of the dense combined result and every
    output (sum, min, max, avg's parts, the counts) what
    _compact_group_xfer gathers there, byte for byte; more distinct ids
    than the list holds, or a segment whose own post spilled, flag
    group_overflow and the dense retry answers."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from pinot_tpu.engine.executor import resolve_params
    from pinot_tpu.engine.reduce import reduce_partials
    from pinot_tpu.ops import kernels
    from pinot_tpu.parallel import distributed
    from pinot_tpu.utils import phases as ph
    from pinot_tpu.utils.metrics import global_metrics

    where, matches, cap, n_live, retried = LIVE_LIST_CASES[case]
    if cap is not None:
        monkeypatch.setattr(kernels, "GROUP_XFER_CAP", cap)
    cap = kernels.GROUP_XFER_CAP
    dm, data = pair_table
    dist = DistributedTable(
        dm.acquire_segments(), segment_mesh(devices=jax.devices()[:4]),
        # any limit under a local shard's rows routes per local segment
        sort_row_limit=1 if route == "routed" else None)
    ctx = _ctx("SELECT ka, kb, COUNT(*), SUM(v), MIN(v), MAX(v), AVG(v) "
               f"FROM pairs WHERE {where} GROUP BY ka, kb "
               "ORDER BY ka, kb LIMIT 100000")
    plan = dist.mesh_plan(ctx)
    kp = plan.kernel_plan
    family = dist._route(kp)
    assert family == (ph.MESH_COMPACT_PER_SEGMENT if route == "routed"
                      else ph.MESH_COMPACT)
    assert dist.local_segments == 2
    assert distributed.lists_live_groups_sparse(kp, family, True, False)
    space = kp.group_space
    assert space == 65536 >= kernels.GROUP_XFER_SPACE

    cols = tuple(dist.device_col(n) for n in plan.col_names)
    params = resolve_params(plan, sharding=dist._sharding(P()))
    slots = dist._cost_model_cap(
        plan, dist.bucket * (1 if route == "routed" else 2))
    got = dist._launch(plan, family, slots, cols, params)
    dense = dist._launch(plan, family, slots, cols, params,
                         xfer_compact=False)
    assert not int(got.pop("overflow")) and not int(dense.pop("overflow"))
    assert dense["group_count"].shape == (space,)
    assert int((dense["group_count"] > 0).sum()) == n_live

    want_idx, = jnp.nonzero(jnp.asarray(dense["group_count"]) > 0,
                            size=cap, fill_value=space)
    assert _same_bytes(got["group_idx"], want_idx.astype(jnp.int32))
    want = {k: jnp.asarray(v) for k, v in dense.items()}
    kernels._compact_group_xfer(kp, want)
    assert int(want.pop("group_overflow")) == int(n_live > cap)
    assert bool(got.pop("group_overflow")) == retried
    assert sorted(got) == sorted(want)
    assert {"agg1_sum", "agg2_min", "agg3_max", "agg4_avg_sum",
            "agg4_avg_cnt", "group_count"} <= set(got)
    for k in want:
        assert _same_bytes(got[k], want[k]), k

    before = dict(global_metrics.snapshot()["counters"])
    rows = reduce_partials(ctx, [dist.execute(plan)]).rows
    after = global_metrics.snapshot()["counters"]
    moved = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("group_xfer_overflow_retries",
                       "mesh_live_list_sparse", "mesh_live_list_dense")}
    assert moved == {"group_xfer_overflow_retries": int(retried),
                     "mesh_live_list_sparse": int(not retried),
                     "mesh_live_list_dense": 0}
    mask = matches(data)
    groups = {}
    for ka, kb, v in zip(data["ka"][mask], data["kb"][mask],
                         data["v"][mask]):
        groups.setdefault((int(ka), int(kb)), []).append(int(v))
    assert len(groups) == n_live
    assert [tuple(r) for r in rows] == [
        (ka, kb, len(vs), sum(vs), min(vs), max(vs),
         pytest.approx(sum(vs) / len(vs)))
        for (ka, kb), vs in sorted(groups.items())]
