"""One parameter resolution and no eager launch in front of a one-chip
kernel (PR 30): a query group's params are resolved once, on the host
(engine/executor.resolve_params_host), reach the device in one transfer
(executor.upload_params), segment-resident params come stacked from the
stack cache (engine/batch._stacked_resident), and no eager ``jax``
operation runs between planning and the kernel's launch — on each of
the three routes of ``execute_plans_batched``: a dense vmapped group, a
segmented compact group, a compact group sent down the per-segment
route. A group-by's segments come back combined into one partial
(engine/executor.place_group_partials): the segment-by-segment answers
are held to it through the broker's merge (engine/reduce.merge_groups).
"""
import jax
import numpy as np
import pytest

from pinot_tpu.engine import batch as eb
from pinot_tpu.engine.executor import (GroupByPartial, execute_plan,
                                       param_sig, resolve_params,
                                       resolve_params_host)
from pinot_tpu.engine.reduce import merge_groups
from pinot_tpu.ops import kernels as K
from pinot_tpu.query.context import build_query_context
from pinot_tpu.query.planner import SegmentPlanner
from pinot_tpu.query.sql import parse_sql
from pinot_tpu.segment import ImmutableSegment, SegmentBuilder
from pinot_tpu.spi import (DataType, FieldSpec, FieldType, IndexingConfig,
                           Schema, TableConfig)
from pinot_tpu.utils.devmem import global_device_memory
from pinot_tpu.utils.metrics import global_metrics

N_SEG = 4
ROWS = 600
CARD_A, CARD_B = 40, 210       # group space 8400 -> the compact strategy
WORDS = ["tpu", "olap", "column", "segment", "broker"]
# one literal, another dictionary id in every segment: 'west' sorts to
# id 1, 0, 1, 0 among each segment's two regions
REGIONS = [("east", "west"), ("west", "zulu"), ("alpha", "west"),
           ("west", "yak")]
# the MV column's padded width differs between the two halves of the
# table, so one statement makes two groups (the shape part of the key)
MV_WIDTH = [3, 3, 5, 5]

DENSE = "SELECT SUM(amount * tier) FROM pd WHERE tier BETWEEN 1 AND 3"
COMPACT = ("SELECT ka, kb, SUM(amount), COUNT(*) FROM pd WHERE sel < 45 "
           "GROUP BY ka, kb LIMIT 100000")
ROUTES = ["dense", "segc", "per_segment"]
# per route: the statement, `param_uploads` a query, resident params a group
ROUTE_SQL = {"dense": DENSE, "segc": COMPACT, "per_segment": COMPACT}
UPLOADS = {"dense": 1, "segc": 1, "per_segment": N_SEG}
RESIDENT = {"dense": 1, "segc": 0, "per_segment": 0}

STATEMENTS = {
    "dense_dictvals": DENSE,
    "compact_group_by": COMPACT,
    "literal_to_other_ids": "SELECT SUM(amount), COUNT(*) FROM pd "
                            "WHERE region = 'west'",
    "mv_column": "SELECT SUMMV(scores), COUNT(*) FROM pd WHERE sel < 60",
    "nullmask": "SELECT SUM(nv), COUNT(*) FROM pd WHERE nv > 5 "
                "OPTION(enableNullHandling=true)",
    "docmask": "SELECT COUNT(*), SUM(amount) FROM pd "
               "WHERE TEXT_MATCH(doc, 'tpu') AND sel < 80",
}


@pytest.fixture(scope="module")
def segments(tmp_path_factory):
    rng = np.random.default_rng(30)
    schema = Schema("pd", [
        FieldSpec("ka", DataType.STRING), FieldSpec("kb", DataType.STRING),
        FieldSpec("region", DataType.STRING),
        FieldSpec("doc", DataType.STRING),
        FieldSpec("sel", DataType.INT), FieldSpec("tier", DataType.INT),
        FieldSpec("scores", DataType.INT, single_value=False),
        FieldSpec("amount", DataType.INT, FieldType.METRIC),
        FieldSpec("nv", DataType.INT, FieldType.METRIC)])
    cfg = TableConfig("pd", indexing=IndexingConfig(
        text_index_columns=["doc"]))
    out = str(tmp_path_factory.mktemp("pd"))
    segs = []
    for i in range(N_SEG):
        rows = []
        for r in range(ROWS):
            # every segment sees every key and every tier, so the
            # dictionaries agree in shape and the plans make one group
            ka = r if r < CARD_A else int(rng.integers(0, CARD_A))
            kb = r if r < CARD_B else int(rng.integers(0, CARD_B))
            n_mv = MV_WIDTH[i] if r == 0 else int(
                rng.integers(0, MV_WIDTH[i] + 1))
            rows.append({
                "ka": f"a{ka:02d}", "kb": f"b{kb:03d}",
                "region": REGIONS[i][r % 2],
                "doc": " ".join(rng.choice(WORDS, 2)),
                "sel": int(rng.integers(0, 100)), "tier": r % 5,
                "scores": rng.integers(0, 50, n_mv).tolist(),
                "amount": int(rng.integers(0, 1000)),
                "nv": None if r % 7 == 0 else int(rng.integers(0, 20))})
        segs.append(ImmutableSegment.load(
            SegmentBuilder(schema, cfg).build(rows, out, f"pd_seg_{i}")))
    return segs


def plans_for(segments, sql):
    ctx = build_query_context(parse_sql(sql))
    plans = [SegmentPlanner(ctx, seg).plan() for seg in segments]
    assert all(p.kind == "kernel" for p in plans), [p.kind for p in plans]
    return plans


def counter(name):
    return global_metrics.snapshot()["counters"].get(name, 0)


def ordered(partials):
    """Partials with a group-by's groups as a list: in their order."""
    return [list(p.groups.items()) if isinstance(p, GroupByPartial)
            else p for p in partials]


def answered(sql, solo):
    """The segments' own partials as the statement answers them: a
    group-by's merged at the first segment, an empty partial at each
    other."""
    ctx = build_query_context(parse_sql(sql))
    if ctx.is_group_by:
        solo = [GroupByPartial(merge_groups(ctx.aggregations, solo))] + \
            [GroupByPartial({})] * (len(solo) - 1)
    return ordered(solo)


@pytest.fixture
def route(request, monkeypatch):
    """One route's name; 'per_segment' refuses the segmented batch the
    way the chip does (the sort core's row limit)."""
    if request.param == "per_segment":
        monkeypatch.setattr(K, "SEGMENTED_SORT_ROW_LIMIT", 1)
    return request.param


@pytest.fixture
def eager(monkeypatch):
    """Names of the eager one-primitive programs jax launches:
    ``dispatch.apply_primitive`` fetches each through
    ``xla_primitive_callable``."""
    from jax._src import dispatch
    seen = []
    fetch = dispatch.xla_primitive_callable

    def recording(prim, **params):
        seen.append(prim.name)
        return fetch(prim, **params)

    monkeypatch.setattr(dispatch, "xla_primitive_callable", recording)
    return seen


FAMILY = {"dense": "dense_vmap", "segc": "compact_segmented",
          "per_segment": "compact_per_segment"}


@pytest.mark.parametrize("route", ROUTES, indirect=True)
def test_no_eager_program_in_front_of_the_kernel(segments, route, eager):
    sql = ROUTE_SQL[route]
    eb.execute_plans_batched(plans_for(segments, sql))   # stacks, compiles
    family = "kernel_dispatches_" + FAMILY[route]
    launches = counter(family)
    del eager[:]
    eb.execute_plans_batched(plans_for(segments, sql))
    assert eager == [], eager
    assert counter(family) - launches == (
        N_SEG if route == "per_segment" else 1)


@pytest.mark.parametrize("route", ROUTES, indirect=True)
def test_one_upload_a_group_and_resident_params_from_the_cache(segments,
                                                               route):
    sql = ROUTE_SQL[route]
    eb.execute_plans_batched(plans_for(segments, sql))
    before = {n: counter(n) for n in (
        "param_uploads", "param_stack_hits", "param_stack_builds")}
    eb.execute_plans_batched(plans_for(segments, sql))
    assert counter("param_uploads") - before["param_uploads"] == \
        UPLOADS[route]
    assert counter("param_stack_hits") - before["param_stack_hits"] == \
        RESIDENT[route]
    assert counter("param_stack_builds") == before["param_stack_builds"]


@pytest.mark.parametrize("name", sorted(STATEMENTS))
def test_answers_equal_execute_plan_segment_by_segment(segments, name):
    sql = STATEMENTS[name]
    solo = [execute_plan(p) for p in plans_for(segments, sql)]
    launches = counter("kernel_dispatches")
    # twice: the second pass answers from warm stacks
    for _ in range(2):
        assert ordered(eb.execute_plans_batched(plans_for(segments, sql))) \
            == answered(sql, solo)
    batched = (counter("kernel_dispatches") - launches) // 2
    # the MV statement makes one group a padded width, every other one
    assert batched == (2 if name == "mv_column" else 1)


def test_per_segment_route_answers_equal(segments, monkeypatch):
    solo = [execute_plan(p) for p in plans_for(segments, COMPACT)]
    monkeypatch.setattr(K, "SEGMENTED_SORT_ROW_LIMIT", 1)
    assert ordered(eb.execute_plans_batched(plans_for(segments, COMPACT))) \
        == answered(COMPACT, solo)


def test_one_literal_becomes_another_id_in_every_segment(segments):
    sql = STATEMENTS["literal_to_other_ids"]
    hosts = [resolve_params_host(p) for p in plans_for(segments, sql)]
    ids = [[int(x) for x in h if not isinstance(x, tuple) and x.ndim == 0]
           for h in hosts]
    assert len({tuple(i) for i in ids}) > 1, ids


@pytest.mark.parametrize("name", sorted(STATEMENTS))
def test_the_group_key_reads_what_the_device_would_hold(segments, name):
    for plan in plans_for(segments, STATEMENTS[name]):
        host = resolve_params_host(plan)
        assert all(isinstance(p, (tuple, np.ndarray)) for p in host)
        # the old way: every planner param through jax.device_put
        dev = [jax.device_put(h) if not isinstance(h, tuple) else d
               for h, d in zip(host, resolve_params(plan, host=host))]
        assert all(isinstance(d, jax.Array) for d in dev)
        assert param_sig(plan, host) == tuple(
            (tuple(d.shape), str(d.dtype)) for d in dev)
        for h, d in zip(host, dev):
            if not isinstance(h, tuple):
                np.testing.assert_array_equal(np.asarray(d), h)


def test_an_int64_literal_reads_int32_with_x64_off(segments):
    plan = plans_for(segments, "SELECT SUM(amount + 7) FROM pd")[0]
    assert any(not isinstance(p, tuple) and p.dtype == np.int64
               for p in resolve_params_host(plan))
    with jax.enable_x64(False):
        host = resolve_params_host(plan)
        assert all(p.dtype != np.int64 for p in host
                   if not isinstance(p, tuple))
        assert param_sig(plan, host) == tuple(
            (tuple(d.shape), str(d.dtype))
            for d in jax.device_put(resolve_params(plan, host=host)))


def param_stack_keys():
    return [k for k in eb._STACK_CACHE
            if isinstance(k[1], tuple) and k[1][:1] == ("param",)]


def test_evicting_a_segment_drops_its_resident_param_stacks(segments):
    eb.execute_plans_batched(plans_for(segments, DENSE))
    keys = param_stack_keys()
    assert len(keys) == RESIDENT["dense"]
    pool = global_device_memory._pools["stack_cache"]
    for key in keys:
        assert pool[key] == sum(
            int(a.nbytes) for a in eb._STACK_CACHE[key][1])
    evictions = global_device_memory.snapshot()["stack_cache"]["evictions"]
    eb.evict_stacks_containing(segments[1].name)
    assert param_stack_keys() == []
    assert all(key not in pool for key in keys)
    snap = global_device_memory.snapshot()["stack_cache"]
    assert snap["evictions"] >= evictions + len(keys)
    # the next query builds them again and answers the same
    builds = counter("param_stack_builds")
    solo = [execute_plan(p) for p in plans_for(segments, DENSE)]
    assert eb.execute_plans_batched(plans_for(segments, DENSE)) == solo
    assert counter("param_stack_builds") - builds == RESIDENT["dense"]


def test_a_build_that_races_an_eviction_is_served_and_not_cached(
        segments, monkeypatch):
    solo = [execute_plan(p) for p in plans_for(segments, DENSE)]
    eb.clear_stack_cache()
    resident = eb.resident_param

    def evicting(seg, marker, sharding=None):
        # an unrelated segment is evicted while this stack is built
        eb.evict_stacks_containing("some_other_segment")
        return resident(seg, marker, sharding)

    monkeypatch.setattr(eb, "resident_param", evicting)
    builds = counter("param_stack_builds")
    assert eb.execute_plans_batched(plans_for(segments, DENSE)) == solo
    assert counter("param_stack_builds") - builds == RESIDENT["dense"]
    assert param_stack_keys() == []
    assert not any(
        isinstance(k[1], tuple) and k[1][:1] == ("param",)
        for k in global_device_memory._pools.get("stack_cache", {}))


def test_a_newer_validity_mask_replaces_the_stacked_one(segments):
    """An upsert table's valid-docs mask changes under the same segments:
    the stacked copy carries the versions it was built from."""
    sql = "SELECT SUM(amount), COUNT(*) FROM pd WHERE sel < 70"
    try:
        for seg in segments:
            seg.set_valid_docs(np.arange(seg.n_docs) % 2 == 0)
        first = eb.execute_plans_batched(plans_for(segments, sql))
        assert first == [execute_plan(p) for p in plans_for(segments, sql)]
        keys = param_stack_keys()
        assert [k[1] for k in keys] == [("param", "validdocs", None)]
        segments[2].set_valid_docs(np.arange(segments[2].n_docs) % 3 == 0)
        builds = counter("param_stack_builds")
        second = eb.execute_plans_batched(plans_for(segments, sql))
        assert second == [execute_plan(p)
                          for p in plans_for(segments, sql)]
        assert second[2] != first[2] and second[:2] == first[:2]
        assert counter("param_stack_builds") - builds == 1
        assert param_stack_keys() == keys       # replaced, not added
    finally:
        for seg in segments:
            seg.set_valid_docs(None)


def test_the_counters_show_in_prometheus(segments):
    eb.execute_plans_batched(plans_for(segments, DENSE))
    eb.execute_plans_batched(plans_for(segments, DENSE))
    text = global_metrics.prometheus()
    for name in ("param_uploads", "param_stack_builds", "param_stack_hits"):
        assert name in text
