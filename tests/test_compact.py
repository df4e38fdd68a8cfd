"""Compact-strategy group-by: compaction primitive + engine plans.

Reference parity: DocIdSetOperator/ProjectionOperator materialize filtered
docIds then project (pinot-core/.../operator/DocIdSetOperator.java:59-86);
our compact strategy (ops/compact.py + ops/kernels._compact_group_aggs)
is the TPU equivalent: Pallas row compaction (XLA nonzero fallback off-TPU)
followed by factorized one-hot matmuls or sort-based aggregation. These
tests run the full engine against numpy oracles with group spaces above
DENSE_SMALL_GROUPS so plans take strategy == 'compact'.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pinot_tpu.broker import Broker
from pinot_tpu.ops import compact as C
from pinot_tpu.query.context import build_query_context
from pinot_tpu.query.planner import SegmentPlanner
from pinot_tpu.query.sql import parse_sql
from pinot_tpu.segment import ImmutableSegment, SegmentBuilder
from pinot_tpu.server import TableDataManager
from pinot_tpu.spi import (DataType, FieldSpec, FieldType, Schema,
                           TableConfig)

N_ROWS = 6000
CARD_A = 40
CARD_B = 50          # space = 2000 > DENSE_SMALL_GROUPS


# ---------------------------------------------------------------------------
# the compaction primitive
# ---------------------------------------------------------------------------

def test_compact_multiset_and_alignment():
    rng = np.random.default_rng(3)
    n = 1 << 14
    mask = rng.random(n) < 0.1
    a = rng.integers(0, 1000, n).astype(np.int32)
    b = rng.integers(-5_000_000_000, 5_000_000_000, n).astype(np.int64)
    cap = C.default_slots_cap(n)
    valid, (ac, bc), _, matched, ov, _ = C.compact(
        jnp.asarray(mask), (jnp.asarray(a), jnp.asarray(b)), cap)
    valid, ac, bc = map(np.asarray, (valid, ac, bc))
    assert int(matched) == mask.sum()
    assert int(ov) == 0
    assert valid.sum() == mask.sum()
    assert sorted(zip(a[mask].tolist(), b[mask].tolist())) == \
        sorted(zip(ac[valid].tolist(), bc[valid].tolist()))


def test_compact_float64_column():
    rng = np.random.default_rng(4)
    n = 1 << 12
    mask = rng.random(n) < 0.3
    f = rng.normal(0, 1e9, n)
    valid, (fc,), _, matched, ov, _ = C.compact(
        jnp.asarray(mask), (jnp.asarray(f),), C.default_slots_cap(n))
    valid, fc = np.asarray(valid), np.asarray(fc)
    assert np.array_equal(np.sort(f[mask]), np.sort(fc[valid]))


def test_compact_overflow_flag_and_full_cap():
    n = 1 << 12
    mask = np.ones(n, bool)
    a = np.arange(n, dtype=np.int32)
    *_, ov, _ = C.compact(jnp.asarray(mask), (jnp.asarray(a),), 4)
    assert int(ov) == 1
    valid, (ac,), _, matched, ov, _ = C.compact(
        jnp.asarray(mask), (jnp.asarray(a),), C.full_slots_cap(n))
    assert int(ov) == 0
    assert np.array_equal(np.sort(np.asarray(ac)[np.asarray(valid)]), a)


def test_compact_empty_mask():
    n = 1 << 12
    valid, (ac,), _, matched, ov, _ = C.compact(
        jnp.zeros(n, bool), (jnp.arange(n, dtype=jnp.int32),),
        C.default_slots_cap(n))
    assert int(matched) == 0
    assert not np.asarray(valid).any()


# ---------------------------------------------------------------------------
# engine plans with compact strategy
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(11)
    n = N_ROWS
    return {
        "ka": np.array([f"a{i:03d}" for i in
                        rng.integers(0, CARD_A, n)]),
        "kb": np.array([f"b{i:03d}" for i in
                        rng.integers(0, CARD_B, n)]),
        "sel": rng.integers(0, 100, n).astype(np.int32),
        "v": rng.integers(-1000, 1000, n).astype(np.int32),
        "big": rng.integers(-4_000_000_000, 4_000_000_000,
                            n).astype(np.int64),
        "f": np.round(rng.normal(0, 50, n), 3),
    }


@pytest.fixture(scope="module")
def broker(data, tmp_path_factory):
    schema = Schema("t", [
        FieldSpec("ka", DataType.STRING, FieldType.DIMENSION),
        FieldSpec("kb", DataType.STRING, FieldType.DIMENSION),
        FieldSpec("sel", DataType.INT, FieldType.DIMENSION),
        FieldSpec("v", DataType.INT, FieldType.METRIC),
        FieldSpec("big", DataType.LONG, FieldType.METRIC),
        FieldSpec("f", DataType.DOUBLE, FieldType.METRIC),
    ])
    out = tmp_path_factory.mktemp("compact_table")
    d = SegmentBuilder(schema, TableConfig("t")).build(
        data, str(out), "seg_0")
    dm = TableDataManager("t")
    dm.add_segment_dir(d)
    b = Broker()
    b.register_table(dm)
    b._seg_dir = d

    # correctness tests must not flake on XLA compile time under host
    # load (first execution of each plan shape compiles inside the query
    # budget); latency enforcement is covered by test_scheduler
    orig = b.query

    def patient_query(sql):
        if "OPTION(" not in sql:
            sql += " OPTION(timeoutMs=300000)"
        return orig(sql)

    b.query = patient_query
    return b


def _plan_strategy(broker, sql):
    seg = ImmutableSegment.load(broker._seg_dir)
    ctx = build_query_context(parse_sql(sql))
    plan = SegmentPlanner(ctx, seg).plan()
    return plan


def test_plan_takes_compact_strategy(broker):
    plan = _plan_strategy(
        broker, "SELECT ka, kb, SUM(v) FROM t GROUP BY ka, kb")
    assert plan.kind == "kernel"
    assert plan.kernel_plan.strategy == "compact"


def test_small_space_stays_dense(broker):
    plan = _plan_strategy(broker, "SELECT ka, SUM(v) FROM t GROUP BY ka")
    assert plan.kind == "kernel"
    assert plan.kernel_plan.strategy == "dense"


def test_compact_group_sums_vs_oracle(broker, data):
    res = broker.query(
        "SELECT ka, kb, SUM(v), COUNT(*), SUM(big) FROM t "
        "WHERE sel < 20 GROUP BY ka, kb LIMIT 100000")
    m = data["sel"] < 20
    oracle = {}
    for i in np.nonzero(m)[0]:
        k = (data["ka"][i], data["kb"][i])
        s = oracle.setdefault(k, [0, 0, 0])
        s[0] += int(data["v"][i])
        s[1] += 1
        s[2] += int(data["big"][i])
    got = {(r[0], r[1]): (r[2], r[3], r[4]) for r in res.rows}
    assert got == {k: tuple(v) for k, v in oracle.items()}


def test_compact_group_min_max_avg_vs_oracle(broker, data):
    res = broker.query(
        "SELECT ka, kb, MIN(v), MAX(v), AVG(v), MIN(f), MAX(f) FROM t "
        "WHERE sel >= 50 GROUP BY ka, kb LIMIT 100000")
    m = data["sel"] >= 50
    oracle = {}
    for i in np.nonzero(m)[0]:
        k = (data["ka"][i], data["kb"][i])
        oracle.setdefault(k, []).append(i)
    assert len(res.rows) == len(oracle)
    for r in res.rows:
        idx = oracle[(r[0], r[1])]
        vs = data["v"][idx]
        fs = data["f"][idx]
        assert r[2] == vs.min()
        assert r[3] == vs.max()
        assert abs(r[4] - vs.mean()) < 1e-9
        assert abs(r[5] - fs.min()) < 1e-6
        assert abs(r[6] - fs.max()) < 1e-6


def test_compact_group_float_sum_tolerance(broker, data):
    res = broker.query(
        "SELECT ka, kb, SUM(f) FROM t WHERE sel < 30 "
        "GROUP BY ka, kb LIMIT 100000")
    m = data["sel"] < 30
    oracle = {}
    for i in np.nonzero(m)[0]:
        k = (data["ka"][i], data["kb"][i])
        oracle[k] = oracle.get(k, 0.0) + data["f"][i]
    for r in res.rows:
        assert abs(r[2] - oracle[(r[0], r[1])]) < 1e-6 * max(
            1.0, abs(oracle[(r[0], r[1])]))


def test_compact_group_expression_sum(broker, data):
    res = broker.query(
        "SELECT ka, kb, SUM(v * sel) FROM t WHERE sel < 70 "
        "GROUP BY ka, kb LIMIT 100000")
    m = data["sel"] < 70
    oracle = {}
    for i in np.nonzero(m)[0]:
        k = (data["ka"][i], data["kb"][i])
        oracle[k] = oracle.get(k, 0) + int(data["v"][i]) * int(data["sel"][i])
    got = {(r[0], r[1]): r[2] for r in res.rows}
    assert got == oracle


def test_compact_group_empty_result(broker):
    res = broker.query(
        "SELECT ka, kb, SUM(v) FROM t WHERE sel < 0 GROUP BY ka, kb")
    assert res.rows == []


def test_compact_overflow_retry_full_selectivity(broker, data):
    """All rows match -> default capacity (bucket/8) overflows -> the
    executor retries with full capacity and results stay exact."""
    res = broker.query(
        "SELECT ka, kb, COUNT(*) FROM t GROUP BY ka, kb LIMIT 100000")
    oracle = {}
    for i in range(N_ROWS):
        k = (data["ka"][i], data["kb"][i])
        oracle[k] = oracle.get(k, 0) + 1
    got = {(r[0], r[1]): r[2] for r in res.rows}
    assert got == oracle


def test_compact_sort_path_large_space(broker, data):
    """3-key group space (40*50*100 = 200k) exceeds the factorized limit,
    exercising the sort + chunked-cumsum + boundary-diff path."""
    plan = _plan_strategy(
        broker, "SELECT ka, kb, sel, SUM(v) FROM t GROUP BY ka, kb, sel")
    assert plan.kernel_plan.strategy == "compact"
    from pinot_tpu.ops.kernels import FACTORIZED_GROUP_LIMIT
    assert plan.kernel_plan.group_space > FACTORIZED_GROUP_LIMIT

    res = broker.query(
        "SELECT ka, kb, sel, SUM(v), COUNT(*) FROM t WHERE v > 0 "
        "GROUP BY ka, kb, sel LIMIT 1000000")
    m = data["v"] > 0
    oracle = {}
    for i in np.nonzero(m)[0]:
        k = (data["ka"][i], data["kb"][i], int(data["sel"][i]))
        s = oracle.setdefault(k, [0, 0])
        s[0] += int(data["v"][i])
        s[1] += 1
    got = {(r[0], r[1], r[2]): (r[3], r[4]) for r in res.rows}
    assert got == {k: tuple(v) for k, v in oracle.items()}
