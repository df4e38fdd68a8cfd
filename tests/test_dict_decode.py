"""How a dictionary id becomes a value inside a kernel
(ops/kernels._decode_dict): a select chain up to DICT_SELECT_MAX entries,
jnp.take beyond, chosen by the static length of the dictionary handed
over. Both hand back the entry's own bits for every in-range id."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from pinot_tpu.broker import Broker, connect  # noqa: E402
from pinot_tpu.engine.executor import resolve_params  # noqa: E402
from pinot_tpu.ops import kernels  # noqa: E402
from pinot_tpu.ops.ir import MvReduce  # noqa: E402
from pinot_tpu.ops.kernels import DICT_SELECT_MAX, _decode_dict  # noqa: E402
from pinot_tpu.query.context import build_query_context  # noqa: E402
from pinot_tpu.query.planner import SegmentPlanner  # noqa: E402
from pinot_tpu.query.sql import parse_sql  # noqa: E402
from pinot_tpu.segment import SegmentBuilder  # noqa: E402
from pinot_tpu.server import TableDataManager  # noqa: E402
from pinot_tpu.spi import (DataType, FieldSpec, FieldType,  # noqa: E402
                           Schema, TableConfig)

LENGTHS = [1, 2, 11, DICT_SELECT_MAX, DICT_SELECT_MAX + 1]
SEGMENTS = 3
ROWS = 500


def _tables(dtype, k, rng):
    """(SEGMENTS, k) sorted dictionaries with the values a bit-for-bit
    comparison should not lose."""
    if np.issubdtype(dtype, np.floating):
        t = rng.normal(scale=1e6, size=(SEGMENTS, k)).astype(dtype)
        t[0, 0], t[1, -1] = -0.0, np.inf
    else:
        info = np.iinfo(dtype)
        t = rng.integers(info.min, info.max, size=(SEGMENTS, k), dtype=dtype)
    return np.sort(t, axis=1)


@pytest.mark.parametrize("stacked", [False, True], ids=["plain", "vmap"])
@pytest.mark.parametrize("dtype", [np.int32, np.int64, np.float32,
                                   np.float64], ids=lambda d: d.__name__)
@pytest.mark.parametrize("k", LENGTHS)
def test_decode_equals_take_on_in_range_ids(k, dtype, stacked):
    rng = np.random.default_rng(k)
    tables = _tables(dtype, k, rng)
    ids = rng.integers(0, k, size=(SEGMENTS, ROWS), dtype=np.int32)
    if stacked:
        got = jax.jit(jax.vmap(_decode_dict))(tables, ids)
        want = np.take_along_axis(tables, ids, axis=1)
    else:
        got = jax.jit(_decode_dict)(tables[0], ids[0])
        want = tables[0][ids[0]]
    got = np.asarray(got)
    assert got.dtype == want.dtype
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("mode", ["sum", "min", "max"])
@pytest.mark.parametrize("k", [11, DICT_SELECT_MAX + 1])
def test_mv_pads_never_reach_a_value(k, mode):
    """An MV row's -1 pads are clamped before the decode and masked after
    it, in either form."""
    rng = np.random.default_rng(k)
    table = np.sort(rng.integers(1, 1000, size=k, dtype=np.int32))
    ids = rng.integers(0, k, size=(ROWS, 4), dtype=np.int32)
    ids[rng.random(ids.shape) < 0.4] = -1
    ids[:, 0] = np.maximum(ids[:, 0], 0)        # every row holds a value
    got = jax.jit(lambda c, p: kernels._eval_value(
        MvReduce(0, mode, 0), (c,), (p,), promote=True))(ids, table)
    vals = np.where(ids >= 0, table[np.maximum(ids, 0)].astype(np.int64),
                    {"sum": 0, "min": np.iinfo(np.int64).max,
                     "max": np.iinfo(np.int64).min}[mode])
    want = getattr(vals, mode)(axis=1)
    assert np.array_equal(np.asarray(got), want)


# three segments whose dictionaries of `tier` differ in cardinality; the
# last is over the constant, so one statement runs both forms. `wide` is
# over the constant in every segment
TIER_CARDS = [11, 7, DICT_SELECT_MAX + 44]
# the same dictionary in every segment: SEGMENTS * BAND entries stay under
# the constant when the segmented compact kernel flattens them, SEGMENTS *
# CENT cross it although CENT alone does not
BAND, CENT = DICT_SELECT_MAX // 4, DICT_SELECT_MAX // 2
assert SEGMENTS * BAND <= DICT_SELECT_MAX < SEGMENTS * CENT
Q1_SHAPED = ("SELECT SUM(price * {col}), COUNT(*) FROM lines "
             "WHERE {col} BETWEEN 2 AND {hi} AND qty < 25")


@pytest.fixture(scope="module")
def lines(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("dict_decode")
    schema = Schema("lines", [
        FieldSpec("tier", DataType.INT), FieldSpec("wide", DataType.INT),
        FieldSpec("band", DataType.INT), FieldSpec("cent", DataType.INT),
        FieldSpec("qty", DataType.INT),
        FieldSpec("price", DataType.INT, FieldType.METRIC)])
    builder = SegmentBuilder(schema, TableConfig("lines"))
    rng = np.random.default_rng(26)
    dm = TableDataManager("lines")
    host = []
    for i, card in enumerate(TIER_CARDS):
        n = 4000 + 30 * i                       # one bucket: 4096
        cols = {"tier": rng.integers(0, card, n).astype(np.int32),
                "wide": rng.integers(0, DICT_SELECT_MAX + 300,
                                     n).astype(np.int32),
                "band": rng.integers(0, BAND, n).astype(np.int32),
                "cent": rng.integers(0, CENT, n).astype(np.int32),
                "qty": rng.integers(1, 51, n).astype(np.int32),
                "price": rng.integers(90_000, 10_000_000,
                                      n).astype(np.int32)}
        # every value present, so the cardinalities are the ones named
        cols["tier"][:card] = np.arange(card)
        cols["band"][:BAND], cols["cent"][:CENT] = np.arange(BAND), \
            np.arange(CENT)
        cols["qty"][:50] = np.arange(1, 51)
        cols["wide"][:DICT_SELECT_MAX + 300] = np.arange(
            DICT_SELECT_MAX + 300)
        dm.add_segment_dir(builder.build(cols, str(tmp), f"lines_{i}"))
        host.append(cols)
    broker = Broker()
    broker.register_table(dm)
    return connect(broker), dm, host


def _want(host, col, hi):
    total = count = 0
    for cols in host:
        m = (cols[col] >= 2) & (cols[col] <= hi) & (cols["qty"] < 25)
        total += int((cols["price"][m].astype(np.int64)
                      * cols[col][m]).sum())
        count += int(m.sum())
    return total, count


@pytest.mark.parametrize("col,hi", [("tier", 9), ("tier", 200),
                                    ("wide", 400)])
def test_sql_sum_over_segments_of_differing_cardinality(lines, col, hi):
    query, dm, host = lines
    cards = [len(s.dictionary(col).values) for s in dm.acquire_segments()]
    assert cards == (TIER_CARDS if col == "tier"
                     else [DICT_SELECT_MAX + 300] * 3)
    rows = query(Q1_SHAPED.format(col=col, hi=hi)).rows
    assert [int(v) for v in rows[0]] == list(_want(host, col, hi))


@pytest.mark.parametrize("col,gathers", [("tier", False), ("wide", True)])
def test_compiled_dense_kernel_gathers_only_over_the_constant(lines, col,
                                                              gathers):
    """The Q1-shaped dense kernel, vmapped over same-shaped segments as
    engine/batch.py launches it: no gather in the compiled HLO for an
    11-entry dictionary, one for a dictionary over the constant."""
    _query, dm, _host = lines
    seg = dm.acquire_segments()[0]
    plan = SegmentPlanner(build_query_context(parse_sql(
        Q1_SHAPED.format(col=col, hi=9))), seg).plan()
    assert plan.kind == "kernel" and plan.kernel_plan.strategy == "dense"
    params = resolve_params(plan)
    assert kernels.dict_decode_forms(plan.kernel_plan, params) == (
        (0, 1) if gathers else (1, 0))
    stack = lambda xs: tuple(jnp.stack([x, x]) for x in xs)  # noqa: E731
    text = jax.jit(jax.vmap(kernels.build_kernel(
        plan.kernel_plan, seg.bucket))).lower(
        stack(seg.device_cols(plan.col_names)),
        jnp.asarray([seg.n_docs] * 2, jnp.int32),
        stack(params)).compile().as_text()
    assert (" gather(" in text) == gathers


@pytest.mark.parametrize("col,forms", [("band", (1, 0)), ("cent", (0, 1))])
def test_segmented_compact_kernel_sees_the_flattened_dictionary(lines, col,
                                                                forms):
    """One segmented launch over the three segments hands the helper
    S * K entries: the form follows that length, the answer does not."""
    from pinot_tpu.utils.metrics import global_metrics
    query, _dm, host = lines
    sql = (f"SELECT qty, SUM(price * {col}), COUNT(*) FROM lines WHERE "
           f"{col} BETWEEN 2 AND 40 GROUP BY qty ORDER BY qty LIMIT 100 "
           "OPTION(groupByStrategy=compact)")
    before = global_metrics.snapshot()["counters"]
    rows = query(sql).rows
    after = global_metrics.snapshot()["counters"]
    moved = lambda k: after.get(k, 0) - before.get(k, 0)  # noqa: E731
    assert moved("kernel_dispatches_compact_segmented") == 1
    assert (moved("dict_decode_select"), moved("dict_decode_gather")) == forms
    want = {}
    for cols in host:
        m = (cols[col] >= 2) & (cols[col] <= 40)
        for q, v in zip(cols["qty"][m], cols["price"][m].astype(np.int64)
                        * cols[col][m]):
            s, c = want.get(int(q), (0, 0))
            want[int(q)] = (s + int(v), c + 1)
    assert [tuple(int(v) for v in r) for r in rows] == [
        (q, *want[q]) for q in sorted(want)]
