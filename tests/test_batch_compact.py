"""Multi-segment compact group-by batching (round-3 item 4): same-plan
compact segments run as ONE device program via the segmented kernel
(segment index = leading group-key factor), per-segment dictionaries
intact. Reference analog: GroupByCombineOperator.java:125.
"""
import numpy as np
import pytest

from pinot_tpu.broker import Broker
from pinot_tpu.ops import kernels as K
from pinot_tpu.segment import SegmentBuilder
from pinot_tpu.server import TableDataManager
from pinot_tpu.spi import (DataType, FieldSpec, FieldType, Schema,
                           TableConfig)

N_SEG = 4
ROWS = 1500
CARD_A, CARD_B = 40, 210       # space 8400 -> compact; 4*8400 >= 2^15
# so the segmented batch also exercises the live-group transfer gather


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    rng = np.random.default_rng(31)
    schema = Schema("t", [
        FieldSpec("ka", DataType.STRING, FieldType.DIMENSION),
        FieldSpec("kb", DataType.STRING, FieldType.DIMENSION),
        FieldSpec("sel", DataType.INT, FieldType.DIMENSION),
        FieldSpec("price", DataType.INT, FieldType.METRIC),
    ])
    out = tmp_path_factory.mktemp("t")
    dm = TableDataManager("t")
    chunks = []
    for i in range(N_SEG):
        # every segment sees every key value, so per-segment dictionaries
        # agree on ids and the plans group into one batch; predicates on
        # 'sel' still resolve per segment
        chunk = {
            "ka": np.array([f"a{k:02d}" for k in
                            rng.integers(0, CARD_A, ROWS)]),
            "kb": np.array([f"b{k:03d}" for k in
                            rng.integers(0, CARD_B, ROWS)]),
            "sel": rng.integers(0, 100, ROWS).astype(np.int32),
            "price": rng.integers(0, 10_000, ROWS).astype(np.int64),
        }
        chunk["ka"][:CARD_A] = [f"a{k:02d}" for k in range(CARD_A)]
        chunk["kb"][:CARD_B] = [f"b{k:03d}" for k in range(CARD_B)]
        chunks.append(chunk)
        d = SegmentBuilder(schema, TableConfig("t")).build(
            chunk, str(out), f"seg_{i}")
        dm.add_segment_dir(d)
    data = {k: np.concatenate([c[k] for c in chunks]) for k in chunks[0]}
    b = Broker()
    b.register_table(dm)
    return b, dm, data


def test_segmented_compact_batch(setup):
    b, dm, data = setup
    before = K.jitted_segmented_compact.cache_info().misses
    sql = ("SELECT ka, kb, SUM(price), COUNT(*) FROM t WHERE sel < 45 "
           "GROUP BY ka, kb LIMIT 100000 OPTION(timeoutMs=300000)")
    res = b.query(sql)
    after = K.jitted_segmented_compact.cache_info().misses
    assert after > before, "multi-segment compact must take the " \
        "segmented batch kernel, not per-segment launches"

    mask = data["sel"] < 45
    oracle = {}
    for i in np.nonzero(mask)[0]:
        k = (data["ka"][i], data["kb"][i])
        s, c = oracle.get(k, (0, 0))
        oracle[k] = (s + int(data["price"][i]), c + 1)
    got = {(r[0], r[1]): (r[2], r[3]) for r in res.rows}
    assert got == oracle


def test_segmented_compact_overflow_retry(setup):
    """A predicate matching ~everything overflows the default compaction
    capacity; the batched path must retry at full capacity and stay
    correct."""
    b, dm, data = setup
    sql = ("SELECT ka, kb, COUNT(*) FROM t WHERE sel < 99 "
           "GROUP BY ka, kb LIMIT 100000 OPTION(timeoutMs=300000)")
    res = b.query(sql)
    mask = data["sel"] < 99
    oracle = {}
    for i in np.nonzero(mask)[0]:
        k = (data["ka"][i], data["kb"][i])
        oracle[k] = oracle.get(k, 0) + 1
    got = {(r[0], r[1]): r[2] for r in res.rows}
    assert got == oracle


def test_sort_core_batch_over_row_limit_runs_per_segment(setup,
                                                         monkeypatch):
    """The segment index multiplies the group space, so this batch
    (4 x 8400 groups) lands on the sort core — which XLA refuses to
    compile as one program at SSB sizes on the chip
    (ops/kernels.SEGMENTED_SORT_ROW_LIMIT). Above the row limit the
    group runs per segment, where each plan keeps its own smaller
    space; a batch that stays factorized is not bounded by it."""
    from pinot_tpu.engine import batch as eb

    b, dm, data = setup
    bucket = dm.acquire_segments()[0].bucket
    monkeypatch.setattr(K, "SEGMENTED_SORT_ROW_LIMIT", N_SEG * bucket - 1)
    eb.clear_stack_cache()
    before = K.jitted_segmented_compact.cache_info()
    sql = ("SELECT ka, kb, SUM(price), COUNT(*) FROM t WHERE sel < 37 "
           "GROUP BY ka, kb LIMIT 100000 OPTION(timeoutMs=300000)")
    res = b.query(sql)
    after = K.jitted_segmented_compact.cache_info()
    assert (after.misses, after.hits) == (before.misses, before.hits), \
        "a sort-core batch over the row limit must not reach the " \
        "segmented kernel"
    mask = data["sel"] < 37
    oracle = {}
    for i in np.nonzero(mask)[0]:
        k = (data["ka"][i], data["kb"][i])
        s, c = oracle.get(k, (0, 0))
        oracle[k] = (s + int(data["price"][i]), c + 1)
    assert {(r[0], r[1]): (r[2], r[3]) for r in res.rows} == oracle


def test_segmented_compact_fits_rules():
    from pinot_tpu.ops.ir import AggSpec, KernelPlan

    def plan(space, kind="sum"):
        return KernelPlan(pred=None, aggs=(AggSpec(kind, None),),
                          group_keys=((0, space),), strategy="compact")

    big = K.SEGMENTED_SORT_ROW_LIMIT
    # factorized as a batch: no row bound
    assert K.segmented_compact_fits(plan(175), big, 8)
    # 8 x 7000 > FACTORIZED_GROUP_LIMIT: the sort core, bounded by rows
    assert not K._needs_sort(plan(7000))
    assert K.segmented_compact_fits(plan(7000), big // 8, 8)
    assert not K.segmented_compact_fits(plan(7000), big // 4, 8)
    # min/max always sorts
    assert not K.segmented_compact_fits(plan(175, "max"), big // 4, 8)
    # the combined space ceiling is unchanged
    assert not K.segmented_compact_fits(
        plan(K.COMPACT_GROUP_LIMIT // 4), 1024, 8)


def test_stack_cache_not_fooled_by_recurring_segment_names(tmp_path):
    """Two tables whose segments share names, column names, and bucket
    must not share stacked device columns: the batch stack cache keys on
    the segments' load uid, not the name (a name-only key served the
    FIRST table's device data to the second table's queries — found by
    the round-9 chaos soak, where two in-process clusters both named
    their segments seg_0..seg_3)."""
    rng = np.random.default_rng(7)
    results = []
    for tbl, scale in (("t_first", 1), ("t_second", 1000)):
        schema = Schema(tbl, [
            FieldSpec("k", DataType.STRING, FieldType.DIMENSION),
            FieldSpec("v", DataType.INT, FieldType.METRIC),
        ])
        builder = SegmentBuilder(schema, TableConfig(tbl))
        dm = TableDataManager(tbl)
        total = 0
        for i in range(3):
            vals = (rng.integers(0, 10, 600) * scale).astype(np.int32)
            total += int(vals.sum())
            d = builder.build(
                {"k": np.array(["x", "y"] * 300), "v": vals},
                str(tmp_path / tbl), f"seg_{i}")  # same names both tables
            dm.add_segment_dir(d)
        b = Broker()
        b.register_table(dm)
        res = b.query(f"SELECT k, SUM(v) FROM {tbl} GROUP BY k "
                      "ORDER BY k OPTION(timeoutMs=300000)")
        assert sum(r[1] for r in res.rows) == total, \
            f"{tbl}: stacked columns served another table's data"
        results.append(res.rows)
    assert results[0] != results[1]


def test_stack_cache_lru_mutation_holds_lock():
    """The stacked-column cache is hit from broker pool / scheduler
    worker threads while evict_stacks_containing runs on the reload
    path; OrderedDict LRU mutation (move_to_end/popitem) is a
    multi-step linked-list relink that is NOT GIL-atomic (the
    segdir._CACHE_LOCK lesson, resurfaced by concur CC201). Pinned by
    lock-assertion: every cache mutation must hold _STACK_LOCK."""
    from collections import OrderedDict

    import jax.numpy as jnp

    from pinot_tpu.engine import batch as eb

    class _Seg:
        def __init__(self, uid, name):
            self.uid, self.name = uid, name

        def device_col(self, col, bucket):
            return jnp.zeros((bucket,), jnp.int32)

    class _Plan:
        col_names = ("c0",)

        def __init__(self, uid, name):
            self.segment = _Seg(uid, name)

    class _Guarded(OrderedDict):
        def _check(self):
            assert eb._STACK_LOCK.locked(), \
                "stack-cache LRU mutated without _STACK_LOCK"

        def __setitem__(self, k, v):
            self._check()
            OrderedDict.__setitem__(self, k, v)

        def __delitem__(self, k):
            self._check()
            OrderedDict.__delitem__(self, k)

        def move_to_end(self, k, last=True):
            self._check()
            OrderedDict.move_to_end(self, k, last)

        def popitem(self, last=True):
            self._check()
            return OrderedDict.popitem(self, last)

    saved = eb._STACK_CACHE
    eb._STACK_CACHE = _Guarded()
    try:
        plans = [_Plan(990001, "seg_lockpin")]
        cols = eb._stacked_cols(plans, 8)
        assert eb._stacked_cols(plans, 8) is cols   # hit: move_to_end
        # overflow the LRU so the popitem eviction path runs too
        for i in range(eb._STACK_CACHE_MAX + 2):
            eb._stacked_cols([_Plan(990100 + i, f"s{i}")], 8)
        assert len(eb._STACK_CACHE) <= eb._STACK_CACHE_MAX
        eb.evict_stacks_containing("seg_lockpin")   # reload-path delete
        assert all(n != "seg_lockpin"
                   for k in eb._STACK_CACHE for _u, n in k[0])
    finally:
        eb._STACK_CACHE = saved
