"""Device-side group-output transfer compaction (ops/kernels.
_compact_group_xfer): big group spaces ship only live groups to the host;
spill past GROUP_XFER_CAP falls back to dense outputs via the executor
retry. Oracle-checked through the full broker path.
"""
import functools

import numpy as np
import pytest

from pinot_tpu.broker import Broker
from pinot_tpu.ops import kernels as K
from pinot_tpu.ops.compact import LANES
from pinot_tpu.segment import ImmutableSegment, SegmentBuilder
from pinot_tpu.server import TableDataManager
from pinot_tpu.spi import (DataType, FieldSpec, FieldType, Schema,
                           TableConfig)

CARD = 200          # space = 200*200 = 40000 >= GROUP_XFER_SPACE


def _broker(tmp_path, n, distinct_groups):
    rng = np.random.default_rng(5)
    g = np.arange(n) % distinct_groups
    data = {
        "ka": (g // CARD).astype(np.int32),
        "kb": (g % CARD).astype(np.int32),
        "v": rng.integers(0, 1000, n).astype(np.int64),
    }
    schema = Schema("t", [
        FieldSpec("ka", DataType.INT, FieldType.DIMENSION),
        FieldSpec("kb", DataType.INT, FieldType.DIMENSION),
        FieldSpec("v", DataType.LONG, FieldType.METRIC),
    ])
    d = SegmentBuilder(schema, TableConfig("t")).build(
        data, str(tmp_path), "seg_0")
    dm = TableDataManager("t")
    dm.add_segment_dir(d)
    b = Broker()
    b.register_table(dm)
    return b, data


def _oracle(data):
    out = {}
    for a, b, v in zip(data["ka"], data["kb"], data["v"]):
        k = (int(a), int(b))
        s, c = out.get(k, (0, 0))
        out[k] = (s + int(v), c + 1)
    return out


@pytest.mark.parametrize("distinct_groups", [
    500,                      # few live groups: compacted transfer path
    K.GROUP_XFER_CAP + 200,   # spill: group_overflow -> dense retry
], ids=["compacted", "overflow_dense_retry"])
def test_big_space_group_by(tmp_path, distinct_groups):
    n = max(60_000, distinct_groups)
    broker, data = _broker(tmp_path, n, distinct_groups)
    res = broker.query(
        "SELECT ka, kb, SUM(v), COUNT(*) FROM t GROUP BY ka, kb "
        "LIMIT 100000 OPTION(timeoutMs=300000)")
    oracle = _oracle(data)
    assert len(res.rows) == distinct_groups
    for ka, kb, s, c in res.rows:
        assert oracle[(ka, kb)] == (s, c)


# -- the sparse sorted post against the dense post + transfer compaction ----
#
# One segment whose row i belongs to group i % _GROUPS and carries its group
# number in ``sel``: ``WHERE sel < L`` leaves exactly L live groups of two
# rows each, and L is a literal param, so one compiled pair of kernels per
# aggregation list serves every L.

_GROUPS = 36_000                  # space = 180 * 200 >= GROUP_XFER_SPACE
_LIVE = [0, 1, 511, 512, 513, 4096, 4097, K.GROUP_XFER_CAP,
         K.GROUP_XFER_CAP + 200]
_AGGS = {
    "count_sum": "SUM(v), COUNT(*)",
    "avg": "AVG(v)",
    "min_max": "MIN(w), MAX(w)",
    "two_minmax_exprs": "MIN(w), MAX(v)",
}


@pytest.fixture(scope="module")
def live_seg(tmp_path_factory):
    rng = np.random.default_rng(7)
    n = 2 * _GROUPS
    g = np.arange(n) % _GROUPS
    data = {
        "ka": (g // CARD).astype(np.int32),
        "kb": (g % CARD).astype(np.int32),
        "sel": g.astype(np.int32),
        "v": rng.integers(0, 1000, n).astype(np.int64),
        "w": rng.integers(-500, 500, n).astype(np.int32),
    }
    schema = Schema("t", [
        FieldSpec("ka", DataType.INT, FieldType.DIMENSION),
        FieldSpec("kb", DataType.INT, FieldType.DIMENSION),
        FieldSpec("sel", DataType.INT, FieldType.METRIC),
        FieldSpec("v", DataType.LONG, FieldType.METRIC),
        FieldSpec("w", DataType.INT, FieldType.METRIC),
    ])
    d = SegmentBuilder(schema, TableConfig("t")).build(
        data, str(tmp_path_factory.mktemp("live")), "seg_0")
    return ImmutableSegment.load(d), data


def _plan(seg, aggs, live):
    from pinot_tpu.query.context import build_query_context
    from pinot_tpu.query.planner import SegmentPlanner
    from pinot_tpu.query.sql import parse_sql
    sql = (f"SELECT ka, kb, {aggs} FROM t WHERE sel < {live} "
           "GROUP BY ka, kb LIMIT 100000")
    plan = SegmentPlanner(build_query_context(parse_sql(sql)), seg).plan()
    assert plan.kind == "kernel" and plan.kernel_plan.strategy == "compact"
    assert K.takes_sparse_post(plan.kernel_plan)
    return plan


@functools.lru_cache(maxsize=None)
def _kernel_pair(kernel_plan, bucket):
    """(the kernel with the sparse post, the dense sorted post followed by
    _compact_group_xfer), the sorted core forced, at a capacity no match
    count overflows; jitted once an aggregation list."""
    import jax

    from pinot_tpu.ops.compact import full_slots_cap
    cap = full_slots_cap(bucket)
    dense = K.build_kernel(kernel_plan, bucket, cap,
                           xfer_compact=False, scatter=False)

    def dense_then_xfer(cols, n, params):
        out = dense(cols, n, params)
        K._compact_group_xfer(kernel_plan, out)
        return out

    return (jax.jit(K.build_kernel(kernel_plan, bucket, cap,
                                   xfer_compact=True, scatter=False)),
            jax.jit(dense_then_xfer))


@pytest.mark.parametrize("live", _LIVE)
@pytest.mark.parametrize("aggs", sorted(_AGGS))
def test_sparse_post_equals_dense_post_and_xfer(live_seg, aggs, live):
    """Every output of the sparse post, padding included, is the dense
    post's compacted for the transfer, whichever probe count its tail
    took; only more live groups than the cap raise group_overflow. The
    one row the two contracts differ in: past the live groups a min or
    max holds its neutral extreme in the sparse post (what the mesh's
    scatter-min/-max wants) and 0 after _compact_group_xfer."""
    import jax

    from pinot_tpu.engine.executor import resolve_params
    seg, _data = live_seg
    plan = _plan(seg, _AGGS[aggs], live)
    sparse_fn, dense_fn = _kernel_pair(plan.kernel_plan, seg.bucket)
    args = (seg.device_cols(plan.col_names), np.int32(seg.n_docs),
            resolve_params(plan))
    sparse = jax.device_get(sparse_fn(*args))
    dense = jax.device_get(dense_fn(*args))
    assert int(sparse["overflow"]) == 0 and int(sparse["matched"]) == 2 * live
    assert int(sparse["group_overflow"]) == (live > K.GROUP_XFER_CAP)
    assert sorted(sparse) == sorted(dense)
    space = plan.kernel_plan.group_space
    assert np.count_nonzero(sparse["group_idx"] < space) \
        == min(live, K.GROUP_XFER_CAP)
    pad = sparse["group_idx"] == space
    assert not pad[:min(live, K.GROUP_XFER_CAP)].any()
    for name, got in sparse.items():
        want = dense[name]
        assert got.dtype == want.dtype and got.shape == want.shape, name
        kind = name.rsplit("_", 1)[-1]
        if kind in ("min", "max"):
            want = np.where(pad, K._extreme(
                want.dtype, 1 if kind == "min" else -1), want)
        np.testing.assert_array_equal(got, want, name)


def _group_oracle(data, live, cols):
    rows = np.nonzero(data["sel"] < live)[0]
    out = {}
    for r in rows:
        out.setdefault((int(data["ka"][r]), int(data["kb"][r])), []).append(
            tuple(int(data[c][r]) for c in cols))
    return out


@pytest.mark.parametrize("live", [100, 600, 5000, K.GROUP_XFER_CAP + 200])
def test_sparse_post_counters_follow_the_kernels_ladder(live_seg, live):
    """One per-segment query moves sparse_post_results by one and the
    counter of the probe count the kernel's own ladder gives its live
    groups; a spill answers through the dense retry and counts nothing."""
    from pinot_tpu.engine.executor import extract_partial, run_kernel
    from pinot_tpu.utils.metrics import global_metrics
    seg, data = live_seg
    plan = _plan(seg, "SUM(v), MIN(w)", live)
    spilled = live > K.GROUP_XFER_CAP
    probes = [s * LANES for s in K._sparse_post_sizes(K.GROUP_XFER_CAP)]
    assert probes == [512, 4096, K.GROUP_XFER_CAP]
    expect = K.sparse_post_probes(live)
    assert expect == min(p for p in probes if p >= min(live, probes[-1]))
    names = ["sparse_post_results", "group_xfer_overflow_retries"] \
        + [f"sparse_post_probes_{p}" for p in probes]
    before = global_metrics.snapshot()["counters"]
    part = extract_partial(plan, run_kernel(plan))
    after = global_metrics.snapshot()["counters"]
    moved = {n: after.get(n, 0) - before.get(n, 0) for n in names}
    assert moved == {
        "sparse_post_results": 0 if spilled else 1,
        "group_xfer_overflow_retries": 1 if spilled else 0,
        **{f"sparse_post_probes_{p}": int(p == expect and not spilled)
           for p in probes}}
    oracle = _group_oracle(data, live, ("v", "w"))
    assert len(part.groups) == live == len(oracle)
    for key, (s, mn) in part.groups.items():
        rows = oracle[key]
        assert (s, mn) == (sum(v for v, _ in rows), min(w for _, w in rows))
