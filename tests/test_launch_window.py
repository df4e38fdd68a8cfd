"""The launch window over the per-segment route (engine/executor.py
execute_kernel_plans, engine/batch.py): a statement whose kernel plans
run one program a segment launches every segment before it collects the
first, and is held to what the serial route (one run_kernel a segment)
answers — the same partials in the same order, the same retry ladder a
segment, nothing pinned or in flight after a kill.

At this size the segmented kernel would take these statements in one
launch; the tests steer them onto the route the chip takes at 2^23 rows a
segment (ops/kernels.segmented_compact_fits refuses there) by patching
that predicate, not through an option of the program. The route hands
each segment's groups over in array form, and the statement combines
them into one partial (executor.place_group_partials): the serial
partials are held to it through the broker's merge (reduce.merge_groups)."""
import numpy as np
import pytest

from pinot_tpu.engine import executor as ex
from pinot_tpu.engine.accounting import (QueryKilledError,
                                         global_accountant)
from pinot_tpu.engine.batch import execute_plans_batched
from pinot_tpu.engine.reduce import merge_groups
from pinot_tpu.engine.tier import global_tier
from pinot_tpu.ops import kernels as K
from pinot_tpu.ops import plan_cache as pc
from pinot_tpu.query.context import build_query_context
from pinot_tpu.query.planner import SegmentPlanner
from pinot_tpu.query.sql import parse_sql
from pinot_tpu.segment import SegmentBuilder
from pinot_tpu.segment.immutable import ImmutableSegment
from pinot_tpu.spi import Schema, TableConfig
from pinot_tpu.tools import corpus
from pinot_tpu.utils import faults
from pinot_tpu.utils.metrics import global_metrics

N_SEG = 8
ROWS = 1 << 12
COUNTERS = ("plan_launch_windowed", "plan_launch_solo",
            "compact_overflow_retries", "group_xfer_overflow_retries",
            "kernel_dispatches_compact_per_segment", "segments_combined",
            "segments_extracted")


@pytest.fixture(scope="module")
def segments(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("ssb_window"))
    segs = []
    for i in range(N_SEG):
        cols = corpus.ssb_columns(ROWS, seed=(1992, i))
        d = SegmentBuilder(Schema("lineorder", corpus.ssb_fields(cols)),
                           TableConfig("lineorder")).build(
                               cols, out, f"seg_{i}")
        segs.append(ImmutableSegment.load(d))
    return segs


def _plans(segments, qid, n=N_SEG):
    _, preds, vexpr, gcols = next(q for q in corpus.SSB_QUERIES
                                  if q[0] == qid)
    ctx = build_query_context(parse_sql(
        corpus.spec_to_sql(preds, vexpr, gcols) + corpus.OPTION))
    return [SegmentPlanner(ctx, s).plan() for s in segments[:n]]


# the group-by statements of the corpus whose plans take the compact
# strategy at this size (q4.1's 175 groups stay dense):
# test_window_equals_the_serial_route holds each to it
COMPACT = [q[0] for q in corpus.SSB_QUERIES if q[3] and q[0] != "q4.1"]


@pytest.fixture
def per_segment(monkeypatch):
    monkeypatch.setattr(K, "segmented_compact_fits",
                        lambda plan, bucket, n: False)


def _moved(fn):
    before = global_metrics.snapshot()["counters"]
    out = fn()
    after = global_metrics.snapshot()["counters"]
    return out, {c: after.get(c, 0) - before.get(c, 0) for c in COUNTERS}


def _groups(partials):
    return [list(p.groups.items()) for p in partials]


def _combined(plans, serial):
    """The serial partials as the statement answers them: merged in
    segment order at the first segment, an empty partial at each other."""
    merged = merge_groups(plans[0].ctx.aggregations, serial)
    return [ex.GroupByPartial(merged)] + \
        [ex.GroupByPartial({})] * (len(serial) - 1)


@pytest.mark.parametrize("qid", COMPACT)
def test_window_equals_the_serial_route(segments, per_segment, qid):
    plans = _plans(segments, qid)
    assert {p.kernel_plan.strategy for p in plans} == {"compact"}
    serial = [ex.execute_plan(p) for p in plans]
    windowed, moved = _moved(lambda: execute_plans_batched(plans))
    assert _groups(windowed) == _groups(_combined(plans, serial))
    # (two cities of each side: a few rows of 2^15 match, or none)
    assert any(p.groups for p in serial) or qid in ("q3.3", "q3.4")
    assert moved == {"plan_launch_windowed": N_SEG, "plan_launch_solo": 0,
                     "compact_overflow_retries": 0,
                     "group_xfer_overflow_retries": 0,
                     "kernel_dispatches_compact_per_segment": N_SEG,
                     "segments_combined": N_SEG, "segments_extracted": 0}
    assert getattr(global_tier._pins, "uids", frozenset()) == frozenset()


def test_overflow_fault_retries_its_segment_alone(segments, per_segment):
    plans = _plans(segments, "q3.1")
    serial = [ex.execute_plan(p) for p in plans]
    plan = faults.install("seed=3; device.overflow: match=seg_5, times=1")
    try:
        windowed, moved = _moved(lambda: execute_plans_batched(plans))
    finally:
        faults.clear()
        pc.global_plan_cache.clear()    # the fault marked the entry
    assert [k for _p, k, _n in plan.fired_summary()] == ["seg_5"]
    assert _groups(windowed) == _groups(_combined(plans, serial))
    assert moved == {"plan_launch_windowed": N_SEG, "plan_launch_solo": 1,
                     "compact_overflow_retries": 1,
                     "group_xfer_overflow_retries": 0,
                     "kernel_dispatches_compact_per_segment": N_SEG + 1,
                     "segments_combined": N_SEG, "segments_extracted": 0}


def test_compact_steps_count_the_launch_that_answered(segments, per_segment,
                                                     monkeypatch):
    """The compactor's step counts are taken after the retry ladder: an
    overflowed launch's counts go with it, the retry's are counted, and
    no count reaches extract_partial. Each collection is numbered by
    its narrow count (1, 2, ...): seg_5's first is the sixth, its retry
    the seventh. (The route's extraction is the array stage,
    group_columns.)"""
    plans = _plans(segments, "q3.1")
    real, seen, partial_keys = pc.PlanCacheEntry.collect, [], set()

    def numbered(out):
        host = real(out)
        seen.append(out)
        host["compact_steps_narrow"] = np.int32(len(seen))
        host["compact_steps_wide"] = np.int32(0)
        return host
    real_extract = ex.group_columns

    def extract(plan, out):
        partial_keys.update(out)
        return real_extract(plan, out)
    monkeypatch.setattr(pc.PlanCacheEntry, "collect", staticmethod(numbered))
    monkeypatch.setattr(ex, "group_columns", extract)
    before = global_metrics.snapshot()["counters"]
    faults.install("seed=3; device.overflow: match=seg_5, times=1")
    try:
        execute_plans_batched(plans)
    finally:
        faults.clear()
        pc.global_plan_cache.clear()    # the fault marked the entry
    after = global_metrics.snapshot()["counters"]
    assert len(seen) == N_SEG + 1
    assert (after.get("compact_steps_narrow", 0)
            - before.get("compact_steps_narrow", 0)) == sum(
                i for i in range(1, N_SEG + 2) if i != 6)
    assert not partial_keys & set(K.COMPACT_STEP_OUTPUTS)


def test_group_overflow_retries_its_segment_alone(segments, per_segment,
                                                  monkeypatch):
    """A segment whose live groups spill the transfer compaction is run
    again alone, straight to dense outputs, while the others' kernels
    are in flight; the spill is forced on the third collection."""
    plans = _plans(segments, "q3.2")
    serial = [ex.execute_plan(p) for p in plans]
    real, seen = pc.PlanCacheEntry.collect, []

    def spill_third(out):
        host = real(out)
        seen.append(out)
        if len(seen) == 3:
            assert "group_overflow" in host and "group_idx" in host
            host["group_overflow"] = np.int32(1)
        return host
    monkeypatch.setattr(pc.PlanCacheEntry, "collect",
                        staticmethod(spill_third))
    windowed, moved = _moved(lambda: execute_plans_batched(plans))
    assert _groups(windowed) == _groups(_combined(plans, serial))
    assert len(seen) == N_SEG + 1
    assert moved == {"plan_launch_windowed": N_SEG, "plan_launch_solo": 1,
                     "compact_overflow_retries": 0,
                     "group_xfer_overflow_retries": 1,
                     "kernel_dispatches_compact_per_segment": N_SEG + 1,
                     "segments_combined": N_SEG, "segments_extracted": 0}


def test_a_kill_between_collections_leaves_nothing_behind(
        segments, per_segment, monkeypatch):
    """The accountant's sample sits between two collections: a query
    killed after its third raises there, with five launches uncollected.
    They are dropped whole (nothing is donated, so no entry waits for
    them): no segment stays pinned, the entry is not locked, and the
    statement answers again."""
    plans = _plans(segments, "q2.2")
    serial = [ex.execute_plan(p) for p in plans]
    real, done = ex.finish_kernel, []

    def kill_after_third(flight):
        host = real(flight)
        done.append(flight)
        if len(done) == 3:
            global_accountant.kill("q-window-kill", "test")
        return host
    monkeypatch.setattr(ex, "finish_kernel", kill_after_third)
    global_accountant.register("q-window-kill")
    try:
        with pytest.raises(QueryKilledError):
            execute_plans_batched(plans)
    finally:
        global_accountant.unregister("q-window-kill")
    assert len(done) == 3
    assert getattr(global_tier._pins, "uids", frozenset()) == frozenset()
    assert not any(f.entry.lock.locked() for f in done)
    monkeypatch.setattr(ex, "finish_kernel", real)
    again, moved = _moved(lambda: execute_plans_batched(plans))
    assert _groups(again) == _groups(_combined(plans, serial))
    assert moved["plan_launch_windowed"] == N_SEG


def test_one_segment_counts_a_solo_launch(segments, per_segment):
    plans = _plans(segments, "q4.2", 1)
    (windowed,), moved = _moved(lambda: execute_plans_batched(plans))
    assert windowed.groups == ex.execute_plan(plans[0]).groups
    assert (moved["plan_launch_windowed"], moved["plan_launch_solo"]) \
        == (0, 1)


def test_a_traced_statement_runs_its_segments_one_by_one(segments,
                                                         per_segment):
    """A span tree nests, and a traced launch is fenced: under a trace
    the route keeps one segment_kernel span a segment, each with its
    launch and its collection inside."""
    from pinot_tpu.utils.spans import span_tracer
    plans = _plans(segments, "q2.3")
    root = span_tracer.start("query")
    try:
        _res, moved = _moved(lambda: execute_plans_batched(plans))
    finally:
        root = span_tracer.stop() or root
    kernels = [c for c in root.children if c.name == "segment_kernel"]
    assert len(kernels) == N_SEG
    for k in kernels:
        names = [c.name for c in k.children]
        assert names.index("device_execute") \
            < names.index("device_transfer")
    assert (moved["plan_launch_windowed"], moved["plan_launch_solo"]) \
        == (0, N_SEG)
