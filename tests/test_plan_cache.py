"""Keyed kernel-plan cache (ops/plan_cache.py): zero retrace across
query iterations, stable cost-model capacities as cache keys, and
result-stability of the launch / collect run path.

The bench's round-6 acceptance gate ("second iteration of each query
shows zero retrace") asserts exactly the counters covered here."""
import numpy as np
import pytest

import jax.numpy as jnp

from pinot_tpu.broker import Broker
from pinot_tpu.ops.ir import AggSpec, Cmp, Col, KernelPlan
from pinot_tpu.ops.plan_cache import KernelPlanCache, global_plan_cache
from pinot_tpu.segment import SegmentBuilder
from pinot_tpu.server import TableDataManager
from pinot_tpu.spi import (DataType, FieldSpec, FieldType, Schema,
                           TableConfig)

N = 4096


def _plan():
    return KernelPlan(
        pred=Cmp(Col(1), "<", 0),
        aggs=(AggSpec(kind="sum", value=Col(2), integral=True,
                      bits=11, signed=True),),
        group_keys=((0, 40),),
        strategy="dense",
    )


def _cols(rng):
    return (jnp.asarray(rng.integers(0, 40, N).astype(np.int32)),
            jnp.asarray(rng.integers(0, 100, N).astype(np.int32)),
            jnp.asarray(rng.integers(-1000, 1000, N).astype(np.int32)))


def test_entry_reuse_and_counters():
    cache = KernelPlanCache()
    plan = _plan()
    e1 = cache.entry(plan, N)
    stats = cache.stats()
    assert (stats["hits"], stats["misses"], stats["entries"]) == (0, 1, 1)
    e2 = cache.entry(plan, N)
    assert e2 is e1
    assert cache.stats()["hits"] == 1
    # a different capacity is a different compiled program
    e3 = cache.entry(plan, N, slots_cap=64)
    assert e3 is not e1
    assert cache.stats()["misses"] == 2


def test_repeated_runs_are_stable_and_traceless():
    """Back-to-back runs through one entry return identical results and
    never create new entries."""
    rng = np.random.default_rng(3)
    cache = KernelPlanCache()
    cols = _cols(rng)
    params = (jnp.asarray(np.int32(30)),)
    ent = cache.entry(_plan(), N)
    first = ent.run(cols, np.int32(N), params)
    misses = cache.stats()["misses"]
    for _ in range(3):
        again = cache.entry(_plan(), N).run(cols, np.int32(N), params)
        for k in first:
            assert np.array_equal(first[k], again[k]), k
    assert cache.stats()["misses"] == misses
    assert ent.runs == 4


def test_measured_selectivity_recorded():
    cache = KernelPlanCache()
    ent = cache.entry(_plan(), N)
    ent.record_measured(123, 4096)
    assert ent.measured_selectivity == pytest.approx(123 / 4096)


def test_run_is_collect_of_launch():
    """launch() returns the DEVICE outputs without waiting, collect()
    their host copy; run() is the two in a row."""
    import jax
    rng = np.random.default_rng(5)
    cols = _cols(rng)
    params = (jnp.asarray(np.int32(30)),)
    ent = KernelPlanCache().entry(_plan(), N)
    ran = ent.run(cols, np.int32(N), params)
    out = ent.launch(cols, np.int32(N), params)
    assert all(isinstance(v, jax.Array) for v in out.values())
    host = ent.collect(out)
    assert sorted(host) == sorted(ran) and ent.runs == 2
    for k in ran:
        assert isinstance(host[k], np.ndarray)
        assert np.array_equal(host[k], ran[k]), k


def test_launches_of_one_entry_overlap_under_threads():
    """N threads launch and collect on ONE entry, two launches ahead
    each: no launch waits for another's collection (nothing is donated,
    so no buffer is shared between two launches), every answer is the
    serial one and every launch is counted."""
    import threading
    rng = np.random.default_rng(9)
    cols = _cols(rng)
    params = (jnp.asarray(np.int32(30)),)
    ent = KernelPlanCache().entry(_plan(), N)
    want = ent.run(cols, np.int32(N), params)
    wrong, n_threads, rounds = [], 6, 8

    def work():
        for _ in range(rounds):
            outs = [ent.launch(cols, np.int32(N), params)
                    for _ in range(2)]
            if len({id(v) for o in outs for v in o.values()}) \
                    != 2 * len(want):
                wrong.append(outs)          # two launches share a buffer
            for out in outs:
                host = ent.collect(out)
                if not all(np.array_equal(host[k], want[k]) for k in want):
                    wrong.append(host)
    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert not wrong
    assert ent.runs == 1 + n_threads * rounds * 2


def test_collect_does_not_hold_up_a_launch(monkeypatch):
    """The entry lock is never held across device_get: while one thread
    sits in its collection, another launches and collects on the same
    entry."""
    import threading

    from pinot_tpu.ops import plan_cache as pc
    rng = np.random.default_rng(11)
    cols = _cols(rng)
    params = (jnp.asarray(np.int32(30)),)
    ent = KernelPlanCache().entry(_plan(), N)
    ent.run(cols, np.int32(N), params)          # compiled
    in_get, release = threading.Event(), threading.Event()
    real_get = pc.jax.device_get

    def blocking_get(out):
        if threading.current_thread().name == "blocked":
            in_get.set()
            assert release.wait(timeout=60)
        return real_get(out)
    monkeypatch.setattr(pc.jax, "device_get", blocking_get)
    blocked = threading.Thread(
        name="blocked", target=lambda: ent.run(cols, np.int32(N), params))
    blocked.start()
    assert in_get.wait(timeout=60)
    done = []
    other = threading.Thread(target=lambda: done.append(
        ent.collect(ent.launch(cols, np.int32(N), params))))
    other.start()
    other.join(timeout=60)
    try:
        assert not other.is_alive() and len(done) == 1
        assert blocked.is_alive() and not ent.lock.locked()
    finally:
        release.set()
        blocked.join(timeout=60)
    assert not blocked.is_alive() and ent.runs == 3


def test_no_device_get_under_the_entry_lock():
    """The source holds what the two tests above observe: no
    ``device_get`` of ops/plan_cache.py sits inside a ``with self.lock``
    or ``with self._lock`` block."""
    import ast
    import inspect

    from pinot_tpu.ops import plan_cache as pc
    tree = ast.parse(inspect.getsource(pc))
    locked = [w for w in ast.walk(tree) if isinstance(w, ast.With)
              and any("lock" in ast.unparse(i.context_expr).lower()
                      for i in w.items)]
    assert locked
    for w in locked:
        assert "device_get" not in ast.unparse(w)


@pytest.fixture(scope="module")
def broker(tmp_path_factory):
    rng = np.random.default_rng(7)
    n = 5000
    data = {
        "ka": np.array([f"a{i:03d}" for i in rng.integers(0, 40, n)]),
        "kb": np.array([f"b{i:03d}" for i in rng.integers(0, 50, n)]),
        "sel": rng.integers(0, 100, n).astype(np.int32),
        "v": rng.integers(-1000, 1000, n).astype(np.int32),
    }
    schema = Schema("pc", [
        FieldSpec("ka", DataType.STRING, FieldType.DIMENSION),
        FieldSpec("kb", DataType.STRING, FieldType.DIMENSION),
        FieldSpec("sel", DataType.INT, FieldType.DIMENSION),
        FieldSpec("v", DataType.INT, FieldType.METRIC),
    ])
    d = SegmentBuilder(schema, TableConfig("pc")).build(
        data, str(tmp_path_factory.mktemp("pc_table")), "seg_0")
    dm = TableDataManager("pc")
    dm.add_segment_dir(d)
    b = Broker()
    b.register_table(dm)
    return b


def test_second_query_iteration_zero_retrace(broker):
    """The end-to-end property the bench asserts: repeat executions of
    the same SQL (compact strategy, cost-model capacity) add ZERO plan
    cache misses after the first."""
    sql = ("SELECT ka, kb, SUM(v), COUNT(*) FROM pc WHERE sel < 20 "
           "GROUP BY ka, kb LIMIT 100000 OPTION(timeoutMs=300000)")
    first = broker.query(sql)
    misses = global_plan_cache.snapshot_misses()
    for _ in range(2):
        again = broker.query(sql)
        assert sorted(map(tuple, again.rows)) == \
            sorted(map(tuple, first.rows))
    assert global_plan_cache.snapshot_misses() == misses
