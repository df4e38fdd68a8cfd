"""The dashboard mix (``dash_c8``): its 52 statements are what
``make_dash_shapes.py`` draws from seed 33 and keep their shapes' plan
structures, its eight clients start where the cell's row says, its
configuration is ``ssb_flat_sf100_1chip``'s with the dashboard's keys,
and same-shape variants sent at once through the entry, after its
warm-up, come back fused and equal to the plain reference. CPU, tiny
sizes: nothing here is a speed. ``test_cells_cpu.py`` walks the cell over
its twelve seeds, as it does every cell; the control and a planted fault
for this cell are at the end of this file.
"""
import json
import os
import shutil
import tempfile
import threading

import pytest

from benchmark import catalog as cat
from benchmark import run
from benchmark import traffic as tr
from benchmark.ssb import make_dash_shapes as mk
from benchmark.ssb import oracle, statements

C = cat.Catalog()
CELL = "ssb1.dash_c8"
MIX = C.traffic(C.cell(CELL)["traffic"])
PATH = os.path.join(cat.HERE, MIX["statements"])
SHAPES = statements.load_shapes(PATH)
SOURCE = statements.load_shapes()
VARIANTS = [(sid, f"{sid}.v{n}") for sid in SOURCE for n in (1, 2, 3)]


def test_seed_33_reproduces_the_file():
    with open(PATH) as f:
        text = f.read()
    assert mk.dumps(mk.make(33)) == text
    assert json.loads(text)["seed"] == mk.SEED == 33
    assert mk.make(34)["shapes"] != mk.make(33)["shapes"]


def test_the_order_is_variant_major():
    ids = list(SOURCE)
    assert list(SHAPES) == ids + [f"{i}.v{n}" for n in (1, 2, 3)
                                  for i in ids]
    assert len(SHAPES) == 52
    for sid in ids:
        assert SHAPES[sid] == SOURCE[sid]


def _width(op, val):
    if op == "in":
        return len(val)
    if op != "between":
        return None
    if isinstance(val[0], str):         # brands: MFGR#<m><c><tu>
        assert val[0][:-1] == val[1][:-1] and len(val[0]) == 9
        return int(val[1][-1]) - int(val[0][-1])
    return val[1] - val[0]


@pytest.mark.parametrize("sid,vid", VARIANTS, ids=[v for _s, v in VARIANTS])
def test_a_variant_keeps_its_shape(sid, vid):
    src, var = SOURCE[sid], SHAPES[vid]
    for key in ("flight", "value", "group", "order"):
        assert var[key] == src[key]
    assert len(var["preds"]) == len(src["preds"])
    for (c0, op0, v0), (c1, op1, v1) in zip(src["preds"], var["preds"]):
        assert (c0, op0) == (c1, op1)
        assert _width(op0, v0) == _width(op1, v1)
        assert type(v0) is type(v1)
        if op0 == "lt":
            assert v0 == v1
        if op0 == "in":
            assert len(set(v1)) == len(v1)
        # a year drawn is a whole year of dbgen's: 1998 ends on 2 August
        if c0 == "d_year" and op0 in ("eq", "in"):
            assert set([v1] if op0 == "eq" else v1) <= set(range(1992, 1998))
        if c0 == "d_yearmonthnum":
            assert 1992 <= v1 // 100 <= 1997 and 1 <= v1 % 100 <= 12
        if c0 == "d_weeknuminyear":
            assert 1 <= v1 <= 52
    # the same SQL but for its literals: the same plan structure as far
    # as the text decides it
    from benchmark.entries import served_http_dash as entry
    assert (entry._LITERAL.sub("?", statements.to_sql(var))
            == entry._LITERAL.sub("?", statements.to_sql(src)))


@pytest.mark.parametrize("sid", list(SOURCE))
def test_no_two_variants_of_a_shape_are_equal(sid):
    preds = [json.dumps(SHAPES[k]["preds"])
             for k in [sid] + [f"{sid}.v{n}" for n in (1, 2, 3)]]
    assert len(set(preds)) == 4


def test_the_eight_clients_start_where_the_cell_says():
    sts = tr.build_statements(MIX, SHAPES, statements.to_sql)
    assert MIX["clients"] == 8 and "shapes" not in MIX
    first = [next(tr.walk(sts, c, 8)).key for c in range(8)]
    assert first == ["q1.1", "q3.1", "q1.1.v1", "q3.1.v1",
                     "q1.1.v2", "q3.1.v2", "q1.1.v3", "q3.1.v3"]


def test_the_configuration_is_the_one_chip_table_with_the_dashboard_keys():
    base = C.config("ssb_flat_sf100_1chip")
    dash = C.config(C.cell(CELL)["config"])
    own = {"name", "source", "deployment", "entry", "segment_rows_at_most",
           "assumed", "guarantees"}
    assert set(dash) == set(base)
    for key in set(base) - own:
        assert dash[key] == base[key], key
    assert dash["entry"] == "served_http_dash" and len(dash["source"]) <= 200
    assert dash["guarantees"][:3] == base["guarantees"]
    assert len(dash["guarantees"]) == 4
    for key, why in base["assumed"].items():
        assert dash["assumed"][key] == why
    assert {"threads", "variants", "order"} <= set(dash["assumed"])
    # S13 is per city pair: q3.3 and each of its variants is capped
    assert dash["segment_rows_at_most"]["rows"] == {
        k: 512 for k in ("q3.3", "q3.3.v1", "q3.3.v2", "q3.3.v3")}
    assert set(dash["segment_rows_at_most"]["rows"]) <= set(SHAPES)


def test_the_five_metrics_and_the_lists_that_gained_the_cell():
    new = {"fused_pct", "solo_cold_per_query", "cube_builds_per_query",
           "ragged_wait_ms_per_query", "server_queue_ms_per_query"}
    traced = {m["name"] for m in C.metrics_for(CELL, True)}
    assert new <= traced
    for name in new:
        assert C.per_layer[name]["workloads"] == [CELL]
    # device-busy time inside one request's span is other requests' work
    # when they overlap, and a fused answer reads cube cells, not rows
    assert not {"outside_device_ms", "scan_roofline"} & traced
    assert {m["name"] for m in C.metrics_for(CELL, False)} == {
        "queries_per_s", "query_p50_ms", "query_p90_ms", "setup_s"}


TINY = {"rows": 1 << 17, "segments": 4}


@pytest.fixture(scope="module")
def served():
    """The entry's system over a tiny table of seed 7, with the host
    columns the reference reads."""
    config = {**C.config(C.cell(CELL)["config"]), **TINY}
    ds = cat.dataset(config["dataset"])
    entry = cat.entry(config["entry"])
    keep = sorted({c for s in SHAPES.values()
                   for c in ds["bytes"].columns_read(s)})
    work = tempfile.mkdtemp(prefix="bench_dash_")
    old_tmp, tempfile.tempdir = tempfile.tempdir, work
    system = None
    try:
        seg_dirs, host = run.make_table(config, ds, entry, work, 7, keep)
        system = entry.start(config, seg_dirs, work)
        yield system, host
    finally:
        tempfile.tempdir = old_tmp
        if system is not None:
            system.stop()
        shutil.rmtree(work, ignore_errors=True)


def test_four_variants_at_once_come_back_fused_and_right(served):
    system, host = served
    keys = ["q1.1", "q1.1.v1", "q1.1.v2", "q1.1.v3"]
    sql = {k: statements.to_sql(SHAPES[k]) for k in keys}
    want = {k: oracle.answer(host, SHAPES[k]) for k in keys}
    before = system.counters()
    for k in keys:                  # the first brings the bursts with it
        assert oracle.same(system.execute_warm(sql[k]), want[k], SHAPES[k])
    warmed = system.counters()
    assert warmed.get("cube_builds_background", 0) \
        > before.get("cube_builds_background", 0)
    assert warmed.get("batched_queries", 0) > before.get("batched_queries", 0)

    def burst():
        got, barrier = {}, threading.Barrier(len(keys))

        def one(k):
            barrier.wait(30.0)
            got[k] = system.execute(sql[k])

        threads = [threading.Thread(target=one, args=(k,)) for k in keys]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return got

    fused0 = warmed.get("batched_queries", 0)
    for _ in range(10):             # who meets whom is the scheduler's
        got = burst()
        for k in keys:
            assert oracle.same(got[k], want[k], SHAPES[k]), k
        if system.counters().get("batched_queries", 0) > fused0:
            break
    now = system.counters()
    assert now.get("batched_queries", 0) > fused0
    # nothing was built or compiled on a query's thread: what the window's
    # bursts needed was there, or they went solo and it was made behind
    from pinot_tpu.engine.ragged import global_batcher
    assert global_batcher.wait_ready(60.0)
    assert now.get("phase_n_ragged_wait", 0) > 0


# -- the control and a planted fault, in this cell ---------------------------
# ``inplace.ReferenceInPlace`` knows the statements of ``shapes.json`` only
# (it may not be edited here: PERF.md section 7), so the three planted-fault
# cases ``test_cells_cpu.py`` makes for this cell end in its KeyError. The
# same property is held here with the mix's own statements.

def dash_reference_in_place(system, segments, **kw):
    from benchmark.tests.inplace import ReferenceInPlace
    ref = ReferenceInPlace(system, segments, **kw)
    ref._by_sql = {statements.to_sql(s): s for s in SHAPES.values()}
    return ref


def _tiny(seed, **kw):
    return run.run_cell(CELL, seed, 0.5, False, catalog=C, check_chip=False,
                        config_override=TINY, **kw)


def test_the_reference_in_place_is_correct_and_its_control_is_not():
    import numpy as np
    ok = _tiny(7, wrap_system=dash_reference_in_place)
    assert ok["correct"] and ok["attempted"] > 0
    control = _tiny(7, wrap_system=lambda system, own: dash_reference_in_place(
        system, own, round_to=np.float32))
    assert not control["correct"]
    assert control["compared"]["wrong_answers"]["value"] > 0


def test_a_stale_table_comes_out_as_not_correct():
    from benchmark.ssb import data
    n = TINY["segments"]
    other = [data.gen_segment(TINY["rows"] // n, 18, k) for k in range(n)]
    res = _tiny(17, wrap_system=lambda system, own: dash_reference_in_place(
        system, other))
    assert not res["correct"]
