"""The ClickBench cell through the harness at a small size on the CPU:
the program's answers are ``correct`` on seeds, on the top-groups route;
a per-segment trim before the combine, put in the program's place, is
not, on a table planted for it. The configuration's file, its statements
and its metrics are what the contract asks.

The route needs a key space over ops/kernels.COMPACT_GROUP_LIMIT, which
a small table does not reach: the limit is lowered for these runs."""
from collections import Counter

import numpy as np
import pytest

from benchmark import catalog as cat
from benchmark.hits import bytes as hbytes
from benchmark.hits import data, oracle, statements
from benchmark.ssb.data import Coded

CELL = "hits1.top10_c1"
TINY = {"rows": 1 << 15, "segments": 2}
SHAPES = statements.load_shapes()


@pytest.fixture
def small_limit(monkeypatch):
    from pinot_tpu.ops import kernels
    monkeypatch.setattr(kernels, "COMPACT_GROUP_LIMIT", 64)


def tiny_run(seed, wrap=None, seconds=1.0):
    from benchmark import run
    return run.run_cell(CELL, seed, seconds, False, catalog=cat.Catalog(),
                        check_chip=False, config_override=TINY,
                        wrap_system=wrap)


def counters():
    from pinot_tpu.utils.metrics import global_metrics
    return dict(global_metrics.snapshot()["counters"])


@pytest.mark.parametrize("seed", [7, 3_000_000_019])
def test_the_program_is_correct_on_the_cell(small_limit, seed):
    before = counters()
    res = tiny_run(seed, seconds=2.0)
    after = counters()
    assert res["correct"], res["compared"]
    assert res["compared"]["answers_compared"]["value"] >= 4
    assert set(res["metrics"]) == {"queries_per_s", "query_p50_ms",
                                   "query_p90_ms", "setup_s"}
    moved = {k: after[k] - before.get(k, 0) for k in after}
    assert moved["topgroups_statements"] >= 4
    assert moved["segments_host"] == 0


PLANTED_C = 40          # the planted user's hits in each segment


def planted_segment(n, seed, segment, at_most=()):
    """Ten users of the segment's own with PLANTED_C + 1 hits each, one
    user in every segment with PLANTED_C, the rest one hit a user: the
    planted user is 11th in each segment and 1st over the table."""
    users = np.arange(n, dtype=np.int64) + (segment + 1) * 10**9
    head = np.repeat(np.arange(10, dtype=np.int64) + segment * 100,
                     PLANTED_C + 1)
    users[:len(head)] = head
    users[len(head):len(head) + PLANTED_C] = 7
    return {"UserID": users,
            "SearchPhrase": Coded(np.zeros(n, np.int32), np.array([""])),
            "SearchEngineID": np.zeros(n, np.int32)}


class PerSegmentTrim:
    """The program's place taken by a combine of per-segment top-10
    lists (a trim before the combine, which the configuration forbids),
    answering from the host columns."""

    def __init__(self, system, segments):
        self._system, self._segments = system, segments
        self._by_sql = {statements.to_sql(s): s for s in SHAPES.values()}

    def execute(self, sql):
        shape = self._by_sql[sql.split(" OPTION(")[0]]
        merged = Counter()
        for seg in self._segments:
            for row in oracle.answer([seg], shape)["rows"][:shape["limit"]]:
                merged[row[:-1]] += row[-1]
        return [list(k) + [c] for k, c in sorted(
            merged.items(), key=lambda kv: -kv[1])[:shape["limit"]]]

    execute_warm = execute

    def __getattr__(self, name):     # counters, resident_itemsize, stop ...
        return getattr(self._system, name)


def test_a_per_segment_trim_comes_out_as_not_correct(small_limit,
                                                     monkeypatch):
    monkeypatch.setattr(data, "gen_segment", planted_segment)
    assert tiny_run(11)["correct"]
    res = tiny_run(11, lambda system, own: PerSegmentTrim(system, own))
    assert not res["correct"]
    assert res["compared"]["wrong_answers"]["value"] > 0


def test_the_configurations_file_and_the_cells_metrics():
    c = cat.Catalog()
    conf = c.config("clickbench_hits_1chip")
    assert (conf["rows"], conf["segments"], conf["chips"],
            conf["replication"]) == (1 << 26, 8, 1, 1)
    assert conf["dataset"] == "hits" and conf["entry"] == "served_http_hits"
    assert sorted(conf["reduced"]) == ["columns", "rows"]
    assert conf["distinct"]["table"]["UserID"] == data.USERS
    assert conf["distinct"]["table"]["SearchPhrase_nonempty"] == data.PHRASES
    read = set().union(*(hbytes.columns_read(s) for s in SHAPES.values()))
    assert set(conf["schema"]) == read and len(read) == conf["columns"]
    cell = c.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "top10_c1"
    mix = c.traffic("top10_c1")
    assert mix["shapes"] == ["q12", "q14", "q15", "q16"]
    assert mix["clients"] == 1
    assert {m["name"] for m in c.metrics_for(CELL, True)} >= {
        "topgroups_roofline", "groups_sent_per_query",
        "flight_p50_ms.hits_q16", "host_segments_per_query",
        "kernel_ms_per_query", "device_idle_pct"}
    assert statements.to_sql(SHAPES["q14"]) == (
        "SELECT SearchEngineID, SearchPhrase, COUNT(*) AS c FROM hits "
        "WHERE SearchPhrase <> '' GROUP BY SearchEngineID, SearchPhrase "
        "ORDER BY c DESC LIMIT 10")
    assert statements.to_sql(SHAPES["q15"]) == (
        "SELECT UserID, COUNT(*) FROM hits GROUP BY UserID "
        "ORDER BY COUNT(*) DESC LIMIT 10")
    assert hbytes.logical_bytes(SHAPES["q12"], 10, {
        "SearchPhrase": 4}.__getitem__) == 40


def test_the_generator_holds_its_assumptions():
    n = 1 << 14
    segs = [data.gen_segment(n, 5, k) for k in range(data.SEGMENTS)]
    again = data.gen_segment(n, 5, 3)
    assert (again["UserID"] == segs[3]["UserID"]).all()
    users_here = int(n * data.FIRST_HIT_SHARE["users"])
    assert len(np.unique(np.concatenate([s["UserID"] for s in segs]))) \
        == users_here * data.SEGMENTS
    phrases = set()
    for seg in segs:
        text = np.asarray(seg["SearchPhrase"].values)[
            seg["SearchPhrase"].codes]
        assert 0.12 < (text != "").mean() < 0.14
        engine = seg["SearchEngineID"]
        assert ((engine == 0) == (text == "")).all()
        assert engine.max() <= data.ENGINES
        phrases |= set(seg["SearchPhrase"].values)
    assert len(phrases) == int(n * data.FIRST_HIT_SHARE["phrases"]) \
        * data.SEGMENTS + 1                        # and the empty phrase
    assert data.gen_segment(n, 6, 3)["UserID"][0] != segs[3]["UserID"][0]


@pytest.mark.parametrize("fault,ok", [
    (lambda rows: rows, True),
    (lambda rows: rows[::-1], False),
    (lambda rows: rows[1:], False),
    (lambda rows: [rows[0][:-1] + (rows[0][-1] + 1,)] + rows[1:], False),
    (lambda rows: rows[:-1] + [rows[-1][:-1] + (rows[-1][-1],)], True),
], ids=["exact", "out_of_order", "a_group_missing", "a_count_off",
        "same_rows"])
def test_same_judges_the_order_and_the_counts(fault, ok):
    shape = SHAPES["q15"]
    exact = oracle.answer([data.gen_segment(1 << 12, 9, k)
                           for k in range(2)], shape)
    got = fault([tuple(r) for r in exact["rows"][:10]])
    assert oracle.same(got, exact, shape) is ok


def test_same_accepts_any_group_tied_at_the_limit():
    shape = {**SHAPES["q15"], "limit": 2}
    seg = {"UserID": np.array([1, 1, 1, 2, 2, 3, 3, 4], np.int64)}
    exact = oracle.answer([seg], shape)
    assert exact["cut"] == 2 and len(exact["rows"]) == 3
    assert oracle.same([(1, 3), (3, 2)], exact, shape)
    assert oracle.same([(1, 3), (2, 2)], exact, shape)
    assert not oracle.same([(2, 2), (3, 2)], exact, shape)
    assert not oracle.same([(1, 3), (4, 1)], exact, shape)
