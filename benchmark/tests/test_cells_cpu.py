"""A tiny CPU walk of every cell: the whole run (table from the seed,
the entry's start-up, warm-up, a short window, the comparison with the
plain reference) over twelve seeds, and the control and the planted
faults, which must come out as not correct. It prints no speed and is
never a pass of the benchmark: the harness's look for a chip is skipped.
"""
import numpy as np
import pytest

from benchmark import catalog as cat
from benchmark import run
from benchmark.ssb import data
from benchmark.tests.inplace import ReferenceInPlace

C = cat.Catalog()
TINY = {"rows": 1 << 17, "segments": 4}
SEEDS = [1, 2, 3, 5, 8, 13, 21, 34, 2_147_483_659, 3_000_000_019,
         4_000_000_007, 4_294_967_295]


def tiny_run(cell, seed, **kw):
    return run.run_cell(cell, seed, 0.5, False, catalog=C, check_chip=False,
                        config_override=TINY, **kw)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", sorted(C.cells))
def test_every_answer_equals_the_reference(cell, seed):
    res = tiny_run(cell, seed)
    assert res["correct"], (cell, seed, res["compared"])
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("cell", sorted(C.cells))
def test_a_cell_reports_its_end_to_end_metrics(cell):
    res = tiny_run(cell, 37)
    assert res["correct"]
    assert set(res["metrics"]) == {
        m["name"] for m in C.metrics_for(cell, False)}


def host_segments(seed, keep=None):
    n = TINY["segments"]
    segs = [data.gen_segment(TINY["rows"] // n, seed, k) for k in range(n)]
    return segs if keep is None else segs[:keep]


def in_place(segments=None, **kw):
    """The reference in place, over the run's own table or ``segments``."""
    return lambda system, own: ReferenceInPlace(
        system, own if segments is None else segments, **kw)


@pytest.mark.parametrize("seed", [7, 11, 3_000_000_019])
def test_the_reference_in_place_is_correct_and_its_control_is_not(seed):
    ok = tiny_run("ssb1.suite_c1", seed, wrap_system=in_place())
    assert ok["correct"]
    # the control: sums kept in float32, the precision below the exact
    # 64-bit integers the configuration states
    control = tiny_run("ssb1.suite_c1", seed, wrap_system=in_place(
        round_to=np.float32))
    assert not control["correct"]
    assert control["compared"]["wrong_answers"]["value"] > 0


FAULTS = {
    # half of the batch left out: the answer comes from half the segments
    "half_the_segments_left_out": lambda seed: in_place(
        host_segments(seed, keep=TINY["segments"] // 2)),
    # the exchange between chips left out: one device's shard answers alone
    "one_shard_answers_alone": lambda seed: in_place(
        host_segments(seed, keep=1)),
    # a state left unchanged: the answers of an older table (another seed)
    "stale_table": lambda seed: in_place(host_segments(seed + 1)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", sorted(C.cells))
def test_a_planted_fault_comes_out_as_not_correct(cell, fault):
    res = tiny_run(cell, 17, wrap_system=FAULTS[fault](17))
    assert not res["correct"], (cell, fault)


class OneAnswerAltered:
    """The real system with one sum of one answer altered where it is
    produced: the last column of the first row of every fifth answer."""

    def __init__(self, system, _segments):
        self._system, self._n = system, 0

    def execute(self, sql):
        rows = [list(r) for r in self._system.execute(sql)]
        self._n += 1
        if self._n % 5 == 0 and rows:
            rows[0][-1] = rows[0][-1] + 1
        return rows

    def __getattr__(self, name):
        return getattr(self._system, name)


@pytest.mark.parametrize("cell", sorted(C.cells))
def test_an_altered_answer_comes_out_as_not_correct(cell):
    res = tiny_run(cell, 19, wrap_system=OneAnswerAltered)
    assert not res["correct"]
    assert res["compared"]["wrong_answers"]["value"] >= 1
    assert res["failed"] == res["compared"]["wrong_answers"]["value"]


class Refusing:
    """The real system refusing every third request."""

    def __init__(self, system, _segments):
        self._system, self._n = system, 0

    def execute(self, sql):
        self._n += 1
        if self._n % 3 == 0:
            raise RuntimeError("refused")
        return self._system.execute(sql)

    def __getattr__(self, name):
        return getattr(self._system, name)


def test_an_unanswered_request_is_failed_and_not_correct():
    res = tiny_run("ssb1.q1_scan_c1", 23, wrap_system=Refusing)
    assert not res["correct"] and res["failed"] >= 1
    assert res["compared"]["unanswered"]["value"] == res["failed"]


class RowsReversed:
    """The real system with every answer's rows in the opposite order:
    the right rows, against the statement's ORDER BY."""

    def __init__(self, system, _segments):
        self._system = system

    def execute(self, sql):
        return list(reversed(self._system.execute(sql)))

    def __getattr__(self, name):
        return getattr(self._system, name)


def test_the_right_rows_in_the_wrong_order_are_not_correct():
    res = tiny_run("ssb1.suite_c1", 31, wrap_system=RowsReversed)
    assert not res["correct"]
    # flight 1 answers one row: those requests stay right
    assert 0 < res["compared"]["wrong_answers"]["value"] < res["attempted"]
