"""The control of ``tpch1.q1q6_c1``, at the cell's own size, through the
harness itself: the plain reference put in the program's place under the
timed path with every addend held in float32 before an exact sum — the
precision below the float64 the configuration states, and what a column,
a payload or a partial kept in float32 anywhere on the path amounts to
at best. ``run_cell`` has to say ``correct`` false for it on every seed;
with the addends left exact it has to say true (the same run, first).
Run it on the machine with the chip, so that the size and the harness's
checks are the cell's:

    python3 benchmark/tests/control_tpch_full_size.py <seed> [<seed> ...]
"""
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import catalog as cat  # noqa: E402
from benchmark import run  # noqa: E402
from benchmark.tpch import oracle, statements  # noqa: E402

CELL = "tpch1.q1q6_c1"
WINDOW_S = 0.05     # answers come from memory: thousands a second


class ReferenceInPlace:
    """The plain reference in the program's place: it answers each
    statement from ``segments``, every addend rounded to ``round_to``. A
    statement is computed once; the window is served from that."""

    def __init__(self, system, segments, round_to=None):
        self._system, self._segments = system, segments
        self._round_to = round_to
        self._by_sql = {statements.to_sql(shape): shape
                        for shape in statements.load_shapes().values()}
        self._answers = {}

    def execute(self, sql):
        sql = sql.split(" OPTION(")[0]
        if sql not in self._answers:
            rows = oracle.answer(self._segments, self._by_sql[sql],
                                 round_to=self._round_to)
            self._answers[sql] = [list(r) for r in rows]
        return self._answers[sql]

    execute_warm = execute

    def __getattr__(self, name):     # counters, resident_itemsize, stop ...
        return getattr(self._system, name)


def main(seeds, config_override=None, check_chip=True) -> int:
    catalog = cat.Catalog()
    ok = True
    for seed in seeds:
        for round_to, want in ((None, True), (np.float32, False)):
            res = run.run_cell(
                CELL, seed, WINDOW_S, False, catalog=catalog,
                check_chip=check_chip, config_override=config_override,
                wrap_system=lambda system, segs: ReferenceInPlace(
                    system, segs, round_to=round_to))
            name = "exact" if round_to is None else "float32"
            print(f"seed {seed} cell {CELL} reference in place, addends "
                  f"{name}: correct {res['correct']} compared "
                  f"{json.dumps(res['compared'])}", flush=True)
            ok = ok and res["correct"] is want
    print("the control fails the cell on every seed, the exact reference "
          "passes it:", ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main([int(s) for s in sys.argv[1:]] or [1, 2, 3]))
