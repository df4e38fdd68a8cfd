"""The two readings beside ``taxi1.rides_c1``'s limit on an ``AVG``
(``benchmark/taxi/oracle.TOLERANCE``, 1e-12 relative), at the cell's own
size, with the harness's own table, system and comparison:

- the program: ``run_cell`` serves a window of the cell and judges every
  answer; besides, each distinct answer's largest relative ``AVG`` error
  against the exact reference is read. It has to be ``correct``;
- the control: the plain reference with every addend held in float32
  before an exact sum (the precision below the float64 the configuration
  states, and what a column, a payload or a partial kept in float32
  anywhere on the path amounts to at best), over the same table, judged
  by the harness's comparison (``oracle.same``). It has to be not
  ``correct`` on every seed.

Run it on the machine with the chip, so that the size is the cell's:

    python3 benchmark/tests/control_taxi_full_size.py <seed> [<seed> ...]
"""
import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import catalog as cat  # noqa: E402
from benchmark import run  # noqa: E402
from benchmark.taxi import oracle, statements  # noqa: E402

CELL = "taxi1.rides_c1"
WINDOW_S = 20.0
BY_SQL = {statements.to_sql(shape): (key, shape)
          for key, shape in statements.load_shapes().items()}


class Answers:
    """The program under the timed path, unchanged; the distinct answers
    it gave are kept by statement, and so are the host segments."""

    def __init__(self, system, segments):
        self._system, self.segments = system, segments
        self.seen = {}

    def execute(self, sql):
        rows = self._system.execute(sql)
        key, _shape = BY_SQL[sql.split(" OPTION(")[0]]
        self.seen.setdefault(key, {})[repr(rows)] = rows
        return rows

    def __getattr__(self, name):  # execute_warm, counters, stop ...
        return getattr(self._system, name)


def _answers(segments, round_to=None):
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        keys = list(BY_SQL.values())
        rows = pool.map(lambda ks: oracle.answer(segments, ks[1],
                                                 round_to=round_to), keys)
        return {key: r for (key, _shape), r in zip(keys, rows)}


def reading(seed, config_override=None, check_chip=True,
            seconds=WINDOW_S) -> dict:
    """One seed's two readings: {"program": {correct, largest error by
    statement}, "control": {correct, largest error by statement}}."""
    kept = {}

    def wrap(system, segments):
        kept["answers"] = Answers(system, segments)
        return kept["answers"]

    res = run.run_cell(CELL, seed, seconds, False, catalog=cat.Catalog(),
                       check_chip=check_chip,
                       config_override=config_override, wrap_system=wrap)
    got = kept["answers"]
    exact = _answers(got.segments)
    shapes = dict(BY_SQL.values())
    program = {key: max(oracle.worst_error(rows, exact[key], shapes[key])
                        for rows in seen.values())
               for key, seen in got.seen.items()}
    held = _answers(got.segments, round_to=np.float32)
    control = {key: oracle.worst_error(rows, exact[key], shapes[key])
               for key, rows in held.items()}
    return {"program": {"correct": res["correct"],
                        "compared": res["compared"],
                        "worst": program},
            "control": {"correct": all(oracle.same(held[k], exact[k],
                                                   shapes[k])
                                       for k in held),
                        "worst": control}}


def main(seeds, config_override=None, check_chip=True) -> int:
    ok = True
    for seed in seeds:
        r = reading(seed, config_override, check_chip)
        print(f"seed {seed} cell {CELL} limit {oracle.TOLERANCE}: "
              + json.dumps(r), flush=True)
        ok = ok and r["program"]["correct"] and not r["control"]["correct"]
    print("the program is correct and the float32 control is not, on every "
          "seed:", ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main([int(s) for s in sys.argv[1:]] or [1, 2, 3]))
