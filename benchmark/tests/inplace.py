"""What the tests put under the timed path in the program's place."""
from benchmark.ssb import oracle, statements


class ReferenceInPlace:
    """The plain reference put in the program's place: it answers each
    statement from ``segments``, every sum rounded to ``round_to``. A
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
