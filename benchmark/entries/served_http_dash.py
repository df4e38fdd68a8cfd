"""Entry ``served_http_dash``: the system of ``served_http`` (the same
Controller + ServerNode + BrokerNode, by import) warmed for clients that
overlap. Two differences:

- ``execute_warm(sql)`` sends the statement alone, as ``served_http``
  does, and the first time it meets a plan structure (the SQL with its
  literals struck out: the variants of ``shapes_dash.json`` share their
  shape's cubes and fused programs) also as simultaneous bursts of 2, 4
  and 8 copies through the long-timeout connection, waiting after each
  burst until the micro-batcher's background builder is idle
  (``RaggedBatcher.wait_ready``), round after round until a round meets
  nothing cold. A burst of a shape that is cold answers solo while its
  cubes, and then its fused program, are made behind it; the bursts after
  that fuse. So the window starts with every cube and every fused program
  the bursts could ask for in place, and ``run.py``'s loop (passes until
  one compiles nothing) sees the background's compiles too.
- At import it checks that the program has that repair (PR 33: a query
  never builds a cube or compiles a fused program on its own thread) and
  refuses at once if not: on a program from before it, this warm-up would
  walk cube builds inside query deadlines for a quarter of an hour.
"""
from __future__ import annotations

import re
import sys
import threading
import time
from typing import List

from benchmark.entries import served_http

build_segment = served_http.build_segment

BURSTS = (2, 4, 8)
MAX_ROUNDS = 6
WAIT_S = 900.0          # one wait for the background: a cold cube program
_LITERAL = re.compile(r"'[^']*'|\b\d+\b")


def _batcher():
    from pinot_tpu.engine.ragged import global_batcher
    return global_batcher


if not hasattr(_batcher(), "wait_ready"):
    raise SystemExit(
        "entry served_http_dash: this checkout's micro-batcher has no "
        "wait_ready (pinot_tpu/engine/ragged.py from before PR 33): a cold "
        "fused attempt would build its cubes inside the query's deadline; "
        "the configuration cannot run here")


class ServedDash(served_http.Served):
    def __init__(self, seg_dirs: List[str], work_dir: str):
        super().__init__(seg_dirs, work_dir)
        self._walked: set = set()

    def execute_warm(self, sql: str) -> list:
        rows = super().execute_warm(sql)
        structure = _LITERAL.sub("?", sql)
        if structure not in self._walked:
            self._walked.add(structure)
            self._bursts(sql)
        return rows

    def _bursts(self, sql: str) -> None:
        t0, first = time.perf_counter(), self.counters()
        for rounds in range(1, MAX_ROUNDS + 1):
            cold = self.counters().get("solo_fallback_cold", 0)
            for n in BURSTS:
                self._burst(sql, n)
                if not _batcher().wait_ready(WAIT_S):
                    raise RuntimeError("the micro-batcher's background "
                                       f"builder was busy for {WAIT_S} s")
            if self.counters().get("solo_fallback_cold", 0) == cold:
                break
        else:
            raise RuntimeError(f"bursts of {sql!r} still met something "
                               f"cold after {MAX_ROUNDS} rounds")
        last = self.counters()
        moved = {k: int(last.get(k, 0) - first.get(k, 0)) for k in (
            "solo_fallback_cold", "cube_builds_background",
            "fused_compiles_background", "batched_queries")}
        print(f"bursts: {rounds} round(s) of {BURSTS} in "
              f"{time.perf_counter() - t0:.1f}s {moved} {sql[:72]}",
              file=sys.stderr, flush=True)

    def _burst(self, sql: str, n: int) -> None:
        """``n`` copies at once; a failure of any is the burst's."""
        barrier = threading.Barrier(n)
        errors: List[BaseException] = []

        def one():
            try:
                barrier.wait(60.0)
                served_http.Served.execute_warm(self, sql)
            except BaseException as e:  # noqa: BLE001 — raised below
                errors.append(e)

        threads = [threading.Thread(target=one, daemon=True)
                   for _ in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]


def start(config: dict, seg_dirs: List[str], work_dir: str) -> ServedDash:
    return ServedDash(seg_dirs, work_dir)
