"""The one traffic generator. A mix is a data file under ``workloads/``;
this module turns it into its statements and drives them at the system
through a ``send`` function.

Parameters of a mix (``workloads/<traffic>.json``):

- ``statements``: the statement file, relative to ``benchmark/``;
- ``shapes``: the ids to use, in the order a client walks them
  (absent: all of the file, in its order);
- ``clients``: closed-loop clients. Each sends its next statement when
  the last one answered. Client ``c`` starts its walk
  ``c * len(shapes) // clients`` shapes in.

``--seed`` makes the table, not the traffic: every seed sends the same
statements in the same order.
"""
from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, Iterator, List, Optional, Tuple


@dataclass
class Statement:
    key: str            # the shape's id (no "#": TraceMe cuts there)
    shape: dict
    sql: str


@dataclass
class Request:
    client: int
    key: str
    sent: float
    done: float
    rows: Optional[list]
    error: Optional[str]

    @property
    def latency_ms(self) -> float:
        return (self.done - self.sent) * 1e3


def build_statements(traffic: dict, shapes: Dict[str, dict], to_sql
                     ) -> Dict[str, Statement]:
    """{key: statement} of the mix, in walking order."""
    return {sid: Statement(sid, shapes[sid], to_sql(shapes[sid]))
            for sid in traffic.get("shapes") or list(shapes)}


def walk(statements: Dict[str, Statement], client: int, clients: int
         ) -> Iterator[Statement]:
    """The endless sequence of statements client ``client`` sends."""
    order = list(statements.values())
    pos = client * len(order) // clients
    while True:
        yield order[pos % len(order)]
        pos += 1


def drive(send: Callable[[str], list], traffic: dict,
          statements: Dict[str, Statement], seconds: float,
          annotate=None) -> Tuple[float, List[Request]]:
    """Run the mix for ``seconds``. Returns (t0, every request sent in
    the window), waiting for those still open when it closes. ``annotate
    (statement, n)`` may give a context manager to wrap each request in."""
    clients = int(traffic["clients"])
    annotate = annotate or (lambda st, n: contextlib.nullcontext())
    per_client: List[List[Request]] = [[] for _ in range(clients)]
    barrier = threading.Barrier(clients + 1)
    t0_box: List[float] = []

    def client(c: int) -> None:
        mine = walk(statements, c, clients)
        barrier.wait()
        t_end = t0_box[0] + seconds
        n = 0
        while time.perf_counter() < t_end:
            st = next(mine)
            with annotate(st, c * 1_000_000 + n):
                sent = time.perf_counter()
                try:
                    rows, err = send(st.sql), None
                except Exception as e:  # noqa: BLE001 — a failed request is data
                    rows, err = None, f"{type(e).__name__}: {e}"[:300]
                done = time.perf_counter()
            per_client[c].append(Request(c, st.key, sent, done, rows, err))
            n += 1

    threads = [threading.Thread(target=client, args=(c,),
                                name=f"bench-client-{c}", daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    t0_box.append(time.perf_counter())
    barrier.wait()
    for t in threads:
        t.join()
    return t0_box[0], [r for reqs in per_client for r in reqs]
