"""Entry ``served_http``: Controller + ServerNode + BrokerNode in this
process, as ``tools/admin.py`` constructs them, the table registered by
location over the controller's REST API, and SQL sent to the broker's
HTTP endpoint through ``clients.connect_url`` (the pattern of
``chip_smoke.py``). The server holds every segment on the process's
first device.

An entry is the only file of the benchmark that imports the program. It
gives ``build_segment`` (host work, thread-safe) and
``start`` -> a system with ``execute``, ``counters``,
``resident_itemsize``, ``describe`` and ``stop``.
"""
from __future__ import annotations

import os
from typing import Dict, List

import numpy as np

TABLE = "lineorder"
WARM_OPTION = " OPTION(timeoutMs=1100000)"


def _schema(cols: Dict, measures):
    from pinot_tpu.spi import DataType, FieldSpec, FieldType, Schema

    fields = []
    for name, col in cols.items():
        if name in measures:
            fields.append(FieldSpec(name, DataType.INT, FieldType.METRIC))
        elif isinstance(col, np.ndarray):
            fields.append(FieldSpec(name, DataType.INT, FieldType.DIMENSION))
        else:
            fields.append(FieldSpec(name, DataType.STRING,
                                    FieldType.DIMENSION))
    return Schema(TABLE, fields)


def build_segment(cols: Dict, measures, out_dir: str, name: str) -> str:
    """Write one segment directory from host columns; returns its path."""
    from pinot_tpu.segment import SegmentBuilder
    from pinot_tpu.segment.builder import Categorical
    from pinot_tpu.spi import TableConfig

    given = {n: c if isinstance(c, np.ndarray) else Categorical(*c)
             for n, c in cols.items()}
    return SegmentBuilder(_schema(cols, measures), TableConfig(TABLE)).build(
        given, out_dir, name)


class Served:
    def __init__(self, seg_dirs: List[str], work_dir: str):
        from pinot_tpu.clients import connect_url
        from pinot_tpu.cluster import BrokerNode, Controller, ServerNode
        from pinot_tpu.cluster.http_util import http_json
        from pinot_tpu.segment import ImmutableSegment

        self.controller = Controller(os.path.join(work_dir, "controller"))
        self.server = ServerNode("bench_server", self.controller.url)
        self.broker = BrokerNode(self.controller.url)
        try:
            schema = ImmutableSegment.load(seg_dirs[0]).schema
            http_json("POST", f"{self.controller.url}/tables",
                      {"name": TABLE, "schema": schema.to_dict(),
                       "replication": 1})
            for d in seg_dirs:
                http_json("POST", f"{self.controller.url}/segments",
                          {"table": TABLE, "segment": os.path.basename(d),
                           "location": d})
            version = self.controller.routing_snapshot()["version"]
            if not (self.server.wait_for_version(version, timeout=120.0)
                    and self.broker.wait_for_version(version, timeout=120.0)):
                raise RuntimeError("server/broker did not reach the "
                                   f"controller's routing version {version}")
            held = len(self._segments())
            if held != len(seg_dirs):
                raise RuntimeError(f"server holds {held} of {len(seg_dirs)} "
                                   "segments")
        except BaseException:
            self.stop()
            raise
        # the window's client: no OPTION, the 10 s default is the limit
        self._conn = connect_url(self.broker.url, timeout=60.0)
        self._warm = connect_url(self.broker.url, timeout=1200.0)

    def _segments(self):
        server = getattr(self, "server", None)
        dm = server._tables.get(TABLE) if server is not None else None
        return dm.acquire_segments() if dm is not None else []

    def execute(self, sql: str) -> list:
        return self._conn.execute(sql).rows

    def execute_warm(self, sql: str) -> list:
        """For set-up only: outlasts a cold compile."""
        return self._warm.execute(sql + WARM_OPTION).rows

    def counters(self) -> Dict[str, float]:
        from pinot_tpu.utils.metrics import global_metrics
        return dict(global_metrics.snapshot()["counters"])

    def resident_itemsize(self, column: str) -> int:
        """Itemsize of ``column`` as it sits on the device now (a cache
        hit after warm-up: ``device_col`` is what the kernels read)."""
        return int(self._segments()[0].device_col(column).dtype.itemsize)

    def describe(self, sql: str) -> str:
        """What the server ran for ``sql``: the dispatch spans of the
        tree EXPLAIN ANALYZE brings back, for a mismatch's report."""
        rows = self._warm.execute("EXPLAIN ANALYZE " + sql + WARM_OPTION).rows
        keep = ("ragged_dispatch", "vmap_dispatch", "segment_kernel",
                "segmented_compact_dispatch", "segment_host",
                "overflow_retry", "group_overflow_retry")
        return "; ".join(f"{node}[{detail}]" for node, _i, _p, _ms, detail
                         in rows if node in keep)

    def stop(self) -> None:
        for seg in self._segments():
            seg.evict_device()
        for name in ("broker", "server", "controller"):
            node = getattr(self, name, None)    # a start that failed half way
            if node is not None:
                node.stop()


def start(config: dict, seg_dirs: List[str], work_dir: str) -> Served:
    return Served(seg_dirs, work_dir)
