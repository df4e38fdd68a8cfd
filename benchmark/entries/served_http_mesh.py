"""Entry ``served_http_mesh``: the trio of ``served_http`` with the
server constructed over the first ``config["chips"]`` devices as one
mesh. The table's segments are resident across the mesh
(``pinot_tpu/parallel/distributed.py``) and an aggregation is answered by
one mesh program, its collectives in the place of the servers' response
hops; nothing is uploaded to the default device unless a statement falls
back to the per-segment path (the counter ``mesh_fallbacks``).

Everything but the server's construction and what is read off the mesh
is ``served_http``'s, by import.
"""
from __future__ import annotations

import inspect
import os
from typing import List

from benchmark.entries import served_http
from benchmark.entries.served_http import TABLE, WARM_OPTION  # noqa: F401

build_segment = served_http.build_segment


def _server_takes_a_mesh() -> bool:
    from pinot_tpu.cluster import ServerNode
    return "mesh" in inspect.signature(ServerNode.__init__).parameters


if not _server_takes_a_mesh():
    # a checkout from before the mesh-holding server: say so at once,
    # before a table is made for nothing
    raise SystemExit("entry served_http_mesh: this checkout's ServerNode "
                     "takes no mesh; the configuration cannot run here")


class ServedMesh(served_http.Served):
    def __init__(self, seg_dirs: List[str], work_dir: str, chips: int):
        import jax
        from pinot_tpu.clients import connect_url
        from pinot_tpu.cluster import BrokerNode, Controller, ServerNode
        from pinot_tpu.cluster.http_util import http_json
        from pinot_tpu.segment import ImmutableSegment

        devices = jax.devices()
        if len(devices) < chips:
            raise RuntimeError(f"the configuration spreads its table over "
                               f"{chips} devices, JAX found {len(devices)}")
        self.controller = Controller(os.path.join(work_dir, "controller"))
        self.server = ServerNode("bench_server", self.controller.url,
                                 mesh=devices[:chips])
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
            dist = self._mesh_table()
            if dist is None or len(dist.segments) != len(seg_dirs):
                raise RuntimeError(
                    f"the mesh holds {0 if dist is None else len(dist.segments)}"
                    f" of {len(seg_dirs)} segments (segments that share no "
                    "table dictionaries stay off it)")
        except BaseException:
            self.stop()
            raise
        # the window's client: no OPTION, the 10 s default is the limit
        self._conn = connect_url(self.broker.url, timeout=60.0)
        self._warm = connect_url(self.broker.url, timeout=1200.0)

    def _mesh_table(self):
        server = getattr(self, "server", None)
        dm = server._tables.get(TABLE) if server is not None else None
        return dm.distributed if dm is not None else None

    def resident_itemsize(self, column: str) -> int:
        """Itemsize of ``column`` as it sits across the mesh now (a cache
        hit after warm-up: the mesh program reads this array)."""
        return int(self._mesh_table().device_col(column).dtype.itemsize)

    def describe(self, sql: str) -> str:
        """What the server ran for ``sql``: the mesh dispatch (devices,
        local segments, route, capacity) or, after a fallback, the
        per-segment dispatch spans, from the tree EXPLAIN ANALYZE brings
        back."""
        rows = self._warm.execute("EXPLAIN ANALYZE " + sql + WARM_OPTION).rows
        keep = ("mesh_dispatch", "overflow_retry", "ragged_dispatch",
                "vmap_dispatch", "segment_kernel",
                "segmented_compact_dispatch", "segment_host",
                "group_overflow_retry")
        return "; ".join(f"{node}[{detail}]" for node, _i, _p, _ms, detail
                         in rows if node in keep)


def start(config: dict, seg_dirs: List[str], work_dir: str) -> ServedMesh:
    return ServedMesh(seg_dirs, work_dir, int(config["chips"]))
