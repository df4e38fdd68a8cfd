"""gRPC data plane: streaming query Submit + client-streamed mailbox
delivery (reference server.proto:25 / mailbox.proto:25 analogs; see
protos/server.proto for the wire contract).
"""
import numpy as np
import pytest

pytest.importorskip("grpc")

from pinot_tpu.cluster import Controller, ServerNode
from pinot_tpu.cluster.grpc_plane import mailbox_send, submit_stream
from pinot_tpu.engine.reduce import reduce_partials
from pinot_tpu.multistage.dispatch import encode_mailbox_frame
from pinot_tpu.multistage.relation import Relation
from pinot_tpu.query.context import build_query_context
from pinot_tpu.query.sql import parse_sql
from pinot_tpu.segment import SegmentBuilder
from pinot_tpu.spi import (DataType, FieldSpec, FieldType, Schema,
                           TableConfig)

N_SEGMENTS = 3
ROWS = 400


@pytest.fixture
def cluster(tmp_path):
    ctrl = Controller(str(tmp_path / "ctrl"), heartbeat_timeout=2.0,
                      reconcile_interval=0.1)
    server = ServerNode("server_0", ctrl.url, poll_interval=0.1)
    rng = np.random.default_rng(5)
    schema = Schema("g", [
        FieldSpec("k", DataType.STRING, FieldType.DIMENSION),
        FieldSpec("v", DataType.INT, FieldType.METRIC),
    ])
    ctrl.add_table("g", schema.to_dict(), replication=1)
    data = {"k": [], "v": []}
    for i in range(N_SEGMENTS):
        cols = {"k": rng.choice(["a", "b"], ROWS),
                "v": rng.integers(0, 100, ROWS).astype(np.int32)}
        d = SegmentBuilder(schema, TableConfig("g")).build(
            cols, str(tmp_path / "seg"), f"seg_{i}")
        ctrl.add_segment("g", f"seg_{i}", d)
        data["k"].append(cols["k"])
        data["v"].append(cols["v"])
    import time
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if server._tables.get("g") is not None and \
                len(server._tables["g"].acquire_segments()) == N_SEGMENTS:
            break
        time.sleep(0.05)
    yield server, {k: np.concatenate(v) for k, v in data.items()}
    server.stop()
    ctrl.stop()


def test_streaming_submit(cluster):
    server, data = cluster
    assert server.grpc_port, "gRPC plane must be up"
    sql = "SELECT k, SUM(v), COUNT(*) FROM g GROUP BY k ORDER BY k LIMIT 5"
    header, partials = submit_stream(f"127.0.0.1:{server.grpc_port}", sql)
    assert header["segmentsQueried"] == N_SEGMENTS
    # one streamed block per segment: the server combines the
    # statement's group-by segments, so the first block holds every
    # group and each other block is empty
    assert len(partials) == N_SEGMENTS
    assert [len(p.groups) for p in partials] == [2] + [0] * (N_SEGMENTS - 1)
    ctx = build_query_context(parse_sql(sql))
    result = reduce_partials(ctx, partials)
    exp = [(k, int(data["v"][data["k"] == k].sum()),
            int((data["k"] == k).sum())) for k in ("a", "b")]
    assert [tuple(r) for r in result.rows] == exp


def test_grpc_mailbox_delivery(cluster):
    server, _ = cluster
    rel = Relation({"x": np.arange(4)}, {}, "t")
    frames = [encode_mailbox_frame("q1", 7, 0, rel),
              encode_mailbox_frame("q1", 7, 0, None)]
    delivered = mailbox_send(f"127.0.0.1:{server.grpc_port}", frames)
    assert delivered == 2
    blocks = server.mailboxes.mailbox("q1", 7, 0).drain(timeout=5)
    assert len(blocks) == 1
    assert blocks[0].data["x"].tolist() == [0, 1, 2, 3]
