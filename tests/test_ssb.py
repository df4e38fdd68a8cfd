"""SSB suite correctness at CI scale: all 13 north-star queries against
the numpy oracle.

Reference test strategy analog: SSBQueryIntegrationTest.java:46-96 diffs
the 13 queries against H2; here the oracle is corpus.ssb_oracle (numpy on
dict ids) and the scale is tiny so the suite stays fast. chip_smoke.py
runs the same specs at full size on the chip.
"""
import pytest

from pinot_tpu.tools import corpus

N = 1 << 14


@pytest.fixture(scope="module")
def ssb(tmp_path_factory):
    seg = corpus.build_ssb_segment(N, str(tmp_path_factory.mktemp("ssb")))
    from pinot_tpu.broker import Broker
    from pinot_tpu.server import TableDataManager

    dm = TableDataManager("lineorder")
    dm.add_segment(seg)
    broker = Broker()
    broker.register_table(dm)
    return seg, broker


@pytest.mark.parametrize("qid,preds,vexpr,gcols",
                         corpus.SSB_QUERIES,
                         ids=[q[0] for q in corpus.SSB_QUERIES])
def test_ssb_query(ssb, qid, preds, vexpr, gcols):
    seg, broker = ssb
    sql = corpus.spec_to_sql(preds, vexpr, gcols)
    expected = corpus.ssb_oracle(seg, preds, vexpr, gcols)
    res = broker.query(sql + corpus.OPTION)
    assert corpus.digest(res.rows) == corpus.digest(expected)

    # every SSB query must run on the device kernel path — never host
    from pinot_tpu.query.context import build_query_context
    from pinot_tpu.query.planner import SegmentPlanner
    from pinot_tpu.query.sql import parse_sql

    plan = SegmentPlanner(build_query_context(parse_sql(sql)), seg).plan()
    assert plan.kind == "kernel", f"{qid} planned {plan.kind}"
