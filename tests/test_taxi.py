"""NYC-taxi specs at CI scale vs the numpy oracle (BASELINE.md config
4)."""
import pytest

from pinot_tpu.tools import corpus

N = 1 << 15


@pytest.fixture(scope="module")
def setup(tmp_path_factory, monkeypatch=None):
    seg = corpus.build_taxi_segment(N, str(tmp_path_factory.mktemp("taxi")))
    from pinot_tpu.broker import Broker
    from pinot_tpu.server import TableDataManager

    dm = TableDataManager("trips")
    dm.add_segment(seg)
    b = Broker()
    b.register_table(dm)
    return seg, b


@pytest.mark.parametrize("qid,key,where", corpus.TAXI_QUERIES,
                         ids=[q[0] for q in corpus.TAXI_QUERIES])
def test_taxi_query(setup, qid, key, where):
    seg, b = setup
    sql = corpus.taxi_sql(key, where)
    oracle = corpus.taxi_oracle(seg, key, where)
    res = b.query(sql + corpus.OPTION)
    got = {int(r[0]): (int(r[1]), float(r[2])) for r in res.rows}
    assert set(got) == set(oracle)
    for k, (c, a) in oracle.items():
        assert got[k][0] == c
        assert abs(got[k][1] - a) <= 1e-6 * max(1.0, abs(a))

    from pinot_tpu.query.context import build_query_context
    from pinot_tpu.query.planner import SegmentPlanner
    from pinot_tpu.query.sql import parse_sql
    plan = SegmentPlanner(build_query_context(parse_sql(sql)), seg).plan()
    assert plan.kind == "kernel", f"{qid} planned {plan.kind}"
