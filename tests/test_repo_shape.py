"""Guards on the repo's shape, so removed debt does not grow back, and
the direct tests of the test corpus (pinot_tpu/tools/corpus.py).

- one benchmark harness: ``benchmark/run.py``. No ``bench*.py`` at the
  repo root, and nothing outside ``benchmark/`` imports one;
- the ``PINOT_*`` environment names in tracked Python are exactly the
  checked-in list (tests/resources/pinot_env_names.txt), and that list
  only shrinks (ROADMAP D7);
- the corpus's data is the bytes it has always been (sha256 of the
  seeded columns, taken from the generators before they moved), and
  each public function without a test elsewhere has a case here.
"""
import hashlib
import os
import re
import subprocess

import numpy as np
import pytest

from pinot_tpu.query import sql as psql
from pinot_tpu.segment.builder import Categorical
from pinot_tpu.spi import DataType, FieldType
from pinot_tpu.tools import corpus

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OLD_HARNESS = ("bench", "bench_common", "bench_taxi", "bench_vector",
               "bench_ingest")


def _tracked_python():
    """The repo's own ``*.py`` files, paths relative to it: what git
    tracks, or, in a checkout that is no git repository (it then holds
    the committed files only), every one outside dot-directories."""
    try:
        out = subprocess.run(["git", "ls-files", "*.py"], cwd=REPO,
                             capture_output=True, text=True, timeout=60,
                             check=True).stdout.splitlines()
    except (OSError, subprocess.SubprocessError):
        out = []
        for root, dirs, files in os.walk(REPO):
            dirs[:] = [d for d in dirs if not d.startswith(".")
                       and d not in ("chiprun_out", "__pycache__")]
            out += [os.path.relpath(os.path.join(root, f), REPO)
                    for f in files if f.endswith(".py")]
    return [p for p in out if os.path.exists(os.path.join(REPO, p))]


def _read(path):
    with open(os.path.join(REPO, path), encoding="utf-8") as fh:
        return fh.read()


def test_one_benchmark_harness():
    files = _tracked_python()
    at_root = [p for p in files
               if "/" not in p and p.startswith("bench")]
    assert not at_root, f"benchmark harnesses beside benchmark/: {at_root}"
    pat = re.compile(r"^\s*(?:import|from)\s+(%s)\b(?!\.)"
                     % "|".join(OLD_HARNESS), re.M)
    importers = {p: sorted(set(pat.findall(_read(p))))
                 for p in files if not p.startswith("benchmark/")}
    importers = {p: m for p, m in importers.items() if m}
    assert not importers, f"imports of the retired harness: {importers}"


def test_pinot_env_names_only_shrink():
    listed = _read("tests/resources/pinot_env_names.txt").split()
    assert listed == sorted(set(listed)), "keep the list sorted, unique"
    found = set()
    for p in _tracked_python():
        found.update(re.findall(r"PINOT_[A-Z0-9_]+", _read(p)))
    added = sorted(found - set(listed))
    gone = sorted(set(listed) - found)
    assert not added and not gone, (
        f"PINOT_* names in tracked Python differ from "
        f"tests/resources/pinot_env_names.txt ({len(listed)} names): "
        f"new {added}, no longer used {gone}. The list only shrinks: "
        f"delete a name that went; a new knob needs two callers that "
        f"need different values (ROADMAP D7), or it is a constant.")


# ---------------------------------------------------------------------------
# the corpus: one case a public function
# ---------------------------------------------------------------------------

def _sha(cols):
    h = hashlib.sha256()
    for name, v in cols.items():
        a = v.codes if isinstance(v, Categorical) else v
        h.update(name.encode())
        h.update(str(a.dtype).encode())
        h.update(np.ascontiguousarray(a).tobytes())
        if isinstance(v, Categorical):
            h.update("\0".join(map(str, v.values)).encode())
    return h.hexdigest()


def _case_ssb_columns(_tmp_path):
    # byte for byte what bench.gen_columns drew (hashes taken at the
    # parent of the PR that moved it)
    cols = corpus.ssb_columns(4096)
    assert _sha(cols) == ("10c187bb6d75f3f54192de54071fc896"
                          "f238c2220fa13e863c2606e0b907aa79")
    assert _sha(corpus.ssb_columns(4096, seed=(7, 3))) == (
        "bddd55bcb0a56200e3ce345b0f0940c1159237ddb77bf332ab747edbd61388f5")
    # the hierarchies the SSB spec fixes
    assert np.array_equal(cols["s_city"].codes // 10, cols["s_nation"].codes)
    assert np.array_equal(cols["c_nation"].codes // 5, cols["c_region"].codes)
    assert np.array_equal(cols["p_brand1"].codes // 40,
                          cols["p_category"].codes)
    assert np.array_equal(cols["p_category"].codes // 5, cols["p_mfgr"].codes)
    assert np.array_equal(cols["d_yearmonthnum"] // 100, cols["d_year"])


def _case_ssb_fields(_tmp_path):
    cols = corpus.ssb_columns(64)
    fields = corpus.ssb_fields(cols)
    assert [f.name for f in fields] == list(cols)
    by = {f.name: f for f in fields}
    metrics = {n for n, f in by.items() if f.field_type == FieldType.METRIC}
    assert metrics == {"lo_extendedprice", "lo_revenue", "lo_supplycost"}
    strings = {n for n, f in by.items() if f.data_type == DataType.STRING}
    assert strings == {n for n, v in cols.items()
                       if isinstance(v, Categorical)}
    assert by["lo_quantity"].field_type == FieldType.DIMENSION
    assert by["d_year"].data_type == DataType.INT


def _spec_of(stmt):
    """(preds, value_expr, group_cols) read back from a parsed
    statement: the inverse of ``corpus.spec_to_sql``."""
    def pred(node):
        if isinstance(node, psql.Between):
            return (node.expr.name, "between",
                    (node.lo.value, node.hi.value))
        if isinstance(node, psql.BoolOr):
            cols = {c.lhs.name for c in node.children}
            assert len(cols) == 1 and all(c.op == "=="
                                          for c in node.children)
            return (cols.pop(), "in",
                    tuple(c.rhs.value for c in node.children))
        op = {"==": "eq", "<": "lt"}[node.op]
        return (node.lhs.name, op, node.rhs.value)

    where = stmt.where
    conjuncts = where.children if isinstance(where, psql.BoolAnd) \
        else (where,)
    (arg,) = stmt.select[-1].expr.args
    vexpr = (arg.name,) if isinstance(arg, psql.Identifier) \
        else (arg.lhs.name, arg.op, arg.rhs.name)
    return ([pred(c) for c in conjuncts], vexpr,
            [g.name for g in stmt.group_by])


def _case_spec_to_sql(_tmp_path):
    for qid, preds, vexpr, gcols in corpus.SSB_QUERIES:
        stmt = psql.parse_sql(corpus.spec_to_sql(preds, vexpr, gcols))
        assert stmt.table == "lineorder", qid
        assert stmt.select[-1].expr.name == "sum", qid
        assert _spec_of(stmt) == (preds, vexpr, gcols), qid
        assert [s.expr.name for s in stmt.select[:-1]] == gcols, qid
        assert [o.expr.name for o in stmt.order_by] == gcols, qid
        assert stmt.limit == (100000 if gcols else None), qid
    assert len(corpus.SSB_QUERIES) == 13


def _case_digest(_tmp_path):
    rows = [("b", np.int64(2), 3.0), ("a", 1, np.int32(7))]
    want = [("a", 1, 7), ("b", 2, 3)]
    assert corpus.digest(rows) == want
    assert corpus.digest(reversed(rows)) == want      # order-insensitive
    assert corpus.digest([list(r) for r in rows]) == want
    got = corpus.digest(rows)
    assert all(type(x) in (str, int) for r in got for x in r)
    assert corpus.digest([(np.str_("x"), True)]) == [("x", 1)]
    assert corpus.digest([]) == []
    # a multiset: duplicates are kept
    assert corpus.digest([(1,), (1,)]) == [(1,), (1,)]


def _case_ssb_oracle(tmp_path):
    # the oracle against a loop over the decoded rows, one spec of each
    # predicate kind (eq, in, between, lt; no group, grouped, '-')
    seg = corpus.build_ssb_segment(2048, str(tmp_path))
    cols = {n: (np.asarray(v.values, dtype=object)[v.codes]
                if isinstance(v, Categorical) else v)
            for n, v in corpus.ssb_columns(2048).items()}
    ops = {"eq": lambda x, v: x == v, "lt": lambda x, v: x < v,
           "in": lambda x, v: x in v,
           "between": lambda x, v: v[0] <= x <= v[1]}
    by_id = {q[0]: q for q in corpus.SSB_QUERIES}
    for qid in ("q1.1", "q2.2", "q4.2"):
        _, preds, vexpr, gcols = by_id[qid]
        acc = {}
        for i in range(2048):
            if all(ops[op](cols[c][i], v) for c, op, v in preds):
                val = int(cols[vexpr[0]][i])
                if len(vexpr) == 3:
                    b = int(cols[vexpr[2]][i])
                    val = val * b if vexpr[1] == "*" else val - b
                key = tuple(cols[g][i] for g in gcols)
                acc[key] = acc.get(key, 0) + val
        want = [k + (v,) for k, v in acc.items()] if gcols \
            else [(sum(acc.values()),)]
        got = corpus.ssb_oracle(seg, preds, vexpr, gcols)
        assert corpus.digest(got) == corpus.digest(want), qid


def _case_taxi_columns(_tmp_path):
    cols = corpus.taxi_columns(4096)
    assert _sha(cols) == ("331494da94048edcb3b5542b18381627"
                          "8f84387bcbe901787d0fd3b8ad5256cb")
    assert int(cols["pu_loc"].max()) < corpus.N_ZONES
    assert int(cols["hc_key"].max()) < corpus.HC_CARD


def _case_taxi_sql(_tmp_path):
    for qid, key, where in corpus.TAXI_QUERIES:
        stmt = psql.parse_sql(corpus.taxi_sql(key, where))
        assert stmt.table == "trips" and stmt.limit == 200000, qid
        assert [g.name for g in stmt.group_by] == [key], qid
        assert [s.expr.name for s in stmt.select] == [key, "count", "avg"]
        assert (stmt.where is None) == (where is None), qid


def _case_taxi_oracle(tmp_path):
    seg = corpus.build_taxi_segment(2048, str(tmp_path))
    cols = corpus.taxi_columns(2048)
    for _qid, key, where in corpus.TAXI_QUERIES:
        keep = np.ones(2048, dtype=bool) if where is None else \
            cols["passengers"] >= 2 if where.startswith("passengers") \
            else cols["distance"] < 1500
        want = {}
        for k, f in zip(cols[key][keep], cols["fare"][keep]):
            n, s = want.get(int(k), (0, 0))
            want[int(k)] = (n + 1, s + int(f))
        got = corpus.taxi_oracle(seg, key, where)
        assert set(got) == set(want)
        for k, (n, s) in want.items():
            assert got[k][0] == n and got[k][1] == pytest.approx(s / n)


CORPUS_CASES = {
    "ssb_columns": _case_ssb_columns,
    "ssb_fields": _case_ssb_fields,
    "spec_to_sql": _case_spec_to_sql,
    "digest": _case_digest,
    "ssb_oracle": _case_ssb_oracle,
    "taxi_columns": _case_taxi_columns,
    "taxi_sql": _case_taxi_sql,
    "taxi_oracle": _case_taxi_oracle,
}


@pytest.mark.parametrize("name", sorted(CORPUS_CASES))
def test_corpus_function(name, tmp_path):
    CORPUS_CASES[name](tmp_path)


def test_corpus_cases_cover_the_public_functions():
    public = {n for n, v in vars(corpus).items()
              if callable(v) and not n.startswith("_")
              and getattr(v, "__module__", None) == corpus.__name__}
    # the two segment builders are what test_ssb.py and test_taxi.py
    # (and the oracle cases here) stand on
    assert public - set(CORPUS_CASES) == {"build_ssb_segment",
                                          "build_taxi_segment"}
