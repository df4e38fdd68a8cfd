"""Static analysis gate (pinot_tpu/analysis + tools/check_static.py).

Three surfaces, mirroring the tier-1 contract:

- the plan-IR verifier runs CLEAN over every plan the planner produces
  for the full SSB + taxi + fuzzer query corpus (zero diagnostics);
- each verifier rule id demonstrably FIRES on a targeted negative plan
  (out-of-range col index, unhashable node, overflowing SUM carrier,
  misaligned slots_cap, sketch-on-compact, ...);
- the JAX hazard linter's repo findings exactly match the checked-in
  ratchet baseline (tools/jaxlint_baseline.json) — new findings or
  stale counts fail loudly, and the check_static CLI exits non-zero.
"""
import dataclasses
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

from pinot_tpu.analysis import jaxlint  # noqa: E402
from pinot_tpu.analysis.plan_verify import (  # noqa: E402
    PlanVerificationError, verify_compiled_plan, verify_kernel_plan,
    verify_select_plan)
from pinot_tpu.ops.ir import (AggSpec, Col, EqId, InSet,  # noqa: E402
                              KernelPlan, Lit, SelectPlan, TrueP)
from pinot_tpu.query.context import build_query_context  # noqa: E402
from pinot_tpu.query.planner import SegmentPlanner  # noqa: E402
from pinot_tpu.query.sql import parse_sql  # noqa: E402
from pinot_tpu.tools import corpus  # noqa: E402


def _rules(diags):
    return {d.rule for d in diags}


def _plan(seg, sql):
    return SegmentPlanner(build_query_context(parse_sql(sql)), seg).plan()


# ---------------------------------------------------------------------------
# corpus regression: plan -> verify with zero diagnostics
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ssb_segment(tmp_path_factory):
    return corpus.build_ssb_segment(1 << 12,
                                    str(tmp_path_factory.mktemp("sa_ssb")))


@pytest.fixture(scope="module")
def taxi_segment(tmp_path_factory):
    return corpus.build_taxi_segment(
        1 << 12, str(tmp_path_factory.mktemp("sa_taxi")))


@pytest.mark.parametrize("qid,preds,vexpr,gcols", corpus.SSB_QUERIES,
                         ids=[q[0] for q in corpus.SSB_QUERIES])
def test_ssb_plans_verify_clean(ssb_segment, qid, preds, vexpr, gcols):
    sql = corpus.spec_to_sql(preds, vexpr, gcols) + corpus.OPTION
    plan = _plan(ssb_segment, sql)   # plan() itself fail-fasts too
    assert verify_compiled_plan(plan) == []


@pytest.mark.parametrize("qid,key,where", corpus.TAXI_QUERIES,
                         ids=[q[0] for q in corpus.TAXI_QUERIES])
def test_taxi_plans_verify_clean(taxi_segment, qid, key, where):
    sql = corpus.taxi_sql(key, where) + corpus.OPTION
    plan = _plan(taxi_segment, sql)
    assert verify_compiled_plan(plan) == []


def test_fuzzer_plans_verify_clean(tmp_path):
    from pinot_tpu.tools.fuzzer import (QueryGenerator,
                                        build_fuzz_segment, render_sql)
    seg = build_fuzz_segment(1500, str(tmp_path))
    gen = QueryGenerator(4242, with_exists=False)
    kernels = 0
    for _ in range(80):
        sql = render_sql(gen.generate())
        plan = _plan(seg, sql)
        assert verify_compiled_plan(plan) == [], sql
        kernels += plan.kind in ("kernel", "kselect")
    assert kernels > 10   # the corpus must actually exercise the verifier


# ---------------------------------------------------------------------------
# negative tests: each rule id fires on a targeted bad plan
# ---------------------------------------------------------------------------

def test_pv101_col_index_out_of_range():
    p = KernelPlan(pred=EqId(col=5, param=0),
                   aggs=(AggSpec("count", None, True),))
    diags = verify_kernel_plan(p, n_cols=2, n_params=1)
    assert "PV101" in _rules(diags)


def test_pv102_param_index_out_of_range():
    p = KernelPlan(pred=TrueP(),
                   aggs=(AggSpec("sum", Lit(7), True),))
    diags = verify_kernel_plan(p, n_cols=1, n_params=1)
    assert "PV102" in _rules(diags)


def test_pv103_unhashable_plan_node():
    # a list where the frozen-tuple contract demands a tuple poisons the
    # plan-cache key (hash() raises at runtime, on every query)
    p = KernelPlan(pred=TrueP(), aggs=(AggSpec("count", None, True),),
                   group_keys=[(0, 4)])
    diags = verify_kernel_plan(p)
    assert "PV103" in _rules(diags)


def test_pv104_lossy_bits_claim(ssb_segment):
    sql = ("SELECT SUM(lo_extendedprice) FROM lineorder "
           "WHERE lo_discount BETWEEN 1 AND 3")
    cp = _plan(ssb_segment, sql)
    assert cp.kind == "kernel"
    assert verify_compiled_plan(cp) == []
    spec = cp.kernel_plan.aggs[0]
    assert spec.kind == "sum" and spec.integral
    # corrupt the claimed magnitude bound below what column metadata
    # proves: the int32 carrier / limb decomposition would truncate
    cp.kernel_plan = dataclasses.replace(
        cp.kernel_plan, aggs=(dataclasses.replace(spec, bits=2),))
    assert "PV104" in _rules(verify_compiled_plan(cp))


def test_pv104_carrier_scope(monkeypatch):
    """The carrier-existence check only covers the compact path (the
    one that narrows through sum_carrier_dtype) and keeps the bits=63
    unprofiled-sentinel exemption — dense plans must not hard-fail on
    platforms without a 64-bit carrier."""
    import pinot_tpu.ops.kernels as K
    monkeypatch.setattr(K, "sum_carrier_dtype", lambda bits: None)
    dense = KernelPlan(pred=TrueP(),
                       aggs=(AggSpec("sum", Col(1), True, bits=40),),
                       group_keys=((0, 8),), strategy="dense")
    assert "PV104" not in _rules(verify_kernel_plan(dense, n_cols=2,
                                                    n_params=0))
    compact = dataclasses.replace(dense, strategy="compact")
    assert "PV104" in _rules(verify_kernel_plan(compact, n_cols=2,
                                                n_params=0))
    # the bits=63 sentinel fires too: _payload_columns refuses to build
    # a carrier-less compact sum (ValueError), so the verifier must
    # catch the identical set at plan time
    sentinel = dataclasses.replace(
        compact, aggs=(AggSpec("sum", Col(1), True, bits=63),))
    assert "PV104" in _rules(verify_kernel_plan(sentinel, n_cols=2,
                                                n_params=0))


def test_pv105_sum_accumulator_overflow():
    # a PROVEN 45-bit value summed over 2^20 rows needs 65 bits > int64
    p = KernelPlan(pred=TrueP(),
                   aggs=(AggSpec("sum", Col(0), True, bits=45),))
    diags = verify_kernel_plan(p, n_cols=1, n_params=0, n_docs=1 << 20)
    assert "PV105" in _rules(diags)
    # advisory severity: the overflow wraps in lockstep with the numpy
    # oracle, so PV105 warns (check_static reports it) but must never
    # kill a query through the planner fail-fast
    assert all(d.severity == "warn" for d in diags if d.rule == "PV105")
    # the unprofiled sentinel (bits=63) wraps like the numpy oracle and
    # is exempt by design
    p63 = KernelPlan(pred=TrueP(),
                     aggs=(AggSpec("sum", Col(0), True, bits=63),))
    assert "PV105" not in _rules(
        verify_kernel_plan(p63, n_cols=1, n_params=0, n_docs=1 << 20))


def _compact_plan():
    return KernelPlan(pred=TrueP(),
                      aggs=(AggSpec("sum", Col(1), True, bits=20),),
                      group_keys=((0, 64),), strategy="compact")


def test_pv106_misaligned_slots_cap():
    p = _compact_plan()
    ok = verify_kernel_plan(p, n_cols=2, n_params=0, bucket=1 << 16,
                            n_docs=1 << 16, slots_cap=64)
    assert "PV106" not in _rules(ok)
    # 384 is neither a power of two, the Pallas staging floor, nor
    # full_slots_cap: off the quantization ladder -> retrace hazard
    diags = verify_kernel_plan(p, n_cols=2, n_params=0, bucket=1 << 16,
                               n_docs=1 << 16, slots_cap=384)
    assert "PV106" in _rules(diags)
    # capacity past the can't-overflow bound is pure waste
    diags = verify_kernel_plan(p, n_cols=2, n_params=0, bucket=1 << 16,
                               n_docs=1 << 16, slots_cap=1 << 20)
    assert "PV106" in _rules(diags)
    # slots_cap on the dense strategy is meaningless
    dense = dataclasses.replace(p, strategy="dense")
    diags = verify_kernel_plan(dense, n_cols=2, n_params=0,
                               bucket=1 << 16, slots_cap=64)
    assert "PV106" in _rules(diags)


def test_pv106_cost_model_consistency():
    from pinot_tpu.multistage.costs import compact_slots_cap
    from pinot_tpu.ops.kernels import cpu_scatter_default
    import jax
    plat = jax.default_backend()
    p = _compact_plan()
    good = compact_slots_cap(1 << 16, 0.05, plat, cpu_scatter_default(plat))
    assert "PV106" not in _rules(verify_kernel_plan(
        p, n_cols=2, n_params=0, bucket=1 << 16, n_docs=1 << 16,
        slots_cap=good, est_selectivity=0.05))
    # a capacity the cost model would never emit for this estimate
    bad = good * 4
    diags = verify_kernel_plan(
        p, n_cols=2, n_params=0, bucket=1 << 16, n_docs=1 << 16,
        slots_cap=bad, est_selectivity=0.05)
    assert "PV106" in _rules(diags)


def test_pv107_sketch_never_reaches_compact():
    p = KernelPlan(
        pred=TrueP(),
        aggs=(AggSpec("distinct_count_hll", Col(1), False, card=11),),
        group_keys=((0, 64),), strategy="compact")
    diags = verify_kernel_plan(p, n_cols=2, n_params=0)
    assert "PV107" in _rules(diags)


def test_pv107_dense_space_cap():
    from pinot_tpu.query.planner import MAX_DENSE_GROUPS
    p = KernelPlan(pred=TrueP(), aggs=(AggSpec("count", None, True),),
                   group_keys=((0, MAX_DENSE_GROUPS + 1),),
                   strategy="dense")
    assert "PV107" in _rules(verify_kernel_plan(p, n_cols=1, n_params=0))


def test_pv108_bad_agg_spec():
    p = KernelPlan(pred=TrueP(),
                   aggs=(AggSpec("median", Col(0), False),))
    assert "PV108" in _rules(verify_kernel_plan(p, n_cols=1, n_params=0))
    p = KernelPlan(pred=TrueP(),
                   aggs=(AggSpec("distinct_count_hll", Col(0), False,
                                 card=27),))
    assert "PV108" in _rules(verify_kernel_plan(p, n_cols=1, n_params=0))


def test_pv109_inset_not_pow2():
    p = KernelPlan(pred=InSet(col=0, param=0, n=3),
                   aggs=(AggSpec("count", None, True),))
    assert "PV109" in _rules(verify_kernel_plan(p, n_cols=1, n_params=1))


def test_pv110_zero_cardinality_key():
    p = KernelPlan(pred=TrueP(), aggs=(AggSpec("count", None, True),),
                   group_keys=((0, 0),))
    assert "PV110" in _rules(verify_kernel_plan(p, n_cols=1, n_params=0))


def test_pv111_inset_param_unsorted():
    p = KernelPlan(pred=InSet(col=0, param=0, n=4),
                   aggs=(AggSpec("count", None, True),))
    diags = verify_kernel_plan(
        p, n_cols=1, n_params=1,
        params=[np.asarray([4, 1, 3, 9], dtype=np.int32)])
    assert "PV111" in _rules(diags)


def test_pv112_select_plan():
    sp = SelectPlan(pred=TrueP(), select_cols=(0,), order=(), k=0)
    assert "PV112" in _rules(verify_select_plan(sp, n_cols=1, n_params=0))
    sp = SelectPlan(pred=TrueP(), select_cols=(0,),
                    order=((0, False, 1 << 40), (1, False, 1 << 40)),
                    k=10)
    assert "PV112" in _rules(
        verify_select_plan(sp, n_cols=2, n_params=0, bucket=1 << 14))


# ---------------------------------------------------------------------------
# wiring: planner fail-fast + plan-cache debug assertion
# ---------------------------------------------------------------------------

def test_planner_fail_fast(ssb_segment, monkeypatch):
    sql = "SELECT COUNT(*) FROM lineorder WHERE lo_discount = 1"
    ctx = build_query_context(parse_sql(sql))
    planner = SegmentPlanner(ctx, ssb_segment)
    good = planner._plan()
    assert good.kind == "kernel"
    bad = dataclasses.replace(
        good.kernel_plan,
        pred=EqId(col=99, param=0))      # out-of-bounds column
    monkeypatch.setattr(SegmentPlanner, "_plan",
                        lambda self: good)
    good.kernel_plan = bad
    with pytest.raises(PlanVerificationError) as ei:
        SegmentPlanner(ctx, ssb_segment).plan()
    assert "PV101" in str(ei.value)
    # kill switch: PINOT_PLAN_VERIFY=0 must disable the gate
    monkeypatch.setenv("PINOT_PLAN_VERIFY", "0")
    assert SegmentPlanner(ctx, ssb_segment).plan() is good


def test_warn_severity_never_fails_fast(monkeypatch):
    from pinot_tpu.analysis import plan_verify as PV
    monkeypatch.setattr(
        PV, "verify_compiled_plan",
        lambda cp: [PV.Diagnostic("PV105", "aggs[0]", "advisory",
                                  severity="warn")])
    PV.check_compiled_plan(object())   # warn-only: must not raise
    monkeypatch.setattr(
        PV, "verify_compiled_plan",
        lambda cp: [PV.Diagnostic("PV101", "pred", "broken")])
    with pytest.raises(PlanVerificationError):
        PV.check_compiled_plan(object())


def test_ir_range_mirrors_planner_range(ssb_segment, tmp_path):
    """Drift tripwire (PV104b): the verifier's IR interval arithmetic
    must derive exactly the bits/sign the planner claimed from the SQL
    AST over real segment metadata — if planner._range_of ever tightens
    without _ir_range following, PV104 would start killing valid
    plans. Covers Col, Lit, Bin(+/-/*), and the MvReduce modes."""
    from pinot_tpu.analysis import plan_verify as PV
    from pinot_tpu.tools.fuzzer import build_fuzz_segment
    fz = build_fuzz_segment(800, str(tmp_path))
    cases = [
        (ssb_segment, "SELECT SUM(lo_extendedprice) FROM lineorder"),
        (ssb_segment,
         "SELECT SUM(lo_extendedprice * lo_discount) FROM lineorder"),
        (ssb_segment,
         "SELECT SUM(lo_extendedprice - lo_quantity) FROM lineorder"),
        (ssb_segment, "SELECT SUM(lo_quantity + 7) FROM lineorder"),
        (fz, "SELECT SUMMV(mv) FROM fz"),
        (fz, "SELECT COUNTMV(mv) FROM fz"),
        (fz, "SELECT AVG(m1) FROM fz WHERE ci = 3"),
    ]
    checked = 0
    for seg, sql in cases:
        cp = _plan(seg, sql)
        assert cp.kind == "kernel", sql
        for spec in cp.kernel_plan.aggs:
            if spec.kind not in ("sum", "avg") or not spec.integral:
                continue
            ctx = PV._Ctx(len(cp.col_names), len(cp.params), cp.params,
                          cp.col_names, cp.segment)
            rng = PV._ir_range(spec.value, ctx)
            bits, signed = SegmentPlanner._bits_for(rng)
            assert (bits, signed) == (spec.bits, spec.signed), sql
            checked += 1
    assert checked >= 6


def test_plan_cache_debug_assertion():
    from pinot_tpu.ops.plan_cache import KernelPlanCache
    cache = KernelPlanCache(maxsize=4)
    bad = KernelPlan(
        pred=TrueP(),
        aggs=(AggSpec("distinct_count_hll", Col(0), False, card=11),),
        group_keys=((0, 8),), strategy="compact")
    with pytest.raises(AssertionError) as ei:
        cache.entry(bad, bucket=1 << 10)
    assert "PV107" in str(ei.value)


# ---------------------------------------------------------------------------
# linter rules (synthetic sources) + repo baseline pin
# ---------------------------------------------------------------------------

HOT = "pinot_tpu/engine/somehot.py"


def _keys(findings):
    return {(f.rule, f.line) for f in findings}


def test_lint_host_sync_rule():
    src = ("import numpy as np\n"
           "def f(dev):\n"
           "    a = dev.item()\n"
           "    b = np.asarray(dev)\n"
           "    c = int(dev['x'])\n"
           "    d = int(n_docs)\n")
    fs = jaxlint.lint_source(src, HOT)
    assert {f.line for f in fs if f.rule == "host-sync"} == {3, 4, 5}
    # cold paths (broker, cluster, ...) are out of rule scope
    assert jaxlint.lint_source(src, "pinot_tpu/broker/x.py") == []
    # allowlisted host modules too
    assert jaxlint.lint_source(src, jaxlint.HOST_SYNC_ALLOW[0]) == []


def test_lint_suppression_comment():
    src = ("import numpy as np\n"
           "def f(host):\n"
           "    return np.asarray(host)  # jaxlint: ok host-sync\n")
    assert jaxlint.lint_source(src, HOT) == []


def test_lint_jit_in_loop():
    src = ("import jax\n"
           "def g(fns, x):\n"
           "    for fn in fns:\n"
           "        y = jax.jit(fn)(x)\n"
           "    return jax.jit(fns[0])\n")
    fs = jaxlint.lint_source(src, "pinot_tpu/broker/b.py")
    assert [(f.rule, f.line) for f in fs] == [("jit-in-loop", 4)]


def test_lint_nonstatic_trace():
    src = ("import jax, os\n"
           "@jax.jit\n"
           "def k(x):\n"
           "    flag = os.environ.get('KNOB')\n"
           "    return x\n"
           "def host():\n"
           "    return os.environ.get('KNOB')\n")
    fs = jaxlint.lint_source(src, "pinot_tpu/broker/b.py")
    assert [(f.rule, f.line) for f in fs] == [("nonstatic-trace", 4)]
    # np.random.* under trace fires exactly once (on the submodule node)
    src = ("import jax\nimport numpy as np\n"
           "@jax.jit\n"
           "def k(x):\n"
           "    return x + np.random.uniform()\n")
    fs = jaxlint.lint_source(src, "pinot_tpu/broker/b.py")
    assert [(f.rule, f.line) for f in fs] == [("nonstatic-trace", 5)]


def test_lint_parse_error_never_baselined(tmp_path):
    fs = jaxlint.lint_source("def broken(:\n", "pinot_tpu/broker/b.py")
    assert [f.rule for f in fs] == ["parse-error"]
    # --update-baseline must NOT grandfather it: the gate stays red
    path = str(tmp_path / "base.json")
    jaxlint.write_baseline(fs, path)
    new, _stale = jaxlint.compare_baseline(fs, jaxlint.load_baseline(path))
    assert [f.rule for f in new] == ["parse-error"]


def test_lint_unlocked_mutation():
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self.hits = 0\n"
           "    def a(self):\n"
           "        with self._lock:\n"
           "            self.hits += 1\n"
           "    def b(self):\n"
           "        self.hits += 1\n")
    fs = jaxlint.lint_source(src, "pinot_tpu/broker/b.py")
    assert [(f.rule, f.line, f.scope) for f in fs] == \
        [("unlocked-mutation", 10, "C.b")]


def test_lint_clean_on_shared_registries():
    """Satellite: the unlocked-mutation rule passes on the metrics and
    plan-cache counters (every mutation is under its lock)."""
    for mod in ("pinot_tpu/utils/metrics.py", "pinot_tpu/ops/plan_cache.py"):
        with open(os.path.join(REPO, mod)) as fh:
            src = fh.read()
        bad = [f for f in jaxlint.lint_source(src, mod)
               if f.rule == "unlocked-mutation"]
        assert bad == [], bad


def test_baseline_pinned():
    """Repo findings must exactly match the checked-in ratchet baseline:
    new findings fail (fix or consciously re-baseline), and counts that
    drop fail too (ratchet the baseline down so wins stick)."""
    findings = jaxlint.lint_tree(REPO)
    baseline = jaxlint.load_baseline(
        os.path.join(REPO, "tools", "jaxlint_baseline.json"))
    new, stale = jaxlint.compare_baseline(findings, baseline)
    assert new == [], "\n".join(str(f) for f in new)
    assert stale == [], stale


def test_baseline_compare_semantics():
    fs = jaxlint.lint_source(
        "import numpy as np\ndef f(d):\n    return np.asarray(d)\n", HOT)
    assert len(fs) == 1
    key = fs[0].key
    new, stale = jaxlint.compare_baseline(fs, {})
    assert [f.key for f in new] == [key] and stale == []
    new, stale = jaxlint.compare_baseline(fs, {key: 1})
    assert new == [] and stale == []
    new, stale = jaxlint.compare_baseline([], {key: 1})
    assert new == [] and stale == [(key, 1, 0)]


# ---------------------------------------------------------------------------
# the tier-1 CLI gate
# ---------------------------------------------------------------------------

def test_check_static_cli_runs_clean(capsys):
    import check_static
    assert check_static.main(["--fuzz", "40"]) == 0
    out = capsys.readouterr().out
    # the zero-diagnostic verdict must not be vacuous: every SSB+taxi
    # query planned onto the device path and was verified
    import json
    summary = json.loads(out.strip().splitlines()[-1])
    assert summary["verify"]["coverage_failures"] == 0
    assert summary["verify"]["device_plans"] >= \
        len(corpus.SSB_QUERIES) + len(corpus.TAXI_QUERIES)


def test_check_static_update_baseline_keeps_parse_errors_red(
        monkeypatch, tmp_path, capsys):
    import check_static
    broken = jaxlint.lint_source("def broken(:\n", "pinot_tpu/x.py")
    monkeypatch.setattr(check_static, "BASELINE",
                        str(tmp_path / "base.json"))
    monkeypatch.setattr(jaxlint, "lint_tree_ex",
                        lambda root: (broken, []))
    # the re-ratchet run itself must stay red on an unparseable module
    assert check_static.main(["--lint-only", "--update-baseline"]) == 1
    assert "parse-error" in capsys.readouterr().out


def test_check_static_env_restored(monkeypatch):
    import check_static
    monkeypatch.setenv("PINOT_PLAN_VERIFY", "0")
    check_static.run_verify(fuzz_n=3)
    assert os.environ.get("PINOT_PLAN_VERIFY") == "0"


def test_check_static_cli_fails_on_drift(monkeypatch, tmp_path, capsys):
    import check_static
    # an empty baseline turns every grandfathered finding into a NEW one
    empty = tmp_path / "baseline.json"
    empty.write_text('{"version": 1, "counts": {}}')
    monkeypatch.setattr(check_static, "BASELINE", str(empty))
    assert check_static.main(["--lint-only"]) == 1
    assert "NEW" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# concurrency verifier (analysis/concur.py, CC201-CC205)
# ---------------------------------------------------------------------------

from pinot_tpu.analysis import concur  # noqa: E402

CMOD = "pinot_tpu/cluster/somemod.py"


def _concur(src, path=CMOD):
    findings, _sup = concur.analyze_source(src, path)
    return findings


def _crules(findings):
    return {f.rule for f in findings}


def test_cc201_unlocked_mutation_site():
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self.hits = 0\n"
           "    def a(self):\n"
           "        with self._lock:\n"
           "            self.hits += 1\n"
           "    def b(self):\n"
           "        self.hits += 1\n")
    fs = _concur(src)
    assert [(f.rule, f.line, f.scope) for f in fs] == \
        [("CC201", 10, "C.b")]
    # __init__ is exempt: construction precedes sharing
    assert all(f.line != 5 for f in fs)


def test_cc201_read_under_different_lock():
    """The rollup-cursor shape: state mutated under lock A, served
    under lock B — neither lock excludes the other."""
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._a = threading.Lock()\n"
           "        self._b = threading.Lock()\n"
           "        self._d = {}\n"
           "    def writer(self, k, v):\n"
           "        with self._a:\n"
           "            self._d[k] = v\n"
           "    def reader(self):\n"
           "        with self._b:\n"
           "            return dict(self._d)\n")
    fs = _concur(src)
    assert [(f.rule, f.line, f.scope) for f in fs] == \
        [("CC201", 12, "C.reader")]
    assert "read under" in fs[0].message


def test_cc201_unguarded_ordereddict_lru():
    """The engine/batch._STACK_CACHE shape: a shared OrderedDict whose
    LRU ops (multi-step linked-list relinks, not GIL-atomic) run with
    no lock anywhere in sight."""
    src = ("from collections import OrderedDict\n"
           "_CACHE = OrderedDict()\n"
           "def get(key):\n"
           "    hit = _CACHE.get(key)\n"
           "    if hit is not None:\n"
           "        _CACHE.move_to_end(key)\n"
           "    return hit\n"
           "def put(key, v):\n"
           "    _CACHE[key] = v\n"
           "    while len(_CACHE) > 4:\n"
           "        _CACHE.popitem(last=False)\n")
    fs = _concur(src)
    assert [(f.rule, f.line) for f in fs] == \
        [("CC201", 6), ("CC201", 11)]
    assert "not GIL-atomic" in fs[0].message
    # the same LRU fully under a module lock is clean
    clean = ("from collections import OrderedDict\n"
             "import threading\n"
             "_CACHE = OrderedDict()\n"
             "_L = threading.Lock()\n"
             "def get(key):\n"
             "    with _L:\n"
             "        hit = _CACHE.get(key)\n"
             "        if hit is not None:\n"
             "            _CACHE.move_to_end(key)\n"
             "    return hit\n"
             "def put(key, v):\n"
             "    with _L:\n"
             "        _CACHE[key] = v\n"
             "        while len(_CACHE) > 4:\n"
             "            _CACHE.popitem(last=False)\n")
    assert _concur(clean) == []


def test_cc201_module_global_mixed_guard():
    """The manager._FRESHNESS_OWNERS shape: a module-global dict
    mutated under a lock at one site and without it at another."""
    src = ("import threading\n"
           "_OWNERS = {}\n"
           "class M:\n"
           "    def __init__(self):\n"
           "        self._stats_lock = threading.Lock()\n"
           "    def write(self, g):\n"
           "        with self._stats_lock:\n"
           "            _OWNERS[g] = id(self)\n"
           "    def stop(self, g):\n"
           "        if _OWNERS.get(g) == id(self):\n"
           "            _OWNERS.pop(g, None)\n")
    fs = _concur(src)
    assert ("CC205", 10) in {(f.rule, f.line) for f in fs}
    assert ("CC201", 11) in {(f.rule, f.line) for f in fs}


def test_cc202_blocking_under_lock_direct_and_transitive():
    src = ("import threading, time\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "    def direct(self):\n"
           "        with self._lock:\n"
           "            time.sleep(0.1)\n"
           "    def _slow_rpc(self):\n"
           "        return http_json('GET', 'http://x')\n"
           "    def indirect(self):\n"
           "        with self._lock:\n"
           "            self._slow_rpc()\n")
    fs = _concur(src)
    got = {(f.rule, f.line) for f in fs}
    assert ("CC202", 7) in got, fs       # time.sleep under lock
    assert ("CC202", 12) in got, fs      # transitive via _slow_rpc
    assert any("_slow_rpc" in f.message for f in fs)
    # the same calls outside the lock are clean
    clean = ("import threading, time\n"
             "class C:\n"
             "    def __init__(self):\n"
             "        self._lock = threading.Lock()\n"
             "    def ok(self):\n"
             "        time.sleep(0.1)\n"
             "        return http_json('GET', 'http://x')\n")
    assert _concur(clean) == []


def test_cc202_future_result_and_device_sync():
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "    def bad(self, fut, arr):\n"
           "        with self._lock:\n"
           "            x = fut.result()\n"
           "            arr.block_until_ready()\n"
           "            return x\n")
    fs = _concur(src)
    assert {(f.rule, f.line) for f in fs} == \
        {("CC202", 7), ("CC202", 8)}


def test_cc203_lock_order_cycle():
    """A takes its lock then B's; B takes its lock then A's — the
    classic ABBA deadlock, resolved through corpus-unique method
    names."""
    src = ("import threading\n"
           "class A:\n"
           "    def __init__(self, other):\n"
           "        self._lock = threading.Lock()\n"
           "        self.other = other\n"
           "    def azap(self):\n"
           "        with self._lock:\n"
           "            return 1\n"
           "    def cross_a(self, b):\n"
           "        with self._lock:\n"
           "            b.bzap()\n"
           "class B:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "    def bzap(self):\n"
           "        with self._lock:\n"
           "            return 1\n"
           "    def cross_b(self, a):\n"
           "        with self._lock:\n"
           "            a.azap()\n")
    fs = _concur(src)
    assert [f.rule for f in fs] == ["CC203"]
    assert "A._lock" in fs[0].message and "B._lock" in fs[0].message
    # one direction only is clean
    one_way = src.replace("    def cross_b(self, a):\n"
                          "        with self._lock:\n"
                          "            a.azap()\n", "")
    assert _concur(one_way) == []


def test_cc203_self_deadlock_through_call():
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "    def outer(self):\n"
           "        with self._lock:\n"
           "            self.inner()\n"
           "    def inner(self):\n"
           "        with self._lock:\n"
           "            return 1\n")
    fs = _concur(src)
    assert [f.rule for f in fs] == ["CC203"]
    assert "self-deadlock" in fs[0].message
    # an RLock is reentrant: same shape, no finding
    assert _concur(src.replace("threading.Lock()",
                               "threading.RLock()")) == []


def test_cc204_thread_local_escape_and_handoff():
    src = ("from ..utils.spans import span, span_tracer\n"
           "class C:\n"
           "    def scatter(self, pool, srv):\n"
           "        def call():\n"
           "            with span('scatter_call', server=srv):\n"
           "                return 1\n"
           "        return pool.submit(call)\n")
    fs = _concur(src)
    assert [(f.rule, f.line) for f in fs] == [("CC204", 7)]
    assert "span()" in fs[0].message
    # rooting its own tree on the pool thread is the explicit handoff
    handed = ("from ..utils.spans import span, span_tracer\n"
              "class C:\n"
              "    def scatter(self, pool, srv):\n"
              "        def call():\n"
              "            span_tracer.start('remote')\n"
              "            with span('scatter_call', server=srv):\n"
              "                return 1\n"
              "        return pool.submit(call)\n")
    assert _concur(handed) == []
    # threading.Thread(target=...) is a capture site too
    thr = ("from ..utils.spans import annotate\n"
           "import threading\n"
           "class C:\n"
           "    def go(self):\n"
           "        def work():\n"
           "            annotate(x=1)\n"
           "        t = threading.Thread(target=work)\n"
           "        t.start()\n")
    fs = _concur(thr)
    assert [(f.rule, f.line) for f in fs] == [("CC204", 7)]


def test_cc205_check_then_act():
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self._d = {}\n"
           "    def locked_put(self, k):\n"
           "        with self._lock:\n"
           "            self._d[k] = 1\n"
           "    def racy_put(self, k):\n"
           "        if k not in self._d:\n"
           "            self._d[k] = 1\n")
    fs = _concur(src)
    got = {(f.rule, f.line) for f in fs}
    assert ("CC205", 10) in got
    # under the inferred guard the same shape is fine; setdefault is
    # GIL-atomic and exempt by design
    clean = ("import threading\n"
             "class C:\n"
             "    def __init__(self):\n"
             "        self._lock = threading.Lock()\n"
             "        self._d = {}\n"
             "    def locked_put(self, k):\n"
             "        with self._lock:\n"
             "            if k not in self._d:\n"
             "                self._d[k] = 1\n"
             "    def atomic_put(self, k):\n"
             "        self._d.setdefault(k, 1)\n")
    assert _crules(_concur(clean)) <= {"CC201"} and \
        all(f.rule != "CC205" for f in _concur(clean))


def test_concur_caller_holds_lock_inference():
    """A private method whose every same-class call site holds the lock
    is analyzed as holding it (the _run_locked idiom) — no annotation
    required; a second UNLOCKED call site voids the inference."""
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self.n = 0\n"
           "    def bump(self):\n"
           "        with self._lock:\n"
           "            self._bump_locked()\n"
           "    def _bump_locked(self):\n"
           "        self.n += 1\n")
    assert _concur(src) == []
    leaky = src + ("    def oops(self):\n"
                   "        self._bump_locked()\n")
    fs = _concur(leaky)
    assert [(f.rule, f.scope) for f in fs] == \
        [("CC201", "C._bump_locked")]


def test_concur_holds_lock_annotation():
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self.n = 0\n"
           "    def bump(self):\n"
           "        with self._lock:\n"
           "            self.n += 1\n"
           "    def entry(self):  # holds-lock: _lock\n"
           "        self.n += 1\n")
    assert _concur(src) == []
    # without the annotation the same source is a CC201
    bare = src.replace("  # holds-lock: _lock", "")
    assert [(f.rule, f.scope) for f in _concur(bare)] == \
        [("CC201", "C.entry")]


def test_concur_guarded_by_annotation():
    # guarded-by: none — single-writer atomic by design, exempt
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self.flag = False  # guarded-by: none\n"
           "    def a(self):\n"
           "        with self._lock:\n"
           "            self.flag = True\n"
           "    def b(self):\n"
           "        self.flag = False\n")
    assert _concur(src) == []
    # guarded-by: <lock> — pins the guard even when inference can't
    # see a locked mutation site
    pinned = ("import threading\n"
              "class C:\n"
              "    def __init__(self):\n"
              "        self._lock = threading.Lock()\n"
              "        self.n = 0  # guarded-by: _lock\n"
              "    def bump(self):\n"
              "        self.n += 1\n")
    fs = _concur(pinned)
    assert [(f.rule, f.scope) for f in fs] == [("CC201", "C.bump")]


def test_concur_suppression_roundtrip():
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self.hits = 0\n"
           "    def a(self):\n"
           "        with self._lock:\n"
           "            self.hits += 1\n"
           "    def b(self):\n"
           "        self.hits += 1  # concur: ok CC201\n")
    findings, suppressed = concur.analyze_source(src, CMOD)
    assert findings == []
    assert [(f.rule, f.line) for f in suppressed] == [("CC201", 10)]
    # 'all' suppresses every rule on the line
    src_all = src.replace("# concur: ok CC201", "# concur: ok all")
    findings, suppressed = concur.analyze_source(src_all, CMOD)
    assert findings == [] and len(suppressed) == 1


def test_concur_parse_error_never_baselined(tmp_path):
    findings, _sup = concur.analyze_source("def broken(:\n", CMOD)
    assert [f.rule for f in findings] == ["parse-error"]
    path = str(tmp_path / "base.json")
    concur.write_baseline(findings, path)
    new, _stale = concur.compare_baseline(
        findings, concur.load_baseline(path))
    assert [f.rule for f in new] == ["parse-error"]


def test_concur_corpus_clean_and_baseline_pinned():
    """Repo findings must exactly match the checked-in ratchet baseline
    (tools/concur_baseline.json): new findings fail (fix or
    consciously re-baseline), counts that drop fail too (ratchet the
    baseline down so wins stick)."""
    import time
    t0 = time.perf_counter()
    findings, _sup = concur.analyze_tree(REPO)
    assert time.perf_counter() - t0 < 10.0, \
        "concur must stay under the 10s tier-1 budget"
    assert all(f.rule != "parse-error" for f in findings)
    baseline = concur.load_baseline(
        os.path.join(REPO, "tools", "concur_baseline.json"))
    new, stale = concur.compare_baseline(findings, baseline)
    assert new == [], "\n".join(str(f) for f in new)
    assert stale == [], stale
    # the fixed defects stay fixed: no CC201/CC205 anywhere, and the
    # audited round-14/15 surfaces are completely clean
    assert all(f.rule == "CC202" for f in findings), \
        [str(f) for f in findings if f.rule != "CC202"]
    clean_files = {"pinot_tpu/utils/heat.py", "pinot_tpu/utils/devmem.py",
                   "pinot_tpu/engine/scheduler.py",
                   "pinot_tpu/engine/batch.py"}
    assert not [f for f in findings if f.path in clean_files]


# ---------------------------------------------------------------------------
# the tier-1 CLI gate: concur section + --json contract
# ---------------------------------------------------------------------------

def test_check_static_concur_cli_clean_and_json(capsys):
    import json as _json

    import check_static
    assert check_static.main(["--concur-only"]) == 0
    out = capsys.readouterr().out
    summary = _json.loads(out.strip().splitlines()[-1])
    assert summary["ok"] is True
    assert summary["concur"]["new"] == 0
    assert summary["concur"]["stale"] == 0
    # --json: exactly one JSON document with the per-finding detail
    assert check_static.main(["--concur-only", "--json"]) == 0
    doc = _json.loads(capsys.readouterr().out)
    c = doc["concur"]
    assert set(c["rules"]) <= set(concur.CONCUR_RULES)
    assert c["baselined"] == c["findings"] - c["new"]
    assert isinstance(c["detail"]["findings"], list)
    for f in c["detail"]["findings"]:
        assert {"rule", "file", "line", "scope",
                "message", "baselined"} <= set(f)
    assert isinstance(c["detail"]["suppressed"], list)
    assert isinstance(c["detail"]["stale"], list)


def test_check_static_concur_fails_on_drift(monkeypatch, tmp_path,
                                            capsys):
    import check_static
    empty = tmp_path / "concur_baseline.json"
    empty.write_text('{"version": 1, "counts": {}}')
    monkeypatch.setattr(check_static, "CONCUR_BASELINE", str(empty))
    assert check_static.main(["--concur-only"]) == 1
    assert "NEW [concur]" in capsys.readouterr().out


def test_cc205_ignores_mutation_inside_deferred_closure():
    """A check whose mutation happens only inside a nested closure
    (which runs later, typically under its own locking) is not THIS
    site's check-then-act — the body scan prunes nested defs."""
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self._d = {}\n"
           "    def locked_put(self, k):\n"
           "        with self._lock:\n"
           "            self._d[k] = 1\n"
           "    def maybe_schedule(self, pool, k):\n"
           "        if k not in self._d:\n"
           "            def cb():\n"
           "                self.locked_put(k)\n"
           "            pool.submit(cb)\n")
    assert all(f.rule != "CC205" for f in _concur(src))


def test_concur_namesake_classes_stay_distinct():
    """Guard inference, lock nodes and self-call resolution are all
    module-qualified: an unrelated same-named class's locked mutations
    must not poison this class's guard map (the corpus has duplicate
    class names — _Conn, Pred, S)."""
    prog = concur.Program()
    prog.add_source(
        "import threading\n"
        "class Worker:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.n = 0\n"
        "    def bump(self):\n"
        "        with self._lock:\n"
        "            self.n += 1\n", "pinot_tpu/a.py")
    prog.add_source(
        "class Worker:\n"
        "    def __init__(self):\n"
        "        self.n = 0\n"
        "    def bump(self):\n"
        "        self.n += 1\n", "pinot_tpu/b.py")
    findings, _sup = prog.analyze()
    assert findings == [], [str(f) for f in findings]


def test_cc204_unrelated_bare_helper_is_no_handoff():
    """Only the real handoff APIs (span_tracer.start, Tracing.register,
    attach_thread) exempt a closure — a bare call to some unrelated
    start()/register() helper must not silence the rule."""
    src = ("from ..utils.spans import span\n"
           "class C:\n"
           "    def go(self, pool, srv):\n"
           "        def call():\n"
           "            register(srv)\n"
           "            with span('scatter_call'):\n"
           "                return 1\n"
           "        return pool.submit(call)\n")
    fs = _concur(src)
    assert [(f.rule, f.line) for f in fs] == [("CC204", 8)]


def test_cc203_multi_item_with_orders_like_nested():
    """`with a, b:` acquires left-to-right while holding a — the ABBA
    deadlock against a nested `with b: with a:` must be found exactly
    like the two-statement spelling."""
    src = ("import threading\n"
           "_LA = threading.Lock()\n"
           "_LB = threading.Lock()\n"
           "def one():\n"
           "    with _LA, _LB:\n"
           "        return 1\n"
           "def two():\n"
           "    with _LB:\n"
           "        with _LA:\n"
           "            return 1\n")
    fs = _concur(src)
    assert [f.rule for f in fs] == ["CC203"]
    assert "_LA" in fs[0].message and "_LB" in fs[0].message


def test_concur_inference_converges_on_deep_chains():
    """Caller-holds inference iterates to the true fixpoint: a chain of
    private helpers deeper than any fixed round cap still propagates
    the lock to the deepest mutation (no spurious CC201)."""
    depth = 14
    lines = ["import threading",
             "class C:",
             "    def __init__(self):",
             "        self._lock = threading.Lock()",
             "        self.n = 0",
             "    def entry(self):",
             "        with self._lock:",
             "            self._h0()"]
    for i in range(depth):
        lines += [f"    def _h{i}(self):",
                  f"        self._h{i + 1}()"]
    lines += [f"    def _h{depth}(self):",
              "        self.n += 1",
              "    def other(self):",
              "        with self._lock:",
              "            self.n += 1"]
    assert _concur("\n".join(lines) + "\n") == []


# ---------------------------------------------------------------------------
# detlint: the whole-program determinism & replay-safety verifier
# ---------------------------------------------------------------------------

from pinot_tpu.analysis import detlint  # noqa: E402

DMOD = "pinot_tpu/cluster/detmod.py"


def _detlint(src, path=DMOD):
    findings, _sup = detlint.analyze_source(src, path)
    return findings


def test_dt301_wall_clock_in_plane():
    """A clock read transitively reachable from a declared entry point
    is flagged AT ITS SITE — three helpers deep, same module."""
    src = ("import time\n"
           "def decide(seed, qid):  # detlint: entrypoint\n"
           "    return _stamp(qid)\n"
           "def _stamp(qid):\n"
           "    return _now(), qid\n"
           "def _now():\n"
           "    return time.monotonic()\n")
    fs = _detlint(src)
    assert [(f.rule, f.line, f.scope) for f in fs] == \
        [("DT301", 7, "_now")]
    assert "decide" in fs[0].message  # root attribution
    # the identical helpers with no entry point are outside the plane
    assert _detlint(src.replace("  # detlint: entrypoint", "")) == []


def test_dt301_escape_hatch_idioms_are_clean():
    """All three injectable-now idioms the planes actually use: IfExp,
    `if x is None:` on a one-step-derived local, and `or` fallback."""
    src = ("import time\n"
           "def decide(rec, now=None):  # detlint: entrypoint\n"
           "    a = now if now is not None else time.monotonic()\n"
           "    t = now if now is not None else rec.get('ts')\n"
           "    if t is None:\n"
           "        t = time.monotonic()\n"
           "    b = now or time.monotonic()\n"
           "    return a + t + b\n")
    assert _detlint(src) == []
    # the same reads with NO None-default parameter are violations
    bad = ("import time\n"
           "def decide(rec):  # detlint: entrypoint\n"
           "    return time.monotonic()\n")
    assert [f.rule for f in _detlint(bad)] == ["DT301"]


def test_dt301_gmtime_arg_is_pure_conversion():
    src = ("import time\n"
           "def decide(ts):  # detlint: entrypoint\n"
           "    return time.strftime('%Y', time.gmtime(ts))\n")
    assert _detlint(src) == []
    bad = src.replace("time.gmtime(ts)", "time.gmtime()")
    assert [f.rule for f in _detlint(bad)] == ["DT301"]


def test_dt302_ambient_randomness():
    src = ("import random, uuid, os\n"
           "def decide(seed):  # detlint: entrypoint\n"
           "    a = random.random()\n"
           "    b = uuid.uuid4().hex\n"
           "    c = os.urandom(4)\n"
           "    d = hash(seed)\n"
           "    return a, b, c, d\n")
    fs = _detlint(src)
    assert [(f.rule, f.line) for f in fs] == \
        [("DT302", 3), ("DT302", 4), ("DT302", 5), ("DT302", 6)]
    assert "PYTHONHASHSEED" in fs[3].message
    # seeded constructors are deterministic by contract
    clean = ("import random\n"
             "import numpy as np\n"
             "def decide(seed):  # detlint: entrypoint\n"
             "    rng = np.random.default_rng(seed)\n"
             "    r = random.Random(seed)\n"
             "    return rng.integers(10), r.random()\n")
    assert _detlint(clean) == []


def test_dt303_unordered_serialization():
    src = ("import os\n"
           "def emit(xs):  # detlint: entrypoint\n"
           "    out = []\n"
           "    for x in set(xs):\n"
           "        out.append(x)\n"
           "    key = ','.join({str(x) for x in xs})\n"
           "    files = os.listdir('.')\n"
           "    return out, key, files\n")
    fs = _detlint(src)
    assert [(f.rule, f.line) for f in fs] == \
        [("DT303", 4), ("DT303", 6), ("DT303", 7)]
    # sorted() at the site makes every one of them deterministic
    clean = ("import os\n"
             "def emit(xs):  # detlint: entrypoint\n"
             "    out = []\n"
             "    for x in sorted(set(xs)):\n"
             "        out.append(x)\n"
             "    key = ','.join(sorted({str(x) for x in xs}))\n"
             "    files = sorted(os.listdir('.'))\n"
             "    return out, key, files\n")
    assert _detlint(clean) == []


def test_dt304_query_time_environ():
    src = ("import os\n"
           "def decide(qid):  # detlint: entrypoint\n"
           "    ratio = float(os.environ.get('PINOT_DRIFT_RATIO', 1))\n"
           "    mode = os.getenv('PINOT_MODE')\n"
           "    return ratio, mode\n")
    fs = _detlint(src)
    assert [(f.rule, f.line) for f in fs] == \
        [("DT304", 3), ("DT304", 4)]
    assert "PINOT_DRIFT_RATIO" in fs[0].message
    # the startup-parsed-once idiom (module level) is outside any
    # function body and therefore clean
    clean = ("import os\n"
             "_RATIO = float(os.environ.get('PINOT_DRIFT_RATIO', 1))\n"
             "def decide(qid):  # detlint: entrypoint\n"
             "    return _RATIO\n")
    assert _detlint(clean) == []


def test_dt305_completion_order_float_accumulation():
    """Corpus-wide (no entry point needed): float += over
    as_completed() results re-associates the sum."""
    src = ("from concurrent.futures import as_completed\n"
           "def tally(futs):\n"
           "    total = 0.0\n"
           "    done = 0\n"
           "    for f in as_completed(futs):\n"
           "        total += f.result()\n"
           "        done += 1\n"
           "    return total, done\n")
    fs = _detlint(src)
    # the float accumulation is flagged; the integer counter is not
    assert [(f.rule, f.line) for f in fs] == [("DT305", 6)]
    assert "submission order" in fs[0].message
    # sum() over an as_completed generator is the same hazard
    gen = ("from concurrent.futures import as_completed\n"
           "def tally(futs):\n"
           "    return sum(f.result() for f in as_completed(futs))\n")
    assert [f.rule for f in _detlint(gen)] == ["DT305"]
    # submission-order accumulation is the deterministic fix
    clean = ("def tally(futs):\n"
             "    total = 0.0\n"
             "    for f in futs:\n"
             "        total += f.result()\n"
             "    return total\n")
    assert _detlint(clean) == []


def test_detlint_cross_module_taint():
    """Reachability follows imported names and module aliases: the
    entry point lives in one module, the violation in another."""
    prog = detlint.Program()
    prog.add_source(
        "from pinot_tpu.cluster.helpers import stamp\n"
        "from pinot_tpu.cluster import helpers as h\n"
        "def decide(qid):  # detlint: entrypoint\n"
        "    return stamp(qid), h.tag(qid)\n",
        "pinot_tpu/cluster/detmod.py")
    prog.add_source(
        "import time, random\n"
        "def stamp(qid):\n"
        "    return time.time(), qid\n"
        "def tag(qid):\n"
        "    return random.random()\n"
        "def unreached(qid):\n"
        "    return time.time()\n",
        "pinot_tpu/cluster/helpers.py")
    findings, _sup = prog.analyze()
    got = {(f.rule, f.path, f.scope) for f in findings}
    assert ("DT301", "pinot_tpu/cluster/helpers.py", "stamp") in got
    assert ("DT302", "pinot_tpu/cluster/helpers.py", "tag") in got
    # a function nothing on the plane calls stays unflagged
    assert all(f.scope != "unreached" for f in findings)


def test_detlint_suppression_roundtrip():
    src = ("import time\n"
           "def decide(qid):  # detlint: entrypoint\n"
           "    return time.time()  # detlint: ok DT301\n")
    findings, sup = detlint.analyze_source(src, DMOD)
    assert findings == []
    assert [f.rule for f in sup] == ["DT301"]
    # "all" suppresses every rule on the line
    src_all = src.replace("ok DT301", "ok all")
    findings, sup = detlint.analyze_source(src_all, DMOD)
    assert findings == [] and [f.rule for f in sup] == ["DT301"]
    # a mismatched rule id suppresses nothing
    src_other = src.replace("ok DT301", "ok DT302")
    findings, _sup = detlint.analyze_source(src_other, DMOD)
    assert [f.rule for f in findings] == ["DT301"]


def test_detlint_parse_error_never_baselined(tmp_path):
    findings, _sup = detlint.analyze_source("def broken(:\n", DMOD)
    assert [f.rule for f in findings] == ["parse-error"]
    path = str(tmp_path / "base.json")
    detlint.write_baseline(findings, path)
    new, _stale = detlint.compare_baseline(
        findings, detlint.load_baseline(path))
    assert [f.rule for f in new] == ["parse-error"]


def test_detlint_registry_roots_all_resolve():
    """Every ROOTS entry must still name a real function — a rename
    silently disarming the plane is itself a gate failure."""
    prog = detlint.Program()
    prog.add_tree(REPO)
    prog.analyze()
    assert prog.roots_missing == [], prog.roots_missing
    assert len(prog.roots_matched) == len(detlint.ROOTS)


def test_detlint_corpus_clean_and_baseline_pinned():
    """Repo findings must exactly match the checked-in ratchet baseline
    (tools/detlint_baseline.json), inside the 10s tier-1 budget."""
    import time
    t0 = time.perf_counter()
    findings, _sup = detlint.analyze_tree(REPO)
    assert time.perf_counter() - t0 < 10.0, \
        "detlint must stay under the 10s tier-1 budget"
    assert all(f.rule != "parse-error" for f in findings)
    baseline = detlint.load_baseline(
        os.path.join(REPO, "tools", "detlint_baseline.json"))
    new, stale = detlint.compare_baseline(findings, baseline)
    assert new == [], "\n".join(str(f) for f in new)
    assert stale == [], stale
    # the round-23 fix stays fixed: the overload governor makes no
    # clock read on the deterministic plane (pinned/inert replay mode)
    assert not [f for f in findings
                if f.path == "pinot_tpu/broker/workload.py"], \
        [str(f) for f in findings]
    # the one grandfathered site is make_record's documented live-mode
    # ts fallback (ts= through **fields is its escape hatch)
    assert {f.key for f in findings} <= \
        {"pinot_tpu/utils/ledger.py::make_record::DT301"}


def test_check_static_detlint_cli_clean_and_json(capsys):
    import json as _json

    import check_static
    assert check_static.main(["--detlint-only"]) == 0
    out = capsys.readouterr().out
    summary = _json.loads(out.strip().splitlines()[-1])
    assert summary["ok"] is True
    assert summary["detlint"]["new"] == 0
    assert summary["detlint"]["stale"] == 0
    # --json: exactly one JSON document with the per-finding detail
    assert check_static.main(["--detlint-only", "--json"]) == 0
    doc = _json.loads(capsys.readouterr().out)
    d = doc["detlint"]
    assert set(d["rules"]) <= set(detlint.DETLINT_RULES)
    assert d["baselined"] == d["findings"] - d["new"]
    for f in d["detail"]["findings"]:
        assert {"rule", "file", "line", "scope",
                "message", "baselined"} <= set(f)
    assert isinstance(d["detail"]["suppressed"], list)
    assert isinstance(d["detail"]["stale"], list)


def test_check_static_detlint_fails_on_drift(monkeypatch, tmp_path,
                                             capsys):
    import check_static
    empty = tmp_path / "detlint_baseline.json"
    empty.write_text('{"version": 1, "counts": {}}')
    monkeypatch.setattr(check_static, "DETLINT_BASELINE", str(empty))
    assert check_static.main(["--detlint-only"]) == 1
    assert "NEW [detlint]" in capsys.readouterr().out


def test_check_static_changed_mode(monkeypatch, capsys):
    """--changed: findings and baselines restricted to the changed
    files, plan verifier skipped, flag incompatibilities rejected."""
    import json as _json

    import check_static
    monkeypatch.setattr(check_static, "_changed_files",
                        lambda: ["pinot_tpu/utils/ledger.py"])
    assert check_static.main(["--changed", "--json"]) == 0
    doc = _json.loads(capsys.readouterr().out)
    assert doc["changed"] == ["pinot_tpu/utils/ledger.py"]
    assert "verify" not in doc  # plan verifier skipped
    # the one grandfathered ledger site is in scope and baselined
    assert doc["detlint"]["findings"] == 1
    assert doc["detlint"]["new"] == 0
    # every reported finding is inside the changed scope
    for sec in ("lint", "concur", "detlint"):
        for f in doc[sec]["detail"]["findings"]:
            assert f["file"] == "pinot_tpu/utils/ledger.py"
    # no changed .py files: every pass skips, still exit 0
    monkeypatch.setattr(check_static, "_changed_files", lambda: [])
    assert check_static.main(["--changed"]) == 0
    doc = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc == {"changed": [], "ok": True}
    # incompatible flag combinations are usage errors (exit 2)
    with pytest.raises(SystemExit) as e:
        check_static.main(["--changed", "--verify-only"])
    assert e.value.code == 2
    capsys.readouterr()
    with pytest.raises(SystemExit) as e:
        check_static.main(["--changed", "--update-baseline"])
    assert e.value.code == 2
    capsys.readouterr()
