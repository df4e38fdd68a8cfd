"""ISSUE 15: compile-plane forensics.

Contract under test:
- the normalized-SQL shape hash is ONE shared function
  (pinot_tpu/utils/shapehash.py) — span_diff keys and compile_event
  plan_shapes can never drift apart;
- ``compile_event`` and ``alert`` are validated v2 ledger kinds
  (writer-side contract enforcement, per-kind counts in validate_file /
  tools/check_ledger.py);
- every XLA compile over a deterministic corpus lands exactly one
  compile_event whose trigger taxonomy reconciles EXACTLY with the
  RetraceDetector's classification counters (no unattributed
  compiles), with the explicit lower/compile staging split and
  executable memory bytes where the backend reports them;
- trigger refinement: drift_requantize / overflow_retry via the
  expected-compile hints, lru_evict_rebuild via eviction memory;
- compile-storm alerting: rate-windowed, fires ONCE per watermark
  crossing, validated alert record + ring + counters;
- EXPLAIN ANALYZE grows the compile lane: staged ``build_kernel``
  spans with ``lower``/``compile`` children and memory Detail;
- tools/warmup_report.py renders the debt report and ``--gate``
  ratchets post-warmup compiles (anti-vacuous);
- cluster/rollup.rank_plan_shapes ranks shapes by freq x median
  compile ms with (proc, seq) dedup — pinned against an independently
  computed oracle;
- zero-cost contract: a warm call is one staged lookup (no lower(), no
  compile(), no fallback), and warm passes emit no events.
"""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import span_diff  # noqa: E402  (tools/ on sys.path)

from pinot_tpu.ops.plan_cache import global_plan_cache  # noqa: E402
from pinot_tpu.utils import ledger as uledger  # noqa: E402
from pinot_tpu.utils.compileplane import (  # noqa: E402
    StagedFn, clear_staged_caches, compile_health, global_compile_log,
    resolve_trigger, set_staging_enabled, staged)
from pinot_tpu.utils.metrics import global_metrics  # noqa: E402
from pinot_tpu.utils.shapehash import shape_key  # noqa: E402

OPT = " OPTION(timeoutMs=300000,traceRatio=0)"


# ---------------------------------------------------------------------------
# shared shape hash (satellite: span_diff <-> compile_event join pin)
# ---------------------------------------------------------------------------

def test_shape_hash_identity_with_span_diff():
    # the SAME function object, not a lookalike: a private copy would
    # drift one rename at a time and silently break the planes' join
    assert span_diff.shape_key is shape_key
    s = "SELECT  hk, SUM(v)\n FROM t GROUP BY hk"
    assert span_diff.shape_key(s) == shape_key(s)
    assert shape_key(s) == shape_key("select hk, sum(v) from t group by hk")
    assert shape_key(s) != shape_key(s + " LIMIT 5")


# ---------------------------------------------------------------------------
# ledger contracts
# ---------------------------------------------------------------------------

def _event_fields(**over):
    f = dict(site="plan_cache", trigger="cold", plan_shape="ab12cd34ef56",
             key_fp="0011223344ff", backend="cpu", lower_ms=3.2,
             compile_ms=41.0, donated=False, proc="p-1", seq=1,
             memory_bytes=None, flops=None)
    f.update(over)
    return f


def test_compile_event_contract(tmp_path):
    rec = uledger.make_record("compile_event", **_event_fields())
    assert not uledger.validate_record(rec)
    with pytest.raises(ValueError):  # typo'd field must never fork
        uledger.make_record("compile_event",
                            **_event_fields(compil_ms=1.0))
    with pytest.raises(ValueError):  # missing required
        bad = _event_fields()
        bad.pop("trigger")
        uledger.make_record("compile_event", **bad)
    # per-kind counts surface through validate_file (check_ledger.py)
    path = str(tmp_path / "led.jsonl")
    uledger.append_record(rec, path)
    uledger.append_record(uledger.make_record(
        "compile_event", **_event_fields(seq=2, trigger="retrace")), path)
    res = uledger.validate_file(path)
    assert not res["errors"]
    assert res["kinds"] == {"compile_event": 2}


def test_alert_contract(tmp_path):
    rec = uledger.make_record(
        "alert", alert="compile_storm", severity="warn",
        rate_per_min=31, watermark=30, window_s=60.0, proc="p-1",
        triggers={"retrace": 31}, detail="x")
    assert not uledger.validate_record(rec)
    with pytest.raises(ValueError):
        uledger.make_record("alert", alert="compile_storm",
                            severity="warn", rate_per_min=1,
                            watermark=1, window_s=60.0, proc="p",
                            bogus_field=1)
    path = str(tmp_path / "led.jsonl")
    uledger.append_record(rec, path)
    assert uledger.validate_file(path)["kinds"] == {"alert": 1}


def test_fleet_rollup_accepts_plan_shapes():
    rec = uledger.make_record(
        "fleet_rollup", nodes_polled=1, nodes_skipped=0,
        records_pulled=3, tables={},
        plan_shapes=[{"plan_shape": "ab", "compiles": 2,
                      "median_compile_ms": 40.0, "warmup_cost": 80.0}])
    assert not uledger.validate_record(rec)


# ---------------------------------------------------------------------------
# trigger taxonomy units
# ---------------------------------------------------------------------------

def test_resolve_trigger_mapping():
    assert resolve_trigger("cold", {}) == "cold"
    assert resolve_trigger("warmup", {}) == "warmup"
    assert resolve_trigger("retrace", {}) == "retrace"
    assert resolve_trigger("retrace", {"evicted": True}) \
        == "lru_evict_rebuild"
    assert resolve_trigger("expected", {}) == "overflow_retry"
    assert resolve_trigger(
        "expected", {"expected_kind": "drift_requantize"}) \
        == "drift_requantize"


def _events_since(n0):
    return global_compile_log.events()[n0:]


def test_staged_fn_drift_and_overflow_triggers():
    det = global_plan_cache.detector
    tok_a, tok_b = ("cf_drift_tok",), ("cf_overflow_tok",)
    det.begin_query(object())
    # prime both tokens warm (an earlier generation saw them compile)
    assert det.classify_compile(tok_a) == "cold"
    assert det.classify_compile(tok_b) == "cold"
    det.begin_query(object())
    n0 = len(global_compile_log.events())
    exp0 = det.expected_recompiles

    import jax
    fn = staged(jax.jit(lambda x: x + 1), "unit", tok_a,
                hints={"expected_kind": "drift_requantize"})
    fn(jnp.arange(3))
    # overflow: classification inside an expected() bracket, no hint
    fn2 = staged(jax.jit(lambda x: x * 2), "unit", tok_b)
    with det.expected():
        fn2(jnp.arange(3))
    ev = _events_since(n0)
    assert [e["trigger"] for e in ev] \
        == ["drift_requantize", "overflow_retry"]
    assert det.expected_recompiles == exp0 + 2
    # every emitted event is a validated v2 record
    for e in ev:
        assert not uledger.validate_record(e), e
        assert e["lower_ms"] >= 0 and e["compile_ms"] > 0
    # warm re-calls emit nothing
    n1 = len(global_compile_log.events())
    fn(jnp.arange(3))
    fn2(jnp.arange(3))
    assert len(global_compile_log.events()) == n1


def test_staged_fn_extra_signature_is_cold_not_retrace():
    det = global_plan_cache.detector
    import jax
    tok = ("cf_polymorph_tok",)
    det.begin_query(object())
    fn = staged(jax.jit(lambda x: x + 1), "unit", tok)
    fn(jnp.arange(4))
    det.begin_query(object())
    r0 = det.retraces
    n0 = len(global_compile_log.events())
    fn(jnp.arange(8))          # new shape in a LATER generation
    ev = _events_since(n0)
    assert [e["trigger"] for e in ev] == ["cold"]
    assert det.retraces == r0  # shape polymorphism is not a retrace


def test_ragged_registry_lru_evict_rebuild():
    from pinot_tpu.engine.ragged import _KernelRegistry
    det = global_plan_cache.detector
    reg = _KernelRegistry(maxsize=1)
    det.begin_query(object())
    reg.get(("cf_reg_k1",), lambda: (lambda x: x + 1))(jnp.arange(4))
    reg.get(("cf_reg_k2",), lambda: (lambda x: x * 2))(jnp.arange(4))
    det.begin_query(object())
    n0 = len(global_compile_log.events())
    r0 = det.retraces
    # k1 was evicted by k2 (maxsize 1): its rebuild in a later
    # generation is an eviction rebuild — counted under the detector's
    # retraces (post-warmup!) but attributed to the true cause
    reg.get(("cf_reg_k1",), lambda: (lambda x: x + 1))(jnp.arange(4))
    ev = _events_since(n0)
    assert [e["trigger"] for e in ev] == ["lru_evict_rebuild"]
    assert det.retraces == r0 + 1


# ---------------------------------------------------------------------------
# compile-storm alerting
# ---------------------------------------------------------------------------

def test_compile_storm_alert_fires_once_per_crossing():
    global_compile_log.configure(storm_per_min=3)
    a0 = len(global_compile_log.alerts())
    c0 = global_metrics.snapshot()["counters"].get(
        "compile_storm_alerts", 0)
    for i in range(3):
        global_compile_log.record("unit", "retrace", 1.0, 2.0,
                                  "fp", False)
    alerts = global_compile_log.alerts()[a0:]
    assert len(alerts) == 1, "one alert at the crossing"
    a = alerts[0]
    assert not uledger.validate_record(a)
    assert a["alert"] == "compile_storm" and a["rate_per_min"] >= 3
    assert a["triggers"].get("retrace", 0) >= 3
    # sustained storm: MORE post-warmup compiles do not re-alert
    for i in range(4):
        global_compile_log.record("unit", "lru_evict_rebuild", 1.0,
                                  2.0, "fp", False)
    assert len(global_compile_log.alerts()[a0:]) == 1
    snap = global_metrics.snapshot()
    assert snap["counters"]["compile_storm_alerts"] == c0 + 1
    assert snap["gauges"]["compile_storm_per_min"] >= 3
    assert snap["gauges"]["compile_storm_watermark"] == 3
    # cold compiles never feed the storm window
    assert global_compile_log.record(
        "unit", "cold", 1.0, 2.0, "fp", False)["trigger"] == "cold"
    assert len(global_compile_log.alerts()[a0:]) == 1


def test_compile_health_block_and_debug_payload():
    global_compile_log.record("unit", "cold", 1.5, 2.5, "fp", False)
    h = compile_health(global_metrics.snapshot())
    assert h["compiles"] >= 1 and h["compile_ms_total"] > 0
    assert "cold" in h["by_trigger"]
    assert "storm_watermark" in h and "recent_alerts" in h
    # the node /debug/ledger payload ships the compile block beside
    # batching (cluster/forensics.py -> rollup-visible)
    from pinot_tpu.cluster.forensics import ledger_debug_payload
    out = ledger_debug_payload("n1", "broker", None, 0)
    assert "compile" in out and out["compile"]["compiles"] >= 1
    # /debug/compile snapshot carries the ring newest-first
    snap = global_compile_log.snapshot()
    assert snap["events"] and snap["events"][0]["kind"] \
        == "compile_event"


# ---------------------------------------------------------------------------
# warmup report + gate
# ---------------------------------------------------------------------------

def test_warmup_report_summarize_oracle():
    import warmup_report
    evs = [
        _event_fields(seq=1, plan_shape="aa", lower_ms=1.0,
                      compile_ms=9.0),
        _event_fields(seq=2, plan_shape="aa", lower_ms=2.0,
                      compile_ms=18.0, trigger="warmup"),
        _event_fields(seq=3, plan_shape="bb", lower_ms=0.5,
                      compile_ms=99.5, trigger="retrace"),
    ]
    evs = [uledger.make_record("compile_event", **e) for e in evs]
    # a fleet ledger ships the same event once per serving node: the
    # duplicate (proc, seq) must count ONCE (a double-counted retrace
    # would spuriously trip the gate)
    evs.append(dict(evs[2], node="broker_b"))
    rep = warmup_report.summarize(evs)
    assert rep["events"] == 3
    assert rep["compile_ms_total"] == pytest.approx(130.0)
    assert rep["by_trigger"] == {"cold": 1, "warmup": 1, "retrace": 1}
    assert rep["post_warmup"] == 1
    by = {s["plan_shape"]: s for s in rep["shapes"]}
    assert by["aa"]["compiles"] == 2
    # the shape block IS rollup.rank_plan_shapes (shared aggregation,
    # registry percentile definition)
    from pinot_tpu.utils.stats import pctl
    assert by["aa"]["median_compile_ms"] == pytest.approx(
        pctl([10.0, 20.0], 0.5))
    assert by["aa"]["warmup_cost"] == pytest.approx(
        2 * pctl([10.0, 20.0], 0.5))
    # ranking: bb (1 x 100) outranks aa
    assert rep["shapes"][0]["plan_shape"] == "bb"


def test_warmup_report_gate_cli(tmp_path):
    tool = os.path.join(REPO, "tools", "warmup_report.py")
    clean = str(tmp_path / "clean.jsonl")
    uledger.append_record(uledger.make_record(
        "compile_event", **_event_fields()), clean)
    r = subprocess.run([sys.executable, tool, "gate", clean],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    assert json.loads(r.stdout.strip().splitlines()[-1])["ok"] is True
    # a post-warmup compile trips the ratchet
    dirty = str(tmp_path / "dirty.jsonl")
    uledger.append_record(uledger.make_record(
        "compile_event", **_event_fields()), dirty)
    uledger.append_record(uledger.make_record(
        "compile_event", **_event_fields(seq=2, trigger="retrace")),
        dirty)
    r = subprocess.run([sys.executable, tool, "gate", dirty],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["post_warmup"] == 1 and not out["ok"]
    # --max-post-warmup ratchets
    r = subprocess.run([sys.executable, tool, "gate", dirty,
                        "--max-post-warmup", "1"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0
    # anti-vacuous: an empty corpus is a broken corpus, not a pass
    empty = str(tmp_path / "empty.jsonl")
    open(empty, "w").close()
    r = subprocess.run([sys.executable, tool, "gate", empty],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    assert "vacuous" in json.loads(
        r.stdout.strip().splitlines()[-1])["failures"][0]


# ---------------------------------------------------------------------------
# fleet plan-shape ranking (rollup oracle)
# ---------------------------------------------------------------------------

def test_rank_plan_shapes_oracle_and_dedup():
    from pinot_tpu.cluster.rollup import rank_plan_shapes
    recs = []
    # shape aa: 3 compiles at 10/20/30 ms -> median 20, cost 60
    for i, ms in enumerate((10.0, 20.0, 30.0)):
        recs.append(uledger.make_record("compile_event", **_event_fields(
            seq=i + 1, plan_shape="aa", lower_ms=0.0, compile_ms=ms,
            sql="select a")))
    # shape bb: 1 compile at 100 -> cost 100 (outranks aa)
    recs.append(uledger.make_record("compile_event", **_event_fields(
        seq=10, plan_shape="bb", lower_ms=40.0, compile_ms=60.0,
        trigger="retrace")))
    # the same (proc, seq) event shipped twice (two in-process nodes
    # sharing one compile ledger) must count ONCE
    recs.append(dict(recs[0], node="broker_b"))
    # a different process's same seq is a DIFFERENT event
    recs.append(uledger.make_record("compile_event", **_event_fields(
        seq=1, proc="p-2", plan_shape="bb", lower_ms=0.0,
        compile_ms=50.0)))
    ranked = rank_plan_shapes(recs)
    by = {r["plan_shape"]: r for r in ranked}
    assert by["aa"]["compiles"] == 3
    assert by["aa"]["median_compile_ms"] == pytest.approx(20.0)
    assert by["aa"]["warmup_cost"] == pytest.approx(60.0)
    assert by["bb"]["compiles"] == 2
    # the registry percentile definition (utils/stats.pctl) — the ONE
    # fleet median, upper-element for even counts
    from pinot_tpu.utils.stats import pctl
    assert by["bb"]["median_compile_ms"] == pytest.approx(
        pctl([50.0, 100.0], 0.5))
    assert by["bb"]["triggers"] == {"retrace": 1, "cold": 1}
    # ranking order: bb outranks aa (60); oracle recomputed
    assert ranked[0]["plan_shape"] == "bb"
    assert ranked[0]["warmup_cost"] == pytest.approx(
        2 * pctl([50.0, 100.0], 0.5))
    assert by["aa"]["sql"] == "select a"


# ---------------------------------------------------------------------------
# end-to-end: corpus reconciliation + explain lane + overhead
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def corpus_broker(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("cf_corpus")
    led = str(tmp / "trace.jsonl")
    b = span_diff.build_corpus_broker(str(tmp), rows=4096,
                                      trace_path=led)
    return b, led


def test_corpus_reconciles_with_retrace_detector(corpus_broker):
    """The acceptance cross-check: over a deterministic corpus, summed
    compile_event counts per trigger reconcile EXACTLY with the
    RetraceDetector's classification counters — no unattributed
    compiles — and every event joins the span plane by shape hash."""
    b, led = corpus_broker
    global_compile_log.configure(path=led)
    clear_staged_caches()          # a fresh cold slate, detector incl.
    det = global_plan_cache.detector
    t0 = det.trigger_snapshot()
    n0 = len(global_compile_log.events())
    sqls = [sql for _, sql in span_diff.CORPUS_SQL]
    digests = [tuple(map(tuple, b.query(s + OPT).rows)) for s in sqls]
    ev = _events_since(n0)
    assert ev, "corpus paid compiles but emitted no compile_events"
    t1 = det.trigger_snapshot()
    counts = {}
    for e in ev:
        counts[e["trigger"]] = counts.get(e["trigger"], 0) + 1
    assert counts.get("cold", 0) + counts.get("warmup", 0) \
        == (t1["cold"] - t0["cold"]) + (t1["warmup"] - t0["warmup"])
    assert counts.get("retrace", 0) + counts.get(
        "lru_evict_rebuild", 0) == t1["retraces"] - t0["retraces"]
    assert counts.get("overflow_retry", 0) + counts.get(
        "drift_requantize", 0) \
        == t1["expected_recompiles"] - t0["expected_recompiles"]
    assert sum(counts.values()) == len(ev)
    # field quality: explicit staging split + key fingerprint + the
    # shared shape hash joining the exact corpus SQL
    shapes = {shape_key(s + OPT) for s in sqls}
    for e in ev:
        assert not uledger.validate_record(e), e
        assert e["compile_ms"] > 0 and e["lower_ms"] >= 0
        assert e["key_fp"] and e["backend"]
        assert e["plan_shape"] in shapes, \
            (e["site"], e["plan_shape"], e.get("sql"))
        assert e["qid"]
    # cpu backend reports memory_analysis: at least one event carries
    # executable bytes (None is legal per-event, fabrication is not)
    assert any(e["memory_bytes"] for e in ev)
    # the events were also appended VALIDATED to the configured ledger
    res = uledger.validate_file(led)
    assert not res["errors"]
    assert res["kinds"].get("compile_event", 0) >= len(ev)
    # warm pass: digests identical, ZERO new events (no ledger I/O on
    # the hot path — the zero-cost contract's structural half)
    n1 = len(global_compile_log.events())
    digests2 = [tuple(map(tuple, b.query(s + OPT).rows)) for s in sqls]
    assert digests2 == digests
    assert len(global_compile_log.events()) == n1


def test_explain_analyze_compile_lane(corpus_broker):
    b, _led = corpus_broker
    # a never-before-compiled shape (fresh literal set) pays its
    # compile INSIDE the analyze run -> the compile lane renders
    res = b.query("EXPLAIN ANALYZE SELECT hk, SUM(v), MIN(v) "
                  "FROM span_corpus WHERE f <= 37 GROUP BY hk "
                  "ORDER BY hk LIMIT 7")
    rows = res.rows
    names = [r[0] for r in rows]
    assert "build_kernel" in names, names
    bk = [r for r in rows if r[0] == "build_kernel"
          and "staged=True" in r[4]]
    assert bk, rows
    bk_ids = {r[1] for r in bk}
    children = {r[0] for r in rows if r[2] in bk_ids}
    assert {"lower", "compile"} <= children
    # executable memory bytes attach as Detail on the staged span
    assert any("memory_bytes=" in r[4] for r in bk)
    assert any("trigger=" in r[4] for r in bk)


def test_staging_overhead_under_one_percent(corpus_broker, count_calls):
    """What staging costs a warm call, in work done and not in seconds:
    with the compile plane in its default state (staging on, no ledger)
    a call of a warm signature is one signature computation and one
    lookup of the compiled program — it never stages (no ``lower()``,
    no ``compile()``), never takes the implicit-jit fallback, and warm
    passes emit no compile event."""
    from pinot_tpu.utils import compileplane
    b, _led = corpus_broker
    assert global_compile_log.path is None  # conftest un-pointed it
    sqls = [sql for _, sql in span_diff.CORPUS_SQL]
    for s in sqls:
        b.query(s + OPT)               # staged-mode warm

    calls = count_calls(StagedFn, "__call__", "_stage", "_fallback")
    count_calls(compileplane, "_sig")
    n0 = len(global_compile_log.events())
    for _ in range(2):
        for s in sqls:
            b.query(s + OPT)
    assert calls["__call__"] >= 2 * len(sqls), dict(calls)
    assert calls["_sig"] == calls["__call__"], dict(calls)
    assert calls["_stage"] == 0 and calls["_fallback"] == 0, dict(calls)
    # zero events during the warm passes
    assert len(global_compile_log.events()) == n0


def test_staged_fn_fallback_when_disabled():
    """PINOT_COMPILE_FORENSICS=0 drops the staging machinery (no
    events, no lower/compile split) but must NOT drop the pre-round-20
    retrace-detection plane: the detector still classifies one compile
    per signature on the fallback path."""
    import jax
    det = global_plan_cache.detector
    tok = ("cf_fallback_tok",)
    det.begin_query(object())
    assert det.classify_compile(tok) == "cold"   # token warm, gen N
    det.begin_query(object())                    # gen N+1
    r0 = det.retraces
    fn = staged(jax.jit(lambda x: x + 5), "unit", tok)
    n0 = len(global_compile_log.events())
    set_staging_enabled(False)
    try:
        out = fn(jnp.arange(3))
        fn(jnp.arange(3))                        # same sig: once only
    finally:
        set_staging_enabled(True)
    assert list(out) == [5, 6, 7]
    assert len(global_compile_log.events()) == n0  # no event, no stage
    # ...but the warm token's fallback compile still reads as a
    # retrace — counters/span annotation survive the hatch
    assert det.retraces == r0 + 1
    assert isinstance(fn, StagedFn)


def test_compiler_rejection_is_not_recompiled():
    """A program the COMPILER rejects (XLA / Mosaic RESOURCE_EXHAUSTED)
    fails the call with the compiler's own error: no fallback to the
    implicit jit — it would pay the same doomed compile again, minutes
    at SSB sizes on the chip — and no second compile on the next call
    with the same signature."""
    import jax

    calls = {"compile": 0, "implicit": 0}

    class _Lowered:
        def compile(self):
            calls["compile"] += 1
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: Ran out of memory in memory space "
                "vmem")

    class _Jitted:
        def lower(self, *args):
            return _Lowered()

        def __call__(self, *args):
            calls["implicit"] += 1

    fn = staged(_Jitted(), "unit", ("cf_rejected_tok",))
    counters = global_metrics.snapshot()["counters"]
    fallbacks0 = counters.get("compile_staging_fallbacks", 0)
    rejected0 = counters.get("compile_rejections", 0)
    for _ in range(2):
        with pytest.raises(jax.errors.JaxRuntimeError,
                           match="RESOURCE_EXHAUSTED"):
            fn(jnp.arange(3))
    assert calls == {"compile": 1, "implicit": 0}
    counters = global_metrics.snapshot()["counters"]
    assert counters.get("compile_staging_fallbacks", 0) == fallbacks0
    assert counters["compile_rejections"] == rejected0 + 1
    # another signature is another program: it gets its own verdict
    with pytest.raises(jax.errors.JaxRuntimeError):
        fn(jnp.arange(4))
    assert calls == {"compile": 2, "implicit": 0}
