"""ISSUE 11: sustained ingest-while-query harness
(pinot_tpu/engine/loadgen.py), the ``ingest_bench`` ledger kind, and
the freshness-gate ratchet (tools/freshness_gate.py vs
tools/freshness_baseline.json).

Contract under test (acceptance):
- seeded row generation and drain-mode runs are deterministic, and
  every run's final queryable state is byte-identical to the
  fault-free oracle (the run's own ``ok``/``oracle_ok`` gate);
- a chaos-armed run (all ingest points, concurrent queries,
  micro-batching at its on-by-default setting) recovers through real
  crash/restarts and still converges byte-exact, emitting validated
  ``ingest_bench`` + per-table ``ingest_stats`` records;
- the freshness ratchet trips on an injected 2x freshness regression,
  while its speed calibration absorbs a uniform machine slowdown and a
  saturated calibration reports an explicit skip (never a phantom
  red); the shared environment pin exits 3 on a foreign baseline;
- the fleet rollup trends the new per-table freshness percentiles.

The sustained 60s multi-backend soak is slow-marked (nightly lane);
tools/chaos_smoke.py --rate (tests/test_faults.py) is the tier-1
end-to-end gate.
"""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import freshness_gate as FG  # noqa: E402

from pinot_tpu.engine import loadgen as LG  # noqa: E402
from pinot_tpu.tools.ingest_fuzz import ingest_plan  # noqa: E402
from pinot_tpu.utils import faults  # noqa: E402
from pinot_tpu.utils import ledger as uledger  # noqa: E402


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    faults.clear()
    yield
    faults.clear()


# ---------------------------------------------------------------------------
# loadgen determinism + oracle exactness
# ---------------------------------------------------------------------------

def test_gen_partition_rows_pure():
    a = LG.gen_partition_rows(7, 0, 1, 50)
    assert a == LG.gen_partition_rows(7, 0, 1, 50)
    assert a != LG.gen_partition_rows(8, 0, 1, 50)      # seed
    assert a != LG.gen_partition_rows(7, 1, 1, 50)      # table
    assert a != LG.gen_partition_rows(7, 0, 0, 50)      # partition
    longer = LG.gen_partition_rows(7, 0, 1, 80)
    assert len(longer) == 80 and len(a) == 50


def test_loadgen_drain_deterministic(tmp_path):
    """Two same-seed fault-free runs: both byte-exact vs the SAME
    oracle (hence identical final states), same produced totals, and
    the summary is shaped for the ingest_bench contract."""
    outs = []
    for tag in ("a", "b"):
        cfg = LG.LoadgenConfig(
            tables=[LG.TableLoadSpec("det_append", partitions=2),
                    LG.TableLoadSpec("det_upsert", partitions=2,
                                     upsert=True, protocol=True)],
            seed=11, rows_per_partition=200, query_concurrency=2)
        s = LG.run_load(str(tmp_path / tag), cfg)
        assert s["ok"] and s["oracle_ok"], s.get("error")
        outs.append(s)
    a, b = outs
    assert a["rows"] == b["rows"] == 800
    assert a["partitions"] == b["partitions"] == 4
    for s in outs:   # summary fields satisfy the writer-side contract
        rec = uledger.make_record(
            "ingest_bench",
            **{k: v for k, v in s.items()
               if k in (uledger.KINDS["ingest_bench"]["required"]
                        | uledger.KINDS["ingest_bench"]["optional"])})
        assert not uledger.validate_record(rec)


def test_loadgen_chaos_crash_restart_exact(tmp_path):
    """All six ingest points armed + concurrent queries + batching at
    its process default: injected process deaths force real
    checkpoint restarts and the final state stays byte-exact (the
    run's own per-table oracle diff)."""
    cfg = LG.LoadgenConfig(
        tables=[LG.TableLoadSpec("cx_append", partitions=2),
                LG.TableLoadSpec("cx_upsert", partitions=2,
                                 upsert=True, protocol=True)],
        seed=40, rows_per_partition=300, query_concurrency=2,
        fault_plan=ingest_plan(40, protocol=True),
        ledger_path=str(tmp_path / "lg.jsonl"), max_wall_s=60)
    s = LG.run_load(str(tmp_path / "run"), cfg)
    assert s["ok"] and s["oracle_ok"], s.get("error")
    assert s["faults_fired"] >= 1
    assert s["chaos"] is True
    # the freshness/commit series actually measured something
    assert s["freshness_p50_ms"] >= 0 and s["commits"] >= 0
    res = uledger.validate_file(str(tmp_path / "lg.jsonl"))
    assert not res["errors"]
    assert res["kinds"].get("ingest_bench") == 1
    assert res["kinds"].get("ingest_stats") == 2
    # per-table records carry the percentile trend for the rollup
    with open(tmp_path / "lg.jsonl") as fh:
        stats = [json.loads(ln) for ln in fh
                 if '"ingest_stats"' in ln]
    assert all("freshness_p50_ms" in r for r in stats)


def test_loadgen_rejects_unknown_backend(tmp_path):
    with pytest.raises(ValueError, match="unknown backend"):
        LG.make_backend(LG.TableLoadSpec("x", backend="carrier-pigeon"),
                        str(tmp_path))


def test_kinesis_shard_keys_cover_all_shards():
    import hashlib
    for n in (1, 2, 3, 5):
        keys = LG._kinesis_shard_keys(n)
        assert sorted(int(hashlib.md5(k.encode()).hexdigest(), 16) % n
                      for k in keys) == list(range(n))


# ---------------------------------------------------------------------------
# ingest_bench ledger contract
# ---------------------------------------------------------------------------

def _bench_fields(**over):
    base = dict(backend="cpu", ok=True, scenario="gate_corpus", seed=1,
                tables=2, partitions=4, rows=1000, rows_per_s=5000.0,
                duration_s=0.4, freshness_p50_ms=0.4,
                freshness_p99_ms=0.8, queries_concurrent=2,
                batched=True)
    base.update(over)
    return base


def test_ingest_bench_contract():
    rec = uledger.make_record("ingest_bench", **_bench_fields(
        commit_p50_ms=15.0, restarts=3, chaos=True, oracle_ok=True))
    assert not uledger.validate_record(rec)
    with pytest.raises(ValueError, match="missing required"):
        uledger.make_record("ingest_bench", backend="cpu", ok=True)
    with pytest.raises(ValueError, match="unknown fields"):
        uledger.make_record("ingest_bench",
                            **_bench_fields(typo_field=1))
    # check_ledger reports the per-kind count
    import check_ledger  # noqa: F401 — registered in tools path
    assert "ingest_bench" in uledger.KINDS


# ---------------------------------------------------------------------------
# freshness gate: trip, calibration, floors, env pin, saturation skip
# ---------------------------------------------------------------------------

BASE_METRICS = {"freshness_p50_ms": 0.4, "freshness_p99_ms": 0.9,
                "commit_p50_ms": 16.0, "commit_p99_ms": 40.0}


def _write_ledger(path, wall_s, metrics, n=3):
    for _ in range(n):
        rec = uledger.make_record("ingest_bench", **_bench_fields(
            duration_s=wall_s, **metrics))
        uledger.append_record(rec, str(path))


def _baseline(tmp_path):
    bp = str(tmp_path / "baseline.json")
    FG.write_baseline(bp, {"gate_corpus": {
        "n": 3, "wall_s": 0.4, "metrics": dict(BASE_METRICS)}})
    return bp


def test_freshness_gate_trips_on_2x_regression(tmp_path, capsys):
    """A 2x freshness regression with an unchanged wall (a stall on
    the fetch->queryable path, not a slower machine) must trip the
    bar (1.8 < 2.0)."""
    bp = _baseline(tmp_path)
    lp = tmp_path / "cand.jsonl"
    bad = dict(BASE_METRICS)
    bad["freshness_p50_ms"] *= 2.0
    bad["freshness_p99_ms"] *= 2.0
    _write_ledger(lp, 0.4, bad)
    rc = FG.main(["check", str(lp), "--baseline", bp])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 1 and not out["ok"]
    tripped = {r["metric"] for r in out["regressions"]}
    assert {"freshness_p50_ms", "freshness_p99_ms"} <= tripped
    assert "commit_p50_ms" not in tripped


def test_freshness_gate_calibration_absorbs_uniform_slowdown(
        tmp_path, capsys):
    """Everything 2x — wall included (a uniformly slower machine):
    the wall-ratio calibration cancels it, green."""
    bp = _baseline(tmp_path)
    lp = tmp_path / "cand.jsonl"
    slow = {k: v * 2.0 for k, v in BASE_METRICS.items()}
    _write_ledger(lp, 0.8, slow)
    rc = FG.main(["check", str(lp), "--baseline", bp])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["ok"]
    assert out["calibration"] == pytest.approx(2.0)
    assert out["checked_metrics"] >= 4


def test_freshness_gate_noise_floor(tmp_path, capsys):
    """Sub-floor-vs-sub-floor jitter cannot trip; a tiny metric
    regressing to something LARGE still does (floored baseline, the
    span_diff rule)."""
    bp = str(tmp_path / "b.json")
    FG.write_baseline(bp, {"gate_corpus": {
        "n": 3, "wall_s": 0.4,
        "metrics": {**BASE_METRICS, "freshness_p50_ms": 0.02}}})
    lp = tmp_path / "c1.jsonl"
    _write_ledger(lp, 0.4, {**BASE_METRICS, "freshness_p50_ms": 0.04})
    assert FG.main(["check", str(lp), "--baseline", bp]) == 0
    capsys.readouterr()
    lp2 = tmp_path / "c2.jsonl"
    _write_ledger(lp2, 0.4, {**BASE_METRICS, "freshness_p50_ms": 5.0})
    rc = FG.main(["check", str(lp2), "--baseline", bp])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 1
    assert any(r["metric"] == "freshness_p50_ms"
               for r in out["regressions"])


def test_freshness_gate_saturated_calibration_skips(tmp_path, capsys):
    """A >5x wall shift clamps the calibration: explicit skip (exit
    0 + skipped), never a phantom regression."""
    bp = _baseline(tmp_path)
    lp = tmp_path / "cand.jsonl"
    _write_ledger(lp, 4.0, {k: v * 10.0 for k, v in
                            BASE_METRICS.items()})
    rc = FG.main(["check", str(lp), "--baseline", bp])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["ok"] and "skipped" in out
    assert out["calibration_saturated"] is True


def test_freshness_gate_env_mismatch_exit_3(tmp_path, capsys):
    """The shared span_diff environment pin: a baseline captured on a
    foreign backend fails LOUDLY with exit 3."""
    bp = str(tmp_path / "b.json")
    FG.write_baseline(bp, {"gate_corpus": {
        "n": 3, "wall_s": 0.4, "metrics": dict(BASE_METRICS)}},
        env={"jax_platforms": "tpu", "x64": False, "backend": "tpu"})
    lp = tmp_path / "cand.jsonl"
    _write_ledger(lp, 0.4, BASE_METRICS)
    assert FG.main(["check", str(lp), "--baseline", bp]) == \
        FG.EXIT_ENV_MISMATCH
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["env_mismatch"]


def test_freshness_gate_newest_records_win(tmp_path, capsys):
    """Append-only ledgers: accumulated green history must not
    out-vote a fresh regression (aggregate only the newest --last)."""
    bp = _baseline(tmp_path)
    lp = tmp_path / "cand.jsonl"
    _write_ledger(lp, 0.4, BASE_METRICS, n=20)        # long green past
    bad = {k: (v * 2.0 if k.startswith("freshness") else v)
           for k, v in BASE_METRICS.items()}
    _write_ledger(lp, 0.4, bad, n=5)                  # fresh regression
    assert FG.main(["check", str(lp), "--baseline", bp]) == 1
    capsys.readouterr()


# ---------------------------------------------------------------------------
# fleet rollup trends the per-table freshness percentiles
# ---------------------------------------------------------------------------

def test_rollup_trends_freshness_percentiles():
    from pinot_tpu.cluster.rollup import aggregate_tables
    recs = [uledger.make_record(
        "ingest_stats", table="rt_events", rows=500, rows_per_s=2500.0,
        freshness_ms=0.5, commits=4, commit_retries=0, faults_fired=0,
        freshness_p50_ms=0.41, freshness_p99_ms=1.9)]
    tables = aggregate_tables(recs)
    assert tables["rt_events"]["freshness_ms"] == 0.5
    assert tables["rt_events"]["freshness_p50_ms"] == 0.41
    assert tables["rt_events"]["freshness_p99_ms"] == 1.9
    # records without the percentiles stay trendable (pre-round-16)
    old = [uledger.make_record(
        "ingest_stats", table="legacy", rows=1, rows_per_s=1.0,
        freshness_ms=2.0, commits=0, commit_retries=0, faults_fired=0)]
    assert "freshness_p50_ms" not in aggregate_tables(old)["legacy"]


# ---------------------------------------------------------------------------
# nightly: sustained multi-backend chaos soak
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_loadgen_multibackend_chaos_soak(tmp_path):
    """~60s nightly lane: every wire-protocol transport sustains a
    chaos-armed, rate-paced, queried multi-partition run byte-exact."""
    for backend in ("mem", "wire", "kafka", "kinesis", "pulsar"):
        cfg = LG.LoadgenConfig(
            tables=[LG.TableLoadSpec(f"soak_{backend}_a", partitions=2,
                                     backend=backend),
                    LG.TableLoadSpec(f"soak_{backend}_u", partitions=2,
                                     upsert=True, protocol=True,
                                     backend=backend)],
            seed=60, rows_per_partition=1200, rate_rows_s=300.0,
            query_concurrency=2,
            fault_plan=ingest_plan(60, protocol=True), max_wall_s=90)
        s = LG.run_load(str(tmp_path / backend), cfg)
        assert s["ok"] and s["oracle_ok"], \
            f"{backend}: {s.get('error', 'oracle mismatch')}"
        assert s["queries"] >= 1
