"""Shared bench harness: the backend check, the capture log and the
capture guard.

A bench measures the chip. ``require_backend`` checks, in this process,
that JAX's platform is ``tpu`` and exits 1 otherwise — before any data is
built, naming what it found. One process holds the chip from start to
end: nothing here probes in a subprocess, retries or carries on on the
CPU, and ``finish()`` starts no JAX child while the backend is ``tpu``.
The one exception is an explicit ``PINOT_BENCH_FORCE_CPU=1`` correctness
rehearsal, whose every output says ``"backend": "cpu"``.

The capture log (append-only JSONL, schema in pinot_tpu/utils/ledger.py)
records every capture's per-query kernel/e2e/cpu-baseline times; each
bench prints deltas vs the previous same-metric capture so baseline
drift is explained the moment it happens. Its default path is
``pinot_tpu.utils.ledger.default_capture_log()`` — never the
checkout-root ``PERF_LEDGER.jsonl``, which is the driver's record.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

from pinot_tpu.utils.ledger import default_capture_log

REPO = os.path.dirname(os.path.abspath(__file__))
LEDGER = default_capture_log()


def _force_cpu() -> bool:
    """PINOT_BENCH_FORCE_CPU=1: an explicit CPU correctness rehearsal."""
    return os.environ.get("PINOT_BENCH_FORCE_CPU") == "1"


def require_backend(metric: str) -> str:
    """Gate a bench run on the chip; returns the platform name.

    Exits 1 unless ``jax.devices()[0].platform`` is ``tpu`` (with
    JAX_PLATFORMS unset and libtpu failing to initialise, JAX itself
    drops to the CPU with a warning — that must not pass as a chip
    run). Under PINOT_BENCH_FORCE_CPU=1 the cpu platform is pinned
    before any backend initialises and ``cpu`` is returned."""
    import jax

    if _force_cpu():
        jax.config.update("jax_platforms", "cpu")
    try:
        platform = jax.devices()[0].platform
        detail = f"jax found platform {platform!r}"
    except RuntimeError as e:
        platform = None
        detail = f"jax backend failed to initialise: {e}"
    if platform == "tpu" or (_force_cpu() and platform == "cpu"):
        return platform
    print(json.dumps({
        "metric": metric, "value": 0, "error": "no_tpu_backend",
        "detail": detail + "; a bench runs on the chip (set "
                  "PINOT_BENCH_FORCE_CPU=1 for a CPU correctness "
                  "rehearsal)"}))
    sys.exit(1)


# ---------------------------------------------------------------------------
# Perf ledger
# ---------------------------------------------------------------------------

def ledger_last(metric: str, backend: str | None = None,
                n_rows: int | None = None) -> dict | None:
    """Most recent ledger entry for `metric`, or None.

    When backend/n_rows are given only comparable captures match —
    diffing a TPU capture against a tiny-row CPU smoke run would make
    every ratio meaningless.
    """
    if not os.path.exists(LEDGER):
        return None
    last = None
    with open(LEDGER) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("metric") != metric:
                continue
            if backend is not None and rec.get("backend") != backend:
                continue
            if n_rows is not None and rec.get("n_rows") != n_rows:
                continue
            if rec.get("ok") is False:  # failed captures are not a baseline
                continue
            last = rec
    return last


def ledger_append(out: dict, backend: str, ok: bool = True) -> None:
    """Append this capture as a validated v2 ``bench_capture`` record
    (pinot_tpu/utils/ledger.py — the ONE schema every writer shares)."""
    from pinot_tpu.utils import ledger as uledger

    fields = {
        "backend": backend,
        "ok": ok,
        "metric": out.get("metric") or "unknown",
        "value": out.get("value") if out.get("value") is not None else 0,
        "vs_baseline": out.get("vs_baseline"),
        "n_rows": out.get("n_rows"),
        "queries": out.get("queries"),
    }
    fields = {k: v for k, v in fields.items() if v is not None
              or k in ("metric", "value", "backend", "ok")}
    try:
        uledger.append_record(uledger.make_record("bench_capture",
                                                  **fields), LEDGER)
    except ValueError as e:
        # the capture tail must never die on a schema bug: fall back to
        # a legacy (no-"v") line, which check_ledger grandfathers
        print(f"  ledger: schema fallback ({e})", file=sys.stderr)
        fields["ts"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
        with open(LEDGER, "a") as f:
            f.write(json.dumps(fields) + "\n")


def ledger_deltas(out: dict, prev: dict | None) -> dict | None:
    """Per-query + headline deltas vs the previous same-metric capture.

    The point: when vs_baseline moves, say WHICH side
    moved — device time, end-to-end overhead, or the CPU baseline
    measurement itself — so drift is attributable at capture time.
    """
    if prev is None:
        return None
    delta = {
        "prev_ts": prev.get("ts"),
        "prev_backend": prev.get("backend"),
        "vs_baseline": (round(out["vs_baseline"] - prev["vs_baseline"], 2)
                        if prev.get("vs_baseline") is not None else None),
        "value_ratio": (round(out["value"] / prev["value"], 3)
                        if prev.get("value") else None),
    }
    pq = prev.get("queries") or {}
    shifts = {}
    for qid, d in (out.get("queries") or {}).items():
        p = pq.get(qid)
        if not p:
            continue
        row = {}
        for k in ("kernel_ms", "e2e_ms", "cpu_ms"):
            if d.get(k) and p.get(k):
                row[k] = round(d[k] / p[k], 3)  # ratio: >1 = slower now
        if row:
            shifts[qid] = row
    if shifts:
        delta["query_time_ratios"] = shifts
    return delta


def ledger_append_raw(rec: dict) -> None:
    """Append a record to the ledger with a timestamp. v2 records
    (carrying "v"/"kind" — see pinot_tpu/utils/ledger.py) are validated;
    anything else lands as a grandfathered legacy line."""
    rec = dict(rec)
    rec.setdefault("ts", time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                       time.gmtime()))
    if "v" in rec:
        from pinot_tpu.utils import ledger as uledger

        uledger.append_record(rec, LEDGER)
        return
    with open(LEDGER, "a") as f:
        f.write(json.dumps(rec) + "\n")


def attach_capture_context(out: dict, backend: str) -> dict:
    """Stamp the payload with where it ran: the platform, and the device
    as JAX reports it. Shared by finish() and the kill guard."""
    import jax

    out["backend"] = backend
    out["device_kind"] = jax.devices()[0].device_kind
    out["device_count"] = len(jax.devices())
    return out


# ---------------------------------------------------------------------------
# Capture guard: a killed bench still prints ONE valid summary JSON line
# ---------------------------------------------------------------------------

_GUARD: dict = {"payload_fn": None, "armed": False}


def install_capture_guard(payload_fn) -> None:
    """Arm a SIGTERM/SIGINT handler that prints the CURRENT summary JSON
    as the last stdout line before exiting, so a capture killed at its
    time limit (SIGTERM, then SIGKILL later) still ships a parseable,
    self-describing partial result instead of nothing. ``payload_fn``
    must return the complete summary dict."""
    import signal

    _GUARD.update(payload_fn=payload_fn, armed=True)

    def _handler(signum, _frame):
        if not _GUARD["armed"]:
            os._exit(1)
        _GUARD["armed"] = False
        try:
            out = _GUARD["payload_fn"]()
            out.setdefault("error",
                           f"capture interrupted by signal {signum}")
            sys.stdout.write(json.dumps(out) + "\n")
            sys.stdout.flush()
        except Exception:  # noqa: BLE001 — dying anyway; exit code says so
            pass
        os._exit(1)

    signal.signal(signal.SIGTERM, _handler)
    signal.signal(signal.SIGINT, _handler)


def disarm_capture_guard() -> None:
    _GUARD["armed"] = False


def span_regression_gate(ledger_path: str | None = None,
                         capture_if_empty: bool = True,
                         baseline_path: str | None = None) -> dict | None:
    """tools/span_diff.py check vs the checked-in
    tools/span_baseline.json — the per-phase regression gate, run at
    bench time so a phase regression fails THIS capture instead of
    waiting for a human to diff the next round. Checks ``ledger_path``'s
    query_trace records when they overlap the baseline corpus; bench
    ledgers normally carry none (bench_capture records only), so the
    gate then captures a fresh corpus run (span_diff capture, the same
    seeded queries the baseline was built from) and checks that —
    otherwise the gate would be a structurally vacuous green. Returns
    the check summary (ok flag included), or None when there is no
    baseline (vacuous pass)."""
    baseline = baseline_path or os.path.join(REPO, "tools",
                                             "span_baseline.json")
    ledger_path = ledger_path or LEDGER
    if not os.path.exists(baseline):
        return None
    span_diff = os.path.join(REPO, "tools", "span_diff.py")

    def run_check(path: str) -> dict:
        proc = subprocess.run(
            [sys.executable, span_diff, "check", path,
             "--baseline", baseline],
            capture_output=True, text=True, timeout=120)
        summary = json.loads(proc.stdout.strip().splitlines()[-1])
        if proc.returncode == 3:
            # span_diff's environment pin (exit 3): the baseline was
            # captured under a different backend/x64/JAX_PLATFORMS, so
            # the per-phase comparison is meaningless here — surface an
            # explicit skip (visible in the bench summary), never a
            # silent miscalibration and never a phantom regression
            return {"ok": True,
                    "skipped": "environment mismatch vs baseline — "
                               "re-capture in this environment",
                    "env_mismatch": summary.get("env_mismatch")}
        summary["ok"] = proc.returncode == 0
        return summary

    try:
        summary = None
        if os.path.exists(ledger_path):
            summary = run_check(ledger_path)
            summary["source"] = "ledger"
        if capture_if_empty and (
                summary is None or (not summary.get("shapes_checked")
                                    and not summary.get("skipped"))):
            tmp = os.path.join(
                tempfile.mkdtemp(prefix="ptpu_span_gate_"),
                "trace.jsonl")
            try:
                # the corpus must run in the SAME engine configuration
                # the checked-in baseline was captured under (the
                # span_diff docstring contract): tier-1 pins the CPU
                # scatter-core hedge OFF, while a bare bench shell
                # defaults it on — without the pin every group-by
                # shape's execution diffs core-vs-core, not
                # code-vs-code. Harmless on TPU backends, where
                # cpu_scatter_default is false either way.
                env = dict(os.environ)
                env["PINOT_CPU_FAST_GROUPBY"] = "0"
                proc = subprocess.run(
                    [sys.executable, span_diff, "capture",
                     "--out", tmp, "--iters", "3"],
                    env=env, capture_output=True, text=True, timeout=300)
                if proc.returncode != 0:
                    return {"ok": False, "error":
                            "capture failed: " + proc.stderr[-200:]}
                summary = run_check(tmp)
                summary["source"] = "capture"
            finally:
                shutil.rmtree(os.path.dirname(tmp), ignore_errors=True)
        return summary
    except Exception as e:  # noqa: BLE001 — a broken gate fails closed
        return {"ok": False, "error": f"{type(e).__name__}: {e}"}


def freshness_regression_gate(ledger_path: str | None = None,
                              capture_if_empty: bool = True,
                              baseline_path: str | None = None
                              ) -> dict | None:
    """tools/freshness_gate.py check vs the checked-in
    tools/freshness_baseline.json — the ingest-freshness ratchet, run at
    bench time beside the span gate. Checks ``ledger_path``'s
    ingest_bench records when they overlap the baseline's scenarios
    (bench_ingest.py runs land there); other benches' ledgers carry
    none, so the gate then captures a fresh gate-corpus run
    (freshness_gate capture — the same deterministic loadgen scenario
    the baseline was built from) and checks that. Returns the check
    summary, or None when there is no baseline (vacuous pass)."""
    baseline = baseline_path or os.path.join(REPO, "tools",
                                             "freshness_baseline.json")
    ledger_path = ledger_path or LEDGER
    if not os.path.exists(baseline):
        return None
    fgate = os.path.join(REPO, "tools", "freshness_gate.py")

    def run_check(path: str) -> dict:
        proc = subprocess.run(
            [sys.executable, fgate, "check", path,
             "--baseline", baseline],
            capture_output=True, text=True, timeout=120)
        summary = json.loads(proc.stdout.strip().splitlines()[-1])
        if proc.returncode == 3:
            # the shared span_diff environment pin (exit 3): baseline
            # captured under a different backend/x64 — explicit skip,
            # never a phantom regression
            return {"ok": True,
                    "skipped": "environment mismatch vs baseline — "
                               "re-capture in this environment",
                    "env_mismatch": summary.get("env_mismatch")}
        summary["ok"] = proc.returncode == 0
        return summary

    try:
        summary = None
        if os.path.exists(ledger_path):
            summary = run_check(ledger_path)
            summary["source"] = "ledger"
        if capture_if_empty and (
                summary is None or (not summary.get("scenarios_checked")
                                    and not summary.get("skipped"))):
            tmp = os.path.join(
                tempfile.mkdtemp(prefix="ptpu_fresh_gate_"),
                "ingest_bench.jsonl")
            try:
                env = dict(os.environ)
                # same engine pin as the span gate's corpus: the
                # baseline is captured in the tier-1 configuration
                env["PINOT_CPU_FAST_GROUPBY"] = "0"
                proc = subprocess.run(
                    [sys.executable, fgate, "capture",
                     "--out", tmp, "--iters", "3"],
                    env=env, capture_output=True, text=True, timeout=300)
                if proc.returncode != 0:
                    return {"ok": False, "error":
                            "capture failed: " + proc.stderr[-200:]}
                summary = run_check(tmp)
                summary["source"] = "capture"
            finally:
                shutil.rmtree(os.path.dirname(tmp), ignore_errors=True)
        return summary
    except Exception as e:  # noqa: BLE001 — a broken gate fails closed
        return {"ok": False, "error": f"{type(e).__name__}: {e}"}


def overload_regression_gate(ledger_path: str | None = None,
                             capture_if_empty: bool = True
                             ) -> dict | None:
    """tools/traffic_replay.py overload gate, run at bench time beside
    the span and freshness gates. Checks ``ledger_path``'s
    ``replay_bench`` records when present (a failed/regressed replay
    run must fail THIS capture); other benches' ledgers carry none, so
    the gate then runs a fresh local-mode replay (in-process broker,
    self-calibrating — pre-spike baseline and recovery bar are measured
    in-run, so no checked-in baseline file is needed). Returns the
    check summary, or None when the harness is absent."""
    replay = os.path.join(REPO, "tools", "traffic_replay.py")
    if not os.path.exists(replay):
        return None
    ledger_path = ledger_path or LEDGER

    def check_records(path: str) -> dict | None:
        import json as _json
        recs = []
        try:
            with open(path) as fh:
                for line in fh:
                    try:
                        rec = _json.loads(line)
                    except ValueError:
                        continue
                    if isinstance(rec, dict) and \
                            rec.get("kind") == "replay_bench":
                        recs.append(rec)
        except OSError:
            return None
        if not recs:
            return None
        bad = [r for r in recs[-3:]
               if not r.get("ok") or r.get("protected_sheds", 0)
               or r.get("recovered") is False]
        return {"ok": not bad, "records_checked": len(recs[-3:]),
                "source": "ledger",
                "failures": [r.get("error") or "not ok" for r in bad]}

    try:
        summary = check_records(ledger_path)
        if summary is not None or not capture_if_empty:
            return summary
        env = dict(os.environ)
        env["PINOT_CPU_FAST_GROUPBY"] = "0"
        proc = subprocess.run(
            [sys.executable, replay, "gate", "--mode", "local",
             "--queries", "32"],
            env=env, capture_output=True, text=True, timeout=300)
        res = json.loads(proc.stdout.strip().splitlines()[-1])
        return {"ok": proc.returncode == 0 and res.get("ok") is True,
                "source": "capture",
                "shed": res.get("shed"),
                "protected_sheds": res.get("protected_sheds"),
                "deterministic": res.get("deterministic"),
                "recovered": res.get("recovered"),
                "failures": res.get("failures") or []}
    except Exception as e:  # noqa: BLE001 — a broken gate fails closed
        return {"ok": False, "error": f"{type(e).__name__}: {e}"}


def warmup_debt_gate(ledger_path: str | None = None,
                     capture_if_empty: bool = True) -> dict | None:
    """tools/warmup_report.py gate over the bench ledger's
    compile_event records (ISSUE 15): post-warmup compiles (retrace /
    lru_evict_rebuild) fail the capture — the compile-storm leading
    indicator, ratcheted at bench time beside the span/freshness/
    overload gates. Bench ledgers without compile events get a fresh
    span-corpus capture (span_diff capture's in-process broker lands
    compile events in the same trace ledger automatically), so the
    gate is never structurally vacuous — the same
    fresh-capture-on-empty cost model the span/freshness/overload
    gates already pay per finish() (one --iters 1 corpus run here,
    cheaper than the span gate's own --iters 3 fallback)."""
    wreport = os.path.join(REPO, "tools", "warmup_report.py")
    if not os.path.exists(wreport):
        return None
    ledger_path = ledger_path or LEDGER

    def run_gate(path: str, min_events: int) -> dict:
        proc = subprocess.run(
            [sys.executable, wreport, "gate", path,
             "--min-events", str(min_events)],
            capture_output=True, text=True, timeout=120)
        summary = json.loads(proc.stdout.strip().splitlines()[-1])
        summary["ok"] = proc.returncode == 0
        return summary

    try:
        summary = None
        if os.path.exists(ledger_path):
            # min_events 0 here: an existing bench ledger legitimately
            # carries no compile events (bench_capture records only) —
            # the fresh-capture fallback below provides the
            # anti-vacuous corpus
            summary = run_gate(ledger_path, 0)
            summary["source"] = "ledger"
        if capture_if_empty and (summary is None
                                 or not summary.get("events")):
            tmp = os.path.join(
                tempfile.mkdtemp(prefix="ptpu_warmup_gate_"),
                "trace.jsonl")
            try:
                env = dict(os.environ)
                env["PINOT_CPU_FAST_GROUPBY"] = "0"
                span_diff = os.path.join(REPO, "tools", "span_diff.py")
                proc = subprocess.run(
                    [sys.executable, span_diff, "capture",
                     "--out", tmp, "--iters", "1"],
                    env=env, capture_output=True, text=True,
                    timeout=300)
                if proc.returncode != 0:
                    return {"ok": False, "error":
                            "capture failed: " + proc.stderr[-200:]}
                summary = run_gate(tmp, 1)
                summary["source"] = "capture"
            finally:
                shutil.rmtree(os.path.dirname(tmp), ignore_errors=True)
        return summary
    except Exception as e:  # noqa: BLE001 — a broken gate fails closed
        return {"ok": False, "error": f"{type(e).__name__}: {e}"}


def slo_gate(ledger_path: str | None = None) -> dict | None:
    """tools/slo_report.py gate over the bench ledger's query_stats
    corpus (ISSUE 17): the FIFTH gate beside span/freshness/overload/
    warmup. The bars come from the environment —
    ``PINOT_SLO_LATENCY_BAR_MS`` and/or ``PINOT_SLO_AVAILABILITY``
    (good-fraction target), plus optional ``PINOT_SLO_OBJECTIVE`` and
    ``PINOT_SLO_BURN_THRESHOLD`` — and with NEITHER bar configured the
    gate passes vacuously *and says so*: an SLO gate with no declared
    objective has nothing to judge, and inventing a default bar would
    fail every bench whose hardware this repo has never seen."""
    sreport = os.path.join(REPO, "tools", "slo_report.py")
    if not os.path.exists(sreport):
        return None
    bar = os.environ.get("PINOT_SLO_LATENCY_BAR_MS")
    avail = os.environ.get("PINOT_SLO_AVAILABILITY")
    if not bar and not avail:
        return {"ok": True, "skipped": "no SLO bars configured "
                "(PINOT_SLO_LATENCY_BAR_MS / PINOT_SLO_AVAILABILITY)"}
    ledger_path = ledger_path or LEDGER
    if not os.path.exists(ledger_path):
        return {"ok": True, "skipped": "no bench ledger to judge"}
    try:
        cmd = [sys.executable, sreport, "gate", ledger_path,
               # an existing bench ledger legitimately carries no
               # query_stats (bench_capture records only) — vacuity is
               # the tool's default; min-events 0 keeps this gate
               # judging only what the corpus actually recorded
               "--min-events", "0"]
        if bar:
            cmd += ["--latency-bar-ms", bar]
        if avail:
            cmd += ["--availability-objective", avail]
        if os.environ.get("PINOT_SLO_OBJECTIVE"):
            cmd += ["--objective", os.environ["PINOT_SLO_OBJECTIVE"]]
        if os.environ.get("PINOT_SLO_BURN_THRESHOLD"):
            cmd += ["--burn-threshold",
                    os.environ["PINOT_SLO_BURN_THRESHOLD"]]
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=120)
        summary = json.loads(proc.stdout.strip().splitlines()[-1])
        summary["ok"] = proc.returncode == 0
        return summary
    except Exception as e:  # noqa: BLE001 — a broken gate fails closed
        return {"ok": False, "error": f"{type(e).__name__}: {e}"}


# (summary key, gate, what a failure is called). All five are CPU
# regression tripwires pinned to CPU baselines (ROADMAP "Standing
# gates") that tier-1 already runs, and each starts JAX children.
_CPU_GATES = (
    ("span_gate", span_regression_gate,
     "span_diff phase-regression gate failed"),
    ("freshness_gate", freshness_regression_gate,
     "freshness_gate regression gate failed"),
    ("overload_gate", overload_regression_gate,
     "overload replay gate failed"),
    ("warmup_gate", warmup_debt_gate, "warmup-debt gate failed"),
    ("slo_gate", slo_gate, "SLO burn gate failed"),
)


def finish(out: dict, backend: str, all_ok: bool) -> None:
    """Shared tail: the CPU gates (forced-CPU rehearsal only), capture-log
    compare+append, print the ONE JSON line, exit."""
    disarm_capture_guard()
    if backend != "tpu":
        # a process that holds the chip starts no JAX child: on the chip
        # the gates are not called at all
        for key, gate_fn, what in _CPU_GATES:
            gate = gate_fn()
            if gate is None:
                continue
            # ALWAYS surfaced, including skips — a gate silently disabled
            # by a broken checker must be visible in the bench summary
            out[key] = gate
            if not gate.get("ok", True):
                all_ok = False
                why = gate.get("failures") or gate.get("regressions") \
                    or [gate.get("error", "not ok")]
                out.setdefault("error", f"{what}: "
                               + "; ".join(str(w) for w in why)[:200])
    prev = ledger_last(out["metric"], backend, out.get("n_rows"))
    d = ledger_deltas(out, prev)
    if d is not None:
        out["delta_vs_last"] = d
        print(f"  deltas vs {d['prev_ts']} ({d['prev_backend']}): "
              f"vs_baseline {d['vs_baseline']:+}"
              if d.get("vs_baseline") is not None else
              "  deltas vs last capture recorded", file=sys.stderr)
    attach_capture_context(out, backend)
    ledger_append(out, backend, ok=all_ok)
    if not all_ok:
        # keep a more specific error (capture failures) when present
        out.setdefault("error", "digest mismatch vs numpy oracle")
        print(json.dumps(out))
        sys.exit(1)
    print(json.dumps(out))
