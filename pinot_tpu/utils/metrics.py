"""Metrics: counters and gauges with a global registry.

Reference parity: pinot-common/.../metrics/AbstractMetrics.java +
pinot-spi metrics SPI (pluggable yammer/dropwizard backends). The registry
snapshot serves the /metrics endpoints of the cluster roles; a Prometheus
text formatter is a render method away.
"""
from __future__ import annotations

import re
import threading
import time
from typing import Any, Dict, Optional


class MetricsRegistry:
    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = {}
        self._gauges: Dict[str, float] = {}
        # per-gauge last-update timestamp (monotonic seconds): a gauge
        # value alone cannot distinguish "freshness 50 ms" from
        # "freshness gauge dead for 10 minutes" — the SLO plane
        # (utils/slo.py) trips the freshness objective on stale gauges
        # instead of silently passing them. ``_now`` is injectable so
        # staleness tests don't sleep.
        self._gauge_ts: Dict[str, float] = {}
        self._now = time.monotonic  # guarded-by: none — test injection

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def count_pair(self, a: str, na: int, b: str, nb: int) -> None:
        """Two counters under one lock acquisition: a phase's elapsed
        microseconds and its call count, a kernel launch and its
        family's (utils/spans.py)."""
        with self._lock:
            c = self._counters
            c[a] = c.get(a, 0) + na
            c[b] = c.get(b, 0) + nb

    def count_four(self, a: str, na: int, b: str, nb: int,
                   c3: str, n3: int, c4: str, n4: int) -> None:
        """Four counters under one lock acquisition: a phase crossing's
        elapsed microseconds, its call count, its self CPU and the wall
        time the CPU was read over (utils/spans.phase)."""
        with self._lock:
            c = self._counters
            c[a] = c.get(a, 0) + na
            c[b] = c.get(b, 0) + nb
            c[c3] = c.get(c3, 0) + n3
            c[c4] = c.get(c4, 0) + n4

    def gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = value
            self._gauge_ts[name] = self._now()

    def remove_gauge(self, name: str) -> None:
        """Drop a gauge (no-op when absent): a stopped table's last
        freshness EWMA must not pin console rollups forever, and table
        churn must not grow the gauge set without bound."""
        with self._lock:
            self._gauges.pop(name, None)
            self._gauge_ts.pop(name, None)

    def gauge_age_s(self, name: str) -> Optional[float]:
        """Seconds since the gauge was last written (None when the
        gauge does not exist) — the dead-gauge signal."""
        with self._lock:
            ts = self._gauge_ts.get(name)
            if ts is None:
                return None
            return max(self._now() - ts, 0.0)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            # ``gauge_age_s`` rides beside ``gauges`` (a NEW key — every
            # existing consumer reads ``gauges`` as plain name->float
            # and keeps working): seconds since each gauge's last write,
            # so snapshot readers can spot a dead gauge
            now = self._now()
            return {"counters": dict(self._counters),
                    "gauges": dict(self._gauges),
                    "gauge_age_s": {
                        k: round(max(now - ts, 0.0), 3)
                        for k, ts in self._gauge_ts.items()}}

    def prometheus(self) -> str:
        return render_prometheus(self.snapshot())


INGEST_COUNTERS = (
    "ingest_rows", "ingest_commits", "ingest_commit_retries",
    "ingest_commit_failures", "ingest_rebalance_resets",
    "ingest_stream_retries", "ingest_upsert_replays",
    "ingest_orphans_cleaned", "ingest_handoff_retries",
    # a consumer thread surviving errors past its bounded retries: the
    # wedged-consumer signal must surface where operators look
    "ingest_consume_errors",
)


def ingest_health(snapshot: Dict[str, Any]) -> Dict[str, Any]:
    """The realtime-plane health block the broker /metrics endpoint and
    both consoles render next to the round-9 scatter counters: recovery
    counters (realtime/manager.py ``ingest_*``) + the end-to-end
    freshness gauges (per table; ``freshness_ms`` is the WORST table —
    the operationally interesting number when several share a
    process)."""
    c = snapshot["counters"]
    out: Dict[str, Any] = {k: c.get(k, 0) for k in INGEST_COUNTERS}
    prefix = "ingest_freshness_ms_"
    by_table = {k[len(prefix):]: v for k, v in snapshot["gauges"].items()
                if k.startswith(prefix)}
    out["freshness_by_table"] = by_table
    out["freshness_ms"] = max(by_table.values()) if by_table else None
    # gauge staleness (ISSUE 17): seconds since each freshness gauge
    # last moved — a frozen gauge under live ingest is a dead writer,
    # and the SLO freshness objective trips on it instead of trusting
    # the last value forever
    ages = snapshot.get("gauge_age_s") or {}
    out["freshness_age_s"] = {k[len(prefix):]: v
                              for k, v in ages.items()
                              if k.startswith(prefix)}
    return out


OVERLOAD_COUNTERS = (
    "overload_shed", "overload_brownout_clamped",
    "overload_retries_suppressed", "scheduler_rejected",
)


def overload_health(snapshot: Dict[str, Any]) -> Dict[str, Any]:
    """The overload-protection block (broker/workload.py) the broker
    /metrics endpoint and both consoles render: shed totals, the
    current degradation rung, shed counts by rung, and per-tenant
    shed counters / in-flight gauges. Tenant names embed in metric
    names (``tenant_shed_<tenant>``) — the Prometheus renderer
    sanitizes them through ``_prom_name``."""
    c = snapshot["counters"]
    g = snapshot["gauges"]
    out: Dict[str, Any] = {k: c.get(k, 0) for k in OVERLOAD_COUNTERS}
    out["rung"] = g.get("overload_rung", 0)
    out["pressure"] = g.get("overload_pressure", 0.0)
    # derived from whatever rung counters exist: budget sheds
    # (inflight/cpu/bytes/retry) land on the CURRENT rung — 0/1
    # included — and the breakdown must sum to the shed total
    rung_prefix = "overload_shed_rung_"
    out["shed_by_rung"] = {k[len(rung_prefix):]: v
                           for k, v in c.items()
                           if k.startswith(rung_prefix)}
    shed_prefix = "tenant_shed_"
    out["shed_by_tenant"] = {k[len(shed_prefix):]: v
                             for k, v in c.items()
                             if k.startswith(shed_prefix)}
    infl_prefix = "tenant_inflight_"
    out["inflight_by_tenant"] = {k[len(infl_prefix):]: v
                                 for k, v in g.items()
                                 if k.startswith(infl_prefix)}
    return out


def _prom_name(name: str) -> str:
    """Sanitize to the Prometheus metric-name alphabet: registry names
    may embed user-supplied strings (ingest_freshness_ms_<table>), and
    one illegal character would make Prometheus reject the whole
    scrape."""
    return re.sub(r"[^a-zA-Z0-9_:]", "_", name)


def render_prometheus(snapshot: Dict[str, Any],
                      prefix: str = "pinot_tpu") -> str:
    """Prometheus exposition text from a snapshot — the ONE place the
    name/suffix rules live (the /metrics endpoints and the textfile sink
    both render through here)."""
    lines = []
    for k, v in snapshot["counters"].items():
        lines.append(f"{prefix}_{_prom_name(k)}_total {v}")
    for k, v in snapshot["gauges"].items():
        lines.append(f"{prefix}_{_prom_name(k)} {v}")
    return "\n".join(lines) + "\n"


global_metrics = MetricsRegistry()
