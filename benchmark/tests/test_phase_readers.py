"""The readers of the program's phase counters, on made-up records: what
each computes, that a program from before the counters reports nothing,
that a renamed phase fails loudly, and that the seven shares of a query's
latency add up to it when every boundary is metered."""
import types

import pytest

from benchmark import catalog as cat

C = cat.Catalog()
SEVEN = ["client_hop_ms_per_query", "broker_ms_per_query",
         "wire_ms_per_query", "serde_ms_per_query",
         "server_plan_ms_per_query", "dispatch_host_ms_per_query",
         "device_wait_ms_per_query"]
NEW = SEVEN + ["kernel_dispatches_per_query"]


def records(counters, latencies_ms=(200.0, 300.0)):
    reqs = [types.SimpleNamespace(latency_ms=ms) for ms in latencies_ms]
    return types.SimpleNamespace(counters=dict(counters), requests=reqs)


def nested_counters(n_requests=2):
    """Microseconds of a made-up window in which every phase nests with no
    remainder: a parent is exactly the sum of its children."""
    us = {"device_execute": 150_000, "device_transfer": 190_000,
          "dispatch_prepare": 20_000, "extract_partial": 8_000,
          "server_queue": 600, "server_parse": 1_400, "planning": 3_000,
          "server_encode": 5_000, "wire_decode": 4_000,
          "broker_parse": 1_000, "broker_route": 500, "broker_select": 700,
          "reduce": 9_000, "broker_respond": 2_800}
    us["execution"] = (us["device_execute"] + us["device_transfer"]
                       + us["dispatch_prepare"] + us["extract_partial"])
    us["server_http"] = (us["server_queue"] + us["server_parse"]
                         + us["planning"] + us["execution"]
                         + us["server_encode"])
    us["scatter_call"] = us["server_http"] + 6_000          # the wire
    us["scatter"] = us["scatter_call"] + us["wire_decode"] + 1_000
    us["broker_query"] = (us["broker_parse"] + us["broker_route"]
                          + us["broker_select"] + us["scatter"]
                          + us["reduce"] + us["broker_respond"])
    out = {"phase_us_" + k: v for k, v in us.items()}
    out.update({"phase_n_" + k: n_requests for k in us})
    out["kernel_dispatches"] = 7
    out["compiles_total"] = 0
    return out


def test_phase_ms_sums_and_subtracts():
    read = C.reader("dispatch_host_ms_per_query")
    rec = records(nested_counters())
    assert read(rec) == pytest.approx((20_000 + 8_000) / 1e3 / 2)
    assert C.reader("device_wait_ms_per_query")(rec) == pytest.approx(170.0)
    assert C.reader("wire_ms_per_query")(rec) == pytest.approx(3.0)
    assert C.reader("serde_ms_per_query")(rec) == pytest.approx(4.5)


def test_counter_per_request():
    rec = records(nested_counters())
    assert C.reader("kernel_dispatches_per_query")(rec) == 3.5


def test_client_hop_is_latency_less_the_broker():
    counters = nested_counters()
    rec = records(counters)
    broker_ms = counters["phase_us_broker_query"] / 1e3 / 2
    assert C.reader("client_hop_ms_per_query")(rec) == \
        pytest.approx(250.0 - broker_ms)


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_phase_counters_reports_nothing(metric):
    """The parent of the PR that brought the phases: its counters hold
    none of them, and the line leaves the metric out."""
    rec = records({"compiles_total": 0, "plan_cache_hits": 12})
    assert C.reader(metric)(rec) is None


@pytest.mark.parametrize("metric", NEW)
def test_a_missing_counter_is_an_error_never_zero(metric):
    spec = cat._json(f"{cat.HERE}/metrics/{metric}.json")
    args = spec.get("args", {})
    named = (args.get("phases", []) + args.get("minus", [])
             or [args.get("counter", "broker_query")])
    counters = nested_counters()
    victim = named[0] if named[0] in counters else "phase_us_" + named[0]
    del counters[victim]
    with pytest.raises(KeyError):
        C.reader(metric)(records(counters))


def test_no_requests_reports_nothing():
    rec = records(nested_counters(), latencies_ms=())
    assert all(C.reader(m)(rec) is None for m in NEW)


@pytest.mark.parametrize("remainder_us", [0, 3_000])
def test_the_seven_close_on_the_mean_latency(remainder_us):
    """With every boundary metered the seven shares sum to the mean client
    latency; time no phase covers (a missing boundary) opens the sum by
    exactly that much."""
    counters = nested_counters()
    counters["phase_us_broker_query"] += remainder_us   # broker self time
    rec = records(counters)
    total = sum(C.reader(m)(rec) for m in SEVEN)
    assert total == pytest.approx(250.0 - remainder_us / 1e3 / 2)


def test_the_new_metrics_are_declared_for_both_cells():
    for m in NEW:
        entry = C.per_layer[m]
        assert entry["workloads"] == ["ssb1.suite_c1", "ssb1.q1_scan_c1"]
        assert entry["better"] == "lower"
        assert entry["source"] in ("program_span", "program_counter")
    assert list(C.per_layer)[-len(NEW):] == NEW
