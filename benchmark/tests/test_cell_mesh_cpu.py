"""The four-chip cell on four virtual CPU devices (``benchmark/conftest.py``
sets them): the table spread four segments a device, every statement one
mesh program and none a fallback, the control in float32 not correct, and
the readers of the metrics the cell brought. ``test_cells_cpu.py`` walks
the cell too (its seeds, the planted faults), one segment a device. No
run here is a result of the benchmark and nothing here is a speed.
"""
import numpy as np
import pytest

from benchmark import catalog as cat
from benchmark import run
from benchmark.tests.inplace import ReferenceInPlace
from benchmark.trace import reduce as R

CELL = "ssb4.suite_c1"
C = cat.Catalog()
# 16 segments, four a device, as the cell has them; 2^15 rows a segment
# keep every dictionary whole, which the mesh's combine needs
TINY16 = {"rows": 1 << 19, "segments": 16}
MESH = ["kernel_dispatches_mesh_dense", "kernel_dispatches_mesh_compact",
        "kernel_dispatches_mesh_compact_per_segment"]
BROUGHT = ["mesh_dispatches_per_query", "mesh_per_segment_share",
           "mesh_fallbacks_per_query", "distributed_execute_ms_per_query"]
SEVEN = ["client_hop_ms_per_query", "broker_ms_per_query",
         "wire_ms_per_query", "serde_ms_per_query",
         "server_plan_ms_per_query", "dispatch_host_ms_per_query",
         "device_wait_ms_per_query"]


class WithProgramMetrics(cat.Catalog):
    """An untraced run that also reads the per-layer metrics that need no
    device trace: the program's counters and spans."""

    def metrics_for(self, cell, traced):
        return super().metrics_for(cell, traced) + [
            m for m in super().metrics_for(cell, True)
            if m["source"] in ("program_counter", "program_span")]


def counters():
    from pinot_tpu.utils.metrics import global_metrics
    return dict(global_metrics.snapshot()["counters"])


def walk(seed, seconds=0.5, **kw):
    return run.run_cell(CELL, seed, seconds, False, check_chip=False,
                        **{"catalog": C, "config_override": TINY16, **kw})


@pytest.mark.parametrize("seed", [1, 3_000_000_019, 4_294_967_295])
def test_four_segments_a_device_answer_as_the_reference(seed):
    before = counters()
    res = walk(seed)
    after = counters()
    assert res["correct"], (seed, res["compared"])
    assert res["failed"] == 0 and res["attempted"] > 0
    moved = {k: after.get(k, 0) - before.get(k, 0)
             for k in MESH + ["mesh_fallbacks", "kernel_dispatches"]}
    assert moved["mesh_fallbacks"] == 0
    # warm-up and window alike: a statement is one mesh program
    assert sum(moved[k] for k in MESH) == moved["kernel_dispatches"]
    assert moved["kernel_dispatches"] >= res["attempted"] + 13


def test_the_cell_reports_its_end_to_end_metrics_and_four_devices():
    res = walk(37)
    assert set(res["metrics"]) == {m["name"] for m in
                                   C.metrics_for(CELL, False)}
    assert res["device"]["count"] >= C.cell(CELL)["chips"] == 4


@pytest.mark.parametrize("seed", [7])
def test_the_control_in_float32_is_not_correct(seed):
    control = walk(seed, wrap_system=lambda system, own: ReferenceInPlace(
        system, own, round_to=np.float32))
    assert not control["correct"]
    assert control["compared"]["wrong_answers"]["value"] > 0
    exact = walk(seed, wrap_system=lambda system, own: ReferenceInPlace(
        system, own))
    assert exact["correct"]


@pytest.fixture(scope="module")
def rehearsal():
    """One walk whose line also carries the counter and span metrics."""
    return walk(41, catalog=WithProgramMetrics())["metrics"]


@pytest.mark.parametrize("metric", BROUGHT + SEVEN
                         + ["kernel_dispatches_per_query"])
def test_a_rehearsal_gives_every_program_metric_a_number(rehearsal, metric):
    assert isinstance(rehearsal[metric]["value"], float)
    assert rehearsal[metric]["unit"] == C.per_layer[metric]["unit"]


def test_the_rehearsals_numbers_say_one_mesh_program_a_query(rehearsal):
    value = {k: v["value"] for k, v in rehearsal.items()}
    assert value["mesh_dispatches_per_query"] == pytest.approx(1.0, abs=0.1)
    assert value["kernel_dispatches_per_query"] == \
        value["mesh_dispatches_per_query"]
    assert value["mesh_fallbacks_per_query"] == 0.0
    # a local shard of 4 x 2^15 rows is under the sort core's row limit
    assert value["mesh_per_segment_share"] == 0.0
    assert 0 < value["distributed_execute_ms_per_query"] \
        <= value["dispatch_host_ms_per_query"] \
        + value["device_wait_ms_per_query"]
    assert all(value[m] > 0 for m in SEVEN)


def test_the_routed_sort_core_shows_in_the_share(monkeypatch):
    """With the program's row limit under the local shard's rows the
    sort-core statements (q3.2-q3.4, q4.3: 4 of 13) take the per-segment
    route inside the mesh program, and the cell stays correct."""
    from pinot_tpu.ops import kernels
    monkeypatch.setattr(kernels, "SEGMENTED_SORT_ROW_LIMIT", 1 << 16)
    # a window long enough for the whole suite, more than once
    res = walk(43, seconds=4.0, catalog=WithProgramMetrics())
    assert res["correct"] and res["attempted"] >= 13
    value = {k: v["value"] for k, v in res["metrics"].items()}
    assert 100 * 4 / 13 - 12 <= value["mesh_per_segment_share"] \
        <= 100 * 4 / 13 + 12
    assert value["mesh_fallbacks_per_query"] == 0.0


def records(counters, n=2):
    import types
    reqs = [types.SimpleNamespace(latency_ms=100.0) for _ in range(n)]
    return types.SimpleNamespace(counters=dict(counters), requests=reqs)


def test_counters_per_request_on_written_out_counters():
    have = {"phase_us_execution": 5, MESH[0]: 3, MESH[1]: 6, MESH[2]: 4}
    rec = records(have, n=13)
    assert C.reader("mesh_dispatches_per_query")(rec) == 1.0
    assert C.reader("mesh_per_segment_share")(rec) == \
        pytest.approx(100 * 4 / 13)
    # a counter that never moved is absent: a fault counter reads 0
    assert C.reader("mesh_fallbacks_per_query")(rec) == 0.0
    rec.counters["mesh_fallbacks"] = 13
    assert C.reader("mesh_fallbacks_per_query")(rec) == 1.0
    # two of the three routes never taken: absent, and 0
    assert C.reader("mesh_dispatches_per_query")(records(
        {"phase_us_execution": 5, MESH[0]: 13}, n=13)) == 1.0


@pytest.mark.parametrize("metric", BROUGHT)
def test_a_program_without_the_mesh_reports_nothing(metric):
    """The parent: phase counters, no mesh counter (and before PR 25, no
    phase counter either): the line leaves the metric out. Only the fault
    counter reads 0 there, as on any program that never falls back."""
    before_phases = records({"compiles_total": 0})
    assert C.reader(metric)(before_phases) is None
    parent = records({"phase_us_execution": 5, "phase_us_planning": 1,
                      "kernel_dispatches": 9})
    if metric == "mesh_fallbacks_per_query":
        assert C.reader(metric)(parent) == 0.0
    elif metric == "distributed_execute_ms_per_query":
        with pytest.raises(KeyError):    # phase_ms: a renamed phase is loud
            C.reader(metric)(parent)
    else:
        assert C.reader(metric)(parent) is None
        assert C.reader(metric)(records(parent.counters, n=0)) is None


def _trace(ops_by_device, requests, window=(0.0, 10.0)):
    ns = lambda s: s * 1e9      # noqa: E731
    planes = [{"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Ops",
         "events": [[n, ns(s), ns(e - s), {}] for n, s, e in ops]}]}
        for i, ops in enumerate(ops_by_device)]
    planes.append({"name": "/host:CPU", "lines": [{"name": "python3",
                   "events": [["bench_window", ns(window[0]),
                               ns(window[1] - window[0]), {}]] + [
        ["bench_request", ns(s), ns(e - s), {"shape": sh, "key": sh}]
        for sh, s, e in requests]}]})
    return {"planes": planes}


def test_collective_ms_reads_the_collectives_self_time():
    """Two devices, two requests: the collectives' self time (a fusion
    nested in an all-reduce is not the all-reduce's), averaged over the
    devices; a fusion that only names a collective's result is not one."""
    import types
    dev0 = [("%fusion.3 = s32[8] fusion(%all-reduce.1)", 0.0, 2.0),
            ("%all-reduce.1 = s64[7000] all-reduce(%x)", 2.0, 3.0),
            ("%all-reduce-start.2 = s64[4] all-reduce-start(%y)", 4.0, 4.5),
            ("%all-reduce-done.2 = s64[4] all-reduce-done(%z)", 5.0, 5.25),
            ("%all-gather.7 = s32[16] all-gather(%w)", 6.0, 7.0),
            ("%fusion.9 = s32[16] fusion(%w)", 6.5, 6.75)]
    dev1 = [("%collective-permute.1 = s32[4] collective-permute(%v)",
             1.0, 1.5),
            ("%reduce-scatter.4 = s32[4] reduce-scatter(%v)", 2.0, 2.25),
            ("%all-to-all.5 = s32[4] all-to-all(%v)", 3.0, 3.25),
            ("%reduce-window.36 = s32[4] reduce-window(%v)", 4.0, 9.0)]
    red = R.reduce_trace(_trace([dev0, dev1], [("q1.1", 0.0, 5.0),
                                               ("q2.1", 5.0, 9.0)]))
    read = C.reader("collective_ms_per_query")
    rec = types.SimpleNamespace(trace=red)
    on0 = 1.0 + 0.5 + 0.25 + (1.0 - 0.25)
    on1 = 0.5 + 0.25 + 0.25
    assert read(rec) == pytest.approx(1e3 * (on0 + on1) / 2 / 2)
    one_chip = R.reduce_trace(_trace([dev0[:1]], [("q1.1", 0.0, 5.0)]))
    assert read(types.SimpleNamespace(trace=one_chip)) is None
    assert read(types.SimpleNamespace(trace=None)) is None


def test_the_cells_metrics_are_declared():
    for m in BROUGHT + ["collective_ms_per_query"]:
        entry = C.per_layer[m]
        assert entry["workloads"] == [CELL]
        assert entry["layer"] == \
            "mesh program and collectives (parallel/distributed.py)"
        assert entry["moves"] in C.end_to_end
    for m in SEVEN + ["scan_roofline", "kernel_ms_per_query",
                      "device_idle_pct", "flight_p50_ms.q4"]:
        assert C.per_layer[m]["workloads"][-1] == CELL
    four = [w for w in C.doc["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in four] == [CELL]
