"""The reader of the program's self-CPU counters (``phase_cpu.py``) on
made-up records: ``host_cpu_ms_per_query`` sums every phase's self CPU,
each scaled from the wall time its clock was read over to its whole wall
time; ``host_offcpu_ms_per_query`` takes the named host-work phases' wall
time less their CPU time; a program from before the counters reports
nothing, a phase the window never crossed counts 0, and one crossed
without its CPU counters fails loudly."""
import types

import pytest

from benchmark import catalog as cat
from pinot_tpu.utils import phases as ph

C = cat.Catalog()
CPU, OFF = "host_cpu_ms_per_query", "host_offcpu_ms_per_query"


def records(counters, n_requests=2):
    reqs = [types.SimpleNamespace(latency_ms=100.0)] * n_requests
    return types.SimpleNamespace(counters=dict(counters), requests=reqs)


def window():
    """Two requests: three host-work leaves, two device-wait phases
    (CPU counted, wall not read by the off-CPU metric) and the queue
    (``record_phase``: wall and crossings, no CPU counters). Each phase's
    clock was read over half its wall time (``phase_cpu_wall_us_<p>``),
    in which it was on a CPU for ``phase_cpu_us_<p>``."""
    wall = {"planning": 6_000, "server_encode": 10_000,
            "broker_parse": 2_000, "device_transfer": 40_000,
            "execution": 70_000, "server_queue": 9_000}
    cpu = {"planning": 2_500, "server_encode": 2_000, "broker_parse": 750,
           "device_transfer": 150, "execution": 600}
    out = {"phase_us_" + p: v for p, v in wall.items()}
    out.update({"phase_n_" + p: 2 for p in wall})
    out.update({"phase_cpu_us_" + p: v for p, v in cpu.items()})
    out.update({"phase_cpu_wall_us_" + p: wall[p] // 2 for p in cpu})
    return out


def test_cpu_sums_every_phase_scaled_to_its_wall():
    assert C.reader(CPU)(records(window())) == pytest.approx(
        2 * (2_500 + 2_000 + 750 + 150 + 600) / 1e3 / 2)


def test_offcpu_is_wall_less_cpu_of_the_host_work_phases():
    assert C.reader(OFF)(records(window())) == pytest.approx(
        ((6_000 - 5_000) + (10_000 - 4_000) + (2_000 - 1_500)) / 1e3 / 2)


def test_a_phase_never_read_in_the_window_counts_zero_cpu():
    counters = window()
    counters["phase_cpu_us_planning"] = 0
    counters["phase_cpu_wall_us_planning"] = 0
    assert C.reader(OFF)(records(counters)) == pytest.approx(
        (6_000 + (10_000 - 4_000) + (2_000 - 1_500)) / 1e3 / 2)


@pytest.mark.parametrize("metric", [CPU, OFF])
def test_a_parent_without_cpu_counters_reads_none(metric):
    old = {k: v for k, v in window().items()
           if not k.startswith("phase_cpu_us_")}
    assert C.reader(metric)(records(old)) is None


@pytest.mark.parametrize("metric", [CPU, OFF])
def test_no_requests_reads_none(metric):
    assert C.reader(metric)(records(window(), n_requests=0)) is None


def test_an_uncrossed_phase_counts_zero():
    """A listed phase with no crossing has no counters; one whose
    counters exist and did not move reads the same."""
    counters = window()
    p = ph.WIRE_DECODE
    assert p in ph.HOST_WORK_PHASES and "phase_n_" + p not in counters
    before = C.reader(OFF)(records(counters))
    counters["phase_n_" + p] = 0          # moved by nothing
    counters["phase_us_" + p] = 0
    counters["phase_cpu_us_" + p] = 0
    counters["phase_cpu_wall_us_" + p] = 0
    assert C.reader(OFF)(records(counters)) == before


@pytest.mark.parametrize("metric", [CPU, OFF])
@pytest.mark.parametrize("victim", ["phase_cpu_us_planning",
                                    "phase_cpu_wall_us_server_encode"])
def test_a_crossed_phase_without_its_cpu_counter_raises(metric, victim):
    counters = window()
    del counters[victim]
    with pytest.raises(KeyError):
        C.reader(metric)(records(counters))


def test_the_metrics_phase_list_is_the_programs():
    spec = cat._json(f"{cat.HERE}/metrics/{OFF}.json")
    assert spec["args"]["phases"] == list(ph.HOST_WORK_PHASES)
    assert spec["args"]["off_cpu"] is True
    assert cat._json(f"{cat.HERE}/metrics/{CPU}.json")["args"] == {}


# the off-CPU split only where it was cross-checked against readings that
# do not rest on the thread CPU clock: the 8-client cell (PERF.md, PR 37)
@pytest.mark.parametrize("metric,cells", [
    (CPU, None), (OFF, ["ssb1.dash_c8"])])
def test_declared_cells(metric, cells):
    m = C.per_layer[metric]
    assert m["workloads"] == (cells or list(C.cells))
    assert m["source"] == "program_counter" and m["unit"] == "ms/query"
