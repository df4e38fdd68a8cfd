"""tools/trace_phases.py on a made-up trace: self times within a query,
idle gaps to the innermost open phase, device time by program and by
scope. The real thing is one ``.xplane.pb`` from the chip (PERF.md)."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import trace_phases as tp  # noqa: E402


def ev(name, start, end, **stats):
    return (name, float(start), float(end), stats)


# one window of 10 s, two requests; the second has a hole the program
# does not cover (the client's side) and the device idles through it
HOST = [
    ev("bench_window", 0, 10),
    ev("bench_request", 1, 4, shape="q1.1"),
    ev("pinot.broker_query", 1.1, 3.9, qid="a"),
    ev("pinot.scatter_call", 1.2, 3.8, qid="a"),
    ev("pinot.server_http", 1.3, 3.7, qid="a"),
    ev("pinot.execution", 1.4, 3.6, qid="a"),
    ev("pinot.dispatch_prepare", 1.4, 2.0, qid="a"),
    ev("pinot.device_execute", 2.0, 2.1, qid="a"),
    ev("pinot.device_transfer", 2.1, 3.5, qid="a"),
    ev("bench_request", 5, 9, shape="q2.1"),
    ev("pinot.broker_query", 6, 8, qid="b"),
    ev("pinot.execution", 6.5, 7.5, qid="b"),
]
OPS = [
    ev("fusion.1", 2.05, 3.05, tf_op="jit(pinot_dense_vmap)/pinot.mask/and"),
    ev("fusion.2", 3.05, 3.45,
       tf_op="jit(x)/pinot.aggregate/pinot.decode_dict/gather"),
    ev("broadcast", 6.6, 6.7),
]
MODS = [ev("jit_pinot_dense_vmap(17)", 2.05, 3.45),
        ev("jit_broadcast_in_dim(3)", 6.6, 6.7)]
DEVICES = {"/device:TPU:0": (OPS, MODS)}


@pytest.fixture(scope="module")
def tables():
    return tp.tables(HOST, DEVICES)


def test_header_counts(tables):
    assert tables["requests"] == 2 and tables["qids"] == 2
    assert tables["window_s"] == 10
    assert tables["busy_s"] == pytest.approx(1.5)
    assert tables["idle_s"] == pytest.approx(8.5)


@pytest.mark.parametrize("phase,count,total,own", [
    ("pinot.broker_query", 2, 4.8, 0.2 + 1.0),
    ("pinot.scatter_call", 1, 2.6, 0.2),
    ("pinot.execution", 2, 3.2, 0.1 + 1.0),
    ("pinot.device_transfer", 1, 1.4, 1.4),
])
def test_phase_self_time_is_duration_less_children(tables, phase, count,
                                                   total, own):
    row = {r[0]: r for r in tables["phases"]}[phase]
    assert row[1] == count
    assert row[2] == pytest.approx(total)
    assert row[3] == pytest.approx(own)
    assert row[4] == pytest.approx(1e3 * total / 2)


def test_idle_goes_to_the_innermost_open_phase(tables):
    idle = dict(tables["idle_by_phase"])
    # request a: idle 1.0-2.05 and 3.45-4.0
    assert idle["pinot.dispatch_prepare"] == pytest.approx(0.6)
    assert idle["pinot.device_execute"] == pytest.approx(0.05)
    assert idle["pinot.device_transfer"] == pytest.approx(0.05)
    assert idle["pinot.execution"] == pytest.approx(0.1 + 0.9)
    # what lies in a request and under no phase is listed, not dropped
    assert idle[tp.UNATTRIBUTED] == pytest.approx(0.1 + 0.1 + 1.0 + 1.0)
    assert idle[tp.NO_REQUEST] == pytest.approx(1.0 + 1.0 + 1.0)
    assert sum(idle.values()) == pytest.approx(tables["idle_s"])
    named = tables["idle_in_request_s"] - idle[tp.UNATTRIBUTED]
    assert tables["idle_named_share"] == pytest.approx(
        named / tables["idle_in_request_s"])


def test_idle_by_the_benchmarks_request_spans(tables):
    by = dict(tables["idle_by_request"])
    assert by["in_request:q1.1"] == pytest.approx(3.0 - 1.4)
    assert by["in_request:q2.1"] == pytest.approx(4.0 - 0.1)


def test_device_time_by_program_and_by_scope(tables):
    prog = dict(tables["device_by_program"])
    assert prog["jit_pinot_dense_vmap"] == pytest.approx(1.4)
    assert prog["jit_broadcast_in_dim"] == pytest.approx(0.1)
    scope = dict(tables["device_by_scope"])
    assert scope["pinot.mask"] == pytest.approx(1.0)
    assert scope["pinot.decode_dict"] == pytest.approx(0.4)   # innermost
    assert scope["(no pinot scope)"] == pytest.approx(0.1)
    assert tables["scope_stat_keys"] == {"tf_op": 2}


def test_render_lists_every_table(tables):
    text = tp.render({"file": "made-up", **tables})
    for needle in ("(a) phases", "(b) device idle", "(c) device self",
                   "pinot.device_transfer", "jit_pinot_dense_vmap",
                   tp.UNATTRIBUTED):
        assert needle in text


# the window's counters of the run, in µs: three phases' wall time, the
# CPU time where their clock was read and the wall time it was read over
# (a quarter of it); server_queue has no CPU counter (record_phase)
COUNTERS = {"phase_us_dispatch_prepare": 600_000,
            "phase_cpu_us_dispatch_prepare": 37_500,
            "phase_cpu_wall_us_dispatch_prepare": 150_000,
            "phase_us_device_transfer": 1_400_000,
            "phase_cpu_us_device_transfer": 17_500,
            "phase_cpu_wall_us_device_transfer": 350_000,
            "phase_us_execution": 3_200_000,
            "phase_cpu_us_execution": 0,
            "phase_cpu_wall_us_execution": 800_000,
            "phase_us_server_queue": 5_000}


@pytest.mark.parametrize("phase,cpu_ms,off", [
    ("pinot.dispatch_prepare", 150.0 / 2, 1 - 0.15 / 0.6),
    ("pinot.device_transfer", 70.0 / 2, 1 - 0.07 / 1.4),
    ("pinot.execution", 0.0, 1.0),
])
def test_cpu_columns_from_the_windows_counters(phase, cpu_ms, off):
    rows = {r[0]: r for r in tp.tables(HOST, DEVICES,
                                       counters=COUNTERS)["phases"]}
    assert rows[phase][6] == pytest.approx(cpu_ms)
    assert rows[phase][7] == pytest.approx(off)


def test_cpu_columns_are_empty_without_counters(tables):
    assert all(r[6] is None and r[7] is None for r in tables["phases"])
    with_counters = tp.tables(HOST, DEVICES, counters=COUNTERS)
    row = {r[0]: r for r in with_counters["phases"]}["pinot.broker_query"]
    assert row[6:] == [None, None]      # no counter named for it
    text = tp.render({"file": "made-up", **with_counters})
    assert "off-CPU % of self" in text
    line = next(ln for ln in text.splitlines()
                if ln.strip().startswith("pinot.dispatch_prepare"))
    assert line.split()[-2:] == ["75.000", "75.0"]


def test_lock_wait_summarises_the_probes_wakes():
    lates = [0.0001] * 8 + [0.004, 0.012]
    got = tp.lock_wait(lates)
    assert got["wakes"] == 10
    assert got["mean_ms"] == pytest.approx(1.68)
    assert got["p50_ms"] == pytest.approx(0.1)
    assert got["p90_ms"] == pytest.approx(12.0)
    assert got["late_1ms_share"] == pytest.approx(0.2)
    assert tp.lock_wait([]) == {"wakes": 0}


def test_the_probe_stops_and_records_its_wakes():
    import threading
    stop, lates = threading.Event(), []
    t = threading.Thread(target=tp.lock_probe, args=(stop, lates))
    t.start()
    while len(lates) < 3:
        stop.wait(0.01)
    stop.set()
    t.join(timeout=5.0)
    assert not t.is_alive()
    assert all(x > -1e-3 for x in lates)


def test_host_work_per_second_sums_the_leaves_only():
    window = {"phase_us_planning": 3_000_000, "phase_us_server_encode":
              1_000_000, "phase_us_device_execute": 9_000_000}
    assert tp.host_work_per_s(window, 2.0) == pytest.approx(2.0)


def test_two_windows_are_refused():
    with pytest.raises(RuntimeError):
        tp.tables(HOST + [ev("bench_window", 11, 12)], DEVICES)


def test_the_raw_reader_agrees_with_jax_on_a_real_trace(tmp_path):
    """tools/xplane_raw.py decodes the proto itself (it needs the stats of
    an event's metadata, which jax's reader leaves out): on a trace taken
    here every host event it reads is the one ``ProfileData`` reads."""
    import glob

    import jax
    import xplane_raw

    from pinot_tpu.utils import phases as ph
    from pinot_tpu.utils.spans import phase, set_query_id

    jax.profiler.start_trace(str(tmp_path))
    try:
        set_query_id("q-raw")
        with phase(ph.EXECUTION, segments=3):
            with phase(ph.DEVICE_TRANSFER):
                jax.device_get(jax.numpy.arange(8) + 1)
    finally:
        jax.profiler.stop_trace()
        set_query_id(None)
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    theirs = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for n, line in enumerate(plane.lines):
            theirs[(plane.name, n)] = [
                (e.name, e.start_ns, e.duration_ns, dict(e.stats))
                for e in line.events]
    ours = {}
    for plane in xplane_raw.read(path):
        for n, line in enumerate(plane["lines"]):
            ours[(plane["name"], n)] = line["events"]
    assert set(ours) == set(theirs) and sum(map(len, ours.values())) > 0
    seen = set()
    for key, events in theirs.items():
        assert len(ours[key]) == len(events)
        for (name, start, dur, stats), mine in zip(events, ours[key]):
            assert mine[0] == name
            assert mine[1] == pytest.approx(start, abs=1)
            assert mine[2] == pytest.approx(dur, abs=1)
            # an event's own stats are there; the metadata's come on top
            for k, v in stats.items():
                assert str(mine[3][k]) == str(v)
            if name.startswith("pinot."):
                seen.add(name)
                assert mine[3]["qid"] == "q-raw"
    assert seen == {"pinot.execution", "pinot.device_transfer"}
