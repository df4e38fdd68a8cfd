"""The reduction from a trace to busy time, self times, launches and
idle gaps: on hand-made events, and on a trace recorded on a TPU v5 lite
(``trace/recorded_q1_scan_v5e.json``), where every number is checked a
second way by painting the events onto a microsecond raster."""
import os

import numpy as np
import pytest

from benchmark.trace import reduce as R
from benchmark.trace import xplane

RECORDED = os.path.join(os.path.dirname(R.__file__),
                        "recorded_q1_scan_v5e.json")


def test_merge_clip_overlap():
    merged = R.merge([(0, 1), (0.5, 2), (3, 4), (3.2, 3.5)])
    assert merged == [(0, 2), (3, 4)]
    assert R.length(merged) == 3
    assert R.overlap(merged, 1.5, 3.5) == pytest.approx(1.0)
    assert R.clip(merged, 5, 6) == []


def test_self_times_take_children_from_parents():
    evs = [("loop", 0, 10), ("a", 1, 3), ("b", 4, 6), ("c", 4.5, 5),
           ("x", 12, 13)]
    self_s = R.self_times(evs)
    assert self_s == {"loop": 6.0, "a": 2.0, "b": 1.5, "c": 0.5, "x": 1.0}
    # self times add up to the union: nothing is counted twice
    assert sum(self_s.values()) == R.length(R.merge(
        [(s, e) for _n, s, e in evs]))


def _trace(ops, requests, window=(0.0, 10.0), modules=()):
    ns = lambda s: s * 1e9      # noqa: E731
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops",
             "events": [[n, ns(s), ns(e - s), {}] for n, s, e in ops]},
            {"name": "XLA Modules",
             "events": [[n, ns(s), ns(e - s), {}] for n, s, e in modules]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench_window", ns(window[0]), ns(window[1] - window[0]), {}]] + [
            ["bench_request", ns(s), ns(e - s), {"shape": sh, "key": sh}]
            for sh, s, e in requests]}]}]}


def test_reduce_clips_to_the_window_and_attributes_gaps():
    red = R.reduce_trace(_trace(
        ops=[("k", -1.0, 1.0), ("k", 2.0, 3.0), ("psum all-reduce", 6.0, 7.0),
             ("all-reduce.1", 9.5, 11.0)],
        requests=[("q1.1", 0.5, 4.0), ("q2.1", 5.0, 8.0)],
        modules=[("jit_kernel(1)", 2.0, 3.0), ("jit_kernel(1)", 6.0, 7.0)]))
    assert red.window_s == 10.0
    assert red.busy_s == pytest.approx(1.0 + 1.0 + 1.0 + 0.5)
    assert len(red.devices[0].launches) == 2
    assert red.op_seconds() == pytest.approx(3.5)
    gaps = dict(R.breakdown(red)["idle_gaps"])
    assert gaps["in_request:q1.1"] == pytest.approx(1.0 + 1.0)   # 1-2, 3-4
    assert gaps["in_request:q2.1"] == pytest.approx(1.0 + 1.0)   # 5-6, 7-8
    assert gaps["no_request_open"] == pytest.approx(1.0 + 1.5)   # 4-5, 8-9.5


def test_no_window_or_no_device_plane_is_an_error():
    tr = _trace([], [])
    tr["planes"][1]["lines"][0]["events"] = []
    with pytest.raises(RuntimeError):
        R.reduce_trace(tr)
    tr = _trace([("k", 0, 1)], [])
    tr["planes"] = tr["planes"][1:]
    with pytest.raises(RuntimeError):
        R.reduce_trace(tr)


def _raster(trace, lo, hi):
    """Busy microseconds of the device, painted event by event."""
    ops = [ln for ln in trace["planes"][0]["lines"]
           if ln["name"] == "XLA Ops"][0]["events"]
    paint = np.zeros(int(round((hi - lo) * 1e6)) + 1, dtype=bool)
    for _n, s, d, _st in ops:
        a = int(round((max(s / 1e9, lo) - lo) * 1e6))
        b = int(round((min((s + d) / 1e9, hi) - lo) * 1e6))
        if b > a:
            paint[a:b] = True
    return paint


def test_recorded_trace():
    trace = xplane.load_json(RECORDED)
    red = R.reduce_trace(trace)
    # what the recording holds (read by hand, PR 24): four whole Q1
    # requests of about 0.52 s, each with about 0.488 s of device time,
    # and the first 50 ms of a fifth
    assert len(red.requests) == 5
    assert [r.stats["shape"] for r in red.requests] == [
        "q1.1", "q1.2", "q1.3", "q1.1", "q1.2"]
    assert red.window_s == pytest.approx(2.165428613, abs=1e-6)
    assert red.busy_s == pytest.approx(1.966478697, abs=1e-6)
    assert len(red.devices[0].launches) == 271
    lo, hi = red.window
    paint = _raster(trace, lo, hi)
    assert paint.sum() / 1e6 == pytest.approx(red.busy_s, abs=2e-3)
    assert sum(red.devices[0].op_self_s.values()) == pytest.approx(
        red.busy_s, rel=1e-9)
    for r in red.requests[:4]:
        a, b = int((r.start - lo) * 1e6), int((r.end - lo) * 1e6)
        assert paint[a:b].sum() / 1e6 == pytest.approx(
            R.overlap(red.devices[0].busy, r.start, r.end), abs=2e-3)
        assert 0.48 < R.overlap(red.devices[0].busy, r.start, r.end) < 0.50
    bd = R.breakdown(red)
    assert sum(s for _n, s in bd["idle_gaps"]) == pytest.approx(
        red.window_s - red.busy_s, abs=1e-9)
    assert len(bd["device_ops"]) <= 10 and bd["device_ops"][0][1] > 1.5


def test_readers_on_the_recorded_trace():
    """Each trace reader against numbers worked out by hand from the
    recording: four whole Q1 requests, int32 columns, one v5e."""
    from benchmark import catalog as cat
    from benchmark import run
    from benchmark.ssb import bytes as ssb_bytes
    from benchmark.ssb import statements

    shapes = statements.load_shapes()
    sts = {f"{sid}.v0": run.tr.Statement(f"{sid}.v0", sh, "")
           for sid, sh in shapes.items()}
    rows = 1 << 26
    red = R.reduce_trace(xplane.load_json(RECORDED))
    rec = run.Records(
        cell={}, config={}, statements=sts, requests=[], wrong=set(), t0=0.0,
        seconds=red.window_s, setup_s=0.0, counters={"xla_programs": 0},
        device_kind="TPU v5 lite", trace=red,
        logical_bytes=lambda sh: ssb_bytes.logical_bytes(sh, rows,
                                                         lambda c: 4))
    c = cat.Catalog()
    read = lambda name: c.reader(name)(rec)     # noqa: E731
    # q1.1 and q1.2 read 4 columns, q1.3 reads 5: 17 column reads of
    # 2^26 x 4 B over four requests, against 819 GB/s and 1.952 s busy
    whole = red.requests[:4]
    busy = sum(R.overlap(red.devices[0].busy, r.start, r.end) for r in whole)
    by_hand = 100 * (17 * rows * 4 / 819e9) / busy
    assert read("scan_roofline") == pytest.approx(by_hand, rel=1e-9)
    assert 0.25 < read("scan_roofline") < 0.30
    assert read("device_idle_pct") == pytest.approx(
        100 * (1 - 1.966478697 / 2.165428613), abs=1e-4)
    assert read("launches_per_query") == pytest.approx(271 / 5)
    assert read("kernel_ms_per_query") == pytest.approx(
        1e3 * 1.966478697 / 5, abs=1e-3)
    outside = sum((r.end - r.start) for r in whole) - busy
    assert read("outside_device_ms") == pytest.approx(1e3 * outside / 4)
    # an untraced run: nothing to read, so nothing returned, never a 0
    rec.trace = None
    for name in ("scan_roofline", "kernel_ms_per_query", "device_idle_pct",
                 "launches_per_query", "outside_device_ms"):
        assert read(name) is None
    assert read("compiles_in_window") == 0.0
