"""Self CPU time of every metered crossing (utils/spans.phase).

A crossing whose nest is drawn (``spans.CPU_SHARE`` of a thread's
outermost crossings) adds its thread's CPU time inside it, less that of
the metered crossings nested inside it on the same thread, to
``phase_cpu_us_<name>`` and its wall time to ``phase_cpu_wall_us_<name>``,
in the lock acquisition that adds ``phase_us_<name>`` and
``phase_n_<name>``; any other adds 0 to both. Where it can, a test here
counts with a clock it drives itself (``spans._thread_ns``) instead of
timing; those that read the real clocks spin for CPU time, or compare a
waiting phase's CPU with a tenth of its wall time.
"""
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from pinot_tpu.cluster import (BrokerNode, Controller,  # noqa: E402
                               ServerNode)
from pinot_tpu.cluster.http_util import http_json  # noqa: E402
from pinot_tpu.segment import SegmentBuilder  # noqa: E402
from pinot_tpu.spi import (DataType, FieldSpec, FieldType,  # noqa: E402
                           Schema, TableConfig)
from pinot_tpu.utils import phases as ph  # noqa: E402
from pinot_tpu.utils import spans  # noqa: E402
from pinot_tpu.utils.metrics import MetricsRegistry  # noqa: E402

# phases nothing on the served path of another module crosses, so a
# thread left over from an earlier file cannot add to them
OUTER, INNER, OTHER = (ph.DISTRIBUTED_EXECUTE, ph.FUSED_EXECUTE,
                       ph.RAGGED_WAIT)


@pytest.fixture
def registry(monkeypatch):
    """A registry of this test's own in place of the process's, and
    every nest read."""
    reg = MetricsRegistry()
    monkeypatch.setattr(spans, "global_metrics", reg)
    monkeypatch.setattr(spans, "CPU_SHARE", 1.0)
    return reg.snapshot


@pytest.fixture
def clock(monkeypatch):
    """A thread CPU clock the test sets: ``clock[0]`` nanoseconds."""
    now = [0]
    monkeypatch.setattr(spans, "_thread_ns", lambda: now[0])
    return now


def spin(seconds):
    """Work until this thread has had ``seconds`` of CPU: under six test
    workers a spin by the wall clock may get a fraction of a core."""
    end = time.thread_time_ns() + int(seconds * 1e9)
    x = 0
    while time.thread_time_ns() < end:
        x += 1
    return x


def test_a_busy_phase_records_its_cpu(registry):
    with spans.phase(OUTER):
        spin(0.02)
    c = registry()["counters"]
    assert c["phase_n_" + OUTER] == 1
    assert 20_000 <= c["phase_cpu_us_" + OUTER] <= c["phase_us_" + OUTER] + 1
    assert c["phase_cpu_wall_us_" + OUTER] == c["phase_us_" + OUTER]


def _blocked_on_a_lock(seconds):
    lock, held = threading.Lock(), threading.Event()

    def holder():
        with lock:
            held.set()
            time.sleep(seconds)

    t = threading.Thread(target=holder)
    t.start()
    held.wait()
    with lock:
        pass
    t.join()


@pytest.mark.parametrize("wait", [lambda: time.sleep(0.05),
                                  lambda: _blocked_on_a_lock(0.05)],
                         ids=["sleep", "lock"])
def test_a_waiting_phase_is_off_cpu(registry, wait):
    with spans.phase(OUTER):
        wait()
    c = registry()["counters"]
    assert c["phase_us_" + OUTER] >= 40_000
    assert c["phase_cpu_us_" + OUTER] < c["phase_us_" + OUTER] / 10


@pytest.mark.parametrize("sampled_query", [False, True],
                         ids=["counters", "span_tree"])
def test_nested_self_cpu_excludes_the_child(registry, clock,
                                            sampled_query):
    """The same with the query sampled: each phase is also a node of the
    span tree, whose own stack must not stand in for the crossings'."""
    if sampled_query:
        spans.span_tracer.start("query")
    clock[0] = 1_000_000
    with spans.phase(OUTER):
        clock[0] += 3_000_000
        with spans.phase(INNER):
            clock[0] += 5_000_000
        clock[0] += 2_000_000
        with spans.phase(INNER):
            clock[0] += 1_000_000
        clock[0] += 4_000
    if sampled_query:
        root = spans.span_tracer.stop()
        assert [n.name for n in root.children] == [OUTER]
        assert [n.name for n in root.children[0].children] == [INNER] * 2
    c = registry()["counters"]
    assert c["phase_cpu_us_" + INNER] == 5_000 + 1_000
    assert c["phase_cpu_us_" + OUTER] == 3_000 + 2_000 + 4
    # the two add up to the outer's inclusive CPU
    assert c["phase_cpu_us_" + OUTER] + c["phase_cpu_us_" + INNER] == \
        11_004


def test_nested_real_clock_adds_up_within_the_grain(registry):
    c0 = time.thread_time_ns()
    with spans.phase(OUTER):
        spin(0.005)
        with spans.phase(INNER):
            spin(0.005)
    inclusive_us = (time.thread_time_ns() - c0) / 1e3
    c = registry()["counters"]
    total = c["phase_cpu_us_" + OUTER] + c["phase_cpu_us_" + INNER]
    # two roundings to a whole µs; the test's own reads sit outside
    assert total <= inclusive_us + 2
    assert c["phase_cpu_us_" + INNER] >= 1_000


def test_an_exception_pops_the_stack(registry, clock):
    with pytest.raises(ValueError):
        with spans.phase(OUTER):
            with spans.phase(INNER):
                clock[0] += 2_000_000
                raise ValueError("inside")
    assert spans._open.stack == []
    c = registry()["counters"]
    assert c["phase_n_" + OUTER] == c["phase_n_" + INNER] == 1
    assert c["phase_cpu_us_" + INNER] == 2_000
    assert c["phase_cpu_us_" + OUTER] == 0
    # and the next crossing on this thread starts from an empty stack
    with spans.phase(OTHER):
        assert spans._open.stack[0].name == OTHER
    assert spans._open.stack == []


def test_two_threads_do_not_mix(registry):
    """A phase open on one thread while another thread crosses its own
    (``scatter`` and its pool's ``scatter_call``): the other thread's
    crossing starts its own stack and takes nothing from the first."""
    opened, done = threading.Event(), threading.Event()
    seen = {}

    def other():
        opened.wait()
        with spans.phase(INNER):
            seen["stack"] = [p.name for p in spans._open.stack]
            spin(0.01)
        done.set()

    t = threading.Thread(target=other)
    t.start()
    with spans.phase(OUTER) as outer:
        opened.set()
        done.wait()
        assert [p.name for p in spans._open.stack] == [OUTER]
    t.join()
    assert seen["stack"] == [INNER]
    assert outer._child_ns == 0
    c = registry()["counters"]
    assert c["phase_cpu_us_" + INNER] >= 1_000
    # the waiting parent's CPU is its own few microseconds, not less
    assert 0 <= c["phase_cpu_us_" + OUTER] < c["phase_cpu_us_" + INNER]


def test_record_phase_adds_no_cpu_counter(registry):
    spans.record_phase(ph.SERVER_QUEUE, 0.25)
    c = registry()["counters"]
    assert c["phase_us_" + ph.SERVER_QUEUE] == 250_000
    assert c["phase_n_" + ph.SERVER_QUEUE] == 1
    assert not any(k.startswith("phase_cpu_") for k in c)


def test_four_counters_under_one_lock(monkeypatch):
    """A crossing takes the registry's lock once, for all four."""
    reg = MetricsRegistry()

    class Counting:
        n = 0

        def __enter__(self):
            Counting.n += 1
            return real.__enter__()

        def __exit__(self, *exc):
            return real.__exit__(*exc)

    real = reg._lock
    reg._lock = Counting()
    monkeypatch.setattr(spans, "global_metrics", reg)
    with spans.phase(OUTER):
        pass
    assert Counting.n == 1
    assert {"phase_us_" + OUTER, "phase_n_" + OUTER,
            "phase_cpu_us_" + OUTER, "phase_cpu_wall_us_" + OUTER} == \
        set(reg.snapshot()["counters"])


def test_an_unread_nest_adds_zero_cpu(registry, monkeypatch, clock):
    monkeypatch.setattr(spans, "CPU_SHARE", 0.0)
    with spans.phase(OUTER):
        clock[0] += 5_000_000
        with spans.phase(INNER):
            clock[0] += 5_000_000
    c = registry()["counters"]
    for p in (OUTER, INNER):
        assert c["phase_n_" + p] == 1
        assert c["phase_cpu_us_" + p] == c["phase_cpu_wall_us_" + p] == 0
    assert spans._open.stack == []


@pytest.mark.parametrize("root_read", [True, False])
def test_the_draw_is_once_a_nest(registry, monkeypatch, root_read):
    """The outermost crossing on a thread draws; its children follow it,
    whatever a draw of theirs would have said."""
    draws = []

    def draw():
        draws.append(1)
        return 0.0 if root_read else 0.99

    monkeypatch.setattr(spans, "_rand", draw)
    monkeypatch.setattr(spans, "CPU_SHARE", 0.5)
    with spans.phase(OUTER) as outer:
        with spans.phase(INNER) as inner:
            spin(0.002)
    assert len(draws) == 1
    assert (outer._c0 is not None) is (inner._c0 is not None) is root_read
    c = registry()["counters"]
    assert (c["phase_cpu_wall_us_" + INNER] > 0) is root_read


def test_a_share_of_nests_is_read(monkeypatch):
    """Over many outermost crossings the read share is CPU_SHARE."""
    reg = MetricsRegistry()
    monkeypatch.setattr(spans, "global_metrics", reg)
    rng = np.random.default_rng(37)
    monkeypatch.setattr(spans, "_rand", lambda: float(rng.random()))
    read = 0
    for _ in range(4000):
        with spans.phase(OUTER) as p:
            pass
        read += p._c0 is not None
    assert 0.10 <= read / 4000 <= 0.15
    assert spans.CPU_SHARE == 0.125


def test_every_metered_phase_has_its_cpu_keys():
    assert {k[3][len("phase_cpu_us_"):] for k in spans._KEYS.values()} \
        == ph.METERED_PHASES
    assert {k[4][len("phase_cpu_wall_us_"):] for k in spans._KEYS.values()} \
        == ph.METERED_PHASES
    assert set(ph.HOST_WORK_PHASES) <= ph.METERED_PHASES
    assert ph.PARAMS_HOST in ph.METERED_PHASES


# ---------------------------------------------------------------------------
# a served query: controller, one server, broker in this process
# ---------------------------------------------------------------------------

ROWS = 1 << 12
# HOST_WORK_PHASES and the pure host leaves left out of it because the
# chip hosts' clock cannot read them (utils/phases.py): leaves all
LEAVES = ph.HOST_WORK_PHASES + (ph.BROKER_ROUTE, ph.BROKER_SELECT,
                                ph.BROKER_RESPOND, ph.SERVER_PARSE,
                                ph.PARAMS_HOST, ph.DISPATCH_PREPARE)
STATEMENTS = [
    "SELECT region, SUM(amount), COUNT(*) FROM ctab GROUP BY region "
    "ORDER BY region",
    "SELECT region, SUM(amount) FROM ctab GROUP BY region "
    "OPTION(groupByStrategy=compact)",
    "SELECT SUM(amount) FROM ctab WHERE tier BETWEEN 1 AND 3",
    # a group key that is also a decoded value: one program a segment,
    # launched from a window (engine/executor.execute_kernel_plans)
    "SELECT tier, SUM(amount * tier) FROM ctab GROUP BY tier "
    "OPTION(groupByStrategy=compact)",
    "SELECT region, amount FROM ctab ORDER BY amount DESC LIMIT 5",
]


@pytest.fixture(scope="module")
def trio(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("phase_cpu")
    ctrl = Controller(str(tmp / "ctrl"), heartbeat_timeout=30.0,
                      reconcile_interval=0.2)
    server = ServerNode("server_0", ctrl.url, poll_interval=0.1)
    broker = BrokerNode(ctrl.url, routing_refresh=0.1)
    schema = Schema("ctab", [
        FieldSpec("region", DataType.STRING),
        FieldSpec("tier", DataType.INT),
        FieldSpec("amount", DataType.INT, FieldType.METRIC)])
    builder = SegmentBuilder(schema, TableConfig("ctab"))
    ctrl.add_table("ctab", schema.to_dict(), replication=1)
    rng = np.random.default_rng(37)
    for i in range(3):
        cols = {"region": rng.choice(["east", "west", "north"], ROWS),
                "tier": rng.integers(0, 5, ROWS).astype(np.int32),
                "amount": rng.integers(0, 1000, ROWS).astype(np.int32)}
        d = builder.build(cols, str(tmp / "segments"), f"ctab_seg_{i}")
        ctrl.add_segment("ctab", f"ctab_seg_{i}", d)
    version = ctrl.routing_snapshot()["version"]
    assert server.wait_for_version(version)
    assert broker.wait_for_version(version)
    for sql in STATEMENTS:                      # compile outside the tests
        assert "resultTable" in http_json(
            "POST", f"{broker.url}/query/sql", {"sql": sql}, timeout=300.0)
    yield broker
    broker.stop()
    server.stop()
    ctrl.stop()


def test_prometheus_shows_the_cpu_counters(trio):
    with urllib.request.urlopen(f"{trio.url}/metrics/prometheus",
                                timeout=60.0) as r:
        text = r.read().decode()
    for p in (ph.BROKER_PARSE, ph.PLANNING, ph.PARAMS_HOST,
              ph.SERVER_ENCODE):
        assert f"pinot_tpu_phase_cpu_us_{p}_total " in text
    assert "pinot_tpu_phase_cpu_us_server_queue_total" not in text


class _Events:
    """A profiler session stand-in: every event's enter and exit, with
    the thread that made each."""
    log: list = []

    def __init__(self, name, **_kw):
        self.name = name

    @staticmethod
    def is_enabled():
        return True

    def __enter__(self):
        self.log.append((self.name, "enter", threading.get_ident()))
        return self

    def __exit__(self, *exc):
        self.log.append((self.name, "exit", threading.get_ident()))

    def set_metadata(self, **_kw):
        pass


def test_the_queue_event_closes_on_the_worker(trio, monkeypatch):
    """Inside a profiler session ``pinot.server_queue`` opens on the
    handler's thread at arrival and closes on the scheduler worker that
    starts the query: the handler waits for nothing but the answer."""
    monkeypatch.setattr(spans, "_annotation", _Events)
    monkeypatch.setattr(_Events, "log", [])
    before = spans.global_metrics.snapshot()["counters"]
    out = http_json("POST", f"{trio.url}/query/sql", {"sql": STATEMENTS[0]},
                    timeout=300.0)
    assert "resultTable" in out
    key = "phase_n_" + ph.SERVER_HTTP
    for _ in range(5000):
        if spans.global_metrics.snapshot()["counters"].get(key, 0) > \
                before.get(key, 0):
            break
        time.sleep(0.002)
    log = list(_Events.log)

    def threads(name, what):
        return [t for n, w, t in log if n == "pinot." + name and w == what]

    (opened,) = threads(ph.SERVER_QUEUE, "enter")
    (closed,) = threads(ph.SERVER_QUEUE, "exit")
    assert opened in threads(ph.SERVER_HTTP, "enter")
    assert closed != opened
    assert closed in threads(ph.SERVER_PARSE, "enter")


@pytest.mark.parametrize("sql", STATEMENTS)
def test_host_work_phases_are_leaves_on_a_served_query(trio, monkeypatch,
                                                       sql):
    """No metered phase opens inside a HOST_WORK_PHASES crossing, or one
    of the other pure host leaves, on its thread, and the statement's
    plans are resolved in ``params_host``."""
    nested = []
    enter = spans.phase.__enter__

    def recording(self):
        stack = spans._open.stack
        nested.append((stack[-1].name if stack else None, self.name))
        return enter(self)

    monkeypatch.setattr(spans.phase, "__enter__", recording)
    before = spans.global_metrics.snapshot()["counters"]
    out = http_json("POST", f"{trio.url}/query/sql", {"sql": sql},
                    timeout=300.0)
    assert "resultTable" in out
    # the server's handler closes its phases after the broker has its
    # answer: wait until it has counted this statement
    key = "phase_n_" + ph.SERVER_HTTP
    for _ in range(5000):
        after = spans.global_metrics.snapshot()["counters"]
        if after.get(key, 0) > before.get(key, 0):
            break
        time.sleep(0.002)
    assert nested, "no nesting recorded: the hook saw nothing"
    inside_work = [(p, c) for p, c in nested if p in LEAVES]
    assert inside_work == []
    crossed = {c for _p, c in nested}
    assert ph.SERVER_HTTP in crossed
    if "amount * tier" in sql:
        assert after["kernel_dispatches_" + ph.COMPACT_PER_SEGMENT] >= \
            before.get("kernel_dispatches_" + ph.COMPACT_PER_SEGMENT, 0) + 3
    if "ORDER BY amount" not in sql:            # a kernel plan's statement
        assert ph.PARAMS_HOST in crossed
        assert after["phase_n_" + ph.PARAMS_HOST] > \
            before.get("phase_n_" + ph.PARAMS_HOST, 0)
    for p in LEAVES:
        if "phase_n_" + p in after:
            assert "phase_cpu_us_" + p in after
