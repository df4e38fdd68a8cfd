"""Test env: force CPU backend with 8 virtual devices BEFORE jax imports.

Mirrors the driver's multi-chip dry-run environment: sharding/collective
tests exercise a jax.sharding.Mesh over 8 virtual CPU devices
(xla_force_host_platform_device_count), per SURVEY.md build notes.
"""
import collections
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The suite's job is to validate the TPU-shaped kernels on the virtual CPU
# mesh, so pin the CPU scatter-core hedge OFF here (ops/kernels.
# cpu_scatter_default) — hard assignment, so an inherited =1 in the
# environment can't silently flip the whole suite onto the scatter core;
# tests/test_cpu_scatter.py flips it on explicitly per-test.
os.environ["PINOT_CPU_FAST_GROUPBY"] = "0"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Tests run on the virtual 8-device CPU mesh even where a chip is visible
# and JAX_PLATFORMS names it: pin the config before any backend
# initializes (chip_smoke.py and the benches are what run on the chip).
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long randomized soaks excluded from tier-1 (-m 'not slow')")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def count_calls(monkeypatch):
    """``count_calls(owner, *names)`` wraps ``owner.<name>`` for the
    length of a test so that every call is counted, and returns the one
    ``collections.Counter`` (keyed by name) that all its wrappers share.
    Overhead contracts are held this way, by work done: a ratio of two
    CPU clocks under six xdist workers is not a gate (ROADMAP D10)."""
    calls = collections.Counter()

    def wrap(owner, *names):
        for name in names:
            def counted(*a, _real=getattr(owner, name), _name=name, **kw):
                calls[_name] += 1
                return _real(*a, **kw)
            monkeypatch.setattr(owner, name, counted)
        return calls
    return wrap


@pytest.fixture(autouse=True)
def _reset_telemetry_registries():
    """The heat and device-memory registries are process-global (round
    14): a test that queries a table leaves segment heat and HBM-pool
    accounting behind, and the top-N heat-ranking tests had to clear by
    hand — cross-test pollution waiting to recur. Reset both after
    every test so each starts from an empty telemetry slate.

    The stack cache is dropped THROUGH its devmem-synced clear so its
    pool accounting stays reconciled (rebuild is one jnp.stack per
    group, cheap). The long-lived caches (plan cache, cube cache,
    segment device columns) are deliberately
    NOT evicted — they are the suite's compile/upload warmth — so their
    accounting restarts at zero each test; devmem.remove tolerates
    untracked keys by design, and reconciliation tests build their own
    entries."""
    yield
    from pinot_tpu.engine.batch import clear_stack_cache
    from pinot_tpu.engine.tier import global_tier
    from pinot_tpu.utils.compileplane import (DEFAULT_STORM_PER_MIN,
                                              global_compile_log)
    from pinot_tpu.utils.devmem import global_device_memory
    from pinot_tpu.utils.heat import global_segment_heat
    global_segment_heat.clear()
    clear_stack_cache()
    global_device_memory.clear()
    # the HBM tier registry is process-global like heat/devmem (and its
    # clear() also disarms any test-configured budget); segments keep
    # their caches — they re-register on their next admission
    global_tier.clear()
    # compile-plane forensics (ISSUE 15): brokers built with a trace/
    # stats ledger auto-point the process-global compile log at it —
    # un-point and drop the rings so one test's (often tmp-dir) ledger
    # can't swallow the next test's compile events. Staged-kernel
    # caches stay warm by design (the suite's compile warmth).
    global_compile_log.reset()
    global_compile_log.path = None
    global_compile_log.storm_per_min = DEFAULT_STORM_PER_MIN
    # SLO plane (ISSUE 17): same discipline — a test that arms
    # objectives or captures incidents must not leak them (clear() also
    # resets the shared alert manager's rules/ring)
    from pinot_tpu.utils.slo import global_incidents, global_slo
    global_slo.clear()
    global_slo.path = None
    global_incidents.reset()
    global_incidents.path = None
    # autopsy plane (round 25): brokers wire the recorder's post hook
    # to the process-global verdict ring and point it at their (tmp)
    # ledger — un-wire both so a later test's incident can't run
    # attribution against a deleted path
    from pinot_tpu.cluster.autopsy import global_autopsy
    global_incidents.post_hook = None
    global_autopsy.reset()
    global_autopsy.path = None
