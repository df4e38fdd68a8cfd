"""The driver entry points must work as the driver invokes them.

Round-1 regression: the multichip dryrun failed because
bare jax.device_put in resolve_params targeted the default (TPU) backend
instead of the CPU mesh. These tests run the actual entry functions.
"""
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as graft  # noqa: E402


def test_entry_jit_compiles_and_runs():
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    assert "matched" in out
    assert int(out["matched"]) > 0


def test_dryrun_multichip_8():
    graft.dryrun_multichip(8)


def test_dryrun_multichip_params_stay_on_mesh():
    """resolve_params with a mesh sharding must place params on the mesh's
    devices, not the default backend."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pinot_tpu.engine.executor import resolve_params
    from pinot_tpu.parallel import DistributedTable, segment_mesh
    from pinot_tpu.query.context import build_query_context
    from pinot_tpu.query.sql import parse_sql
    from pinot_tpu.server import TableDataManager

    _, seg_dirs = graft._build_table(n_segments=4, rows_per_seg=128, seed=9)
    dm = TableDataManager("lineorder")
    for d in seg_dirs:
        dm.add_segment_dir(d)
    mesh = segment_mesh(devices=jax.devices("cpu")[:4])
    dist = DistributedTable(dm.acquire_segments(), mesh)
    plan = dist.plan(build_query_context(parse_sql(graft._SQL)))
    assert plan.kind == "kernel"
    sharding = NamedSharding(mesh, P())
    params = resolve_params(plan, sharding=sharding)
    mesh_devs = set(mesh.devices.flat)
    for p in params:
        assert set(p.sharding.device_set) <= mesh_devs
