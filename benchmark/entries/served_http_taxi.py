"""Entry ``served_http_taxi``: the system of ``served_http`` (the same
Controller + ServerNode + BrokerNode, by import) over the NYC taxi trips.
Two differences:

- the table and its schema (``served_http`` is loaded as a module of
  this entry's own, with its ``TABLE`` set to ``trips``): the three
  measures ``DOUBLE`` ``METRIC`` columns (raw, the segment builder's
  default for a metric), ``pickup_datetime`` a ``LONG`` of milliseconds
  declared without a dictionary, ``passenger_count`` and
  ``pu_location_id`` ``INT`` dimensions, ``cab_type`` a ``STRING`` one;
- at start it plans each statement of the cell on one loaded segment,
  through the program's own planner, and refuses at once if any would
  be answered by the host path (numpy, no kernel) or would carry a float
  aggregate through float32 (``float_acc_narrow``): such a program would
  miss the deadline or the configuration's 1e-12 on every request of a
  whole run.
"""
from __future__ import annotations

import importlib.util
from typing import Dict, List

import numpy as np

TABLE = "trips"
RAW_LONGS = ("pickup_datetime",)


def _served_http_over(table: str):
    """``served_http`` loaded as a module of this entry's own, so that its
    ``TABLE`` (which ``Served`` reads wherever it names the table) can be
    this one's without an edit to that file or a copy of its class."""
    spec = importlib.util.find_spec("benchmark.entries.served_http")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.TABLE = table
    return module


served_http = _served_http_over(TABLE)


def _schema(cols: Dict, measures):
    from pinot_tpu.spi import DataType, FieldSpec, FieldType, Schema

    fields = []
    for name, col in cols.items():
        if name in measures:
            fields.append(FieldSpec(name, DataType.DOUBLE, FieldType.METRIC))
        elif name in RAW_LONGS:
            fields.append(FieldSpec(name, DataType.LONG,
                                    FieldType.DIMENSION))
        elif isinstance(col, np.ndarray):
            fields.append(FieldSpec(name, DataType.INT, FieldType.DIMENSION))
        else:
            fields.append(FieldSpec(name, DataType.STRING,
                                    FieldType.DIMENSION))
    return Schema(TABLE, fields)


def build_segment(cols: Dict, measures, out_dir: str, name: str) -> str:
    """Write one segment directory from host columns; returns its path."""
    from pinot_tpu.segment import SegmentBuilder
    from pinot_tpu.segment.builder import Categorical
    from pinot_tpu.spi import IndexingConfig, TableConfig

    given = {n: c if isinstance(c, np.ndarray) else Categorical(*c)
             for n, c in cols.items()}
    config = TableConfig(TABLE, indexing=IndexingConfig(
        no_dictionary_columns=list(RAW_LONGS)))
    return SegmentBuilder(_schema(cols, measures), config).build(
        given, out_dir, name)


def refusals(segment, sqls: List[str]) -> List[str]:
    """What the program's planner would do wrongly for this cell: one
    line a statement answered by the host path or carrying a float
    aggregate through float32, on ``segment`` as the server holds it."""
    from pinot_tpu.ops.kernels import float_acc_forms
    from pinot_tpu.query.context import build_query_context
    from pinot_tpu.query.planner import SegmentPlanner
    from pinot_tpu.query.sql import parse_sql

    out = []
    for sql in sqls:
        plan = SegmentPlanner(build_query_context(parse_sql(sql)),
                              segment).plan()
        if plan.kind == "host":
            out.append(f"host path: {sql}")
        elif plan.kind == "kernel" and float_acc_forms(plan.kernel_plan)[1]:
            out.append(f"float32 on the path ({plan.kernel_plan.strategy} "
                       f"strategy): {sql}")
    return out


def start(config: dict, seg_dirs: List[str], work_dir: str):
    from benchmark.taxi import statements

    system = served_http.Served(seg_dirs, work_dir)
    sqls = [statements.to_sql(s) for s in statements.load_shapes().values()]
    wrong = refusals(system._segments()[0], sqls)
    if wrong:
        system.stop()
        raise SystemExit(
            "entry served_http_taxi: this checkout cannot serve the cell "
            "on its device path; the configuration's guarantees are every "
            "segment on the device within the 10 s deadline and every "
            "AVG within 1e-12:\n  " + "\n  ".join(wrong))
    return system
