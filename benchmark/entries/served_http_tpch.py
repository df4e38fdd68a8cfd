"""Entry ``served_http_tpch``: the system of ``served_http`` (the same
Controller + ServerNode + BrokerNode, by import) over TPC-H's LINEITEM.
Two differences:

- the table and its schema (``served_http`` is loaded as a module of
  this entry's own, with its ``TABLE`` set to ``lineitem``); the four measures ``DOUBLE``
  ``METRIC`` columns, ``l_shipdate`` an ``INT`` dimension of days since
  1970-01-01, the two flags ``STRING`` dimensions. ``l_extendedprice`` is
  declared without a dictionary; the other three measures are left to the
  segment builder's default, which keeps a metric raw (the
  configuration's file records what it decided);
- at import it asks the program what its float accumulator is on this
  backend (``pinot_tpu.ops.float_acc_dtype``, the one rule every float
  ``SUM``, ``AVG``, ``MIN`` and ``MAX`` of the program follows) and
  refuses at once unless it is float64: a program that accumulates in
  float32 on the chip would answer every statement of this cell, wrongly
  by the configuration's guarantee, for a whole run.
"""
from __future__ import annotations

import importlib.util
from typing import Dict, List

import numpy as np

TABLE = "lineitem"


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


def _float_accumulator() -> np.dtype:
    from pinot_tpu.ops import float_acc_dtype
    return np.dtype(float_acc_dtype())


if _float_accumulator() != np.float64:
    import jax
    raise SystemExit(
        "entry served_http_tpch: this checkout accumulates float "
        f"aggregates in {_float_accumulator()} on the "
        f"{jax.default_backend()!r} backend (pinot_tpu/ops/kernels.py "
        "float_acc_dtype, from before PR 35); the configuration's "
        "guarantee is every SUM and AVG within 1e-12 of the exact value; "
        "the configuration cannot run here")


def _schema(cols: Dict, measures):
    from pinot_tpu.spi import DataType, FieldSpec, FieldType, Schema

    fields = []
    for name, col in cols.items():
        if name in measures:
            fields.append(FieldSpec(name, DataType.DOUBLE, FieldType.METRIC))
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
        no_dictionary_columns=["l_extendedprice"]))
    return SegmentBuilder(_schema(cols, measures), config).build(
        given, out_dir, name)


def start(config: dict, seg_dirs: List[str], work_dir: str):
    return served_http.Served(seg_dirs, work_dir)
