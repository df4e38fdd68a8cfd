"""Entry ``served_http_hits``: the system of ``served_http`` (the same
Controller + ServerNode + BrokerNode, by import) over ClickBench's hits.
Two differences:

- the table and its schema (``served_http`` is loaded as a module of
  this entry's own, with its ``TABLE`` set to ``hits``): ``UserID`` a
  ``LONG`` dimension declared in ``dictionaryColumns`` (a segment holds
  millions of users, over the default threshold that keeps a numeric
  column raw), ``SearchPhrase`` a ``STRING`` and ``SearchEngineID`` an
  ``INT`` dimension;
- at start it plans each statement of the cell on every loaded segment,
  through the program's own planner as the server holds them, and
  refuses at once if any segment of any statement would be answered by
  the host path (numpy, no kernel): such a program would miss the 10 s
  deadline on every request of a whole run.
"""
from __future__ import annotations

import importlib.util
from typing import Dict, List

import numpy as np

TABLE = "hits"
DICT_LONGS = ("UserID",)


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


def _schema(cols: Dict):
    from pinot_tpu.spi import DataType, FieldSpec, Schema

    fields = []
    for name, col in cols.items():
        if name in DICT_LONGS:
            fields.append(FieldSpec(name, DataType.LONG))
        elif isinstance(col, np.ndarray):
            fields.append(FieldSpec(name, DataType.INT))
        else:
            fields.append(FieldSpec(name, DataType.STRING))
    return Schema(TABLE, fields)


def build_segment(cols: Dict, measures, out_dir: str, name: str) -> str:
    """Write one segment directory from host columns; returns its path."""
    from pinot_tpu.segment import SegmentBuilder
    from pinot_tpu.segment.builder import Categorical
    from pinot_tpu.spi import IndexingConfig, TableConfig

    given = {n: c if isinstance(c, np.ndarray) else Categorical(*c)
             for n, c in cols.items()}
    config = TableConfig(TABLE, indexing=IndexingConfig(
        dictionary_columns=list(DICT_LONGS)))
    return SegmentBuilder(_schema(cols), config).build(given, out_dir, name)


def refusals(segments, sqls: List[str]) -> List[str]:
    """What the program's planner would answer on the host: one line a
    statement that some segment of ``segments`` (as the server holds
    them) would answer with numpy and no kernel."""
    from pinot_tpu.query.context import build_query_context
    from pinot_tpu.query.planner import SegmentPlanner
    from pinot_tpu.query.sql import parse_sql

    out = []
    for sql in sqls:
        ctx = build_query_context(parse_sql(sql))
        if any(SegmentPlanner(ctx, seg).plan().kind == "host"
               for seg in segments):
            out.append(f"host path: {sql}")
    return out


def start(config: dict, seg_dirs: List[str], work_dir: str):
    from benchmark.hits import statements

    system = served_http.Served(seg_dirs, work_dir)
    sqls = [statements.to_sql(s) for s in statements.load_shapes().values()]
    wrong = refusals(system._segments(), sqls)
    if wrong:
        system.stop()
        raise SystemExit(
            "entry served_http_hits: this checkout cannot serve the cell "
            "on its device path; the configuration's guarantee is every "
            "segment on the device within the 10 s deadline:\n  "
            + "\n  ".join(wrong))
    return system
