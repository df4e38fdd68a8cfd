"""Statements: ClickBench's four top-10 group-bys as data (``shapes.json``:
group keys, the non-empty phrase filter, LIMIT), written as this
system's SQL. Every one is COUNT(*) by its keys, ORDER BY that count
DESC."""
from __future__ import annotations

import json
import os
from typing import Any, Dict

HERE = os.path.dirname(os.path.abspath(__file__))
Shape = Dict[str, Any]


def load_shapes(path: str = os.path.join(HERE, "shapes.json")
                ) -> Dict[str, Shape]:
    with open(path) as f:
        doc = json.load(f)
    return {s["id"]: s for s in doc["shapes"]}


def to_sql(shape: Shape, table: str = "hits") -> str:
    """The statement as ClickBench writes it: COUNT(*) named where the
    source names it, and ordered by that name."""
    keys = ", ".join(shape["keys"])
    alias = shape["count_alias"]
    count = f"COUNT(*) AS {alias}" if alias else "COUNT(*)"
    where = " WHERE SearchPhrase <> ''" if shape["nonempty_phrase"] else ""
    return (f"SELECT {keys}, {count} FROM {table}{where} GROUP BY {keys} "
            f"ORDER BY {alias or 'COUNT(*)'} DESC LIMIT {shape['limit']}")
