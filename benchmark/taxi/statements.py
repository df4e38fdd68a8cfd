"""Statements: the four "1.1 billion taxi rides" queries and the zone tile
as data (``shapes.json``: group keys, aggregates, ORDER BY and LIMIT),
written as this system's SQL."""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
Shape = Dict[str, Any]

# a named group key: its SQL, and the columns it reads
KEYS = {
    "cab_type": ("cab_type", ["cab_type"]),
    "passenger_count": ("passenger_count", ["passenger_count"]),
    "year": ("YEAR(pickup_datetime)", ["pickup_datetime"]),
    "distance": ("ROUND(trip_distance)", ["trip_distance"]),
    "pu_location_id": ("pu_location_id", ["pu_location_id"]),
}


def load_shapes(path: str = os.path.join(HERE, "shapes.json")
                ) -> Dict[str, Shape]:
    with open(path) as f:
        doc = json.load(f)
    return {s["id"]: s for s in doc["shapes"]}


def agg_sql(agg: List[str]) -> str:
    fn, what = agg
    return "COUNT(*)" if fn == "COUNT" else f"{fn}({what})"


def to_sql(shape: Shape, table: str = "trips") -> str:
    """The SQL text of one shape: the group keys, then the aggregates."""
    keys = [KEYS[k][0] for k in shape["keys"]]
    sql = (f"SELECT {', '.join(keys + [agg_sql(a) for a in shape['aggs']])}"
           f" FROM {table} GROUP BY {', '.join(keys)}")
    if shape["order"]:
        sql += " ORDER BY " + ", ".join(
            ("COUNT(*)" if what == "count" else KEYS[what][0])
            + (" DESC" if how == "desc" else "")
            for what, how in shape["order"])
    if shape["limit"] is not None:
        sql += f" LIMIT {shape['limit']}"
    return sql
