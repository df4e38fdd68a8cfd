"""Statements: a shape (predicates, value expression, group-by and
ORDER BY columns, all data in ``shapes.json``) written as SQL."""
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


def _lit(v) -> str:
    return f"'{v}'" if isinstance(v, str) else str(v)


def to_sql(shape: Shape, table: str = "lineorder") -> str:
    """The SQL text of one shape: the group-by columns then the sum; a
    set of values is an OR-of-equals, as the source writes it; an ORDER BY
    term ``SUM`` is the aggregate itself."""
    agg = "SUM(" + " ".join(shape["value"]) + ")"
    group = list(shape["group"])
    sel = ", ".join(group + [agg])
    conds = []
    for col, op, val in shape["preds"]:
        if op == "eq":
            conds.append(f"{col} = {_lit(val)}")
        elif op == "lt":
            conds.append(f"{col} < {_lit(val)}")
        elif op == "between":
            conds.append(f"{col} BETWEEN {_lit(val[0])} AND {_lit(val[1])}")
        elif op == "in":
            conds.append("(" + " OR ".join(
                f"{col} = {_lit(v)}" for v in val) + ")")
        else:
            raise ValueError(f"unknown predicate op {op!r}")
    sql = f"SELECT {sel} FROM {table} WHERE {' AND '.join(conds)}"
    if group:
        terms = [(agg if col == "SUM" else col)
                 + (" DESC" if way == "desc" else "")
                 for col, way in shape["order"]]
        sql += (" GROUP BY " + ", ".join(group)
                + " ORDER BY " + ", ".join(terms) + " LIMIT 100000")
    return sql
