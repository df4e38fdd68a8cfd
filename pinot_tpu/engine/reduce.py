"""Broker reduce: merge per-segment partials into the final result table.

Reference parity: pinot-core/.../query/reduce/BrokerReduceService.java:61
(merges server DataTables; aggregation/groupby/selection reducers, HAVING,
ORDER BY, LIMIT trimming via IndexedTable). States arriving here are
value-space and mergeable (dict ids were resolved per segment at extract
time), so merging is pure arithmetic/set union regardless of which path
(device kernel, fast metadata, host numpy) produced each partial.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..query.context import AggExpr, QueryContext, _expr_label
from ..query import functions as F
from ..ops import aggregations
from ..query.sql import (Between, BinaryOp, BoolAnd, BoolNot, BoolOr,
                         CaseWhen, Cast, Comparison, FuncCall, Identifier,
                         InList, IsNull, Literal, SqlError, Star)
from .executor import AggPartial, GroupByPartial, SelectionPartial

DEFAULT_LIMIT = 10  # Pinot's default LIMIT for selection/group-by results
# Pinot's server-side trim of a group-by ordered by an aggregate
# (minServerGroupTrimSize): a server may send only its first
# max(5 x (OFFSET + LIMIT), 5000) groups by the ORDER BY, and that is
# exact only where its partial is the statement's only one, so the
# broker asks for it only where it routes every segment to one server
SERVER_TRIM_MIN = 5000


def server_trim_rows(ctx: QueryContext) -> int:
    """Groups a trimmed server partial of ``ctx`` carries."""
    limit = ctx.limit if ctx.limit is not None else DEFAULT_LIMIT
    return max(5 * (ctx.offset + limit), SERVER_TRIM_MIN)


@dataclass
class ResultTable:
    columns: List[str]
    rows: List[tuple]
    num_docs_scanned: int = 0
    num_segments: int = 0
    num_segments_pruned: int = 0
    time_ms: float = 0.0
    trace: Optional[dict] = None
    # scatter-gather health (Pinot BrokerResponseNative metadata):
    # populated by the networked broker's gather; the in-process broker
    # leaves them zero and to_dict omits them (response shape unchanged)
    partial_result: bool = False
    num_servers_queried: int = 0
    num_servers_responded: int = 0
    exceptions: List[Dict[str, Any]] = field(default_factory=list)

    def to_dict(self) -> Dict[str, Any]:
        out = {
            "resultTable": {
                "dataSchema": {"columnNames": self.columns},
                "rows": [list(r) for r in self.rows],
            },
            "numSegmentsQueried": self.num_segments,
            "numSegmentsPruned": self.num_segments_pruned,
            "numDocsScanned": self.num_docs_scanned,
            "timeUsedMs": self.time_ms,
        }
        if self.num_servers_queried or self.exceptions \
                or self.partial_result:
            out["numServersQueried"] = self.num_servers_queried
            out["numServersResponded"] = self.num_servers_responded
            out["partialResult"] = self.partial_result
            out["exceptions"] = list(self.exceptions)
        return out

    def __repr__(self) -> str:
        return f"ResultTable({self.columns}, {len(self.rows)} rows)"


# ---------------------------------------------------------------------------
# state algebra
# ---------------------------------------------------------------------------

def merge_state(agg: AggExpr, a: Any, b: Any) -> Any:
    return aggregations.merge_states(agg, a, b)


def finalize_state(agg: AggExpr, s: Any) -> Any:
    return aggregations.finalize_state(agg, s)


# ---------------------------------------------------------------------------
# reduce
# ---------------------------------------------------------------------------

def reduce_partials(ctx: QueryContext, partials: List[Any]) -> ResultTable:
    if ctx.is_group_by:
        return _reduce_group_by(ctx, [p for p in partials
                                      if isinstance(p, GroupByPartial)])
    if ctx.is_aggregation:
        return _reduce_aggregation(ctx, [p for p in partials
                                         if isinstance(p, AggPartial)])
    return _reduce_selection(ctx, [p for p in partials
                                   if isinstance(p, SelectionPartial)])


def _reduce_aggregation(ctx: QueryContext, partials: List[AggPartial]
                        ) -> ResultTable:
    aggs = ctx.aggregations
    # seed from the first partial (not empty_state) so a null partial —
    # SUM over all-null input under enableNullHandling — stays null
    if partials:
        merged = list(partials[0].states)
    else:
        merged = [aggregations.empty_state(a) for a in aggs]
    for p in partials[1:]:
        for i, a in enumerate(aggs):
            merged[i] = merge_state(a, merged[i], p.states[i])
    env = {a.label: finalize_state(a, merged[i])
           for i, a in enumerate(aggs)}
    if ctx.having is not None and not _eval_scalar_bool(ctx.having, env):
        return ResultTable(list(ctx.labels), [])
    row = tuple(env[item.label] if isinstance(item, AggExpr)
                else _eval_scalar(item, env)
                for item in ctx.select_items)
    labels = [l for item, l in zip(ctx.select_items, ctx.labels)]
    return ResultTable(labels, [row])


def merge_groups(aggs: List[AggExpr], partials: List[GroupByPartial]
                 ) -> Dict[Tuple, List[Any]]:
    """The group-by partials merged in their order: a group's states are
    its first partial's, each later one merged into them."""
    merged: Dict[Tuple, List[Any]] = {}
    for p in partials:
        for key, states in p.groups.items():
            cur = merged.get(key)
            if cur is None:
                merged[key] = list(states)
            else:
                for i, a in enumerate(aggs):
                    cur[i] = merge_state(a, cur[i], states[i])
    return merged


def _reduce_group_by(ctx: QueryContext, partials: List[GroupByPartial]
                     ) -> ResultTable:
    merged = merge_groups(ctx.aggregations, partials)

    group_labels = [_expr_label(g) for g in ctx.group_by]
    rows: List[tuple] = []
    for key, states in merged.items():
        env: Dict[str, Any] = dict(zip(group_labels, key))
        for i, agg in enumerate(ctx.aggregations):
            env[agg.label] = finalize_state(agg, states[i])
        if ctx.having is not None and not _eval_scalar_bool(ctx.having, env):
            continue
        rows.append((_build_row(ctx, env), env))  # env kept for ORDER BY

    if ctx.gapfill is not None:
        rows = _apply_gapfill(ctx, rows)

    if ctx.order_by:
        def sort_key(entry):
            _, env = entry
            parts = []
            for o in ctx.order_by:
                v = _eval_scalar(o.expr, env)
                parts.append(_OrderKey(v, o.ascending))
            return tuple(parts)
        rows.sort(key=sort_key)
    else:
        rows.sort(key=lambda e: _key_sortable(e[0]))

    limit = ctx.limit if ctx.limit is not None else DEFAULT_LIMIT
    rows = rows[ctx.offset: ctx.offset + limit]
    labels = list(ctx.labels)
    return ResultTable(labels, [r for r, _ in rows])


def _build_row(ctx: QueryContext, env: Dict[str, Any]) -> tuple:
    return tuple(env[item.label] if isinstance(item, AggExpr)
                 else env[_expr_label(item)]
                 if _expr_label(item) in env
                 else _eval_scalar(item, env)
                 for item in ctx.select_items)


def _apply_gapfill(ctx: QueryContext, entries: List[tuple]) -> List[tuple]:
    """Time-bucket gapfill over reduced group-by rows (GapfillProcessor
    analog). For every TIMESERIESON series observed in the result, emit
    one row per bucket in [start, end); missing buckets take
    FILL_PREVIOUS_VALUE (carry-forward along the series),
    FILL_DEFAULT_VALUE (zero-value of the column's observed type), or
    NULL for unfilled columns. Runs BEFORE order/limit, so LIMIT applies
    to the gapfilled output like the reference's outer query."""
    g = ctx.gapfill
    tl = g.time_label

    existing: Dict[tuple, Dict[int, Dict[str, Any]]] = {}
    series_order: List[tuple] = []
    other_labels: set = set()
    defaults: Dict[str, Any] = {}
    for _row, env in entries:
        t = env.get(tl)
        if not isinstance(t, (int, float)) or not g.start <= t < g.end:
            continue
        bucket = g.start + int((t - g.start) // g.interval) * g.interval
        sk = tuple(env.get(l) for l in g.series_labels)
        per = existing.get(sk)
        if per is None:
            per = existing[sk] = {}
            series_order.append(sk)
        per.setdefault(bucket, env)  # finer-than-interval rows: first wins
        for lbl, v in env.items():
            other_labels.add(lbl)
            if v is not None and lbl not in defaults:
                defaults[lbl] = type(v)()  # zero-value: 0 / 0.0 / ""
    other_labels -= {tl, *g.series_labels}

    out: List[tuple] = []
    for sk in series_order:
        per = existing[sk]
        prev_env: Optional[Dict[str, Any]] = None
        for bucket in range(g.start, g.end, g.interval):
            env = per.get(bucket)
            if env is None:
                env = {tl: bucket}
                env.update(zip(g.series_labels, sk))
                for lbl in other_labels:
                    mode = g.fills.get(lbl)
                    if mode == "previous" and prev_env is not None:
                        env[lbl] = prev_env.get(lbl)
                    elif mode == "default":
                        env[lbl] = defaults.get(lbl)
                    else:
                        env[lbl] = None
            else:
                env = dict(env)
                env[tl] = bucket
            out.append((_build_row(ctx, env), env))
            prev_env = env
    return out


def _key_sortable(row: tuple) -> tuple:
    return tuple((v is None, v) for v in row)


class _OrderKey:
    """Total-order wrapper handling DESC and None (nulls last)."""
    __slots__ = ("v", "asc")

    def __init__(self, v, asc: bool):
        self.v = v
        self.asc = asc

    def __lt__(self, other: "_OrderKey") -> bool:
        a, b = self.v, other.v
        if a is None:
            return False
        if b is None:
            return True
        return a < b if self.asc else b < a

    def __eq__(self, other) -> bool:
        return self.v == other.v


def _reduce_selection(ctx: QueryContext, partials: List[SelectionPartial]
                      ) -> ResultTable:
    labels: List[str] = []
    rows: List[tuple] = []
    okeys: List[tuple] = []
    for p in partials:
        if p.labels:
            labels = p.labels
        rows.extend(p.rows)
        okeys.extend(p.order_keys)
    if ctx.order_by and okeys:
        order = sorted(
            range(len(rows)),
            key=lambda i: tuple(
                _OrderKey(okeys[i][j], o.ascending)
                for j, o in enumerate(ctx.order_by)))
        rows = [rows[i] for i in order]
    limit = ctx.limit if ctx.limit is not None else DEFAULT_LIMIT
    rows = rows[ctx.offset: ctx.offset + limit]
    if not labels:
        labels = list(ctx.labels)
    return ResultTable(labels, rows)


# ---------------------------------------------------------------------------
# scalar (post-aggregation) expression evaluation for HAVING / ORDER BY
# ---------------------------------------------------------------------------

def _eval_scalar(e: Any, env: Dict[str, Any]) -> Any:
    if isinstance(e, AggExpr):
        return env[e.label]
    if isinstance(e, FuncCall):
        label = _expr_label(e)
        if label in env:
            return env[label]
        if F.lookup(e.name) is not None:
            args = [_eval_scalar(a, env) for a in e.args]
            out = F.call(e.name, *args)
            return out.item() if hasattr(out, "item") and \
                np.asarray(out).ndim == 0 else out
        raise SqlError(f"unknown function result {label!r}")
    if isinstance(e, Identifier):
        if e.name in env:
            return env[e.name]
        raise SqlError(f"unknown output column {e.name!r}")
    if isinstance(e, Literal):
        return e.value
    if isinstance(e, CaseWhen):
        for cond, res in e.whens:
            if _eval_scalar_bool(cond, env):
                return _eval_scalar(res, env)
        return None if e.else_ is None else _eval_scalar(e.else_, env)
    if isinstance(e, Cast):
        v = F.cast_value(_eval_scalar(e.expr, env), e.type_name)
        return v.item() if np.asarray(v).ndim == 0 else v
    if isinstance(e, BinaryOp):
        l = _eval_scalar(e.lhs, env)
        r = _eval_scalar(e.rhs, env)
        if l is None or r is None:
            return None
        if e.op == "+":
            return l + r
        if e.op == "-":
            return l - r
        if e.op == "*":
            return l * r
        if e.op == "/":
            return l / r
        if e.op == "%":
            return l % r
    raise SqlError(f"unsupported post-aggregation expression {e!r}")


def _eval_scalar_bool(e: Any, env: Dict[str, Any]) -> bool:
    """HAVING acceptance: only TRUE passes (SQL three-valued logic —
    a NULL aggregate, e.g. SUM over all-null inputs under
    enableNullHandling, makes the predicate NULL, which filters the
    group instead of raising; round-5 fuzz seed 777/166)."""
    return _bool3(e, env) is True


def _nullish(v: Any) -> bool:
    """NULL in either representation: None, or float NaN (what a null
    aggregate finalizes to on some paths — the same definition the
    IS NULL branch uses, so 3VL is consistent across predicates)."""
    return v is None or (isinstance(v, float) and v != v)


def _bool3(e: Any, env: Dict[str, Any]) -> Optional[bool]:
    """True / False / None (UNKNOWN), Kleene semantics."""
    if isinstance(e, BoolAnd):
        saw_null = False
        for c in e.children:          # short-circuits on False
            v = _bool3(c, env)
            if v is False:
                return False
            saw_null = saw_null or v is None
        return None if saw_null else True
    if isinstance(e, BoolOr):
        saw_null = False
        for c in e.children:          # short-circuits on True
            v = _bool3(c, env)
            if v is True:
                return True
            saw_null = saw_null or v is None
        return None if saw_null else False
    if isinstance(e, BoolNot):
        v = _bool3(e.child, env)
        return None if v is None else not v
    if isinstance(e, Comparison):
        l = _eval_scalar(e.lhs, env)
        r = _eval_scalar(e.rhs, env)
        if _nullish(l) or _nullish(r):
            return None
        try:                          # dispatch per op: == must never
            if e.op == "==":          # evaluate an ordering comparison
                return l == r
            if e.op == "!=":
                return l != r
            if e.op == "<":
                return l < r
            if e.op == "<=":
                return l <= r
            if e.op == ">":
                return l > r
            return l >= r
        except TypeError:
            raise SqlError(
                f"cannot compare {type(l).__name__} with "
                f"{type(r).__name__} in HAVING ({e.op})") from None
    if isinstance(e, Between):
        v = _eval_scalar(e.expr, env)
        lo = _eval_scalar(e.lo, env)
        hi = _eval_scalar(e.hi, env)
        if _nullish(v) or _nullish(lo) or _nullish(hi):
            return None
        ok = lo <= v <= hi
        return not ok if e.negated else ok
    if isinstance(e, InList):
        v = _eval_scalar(e.expr, env)
        if _nullish(v):
            return None
        ok = v in {x.value for x in e.values}
        return not ok if e.negated else ok
    if isinstance(e, IsNull):
        v = _eval_scalar(e.expr, env)
        isnull = _nullish(v)
        return not isnull if e.negated else isnull
    if isinstance(e, (FuncCall, Literal, CaseWhen, Cast)):
        v = _eval_scalar(e, env)
        return None if _nullish(v) else bool(v)
    raise SqlError(f"unsupported HAVING expression {e!r}")
