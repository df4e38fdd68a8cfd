"""Kernel builder: KernelPlan -> jit-able whole-segment function.

Reference parity: replaces the per-block pull loop of pinot-core
(DocIdSetOperator.java:59-86 blocks of <=10k docIds -> ProjectionOperator
gathers -> DefaultAggregationExecutor / DefaultGroupByExecutor.process).
TPU-native: no docId materialization at all — predicates evaluate to a
whole-segment boolean mask (masks replace RoaringBitmap), projections are
gathers, aggregations are masked reductions. The whole query runs as one
fused XLA program per segment; block iteration disappears.

Group-by rides the MXU, not scatters: TPU scatter-add (segment_sum) is
orders of magnitude slower than matmul on this hardware (measured 1.4s vs
~70ms for a 16M-row, G=1024 group-by), so dense group aggregation is a
one-hot dot_general:

    sums[g] = L @ one_hot(keys)           # (rows, N) x (N, G) on the MXU

with masked-out rows routed to an out-of-range sentinel key (one_hot
yields an all-zero column — no pollution, no mask multiply). Integer sums
stay EXACT by decomposing |v| into int8 limbs (base 2^b with
(2^b-1)*bucket <= int32max so the MXU's int8xint8->int32 accumulation
can't overflow), one row per limb per sign, recombined in int64.
DISTINCTCOUNT presence is the same trick squared:
one_hot(keys)^T @ one_hot(ids) > 0. Float sums never ride a float32
matmul: they accumulate in float_acc_dtype, float64 on every backend
(XLA:TPU carries a float64 as a pair of float32, 48 bits), as blocked
reductions (_float_sums) — a Q1 or Q6 answer over 2^26 rows is within
1e-12 relative of the exact decimal sum (PERF.md section 6, PR 35).
The dense cartesian dict-id key is DictionaryBasedGroupKeyGenerator
.java:63 arithmetic.

Kernel signature (shape-stable, no data-dependent shapes):
    fn(cols: tuple[jax.Array], n_docs: int32, params: tuple[jax.Array])
        -> dict[str, jax.Array]
"""
from __future__ import annotations

import functools
import os
from dataclasses import replace as dc_replace
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import phases as ph
from .ir import (AggSpec, And, Bin, Case, Cmp, Col, EqId, FalseP, Func,
                 IdRange, InBitmap, InSet, KernelPlan, Lit, MaskParam,
                 MvReduce, Not, Or, Pred, SelectPlan, TrueP, ValueExpr)

# IN lists longer than this use sorted-membership (raw values) or a
# presence-table gather (dict ids) instead of broadcast compare
INSET_SEARCH_MIN = 64
INSET_BITMAP_MIN = 64
# scalar DISTINCTCOUNT cardinality above which the one-hot presence
# matmul (rows x card MACs) yields to sort + run boundaries
DISTINCT_ONEHOT_CARD = 1 << 12

# unrolled masked-reduce limit for group MIN/MAX (no matmul form exists;
# above this the planner routes to segment ops on CPU or the host path)
MINMAX_UNROLL_GROUPS = 64
# dense group spaces up to this take one blocked masked float sum a group
# (_group_float_sums); the same unroll, for the same reason
FLOAT_UNROLL_GROUPS = MINMAX_UNROLL_GROUPS
# longest dictionary _decode_dict decodes by a select chain instead of a
# gather. From the v5e rows of PERF.md section 6 (PR 26): the Q1-shaped
# dense kernel over 8 x 2^23 rows, chain | gather in ms a launch (chain's
# compile seconds): K=11 3.2 | 490 (1.0), 64 4.6 | 490 (4.7), 256 10.7 |
# 639 (11.4), 1,024 129 | 638 (62.4; XLA cuts the chain into 60 fusions),
# 4,096 did not compile (40 GiB of host memory). 256 is the largest power
# of two that is at least twice as fast and compiles in seconds.
DICT_SELECT_MAX = 256


def cpu_scatter_default(platform: Optional[str] = None) -> bool:
    """Whether group-by kernels should take the scatter (segment-ops) path.

    The one-hot MXU formulation is the TPU design; XLA:CPU executes those
    int8 matmuls 50-100x slower than a plain scatter-add (round-4 CPU
    captures: compact kernels at 0.01-0.16x the numpy baseline).
    CPU scatter-add is fast, so when the execution platform is cpu the
    kernels swap the aggregation core for jax.ops.segment_* — same dense
    (space,) outputs, same extraction. PINOT_CPU_FAST_GROUPBY=0 pins the
    MXU formulation everywhere (the test suite does this so the TPU-shaped
    code stays covered on the virtual CPU mesh)."""
    plat = platform or jax.default_backend()
    return (plat == "cpu"
            and os.environ.get("PINOT_CPU_FAST_GROUPBY", "1") == "1")


def float_acc_dtype() -> jnp.dtype:
    """Float accumulator dtype: the ONE rule for every float SUM / AVG /
    MIN / MAX, the arithmetic of a float value expression and the host's
    count of what a launch did (float_acc_forms). Pinot's float
    aggregates return double, so: float64 wherever jax_enable_x64 is on
    (pinot_tpu/__init__ turns it on at import), on every backend. XLA:TPU
    has no native float64: it carries one as a pair of float32 (hi + lo,
    48 bits of significand, float32's exponent range) and lowers + and *
    to TwoSum / Dekker sequences over the pair, about 20 float32
    operations an add. That is the price of the stated bound: with the
    sums blocked (_float_sums) a SUM or AVG over 2^26 rows of prices is
    within 1e-12 relative of the exact decimal value on the chip
    (measured: PERF.md section 6, PR 35), where a float32 accumulator
    read 1e-7 and a float32 VALUE alone 5e-12. float32 only with x64
    off, where no wider type exists."""
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


# addends of one running float sum: _float_sums reduces blocks of this
# many rows, then the block sums
FLOAT_SUM_BLOCK = 1 << 12


def int_acc_dtype() -> jnp.dtype:
    """int64 when available: a 100M-row int32 segment sum needs ~2^57."""
    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


def sum_carrier_dtype(bits: int):
    """Narrowest EXACT carrier for a compact-path integral sum payload
    whose magnitude the planner bounded at ``bits`` (_payload_columns
    narrows through this; analysis/plan_verify.py checks against it, so
    the narrowing rule cannot fork). Values under 2^31 ride int32 — half
    the compaction bytes, no 64-bit split. Returns None when no exact
    integer carrier of the claimed width exists (jax_enable_x64 off and
    bits >= 32): narrowing would silently truncate, so callers must fail
    loudly instead (PV104)."""
    if bits < 32:
        return jnp.int32
    return jnp.int64 if jax.config.jax_enable_x64 else None


def _limb_base_bits(bucket: int) -> int:
    """Largest b <= 7 with (2^b - 1) * bucket <= int32max: per-group int8
    dot products then can't overflow the MXU's int32 accumulator."""
    b = 7
    while b > 1 and ((1 << b) - 1) * bucket > (1 << 31) - 1:
        b -= 1
    return b


# ---------------------------------------------------------------------------
# value expressions
# ---------------------------------------------------------------------------

def _decodes_by_select(length: int) -> bool:
    """The one predicate of the decode's form: _decode_dict calls it on
    the static length of the dictionary it is handed, dict_decode_forms
    on the same length when the launch is counted."""
    return length <= DICT_SELECT_MAX


def _decode_dict(table: jax.Array, ids: jax.Array) -> jax.Array:
    """value = table[ids] for a 1-D dictionary (under vmap the stacked
    (S, K) arrives as (K,); the segmented compact kernel hands over the
    flattened S*K). The form follows table.shape[-1], static at trace
    time:

    - up to DICT_SELECT_MAX entries: a select chain, K compares and K
      selects an element and no gather in the HLO. It is elementwise, so
      XLA fuses it into the scan that consumes the value (a TPU gather
      is serial in rows: 7-10 ns an element; PERF.md section 6, PR 26);
    - longer: jnp.take.

    Both hand back the entry's own bits, whatever the dtype. They differ
    only on an id outside [0, K): jnp.take fills (NaN, or the integer's
    extreme), the chain yields table[0]. No such id reaches a result:
    padding rows carry id 0 and are masked, and MV pads (-1) are clamped
    to 0 by the caller before the decode and masked by `present` after."""
    n = table.shape[-1]
    if not _decodes_by_select(n):
        return jnp.take(table, ids)
    out = jnp.broadcast_to(table[0], ids.shape)
    for k in range(1, n):
        out = jnp.where(ids == k, table[k], out)
    return out


def _eval_value(ve: ValueExpr, cols, params, promote: bool = False
                ) -> jax.Array:
    """promote=True upcasts integral column leaves to int64 so products in
    aggregation expressions (SUM(price * discount)) can't wrap int32."""
    if isinstance(ve, Col):
        arr = cols[ve.col]
        if ve.dict_param is not None:
            with jax.named_scope(ph.SCOPE_DECODE_DICT):
                arr = _decode_dict(params[ve.dict_param], arr)
        if promote and jnp.issubdtype(arr.dtype, jnp.integer):
            arr = arr.astype(int_acc_dtype())
        return arr
    if isinstance(ve, Lit):
        return params[ve.param]
    if isinstance(ve, MvReduce):
        ids = cols[ve.col]                       # (N, M) int32, pad -1
        present = ids >= 0
        if ve.mode == "count":
            return present.sum(-1).astype(int_acc_dtype())
        vals = ids
        if ve.dict_param is not None:
            with jax.named_scope(ph.SCOPE_DECODE_DICT):
                vals = _decode_dict(params[ve.dict_param],
                                    jnp.maximum(ids, 0))
        if promote and jnp.issubdtype(vals.dtype, jnp.integer):
            vals = vals.astype(int_acc_dtype())
        if ve.mode == "sum":
            return jnp.where(present, vals,
                             jnp.zeros((), vals.dtype)).sum(-1)
        sign = 1 if ve.mode == "min" else -1
        filled = jnp.where(present, vals, _extreme(vals.dtype, sign))
        return filled.min(-1) if ve.mode == "min" else filled.max(-1)
    if isinstance(ve, Bin):
        l = _eval_value(ve.lhs, cols, params, promote)
        r = _eval_value(ve.rhs, cols, params, promote)
        if ve.op == "+":
            return l + r
        if ve.op == "-":
            return l - r
        if ve.op == "*":
            return l * r
        if ve.op == "/":
            # SQL division is double division (ArithmeticFunctions.divide)
            return l.astype(float_acc_dtype()) / r.astype(float_acc_dtype())
        if ve.op == "%":
            return l % r
        if ve.op == "//":
            return jnp.floor_divide(l, r)
        raise ValueError(f"unknown binary op {ve.op!r}")
    if isinstance(ve, Func):
        args = [_eval_value(a, cols, params, promote) for a in ve.args]
        return _eval_func(ve.name, args)
    if isinstance(ve, Case):
        out = _eval_value(ve.else_, cols, params, promote)
        # all-literal CASE (no columns, predicates const-folded) folds at
        # bucket 1 and returns a scalar for the consumer to broadcast
        scalar = not cols and not out.ndim
        bucket = (cols[0].shape[0] if cols
                  else (out.shape[0] if out.ndim else 1))
        out = jnp.broadcast_to(out, (bucket,) + out.shape[1:])
        # reverse order: the first matching WHEN must win
        for pred, val in reversed(ve.whens):
            m = jnp.reshape(_eval_pred(pred, cols, params, bucket),
                            (bucket,))
            v = _eval_value(val, cols, params, promote)
            ct = jnp.promote_types(v.dtype, out.dtype)
            out = jnp.where(m, v.astype(ct), out.astype(ct))
        return out[0] if scalar else out
    raise TypeError(f"unknown value expr {ve!r}")


# closed-form device datetime math over epoch millis. Civil-from-days is
# Howard Hinnant's branchless algorithm — pure integer ops that lower to
# XLA unchanged. Semantics MUST match query/functions.py's numpy
# datetime64 host path (floor division handles pre-1970 correctly).
_MS_DAY = 86_400_000


def _civil_ymd(days):
    # only the era needs 64 bits: the day of the era is in [0, 146096],
    # so the rest is int32 arithmetic. XLA:TPU emulates every int64
    # division in int32 pairs, and the compiler took minutes over the
    # all-int64 form of a YEAR group key (PERF.md section 6)
    z = days.astype(jnp.int64) + 719468
    era = jnp.floor_divide(z, 146097)
    doe = (z - era * 146097).astype(jnp.int32)
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = yoe.astype(jnp.int64) + era * 400
    y = jnp.where(m <= 2, y + 1, y)
    return y, m.astype(jnp.int64), d.astype(jnp.int64)


def _days_from_civil(y, m, d):
    y = y - (m <= 2)
    era = jnp.floor_divide(y, 400)
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = jnp.floor_divide(153 * mp + 2, 5) + d - 1
    doe = yoe * 365 + jnp.floor_divide(yoe, 4)         - jnp.floor_divide(yoe, 100) + doy
    return era * 146097 + doe - 719468


def _whole(x: jax.Array, how: str) -> jax.Array:
    """floor(x), or round(x) half to even (numpy's rule, which the host
    path (query/functions.py) and ClickHouse's round of a Float64 follow),
    from a conversion, a subtraction and compares alone. XLA:TPU's own
    round of an emulated float64 is wrong where the high float32 of the
    pair is a tie and the low one is not 0 (on a TPU v5e,
    round(2.4999999999) read 1.0 and round(0.49999999999) -1.0: PERF.md
    section 6). The conversion may miss by one there too, so the integer
    part is corrected until |x - t| < 1; a double of 2^52 or more is
    whole."""
    t = x.astype(jnp.int64)
    d = x - t.astype(x.dtype)
    t = t + (d >= 1).astype(jnp.int64) - (d <= -1).astype(jnp.int64)
    f = x - t.astype(x.dtype)                  # in (-1, 1)
    if how == "floor":
        r = t - (f < 0).astype(jnp.int64)
    else:
        odd = t & 1
        r = (t + (f > 0.5).astype(jnp.int64) - (f < -0.5).astype(jnp.int64)
             + ((f == 0.5).astype(jnp.int64)
                - (f == -0.5).astype(jnp.int64)) * odd)
    return jnp.where(jnp.abs(x) < 2.0 ** 52, r.astype(x.dtype), x)


def _eval_func(name: str, args) -> jax.Array:
    a = args[0]
    if name in ("cast_long", "cast_int"):
        if jnp.issubdtype(a.dtype, jnp.floating):
            a = jnp.trunc(a)  # C-style truncation (host cast_value)
        return a.astype(jnp.int64 if name == "cast_long" else jnp.int32)
    if name in ("cast_double", "cast_float"):
        return a.astype(jnp.float64 if name == "cast_double"
                        else jnp.float32)
    if name == "abs":
        return jnp.abs(a)
    if name in ("floor", "round"):
        return _whole(a.astype(float_acc_dtype()), name)
    if name == "ceil":
        return jnp.ceil(a.astype(float_acc_dtype()))
    if name == "sqrt":
        return jnp.sqrt(a.astype(float_acc_dtype()))
    if name == "exp":
        return jnp.exp(a.astype(float_acc_dtype()))
    if name == "ln":
        return jnp.log(a.astype(float_acc_dtype()))
    ms = a.astype(jnp.int64)
    days = jnp.floor_divide(ms, _MS_DAY)
    if name == "year":
        return _civil_ymd(days)[0]
    if name == "month":
        return _civil_ymd(days)[1]
    if name == "day":
        return _civil_ymd(days)[2]
    if name == "quarter":
        return jnp.floor_divide(_civil_ymd(days)[1] - 1, 3) + 1
    if name == "dayofweek":
        # 1=Monday..7=Sunday (host _field; epoch day 0 was a Thursday)
        return (days + 3) % 7 + 1
    if name == "hour":
        return jnp.floor_divide(ms, 3_600_000) % 24
    if name == "minute":
        return jnp.floor_divide(ms, 60_000) % 60
    if name == "second":
        return jnp.floor_divide(ms, 1000) % 60
    if name == "millisecond":
        return ms % 1000
    if name.startswith("trunc_"):
        unit = name[6:]
        if unit == "second":
            return jnp.floor_divide(ms, 1000) * 1000
        if unit == "minute":
            return jnp.floor_divide(ms, 60_000) * 60_000
        if unit == "hour":
            return jnp.floor_divide(ms, 3_600_000) * 3_600_000
        if unit == "day":
            return days * _MS_DAY
        if unit == "week":
            # ISO week start (Monday); day 0 = Thursday -> offset 3
            return (jnp.floor_divide(days + 3, 7) * 7 - 3) * _MS_DAY
        y, m, _d = _civil_ymd(days)
        if unit == "month":
            return _days_from_civil(y, m, jnp.ones_like(m)) * _MS_DAY
        if unit == "quarter":
            qm = jnp.floor_divide(m - 1, 3) * 3 + 1
            return _days_from_civil(y, qm, jnp.ones_like(m)) * _MS_DAY
        if unit == "year":
            return _days_from_civil(y, jnp.ones_like(m),
                                    jnp.ones_like(m)) * _MS_DAY
    raise ValueError(f"no device lowering for function {name!r}")


# ---------------------------------------------------------------------------
# predicates -> mask
# ---------------------------------------------------------------------------

def _val_negate(m: jax.Array, arr: jax.Array) -> jax.Array:
    """Value-level predicate negation (!=, NOT IN, NOT BETWEEN): flip the
    per-value mask, keeping MV pad slots (-1) unmatched so the any-
    reduction sees only real values."""
    m = ~m
    if arr.ndim == 2:
        m &= arr >= 0
    return m


def _mv_any(m: jax.Array) -> jax.Array:
    """MV predicate semantics: a row matches when ANY of its values does
    (reference predicate evaluators' applySV vs applyMV split). SV masks
    pass through; (N, M) masks reduce over the value axis. The -1 pad id
    can never equal a dictionary id or fall in an id range, so pad slots
    are inert."""
    return m.any(axis=-1) if m.ndim == 2 else m


def _eval_pred(p: Pred, cols, params, bucket: int) -> jax.Array:
    if isinstance(p, TrueP):
        return jnp.ones((bucket,), dtype=jnp.bool_)
    if isinstance(p, FalseP):
        return jnp.zeros((bucket,), dtype=jnp.bool_)
    if isinstance(p, EqId):
        arr = cols[p.col]
        m = arr == params[p.param]
        return _mv_any(_val_negate(m, arr) if p.negated else m)
    if isinstance(p, IdRange):
        arr = cols[p.col]
        m = jnp.ones(arr.shape, dtype=jnp.bool_)
        if p.lo_param is not None:
            m &= arr >= params[p.lo_param]
        if p.hi_param is not None:
            m &= arr <= params[p.hi_param]
        if p.lo_param is None and arr.ndim == 2:
            # hi-only range on MV: exclude the -1 pad slots (lo-bounded
            # ranges exclude them already: dict-id bounds are >= 0)
            m &= arr >= 0
        return _mv_any(_val_negate(m, arr) if p.negated else m)
    if isinstance(p, InSet):
        arr = cols[p.col]
        vals = params[p.param]  # (n,) sorted ascending
        if p.n > INSET_SEARCH_MIN:
            # sorted membership: binary search beats the O(rows x n)
            # broadcast compare for big IN lists (InPredicateEvaluator
            # analog for raw values; dict columns take InBitmap instead)
            idx = jnp.clip(jnp.searchsorted(vals, arr), 0, p.n - 1)
            m = jnp.take(vals, idx) == arr
        else:
            m = (arr[..., None] == vals[None, :]).any(axis=-1)
        return _mv_any(_val_negate(m, arr) if p.negated else m)
    if isinstance(p, InBitmap):
        arr = cols[p.col]
        tbl = params[p.param]   # (cardinality,) bool presence over ids
        m = jnp.take(tbl, jnp.maximum(arr, 0)) & (arr >= 0)
        return _mv_any(_val_negate(m, arr) if p.negated else m)
    if isinstance(p, Cmp):
        l = _eval_value(p.lhs, cols, params)
        r = params[p.param]
        if p.op == "==":
            return l == r
        if p.op == "!=":
            return l != r
        if p.op == "<":
            return l < r
        if p.op == "<=":
            return l <= r
        if p.op == ">":
            return l > r
        if p.op == ">=":
            return l >= r
        raise ValueError(f"unknown cmp op {p.op!r}")
    if isinstance(p, MaskParam):
        return params[p.param]
    if isinstance(p, And):
        m = _eval_pred(p.children[0], cols, params, bucket)
        for c in p.children[1:]:
            m &= _eval_pred(c, cols, params, bucket)
        return m
    if isinstance(p, Or):
        m = _eval_pred(p.children[0], cols, params, bucket)
        for c in p.children[1:]:
            m |= _eval_pred(c, cols, params, bucket)
        return m
    if isinstance(p, Not):
        return ~_eval_pred(p.child, cols, params, bucket)
    raise TypeError(f"unknown predicate {p!r}")


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def _extreme(dtype, sign: int):
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        return jnp.asarray(info.max if sign > 0 else info.min, dtype=dtype)
    return jnp.asarray(jnp.inf if sign > 0 else -jnp.inf, dtype=dtype)


def _acc_dtype(spec: AggSpec) -> jnp.dtype:
    return int_acc_dtype() if spec.integral else float_acc_dtype()


def _agg_name(i: int, spec: AggSpec) -> str:
    return f"agg{i}_{spec.kind}"


def _int8_dot(lhs: jax.Array, rhs: jax.Array) -> jax.Array:
    """(R, N) int8 x (N, G) int8 -> (R, G) int32 on the MXU."""
    return jax.lax.dot_general(lhs, rhs, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _limb_rows(vals64: jax.Array, mask: jax.Array, bits: int, signed: bool,
               bucket: int) -> Tuple[List[jax.Array], List[int], int]:
    """Decompose a masked int64 vector into int8 limb rows per sign.

    Returns (rows, signs, base_bits): sum(v) over any subset equals
    sum_l sign_l * 2^(b*(l % nl)) * dot(row_l, subset_indicator), exactly.
    When the planner proved the value non-negative, the negative-sign rows
    are omitted entirely.
    """
    b = _limb_base_bits(bucket)
    nl = -(-min(bits, 63) // b)
    rows: List[jax.Array] = []
    signs: List[int] = []
    lim = jnp.uint64((1 << b) - 1)
    if signed:
        sources = ((1, jnp.where(mask & (vals64 >= 0), vals64, 0)),
                   (-1, jnp.where(mask & (vals64 < 0), -vals64, 0)))
    else:
        sources = ((1, jnp.where(mask, vals64, 0)),)
    for sign, src in sources:
        u = src.astype(jnp.uint64)
        for l in range(nl):
            rows.append(((u >> jnp.uint64(b * l)) & lim).astype(jnp.int8))
            signs.append(sign)
    return rows, signs, b


# ---------------------------------------------------------------------------
# device sketch lowerings (round-5, VERDICT r4 next-step #2): the
# flagship sketch aggregations stop demoting queries to host execution.
# Partial-state formats match the host AggImpl registry exactly, so
# kernel partials merge with host partials in the broker reduce.
# ---------------------------------------------------------------------------

def _device_splitmix64(v: jax.Array) -> jax.Array:
    """aggregations._splitmix64 on device (bit-identical): the shared
    64-bit hash for HLL/theta over raw numeric columns. Floats view
    their float64 bits as int64 first, exactly like the host _hash64."""
    if jnp.issubdtype(v.dtype, jnp.floating):
        v = jax.lax.bitcast_convert_type(v.astype(jnp.float64), jnp.int64)
    x = v.astype(jnp.uint64)
    x = x + jnp.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> jnp.uint64(31))


def _agg_hashes(spec: AggSpec, cols, params) -> jax.Array:
    """The 64-bit hash stream for a sketch aggregation: dict columns
    decode a precomputed per-id hash table (params[dict_param], host
    _hash64 over the dictionary values — md5 for strings); raw numeric
    columns hash on device."""
    ve = spec.value
    if isinstance(ve, Col) and ve.dict_param is not None:
        return _decode_dict(params[ve.dict_param], cols[ve.col])
    return _device_splitmix64(_eval_value(ve, cols, params))


def _sorted_presence(comb: jax.Array, n_slots: int) -> jax.Array:
    """(n_slots,) bool: which slot ids appear in comb (sentinel rows
    carry id == n_slots). Sort + searchsorted boundary diffs — the same
    scatter-free shape as the big-cardinality DISTINCTCOUNT path."""
    s = jnp.sort(comb.astype(jnp.int32))
    edges = jnp.searchsorted(s, jnp.arange(n_slots + 1, dtype=jnp.int32))
    return (edges[1:] - edges[:-1]) > 0


def _hll_slots(spec: AggSpec, cols, params):
    """(slot, r_levels): register index = top log2m hash bits, rank =
    leading zeros of the remainder + 1 (sentinel bit bounds it), slot =
    idx * r_levels + (rank - 1). The single source of the device HLL
    scheme (scalar + grouped); must stay bit-identical to the host
    HllAgg._regs."""
    p = spec.card                    # log2m
    r_levels = 64 - p + 1
    h = _agg_hashes(spec, cols, params)
    idx = (h >> jnp.uint64(64 - p)).astype(jnp.int32)
    rest = (h << jnp.uint64(p)) | jnp.uint64(1 << (p - 1))
    rank = jax.lax.clz(rest).astype(jnp.int32) + 1   # 1 .. R
    return idx * r_levels + (rank - 1), r_levels


def _scalar_hll(name: str, spec: AggSpec, mask, cols, params,
                out: Dict[str, jax.Array]) -> None:
    """DISTINCTCOUNTHLL: (m * R) presence bitmap; extraction maxes over
    the rank axis into the host HllAgg register list."""
    slot, r_levels = _hll_slots(spec, cols, params)
    n_slots = (1 << spec.card) * r_levels
    comb = jnp.where(mask, slot, n_slots)
    out[name + "_present"] = _sorted_presence(comb, n_slots)


def _scalar_theta(name: str, spec: AggSpec, mask, cols, params,
                  out: Dict[str, jax.Array]) -> None:
    """KMV theta sketch: the k smallest DISTINCT hashes. Sort with an
    all-ones sentinel for unmatched rows, flag first occurrences, and
    gather the positions of unique-ranks 1..k (searchsorted over the
    cumulative unique count — no data-dependent shapes)."""
    k = spec.card
    h = _agg_hashes(spec, cols, params)
    sentinel = jnp.uint64(0xFFFFFFFFFFFFFFFF)
    s = jnp.sort(jnp.where(mask, h, sentinel))
    uniq = jnp.concatenate([jnp.ones(1, jnp.bool_), s[1:] != s[:-1]])
    ranks = chunked_cumsum(uniq.astype(jnp.int32)).astype(jnp.int32)
    pos = jnp.searchsorted(ranks, jnp.arange(1, k + 1, dtype=jnp.int32))
    picked = s.at[jnp.minimum(pos, s.shape[0] - 1)].get(mode="clip")
    n_uniq = ranks[-1]
    valid = jnp.arange(k, dtype=jnp.int32) < n_uniq
    # sentinel-valued picks are unmatched-row hashes, not data: mask them
    out[name + "_hashes"] = jnp.where(valid & (picked != sentinel),
                                      picked, sentinel)


def _scalar_percentile(name: str, spec: AggSpec, mask, cols, params,
                       out: Dict[str, jax.Array]) -> None:
    """Mergeable quantile summary: device sort of the matched values,
    equal-count chunk boundaries over the matched prefix, centroid
    means via prefix-sum differences. Output (C,) means + weights maps
    to the host PercentileSketchAgg centroid list."""
    c = spec.card                    # number of centroids
    vals = _eval_value(spec.value, cols, params).astype(float_acc_dtype())
    big = jnp.asarray(jnp.inf, vals.dtype)
    s = jnp.sort(jnp.where(mask, vals, big))    # matched prefix first
    mcount = jnp.sum(mask, dtype=jnp.int32)
    ps = chunked_cumsum(jnp.where(jnp.isfinite(s), s, 0))
    bounds = (jnp.arange(c + 1, dtype=jnp.int64) * mcount) // c
    totals = jnp.where(bounds > 0,
                       ps.at[jnp.maximum(bounds - 1, 0)].get(mode="clip"),
                       0)
    w = (bounds[1:] - bounds[:-1]).astype(jnp.int32)
    sums = totals[1:] - totals[:-1]
    out[name + "_pc_mean"] = jnp.where(
        w > 0, sums / jnp.maximum(w, 1).astype(sums.dtype), 0.0)
    out[name + "_pc_w"] = w


_SKETCH_SCALAR = {"distinct_count_hll": _scalar_hll,
                  "distinct_count_theta": _scalar_theta,
                  "percentile_sketch": _scalar_percentile,
                  # RAW forms share the kernels: RawAgg delegates state
                  # to the inner sketch impl, only finalize serializes
                  "raw_hll": _scalar_hll,
                  "raw_theta": _scalar_theta,
                  "percentile_raw_sketch": _scalar_percentile}

_HLL_KINDS = ("distinct_count_hll", "raw_hll")

# grouped HLL presence bitmap cap: space * 2^log2m * rank_levels slots
# (bool). 2^23 = 8MB per aggregation — plenty for dashboard-shaped
# group-bys; larger spaces keep the host registry.
GROUPED_HLL_LIMIT = 1 << 23


def _group_hll(name: str, spec: AggSpec, mask, keys_s, space: int, cols,
               params, out: Dict[str, jax.Array]) -> None:
    """Grouped DISTINCTCOUNTHLL on device (round-5): one combined key
    (group, register, rank) presence bitmap via the scatter-free
    sort+searchsorted shape. Output (space, m*R) bool rows merge across
    segments/shards by elementwise OR; extraction maxes ranks per group
    into host HllAgg register lists."""
    slot, r_levels = _hll_slots(spec, cols, params)
    m = 1 << spec.card
    comb = jnp.where(mask & (keys_s < space),
                     keys_s * (m * r_levels) + slot,
                     space * m * r_levels)
    pres = _sorted_presence(comb, space * m * r_levels)
    out[name + "_present"] = pres.reshape(space, m * r_levels)


# ---------------------------------------------------------------------------
# scalar (non-group-by) aggregation
# ---------------------------------------------------------------------------

@jax.named_scope(ph.SCOPE_AGGREGATE)
def _scalar_agg(i: int, spec: AggSpec, mask, cols, params,
                out: Dict[str, jax.Array]) -> None:
    name = _agg_name(i, spec)
    cnt_dtype = int_acc_dtype()
    if spec.null_param is not None:
        # enableNullHandling: this aggregation skips null-input rows and
        # reports its own non-null count (extract finalizes all-null
        # SUM/MIN/MAX to null from it)
        mask = mask & ~params[spec.null_param]
        out[name + "_nnz"] = jnp.sum(mask, dtype=cnt_dtype)
    sketch_fn = _SKETCH_SCALAR.get(spec.kind)
    if sketch_fn is not None:
        sketch_fn(name, spec, mask, cols, params, out)
        return
    if spec.kind == "count":
        out[name] = jnp.sum(mask, dtype=cnt_dtype)
        return
    if spec.kind == "distinct_count":
        ids = _eval_value(spec.value, cols, params)
        ids_s = jnp.where(mask, ids, spec.card)  # sentinel past the card
        if spec.card > DISTINCT_ONEHOT_CARD:
            # sort + run boundaries: O(n log n) with no card-sized
            # matmul operand — scales DISTINCTCOUNT to 1M+ cardinality
            # (the partial stays the mergeable (card,) presence bitmap)
            s = jnp.sort(ids_s.astype(jnp.int32))
            edges = jnp.searchsorted(
                s, jnp.arange(spec.card + 1, dtype=jnp.int32))
            out[name + "_present"] = (edges[1:] - edges[:-1]) > 0
            return
        # presence via MXU: counts[c] = mask . one_hot(ids)[., c]; > 0
        oh = jax.nn.one_hot(ids_s, spec.card, dtype=jnp.int8)
        counts = _int8_dot(mask.astype(jnp.int8)[None, :], oh)[0]
        out[name + "_present"] = counts > 0
        return
    acc = _acc_dtype(spec)
    if spec.kind in ("sum", "avg") and not spec.integral:
        # the one group of _float_sums: matched rows carry key 0
        total = _float_sums([spec.value], cols, params,
                            jnp.where(mask, 0, 1), 1)[0, 0]
        if spec.kind == "avg":
            out[name + "_sum"] = total
            out[name + "_cnt"] = jnp.sum(mask, dtype=cnt_dtype)
        else:
            out[name] = total
        return
    vals = _eval_value(spec.value, cols, params, promote=spec.integral)
    if spec.kind == "sum":
        out[name] = jnp.sum(jnp.where(mask, vals, 0).astype(acc))
    elif spec.kind == "min":
        big = _extreme(acc, +1)
        out[name] = jnp.min(jnp.where(mask, vals.astype(acc), big))
    elif spec.kind == "max":
        small = _extreme(acc, -1)
        out[name] = jnp.max(jnp.where(mask, vals.astype(acc), small))
    elif spec.kind == "avg":
        out[name + "_sum"] = jnp.sum(jnp.where(mask, vals, 0).astype(acc))
        out[name + "_cnt"] = jnp.sum(mask, dtype=cnt_dtype)
    else:
        raise ValueError(f"unknown agg kind {spec.kind!r}")


# ---------------------------------------------------------------------------
# group-by aggregation (one-hot dot_general; scatter on CPU)
# ---------------------------------------------------------------------------

@jax.named_scope(ph.SCOPE_GROUP_KEY)
def _group_keys_sentinel(plan: KernelPlan, mask, cols, params):
    """Shared cartesian dict-id key build (DictionaryBasedGroupKeyGenerator
    .java:63 arithmetic) + sentinel application: returns (mask, keys_s)
    with unmatched rows (and out-of-range expression keys) mapped to the
    sentinel key == plan.group_space. Single source of truth for the
    one-hot, scatter, and compact cores."""
    space = plan.group_space
    keys = jnp.zeros(mask.shape, dtype=jnp.int32)
    exprs = plan.key_exprs or (None,) * len(plan.group_keys)
    for (col_idx, card), kexpr in zip(plan.group_keys, exprs):
        ids = cols[col_idx] if kexpr is None \
            else _eval_value(kexpr, cols, params)
        keys = keys * jnp.int32(card) + ids.astype(jnp.int32)
    if plan.key_exprs:
        # expression keys have no dictionary guarantee: clamp strays
        # (pre-epoch garbage etc.) onto the sentinel instead of wrapping
        # into a wrong group
        mask = mask & (keys >= 0) & (keys < space)
    return mask, jnp.where(mask, keys, space)


def _scatter_group(plan: KernelPlan, mask, keys_s, cols, params, space: int,
                   out: Dict[str, jax.Array]) -> None:
    """CPU-fast group aggregation core: jax.ops.segment_* over sentinel
    keys (sentinel = space, sliced off). Output contract is identical to
    the one-hot formulation — dense (space,) arrays — so extraction and
    broker reduce are oblivious to which core ran."""
    nseg = space + 1
    cnt_dtype = int_acc_dtype()
    counts = jax.ops.segment_sum(mask.astype(cnt_dtype), keys_s,
                                 num_segments=nseg)[:space]
    out["group_count"] = counts
    for i, spec in enumerate(plan.aggs):
        name = _agg_name(i, spec)
        if spec.kind == "count":
            continue
        if spec.kind in _HLL_KINDS:
            # the grouped HLL presence shape is backend-agnostic
            _group_hll(name, spec, mask, keys_s, space, cols, params, out)
            continue
        if spec.kind == "distinct_count":
            ids = _eval_value(spec.value, cols, params)
            comb = jnp.where(
                mask, keys_s.astype(jnp.int64) * spec.card + ids,
                jnp.int64(space) * spec.card)
            pres = jax.ops.segment_sum(
                jnp.ones(comb.shape, dtype=jnp.int32), comb,
                num_segments=space * spec.card + 1)[:space * spec.card]
            out[name + "_present"] = pres.reshape(space, spec.card) > 0
            continue
        vals = _eval_value(spec.value, cols, params, promote=spec.integral)
        acc = _acc_dtype(spec)
        if spec.kind in ("sum", "avg"):
            s = jax.ops.segment_sum(
                jnp.where(mask, vals, 0).astype(acc), keys_s,
                num_segments=nseg)[:space]
            if spec.kind == "avg":
                out[name + "_sum"] = s
                out[name + "_cnt"] = counts
            else:
                out[name] = s
        elif spec.kind in ("min", "max"):
            sign = +1 if spec.kind == "min" else -1
            segf = (jax.ops.segment_min if spec.kind == "min"
                    else jax.ops.segment_max)
            filled = jnp.where(mask, vals.astype(acc), _extreme(acc, sign))
            out[name] = segf(filled, keys_s, num_segments=nseg)[:space]
        else:
            raise ValueError(f"unknown agg kind {spec.kind!r}")


@jax.named_scope(ph.SCOPE_AGGREGATE)
def _group_aggs(plan: KernelPlan, mask, cols, params, bucket: int,
                out: Dict[str, jax.Array], scatter: bool = False) -> None:
    space = plan.group_space
    mask, keys_s = _group_keys_sentinel(plan, mask, cols, params)
    if scatter:
        _scatter_group(plan, mask, keys_s, cols, params, space, out)
        return
    oh8 = jax.nn.one_hot(keys_s, space, dtype=jnp.int8)

    # one int8 limb matrix serves counts + every exact integer sum
    int_rows: List[jax.Array] = [mask.astype(jnp.int8)]  # row 0: counts
    int_row_meta: List[Tuple[int, List[int], int]] = []  # (start, signs, b)

    float_slots: Dict[ValueExpr, int] = {}   # SUM(x) and AVG(x) share x

    deferred: List[Tuple[int, AggSpec, str]] = []

    for i, spec in enumerate(plan.aggs):
        name = _agg_name(i, spec)
        kind = spec.kind
        if kind == "count":
            continue  # served by the shared count row
        if kind in _HLL_KINDS:
            deferred.append((i, spec, "hll"))
            continue
        if kind in ("sum", "avg") and spec.integral:
            vals = _eval_value(spec.value, cols, params, promote=True)
            rows, signs, b = _limb_rows(vals, mask, spec.bits, spec.signed,
                                        bucket)
            int_row_meta.append((len(int_rows), signs, b))
            int_rows.extend(rows)
            deferred.append((i, spec, "int_sum"))
        elif kind in ("sum", "avg"):
            float_slots.setdefault(spec.value, len(float_slots))
            deferred.append((i, spec, "float_sum"))
        elif kind in ("min", "max"):
            deferred.append((i, spec, "minmax"))
        elif kind == "distinct_count":
            deferred.append((i, spec, "distinct"))
        else:
            raise ValueError(f"unknown agg kind {kind!r}")

    L = jnp.stack(int_rows)                      # (R, bucket) int8
    S = _int8_dot(L, oh8)                        # (R, space) int32
    counts = S[0].astype(int_acc_dtype())
    out["group_count"] = counts

    if float_slots:
        F = _group_float_sums(list(float_slots), mask, keys_s, space, cols,
                              params)

    meta_iter = iter(int_row_meta)
    for i, spec, how in deferred:
        name = _agg_name(i, spec)
        if how == "int_sum":
            start, signs, b = next(meta_iter)
            total = jnp.zeros((space,), dtype=jnp.int64)
            nl = signs.count(1)  # limbs per sign group (positive run first)
            for j, sign in enumerate(signs):
                w = jnp.int64(1) << jnp.int64(b * (j % nl))
                total = total + jnp.int64(sign) * w * \
                    S[start + j].astype(jnp.int64)
            if spec.kind == "avg":
                out[name + "_sum"] = total
                out[name + "_cnt"] = counts
            else:
                out[name] = total
        elif how == "float_sum":
            row = F[float_slots[spec.value]]
            if spec.kind == "avg":
                out[name + "_sum"] = row
                out[name + "_cnt"] = counts
            else:
                out[name] = row
        elif how == "hll":
            _group_hll(name, spec, mask, keys_s, space, cols, params, out)
        elif how == "minmax":
            _group_minmax(i, spec, mask, keys_s, space, cols, params, out)
        elif how == "distinct":
            ids = _eval_value(spec.value, cols, params)
            ids_s = jnp.where(mask, ids, spec.card)
            oh_ids = jax.nn.one_hot(ids_s, spec.card, dtype=jnp.int8)
            pair_counts = jax.lax.dot_general(
                jnp.swapaxes(oh8, 0, 1), oh_ids, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)  # (space, card)
            out[name + "_present"] = pair_counts > 0


def _has_case(ve: ValueExpr) -> bool:
    if isinstance(ve, Case):
        return True
    if isinstance(ve, Bin):
        return _has_case(ve.lhs) or _has_case(ve.rhs)
    if isinstance(ve, Func):
        return any(_has_case(a) for a in ve.args)
    return False


@jax.named_scope(ph.SCOPE_FLOAT_ACC)
def _float_sums(values: List[ValueExpr], cols, params, keys: jax.Array,
                space: int) -> jax.Array:
    """(len(values), space) sums of float value expressions by ``keys``
    (ids in [0, space); any other id is in no sum) at float_acc_dtype().
    The scalar scan is the case of one group.

    One blocked masked sum a group and expression, as _group_minmax
    unrolls — never a float32 matmul: the MXU multiplies float32 in
    bfloat16 passes and accumulates in float32. Blocked: the rows are
    laid out as (rows / FLOAT_SUM_BLOCK, FLOAT_SUM_BLOCK), a block's
    addends are summed, then the block sums, so no accumulator takes more
    addends than a block holds whatever order the backend's reduce walks.

    The COLUMNS are laid out in blocks, before the expressions are
    evaluated, not the addends after: XLA:TPU lowers a float64 sum to a
    two-plane reduce and fuses the masked expression into it only when no
    reshape stands between them. With one, every masked addend vector
    goes through HBM, and TPC-H Q1's thirty (6 groups x 5 expressions)
    over 8 x 2^23 rows are 15 GB: the chip's compiler refused that
    program (PERF.md section 6, PR 35). An expression with a CASE is
    evaluated flat (its predicates are written for (rows,) columns)."""
    acc_f = float_acc_dtype()
    zero = jnp.zeros((), acc_f)
    n = keys.shape[0]
    blocked = n > FLOAT_SUM_BLOCK and n % FLOAT_SUM_BLOCK == 0

    def blocks(a):
        return a.reshape((n // FLOAT_SUM_BLOCK, FLOAT_SUM_BLOCK)
                         + a.shape[1:]) if blocked else a

    keys_b, cols_b = blocks(keys), tuple(blocks(c) for c in cols)
    out = []
    for ve in values:
        if _has_case(ve):
            v = blocks(jnp.broadcast_to(_eval_value(ve, cols, params),
                                        (n,)))
        else:
            v = _eval_value(ve, cols_b, params)
        v = jnp.broadcast_to(v.astype(acc_f), keys_b.shape)
        sums = [jnp.where(keys_b == g, v, zero).sum(axis=-1)
                for g in range(space)]
        out.append(jnp.stack([s.sum() for s in sums]))
    return jnp.stack(out)


def _group_float_sums(values: List[ValueExpr], mask, keys_s, space: int,
                      cols, params) -> jax.Array:
    """(len(values), space) dense group sums of float expressions. Up to
    FLOAT_UNROLL_GROUPS groups: _float_sums. Larger spaces: the one-hot
    dot_general at the accumulator dtype, which XLA:TPU lowers through
    its float64 pairs; it holds the dtype and not the blocking, so such
    a plan's float aggregates count as narrow on backends that emulate
    float64 (float_acc_forms)."""
    if space <= FLOAT_UNROLL_GROUPS:
        return _float_sums(values, cols, params, keys_s, space)
    acc_f = float_acc_dtype()
    with jax.named_scope(ph.SCOPE_FLOAT_ACC):
        rows = jnp.stack([
            jnp.where(mask, _eval_value(ve, cols, params), 0).astype(acc_f)
            for ve in values])
        ohf = jax.nn.one_hot(keys_s, space, dtype=acc_f)
        return jax.lax.dot_general(rows, ohf, (((1,), (0,)), ((), ())),
                                   preferred_element_type=acc_f)


def _group_minmax(i: int, spec: AggSpec, mask, keys, space: int, cols,
                  params, out: Dict[str, jax.Array]) -> None:
    """No matmul form exists for min/max. space <= MINMAX_UNROLL_GROUPS:
    unrolled masked reduces (still one fused pass per group on the VPU);
    larger spaces use segment ops (fast on CPU; the planner hosts them on
    backends with slow scatter)."""
    name = _agg_name(i, spec)
    vals = _eval_value(spec.value, cols, params, promote=spec.integral)
    acc = _acc_dtype(spec)
    sign = +1 if spec.kind == "min" else -1
    sentinel = _extreme(acc, sign)
    red = jnp.min if spec.kind == "min" else jnp.max
    if space <= MINMAX_UNROLL_GROUPS:
        outs = [red(jnp.where(mask & (keys == g), vals.astype(acc), sentinel))
                for g in range(space)]
        out[name] = jnp.stack(outs)
    else:
        seg = (jax.ops.segment_min if spec.kind == "min"
               else jax.ops.segment_max)
        out[name] = seg(jnp.where(mask, vals.astype(acc), sentinel),
                        keys, num_segments=space)


# ---------------------------------------------------------------------------
# compacted group-by (Pallas compaction -> aggregate matched rows only)
# ---------------------------------------------------------------------------

# factorized one-hot matmul above this space would still be cheap, but the
# (M, space/128) int8 operand materialization starts to dominate; the sort
# path takes over (cap: searchsorted probes scale with space)
FACTORIZED_GROUP_LIMIT = 1 << 14
# sort path ceiling: cost is one sort of the *matched* rows + (space+1)
# searchsorted probes + dense (space,) outputs — 2^22 keeps outputs and
# probes cheap while clearing MAX_DENSE_GROUPS (so spaces in (2^21, 2^22]
# that used to fall to host numpy now stay on device; SSB Q4.3's
# 7 x 250 x 1000 = 1.75M space lands here)
COMPACT_GROUP_LIMIT = 1 << 22


def _value_col_indices(ve) -> set:
    """EVERY stored-column index a value expression references —
    including through Func args and Case branches (whose WHEN
    predicates can reference columns too). Completeness matters: the
    segmented kernel picks its synthetic segment-index column past the
    max referenced index, and the ragged batcher's cube eligibility
    turns every predicate column into a cube dimension — a missed
    column would silently corrupt either."""
    if isinstance(ve, (Col, MvReduce)):
        return {ve.col}
    if isinstance(ve, Bin):
        return _value_col_indices(ve.lhs) | _value_col_indices(ve.rhs)
    if isinstance(ve, Func):
        return set().union(set(), *[_value_col_indices(a)
                                    for a in ve.args])
    if isinstance(ve, Case):
        out = _value_col_indices(ve.else_)
        for pred, val in ve.whens:
            out |= _pred_col_indices(pred) | _value_col_indices(val)
        return out
    return set()


def chunked_cumsum(x: jax.Array, chunk: int = 1 << 13) -> jax.Array:
    """Two-level cumsum: XLA's monolithic reduce-window lowering blows
    scoped VMEM beyond ~16M elements on TPU; chunking keeps windows small
    and is faster besides."""
    n = x.shape[0]
    if n <= chunk or n % chunk != 0:
        return jnp.cumsum(x)
    m = n // chunk
    x2 = x.reshape(m, chunk)
    within = jnp.cumsum(x2, axis=1)
    carry = jnp.concatenate(
        [jnp.zeros(1, x.dtype), jnp.cumsum(within[:, -1])[:-1]])
    return (within + carry[:, None]).reshape(n)


_IMIN64 = -(1 << 63)
_IMIN32 = -(1 << 31)


def _to_orderable64(v: jax.Array, integral: bool, platform: str = None):
    """Order-preserving map to int64. Integers pass through (exact); floats
    map via the classic sign-flip bijection on their bit patterns:
    non-negatives keep their bits, negatives reverse order and land below
    (imin + ~bits). f64 bit views only exist on backends whose x64 rewriter
    can lower them (CPU — compact.f64_bitcast_ok); everywhere else floats
    take the 32-bit bijection widened to int64, so no f64 op is ever
    emitted (TPU crashes on f64 bitcast-convert at compile time).
    Returns (orderable, mode) with mode consumed by _from_orderable64."""
    from .compact import f64_bitcast_ok

    if integral:
        return v.astype(jnp.int64), "int"
    if v.dtype == jnp.float64 and f64_bitcast_ok(platform):
        bits = jax.lax.bitcast_convert_type(v, jnp.int64)
        o = jnp.where(bits >= 0, bits,
                      jnp.int64(_IMIN64) + jnp.bitwise_not(bits))
        return o, "f64"
    bits = jax.lax.bitcast_convert_type(v.astype(jnp.float32), jnp.int32)
    o32 = jnp.where(bits >= 0, bits,
                    jnp.int32(_IMIN32) + jnp.bitwise_not(bits))
    return o32.astype(jnp.int64), "f32"


def _from_orderable64(o: jax.Array, mode: str, acc_f) -> jax.Array:
    if mode == "int":
        return o
    if mode == "f64":
        neg_bits = jnp.bitwise_not(o - jnp.int64(_IMIN64))
        bits = jnp.where(o >= 0, o, neg_bits)
        return jax.lax.bitcast_convert_type(bits, jnp.float64).astype(acc_f)
    o32 = o.astype(jnp.int32)
    neg_bits = jnp.bitwise_not(o32 - jnp.int32(_IMIN32))
    bits = jnp.where(o32 >= 0, o32, neg_bits)
    return jax.lax.bitcast_convert_type(bits, jnp.float32).astype(acc_f)


def _to_orderable(v: jax.Array, integral: bool, platform: str = None):
    """_to_orderable64 at the narrowest exact carrier width: 32-bit-or-
    smaller integers and f32-bijection orderables stay int32 so the
    compaction kernel moves half the bytes and the sort compares narrower
    keys. _from_orderable64 accepts either width per mode."""
    if integral and jnp.issubdtype(v.dtype, jnp.integer) \
            and v.dtype.itemsize <= 4:
        return v.astype(jnp.int32), "int"
    o, mode = _to_orderable64(v, integral, platform)
    if mode == "f32":
        return o.astype(jnp.int32), mode
    return o, mode


# post-aggregation size ladder: below this static capacity (elements) the
# sort/matmul cost is trivial and the extra lax.switch branches only cost
# compile time (the CPU test suite lives here). Env override for tests.
def _ladder_min_elems() -> int:
    # host env read resolved at jit-cache-key time, never under trace
    return int(os.environ.get("PINOT_COMPACT_LADDER_MIN",  # jaxlint: ok host-sync
                              1 << 22))


def _two_pass_mode() -> str:
    """'auto' (second compaction pass only after the loose Pallas pass),
    '1' force (tests exercise the wiring on the XLA fallback), '0' off."""
    return os.environ.get("PINOT_COMPACT_TWO_PASS", "auto")


def _post_sizes(cap_rows: int, step: int = 8,
                min_rows: int = 512) -> List[int]:
    """Geometric /step ladder of slot-row sizes up to the full capacity.
    The MXU post keeps the coarse /8 ladder (each branch traces a full
    sort/matmul program); the scatter post uses /4 down to 8 slot rows —
    its segment-op branches are cheap to trace and the finer ladder keeps
    the scatter's input within ~4x of the matched rows."""
    sizes = [cap_rows]
    while sizes[-1] // step >= min_rows:
        sizes.append(sizes[-1] // step)
    return sorted(set(sizes))


def _ladder_index(sizes: List[int], n_valid, xp=jnp):
    """Index of the smallest ladder size (slot rows of LANES elements)
    whose element capacity covers n_valid; the largest where none does.
    ``xp`` is jnp under trace and numpy where the host applies the same
    rule to a count it holds (sparse_post_probes)."""
    from .compact import LANES

    if not sizes[:-1]:
        return xp.int32(0)
    thresholds = xp.asarray([s * LANES for s in sizes[:-1]],
                            dtype=xp.int32)
    return xp.sum((thresholds < n_valid).astype(xp.int32))


def _ladder_switch(sizes: List[int], n_valid, make_branch,
                   extra_branch=None, extra_when=None):
    """Dispatch the post-aggregation at the smallest ladder size whose
    element capacity covers n_valid. extra_branch (with its extra_when
    device predicate) appends an override branch — the two-pass path's
    pass-1 fallback on pass-2 overflow."""
    idx = _ladder_index(sizes, n_valid)
    branches = [make_branch(s) for s in sizes]
    if extra_branch is not None:
        idx = jnp.where(extra_when, jnp.int32(len(sizes)), idx)
        branches.append(extra_branch)
    if len(branches) == 1:
        return branches[0]()
    return jax.lax.switch(idx, branches)


@jax.named_scope(ph.SCOPE_PAYLOAD)
def _payload_columns(plan: KernelPlan, mask, cols, params,
                     platform: str = None):
    """Fused aggregation-input materialization (round-6 tentpole).

    Every aggregation input is evaluated ONCE over the full segment,
    masked, and narrowed to its smallest exact carrier dtype BEFORE
    compaction, so the compaction kernel moves [key] + payloads instead
    of gathering every referenced source column, and the post-aggregation
    never re-evaluates value expressions over capacity-sized arrays.
    A 2-key GROUP BY with SUM(a - b) compacts 2 columns (key + int32
    payload) where the round-5 path compacted 4 and re-ran the key
    arithmetic and subtraction over the full static capacity.

    Returns (arrays, sum_jobs, mm_jobs, ord_modes):
      arrays    tuple of (bucket,) payload columns;
      sum_jobs  [(agg_idx, spec, slot)] for sum/avg — slots deduped by
                (value expression, integral), so SUM(x) + AVG(x) share
                one compacted column;
      mm_jobs   [(agg_idx, spec, slot)] for min/max (orderable slots
                deduped by value expression);
      ord_modes {slot: mode} consumed by _from_orderable64.
    """
    acc_f = float_acc_dtype()
    arrays: List[jax.Array] = []
    sum_slots: Dict[Tuple, int] = {}
    ord_slots: Dict[object, int] = {}
    ord_modes: Dict[int, str] = {}
    sum_jobs: List[Tuple[int, AggSpec, int]] = []
    mm_jobs: List[Tuple[int, AggSpec, int]] = []
    for i, spec in enumerate(plan.aggs):
        if spec.kind == "count":
            continue
        if spec.kind in ("sum", "avg"):
            key = (spec.value, spec.integral)
            slot = sum_slots.get(key)
            if slot is None:
                if spec.integral:
                    v = _eval_value(spec.value, cols, params, promote=True)
                    # the planner's interval arithmetic bounds |v| by
                    # spec.bits: values under 2^31 ride int32 through the
                    # compaction (half the bytes, no 64-bit split)
                    dt = sum_carrier_dtype(spec.bits)
                    if dt is None:
                        # pre-fix this truncated silently through
                        # int_acc_dtype(); exactness is unprovable here
                        raise ValueError(
                            f"no exact {spec.bits}-bit sum carrier with "
                            "jax_enable_x64 off; plan the host path or "
                            "demote the aggregation to float")
                    v = jnp.where(mask, v, 0).astype(dt)
                else:
                    v = _eval_value(spec.value, cols, params).astype(acc_f)
                    v = jnp.where(mask, v, jnp.zeros((), acc_f))
                slot = len(arrays)
                sum_slots[key] = slot
                arrays.append(v)
            sum_jobs.append((i, spec, slot))
        elif spec.kind in ("min", "max"):
            slot = ord_slots.get(spec.value)
            if slot is None:
                v = _eval_value(spec.value, cols, params)
                integral = spec.integral and \
                    jnp.issubdtype(v.dtype, jnp.integer)
                o, mode = _to_orderable(v, integral, platform)
                slot = len(arrays)
                ord_slots[spec.value] = slot
                ord_modes[slot] = mode
                arrays.append(o)
            mm_jobs.append((i, spec, slot))
        else:
            raise ValueError(
                f"compact group-by cannot lower {spec.kind!r}")
    return tuple(arrays), sum_jobs, mm_jobs, ord_modes


def _compact_group_aggs(plan: KernelPlan, mask, cols, params, bucket: int,
                        slots_cap: int, out: Dict[str, jax.Array],
                        platform: str = None,
                        scatter: bool = False,
                        two_pass_mode: Optional[str] = None,
                        ladder_min: Optional[int] = None,
                        xfer_sparse: bool = False) -> None:
    """Group aggregation over compacted matched rows — the fused
    compaction -> sort -> segment-sum ladder (round-6 tentpole rewrite).

    Reference parity: DocIdSetOperator (docId materialization) +
    DefaultGroupByExecutor, reshaped for the TPU. One fused prefix
    evaluates the predicate mask, the cartesian dict-id group key, and
    every aggregation payload (_payload_columns) in a single pass over
    the segment; ONE compaction call (ops/compact.py) then concentrates
    [key] + payloads. The post-aggregation core is picked per plan:

    - scatter (CPU execution, cpu_scatter_default): jax.ops.segment_*
      over the compacted prefix — the exact XLA compaction plus the
      cost-model-tightened capacity mean the scatter touches ~matched
      rows, not the static capacity;
    - sorted (_needs_sort: min/max present or space > the factorized
      limit): ONE lexicographic key sort carries every sum payload and
      the first min/max orderable; all aggregations read one
      searchsorted edges array (sort once, aggregate many);
    - factorized (small spaces, sums only): two-sided one-hot matmul on
      the MXU, fed by the precomputed payload limbs.

    Outputs are the same dense (space,) arrays as the dense strategy, so
    extraction and broker reduce are strategy-agnostic.

    Two refinements keep the post-aggregation cost proportional to the
    rows actually matched instead of the static capacity:

    - a SECOND compaction pass over the first pass's output (Pallas path
      only by default): lane-wise compaction is loose — every 32-row
      subtile with any match advances a full slot row, so a sparse mask
      inflates 10-45x; re-compacting the already-small output costs a
      fraction of pass 1. Pass-2 overflow falls back to the pass-1
      arrays in-kernel (a lax.switch branch), never to a host retry;
    - a lax.switch SIZE LADDER (now on every core, including scatter):
      the post-aggregation is traced at a few static sizes (slot rows,
      /8 apart) and the branch picked on device by the compacted row
      count, so the post sees ~the matched rows even on the
      full-capacity overflow retry.
    """
    from .compact import LANES, _use_pallas, compact

    space = plan.group_space
    needs_sort = _needs_sort(plan)
    mask, keys_s = _group_keys_sentinel(plan, mask, cols, params)
    payloads, sum_jobs, mm_jobs, ord_modes = _payload_columns(
        plan, mask, cols, params, platform)
    valid, comp, n_valid, matched, overflow, steps = compact(
        mask, (keys_s,) + payloads, slots_cap, platform)
    out["overflow"] = overflow
    out["matched"] = matched.astype(int_acc_dtype())
    out["compact_steps_narrow"], out["compact_steps_wide"] = steps

    @jax.named_scope(ph.SCOPE_AGGREGATE)
    def post(valid_a, comp_t, rows: int) -> Dict[str, jax.Array]:
        v = valid_a[:rows]
        # compacted garbage slots were zeroed; re-sentinel their keys so
        # they can never pollute group 0 (payloads are already 0 there)
        k = jnp.where(v, comp_t[0][:rows], jnp.int32(space))
        pls = tuple(c[:rows] for c in comp_t[1:])
        o: Dict[str, jax.Array] = {}
        if scatter:
            _scatter_post(sum_jobs, mm_jobs, ord_modes, k, v, pls,
                          space, o)
        elif needs_sort and xfer_sparse:
            # q4.3 sparse-output contract: (group_idx, value) pairs
            # straight from the one sorted pass — no dense (space,)
            # arrays are ever materialized for big spaces
            _sorted_post_sparse(sum_jobs, mm_jobs, ord_modes, k, v, pls,
                                space, GROUP_XFER_CAP, o)
        elif needs_sort:
            _sorted_post(sum_jobs, mm_jobs, ord_modes, k, v, pls,
                         space, o)
        else:
            _factorized_post(sum_jobs, k, v, pls, space, rows, o)
        return o

    cap_rows = valid.shape[0]          # slots_cap * LANES elements
    mode = two_pass_mode if two_pass_mode is not None else _two_pass_mode()
    min_elems = ladder_min if ladder_min is not None else _ladder_min_elems()
    two_pass = (not scatter) and (
        mode == "1"
        or (mode == "auto" and _use_pallas(bucket, platform)
            and cap_rows >= min_elems))
    if two_pass:
        cap2 = max(slots_cap // 4, 512)
        valid2, comp2, n_valid2, _m2, of2, steps2 = compact(
            valid, comp, cap2, platform)
        for name, n in zip(COMPACT_STEP_OUTPUTS, steps2):
            out[name] = out[name] + n
        out.update(_ladder_switch(
            _post_sizes(valid2.shape[0] // LANES), n_valid2,
            lambda s: functools.partial(post, valid2, comp2, s * LANES),
            # pass-2 overflow: aggregate the (complete) pass-1 arrays
            extra_branch=functools.partial(post, valid, comp, cap_rows),
            extra_when=of2 > 0))
        return

    if scatter:
        # the scatter ladder is always on: its branches trace in
        # milliseconds and the full-capacity overflow retry depends on it
        # to keep the segment ops near the matched count
        sizes = _post_sizes(cap_rows // LANES, step=4, min_rows=8)
    else:
        sizes = (_post_sizes(cap_rows // LANES) if cap_rows >= min_elems
                 else [cap_rows // LANES])
    out.update(_ladder_switch(
        sizes, n_valid,
        lambda s: functools.partial(post, valid, comp, s * LANES)))


def _scatter_post(sum_jobs, mm_jobs, ord_modes, keys, valid, payloads,
                  space: int, out: Dict[str, jax.Array]) -> None:
    """CPU scatter core over the compacted prefix: one jax.ops.segment_sum
    per unique payload slot (counts ride the valid column), segment
    min/max on the orderables. Garbage slots carry the sentinel key ==
    space; the sentinel segment is sliced off."""
    nseg = space + 1
    cnt_dtype = int_acc_dtype()
    acc_f = float_acc_dtype()
    counts = jax.ops.segment_sum(valid.astype(cnt_dtype), keys,
                                 num_segments=nseg)[:space]
    out["group_count"] = counts
    done: Dict[int, jax.Array] = {}
    for i, spec, slot in sum_jobs:
        name = _agg_name(i, spec)
        s = done.get(slot)
        if s is None:
            acc = int_acc_dtype() if spec.integral else acc_f
            s = jax.ops.segment_sum(payloads[slot].astype(acc), keys,
                                    num_segments=nseg)[:space]
            done[slot] = s
        if spec.kind == "avg":
            out[name + "_sum"] = s
            out[name + "_cnt"] = counts
        else:
            out[name] = s
    for i, spec, slot in mm_jobs:
        name = _agg_name(i, spec)
        o = payloads[slot]
        sign = +1 if spec.kind == "min" else -1
        filled = jnp.where(valid, o, _extreme(o.dtype, sign))
        segf = (jax.ops.segment_min if spec.kind == "min"
                else jax.ops.segment_max)
        picked = segf(filled, keys, num_segments=nseg)[:space]
        acc = _acc_dtype(spec)
        vals = _from_orderable64(picked, ord_modes[slot], acc_f)
        out[name] = jnp.where(counts > 0, vals.astype(acc),
                              _extreme(acc, sign))


def _factorized_post(sum_jobs, keys, valid, payloads, space, m, out):
    """sums[hi, lo] = (oh_hi . limb)^T @ oh_lo — two fused one-hot operands
    keep the contraction on the MXU without materializing (M, space).
    Inputs are the precompacted payload columns (_payload_columns), so no
    value expression is ever re-evaluated here.

    The contraction runs as a lax.scan over fixed-size row blocks: the
    (block, n_hi) x (block, 128) one-hot operands are rebuilt per block and
    accumulated into the (rows, n_hi, 128) result, so peak memory is
    independent of M. (Unblocked, XLA materialized the (rows, M, n_hi)
    stacked operand — 34 GB at full_slots_cap on a 134M-row segment.)"""
    g_pad = -(-(space + 1) // 128) * 128
    n_hi = g_pad // 128
    hi = keys >> jnp.int32(7)
    lo = keys & jnp.int32(127)

    cnt_dtype = int_acc_dtype()
    int_rows: List[jax.Array] = [valid.astype(jnp.int8)]
    int_slot_meta: Dict[int, Tuple[int, List[int], int]] = {}
    float_slot_idx: Dict[int, int] = {}
    frows: List[jax.Array] = []
    deferred: List[Tuple[int, AggSpec, str, int]] = []

    for i, spec, slot in sum_jobs:
        if spec.integral:
            if slot not in int_slot_meta:
                rows, signs, b = _limb_rows(payloads[slot], valid,
                                            spec.bits, spec.signed, m)
                int_slot_meta[slot] = (len(int_rows), signs, b)
                int_rows.extend(rows)
            deferred.append((i, spec, "int_sum", slot))
        else:
            if slot not in float_slot_idx:
                float_slot_idx[slot] = len(frows)
                frows.append(payloads[slot])   # already masked acc_f
            deferred.append((i, spec, "float_sum", slot))

    acc_f = float_acc_dtype()

    # block size: keep the per-block (R, MB, n_hi) int8 operand ~<=128MB
    n_int = len(int_rows)
    budget = max((128 << 20) // max(n_int * n_hi, 1), 1 << 15)
    mb = max(1 << 15, min(1 << 21, 1 << (budget.bit_length() - 1)))
    n_b = -(-m // mb)
    pad = n_b * mb - m

    def blocked(x, fill):
        if pad:
            x = jnp.concatenate(
                [x, jnp.full((pad,), fill, dtype=x.dtype)])
        return x.reshape(n_b, mb)

    hi_b = blocked(hi, space >> 7)     # sentinel key -> trimmed pad region
    lo_b = blocked(lo, space & 127)
    ir_b = jnp.stack([blocked(r, 0) for r in int_rows], axis=1)
    xs = (hi_b, lo_b, ir_b)
    fr_b = None
    if frows:
        fr_b = jnp.stack([blocked(r, 0) for r in frows], axis=1)
        xs = xs + (fr_b,)

    S0 = jnp.zeros((n_int, n_hi, 128), jnp.int32)
    # no float rows: the empty carry keeps the dtype integer plans have
    # always compiled with, so their programs are the same programs
    F0 = jnp.zeros((len(frows), n_hi, 128),
                   acc_f if frows else jnp.float32)

    def body(carry, xb):
        S, F = carry
        hb, lb, irb = xb[:3]
        oh_hi = jax.nn.one_hot(hb, n_hi, dtype=jnp.int8)   # (MB, n_hi)
        oh_lo = jax.nn.one_hot(lb, 128, dtype=jnp.int8)    # (MB, 128)
        lhs = oh_hi[None, :, :] * irb[:, :, None]          # (R, MB, n_hi)
        S = S + jax.lax.dot_general(
            lhs, oh_lo, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        if frows:
            flhs = oh_hi.astype(acc_f)[None, :, :] * xb[3][:, :, None]
            F = F + jax.lax.dot_general(
                flhs, oh_lo.astype(acc_f), (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=acc_f)
        return (S, F), None

    if n_b == 1:  # small capacity: no scan, cheaper to compile (tests/CPU)
        (S, F), _ = body((S0, F0), tuple(x[0] for x in xs))
    else:
        (S, F), _ = jax.lax.scan(body, (S0, F0), xs)
    flat = S.reshape(n_int, g_pad)[:, :space]
    counts = flat[0].astype(cnt_dtype)
    out["group_count"] = counts
    if frows:
        Fflat = F.reshape(len(frows), g_pad)[:, :space]

    int_totals: Dict[int, jax.Array] = {}
    for i, spec, how, slot in deferred:
        name = _agg_name(i, spec)
        if how == "int_sum":
            total = int_totals.get(slot)
            if total is None:
                start, signs, b = int_slot_meta[slot]
                total = jnp.zeros((space,), dtype=jnp.int64)
                nl = signs.count(1)
                for j, sign in enumerate(signs):
                    w = jnp.int64(1) << jnp.int64(b * (j % nl))
                    total = total + jnp.int64(sign) * w * \
                        flat[start + j].astype(jnp.int64)
                int_totals[slot] = total
            if spec.kind == "avg":
                out[name + "_sum"] = total
                out[name + "_cnt"] = counts
            else:
                out[name] = total
        else:
            row = Fflat[float_slot_idx[slot]]
            if spec.kind == "avg":
                out[name + "_sum"] = row
                out[name + "_cnt"] = counts
            else:
                out[name] = row


def _needs_sort(plan: KernelPlan, n_segments: int = 1) -> bool:
    """Whether the compact strategy takes the sort path (vs factorized
    one-hot matmuls). Shared by _compact_group_aggs (path selection),
    build_kernel (capacity selection) and segmented_compact_fits (batch
    routing, where the segment index multiplies the space) so they can
    never disagree."""
    return (n_segments * plan.group_space > FACTORIZED_GROUP_LIMIT
            or any(s.kind in ("min", "max") for s in plan.aggs))


def _sorted_post_common(sum_jobs, mm_jobs, keys, payloads, extra=()):
    """The slot dedup + ONE lexicographic sort both sorted posts share
    (dense and sparse must never diverge here — digest parity between
    them is pinned by test). Returns (sorted_ops, sum_slots, mm_slots,
    base): sum payload slots deduped in operand order, min/max
    orderable slots likewise (the first rides the sort as the
    secondary key), and ``base`` indexing the first ``extra`` operand
    (or the first sum payload when no extras ride along)."""
    sum_slots: List[int] = []        # unique payload slots, operand order
    for _i, _s, slot in sum_jobs:
        if slot not in sum_slots:
            sum_slots.append(slot)
    mm_slots: List[int] = []
    for _i, _s, slot in mm_jobs:
        if slot not in mm_slots:
            mm_slots.append(slot)
    first_o = [payloads[mm_slots[0]]] if mm_slots else []
    operands = [keys] + first_o + list(extra) \
        + [payloads[s] for s in sum_slots]
    sorted_ops = jax.lax.sort(operands, num_keys=1 + len(first_o))
    return sorted_ops, sum_slots, mm_slots, 1 + len(first_o)


def _sorted_orderables(keys, payloads, mm_slots, sorted_ops
                       ) -> Dict[int, jax.Array]:
    """Per-slot key-sorted orderables: the first slot already rode the
    main sort as the secondary key; each additional distinct min/max
    expression needs one more (key, orderable) sort of the prefix."""
    out: Dict[int, jax.Array] = {}
    for j, slot in enumerate(mm_slots):
        out[slot] = sorted_ops[1] if j == 0 else jax.lax.sort(
            [keys, payloads[slot]], num_keys=2)[1]
    return out


def _sorted_post(sum_jobs, mm_jobs, ord_modes, keys, valid, payloads,
                 space: int, out: Dict[str, jax.Array]) -> None:
    """Sort-once, aggregate-many: ONE lexicographic sort of the compacted
    prefix carries every sum payload AND the first min/max orderable as
    the secondary key (group min = first element of the run, max = last);
    every aggregation then reads the single searchsorted edges array.
    Additional *distinct* min/max value expressions each need one more
    (key, orderable) sort over the same prefix. Payloads arrive
    precomputed (_payload_columns) — no value expression evaluates here."""
    acc_f = float_acc_dtype()
    cnt_dtype = int_acc_dtype()

    sorted_ops, sum_slots, mm_slots, base = _sorted_post_common(
        sum_jobs, mm_jobs, keys, payloads,
        extra=(valid.astype(jnp.int32),))
    sk = sorted_ops[0]
    edges = jnp.searchsorted(sk, jnp.arange(space + 1, dtype=jnp.int32))

    def group_sums(sorted_vals, dtype):
        cs = chunked_cumsum(sorted_vals.astype(dtype))
        tot = jnp.concatenate([jnp.zeros(1, dtype), cs])
        return tot[edges[1:]] - tot[edges[:-1]]

    counts = group_sums(sorted_ops[base], cnt_dtype).astype(cnt_dtype)
    out["group_count"] = counts

    sums_done: Dict[Tuple[int, bool], jax.Array] = {}
    for i, spec, slot in sum_jobs:
        name = _agg_name(i, spec)
        s = sums_done.get((slot, spec.integral))
        if s is None:
            sv = sorted_ops[base + 1 + sum_slots.index(slot)]
            s = group_sums(sv, int_acc_dtype() if spec.integral else acc_f)
            sums_done[(slot, spec.integral)] = s
        if spec.kind == "avg":
            out[name + "_sum"] = s
            out[name + "_cnt"] = counts
        else:
            out[name] = s

    sorted_orderable = _sorted_orderables(keys, payloads, mm_slots,
                                          sorted_ops)
    n_rows = keys.shape[0]
    pos_min = jnp.minimum(edges[:-1], n_rows - 1)
    pos_max = jnp.clip(edges[1:] - 1, 0, n_rows - 1)
    for i, spec, slot in mm_jobs:
        name = _agg_name(i, spec)
        pos = pos_min if spec.kind == "min" else pos_max
        picked = sorted_orderable[slot].at[pos].get(mode="clip")
        acc = _acc_dtype(spec)
        vals = _from_orderable64(picked, ord_modes[slot], acc_f).astype(acc)
        # an empty group's edges collapse and pick a neighboring run's
        # row; neutralize to the extreme so cross-device pmin/pmax and
        # partial merges stay correct (dense _group_minmax convention)
        out[name] = jnp.where(
            counts > 0, vals,
            _extreme(acc, 1 if spec.kind == "min" else -1))


def _sparse_post_sizes(cap: int) -> List[int]:
    """Probe-count ladder of the sparse post's per-group tail, in slot
    rows of LANES probes: 512 / 4,096 / 32,768 probes at GROUP_XFER_CAP."""
    from .compact import LANES
    return _post_sizes(cap // LANES, min_rows=4)


def sparse_post_probes(n_live) -> int:
    """Probe count the sparse post's tail runs at for ``n_live`` live
    groups: the kernel's own rule (_ladder_index over _sparse_post_sizes)
    applied by the host to a count it holds, so the counters
    sparse_post_probes_<P> (engine/executor.run_kernel) cannot fork from
    the branch the device took."""
    from .compact import LANES
    sizes = _sparse_post_sizes(GROUP_XFER_CAP)
    # numpy over a host count — never a device value
    idx = int(_ladder_index(sizes, n_live, np))  # jaxlint: ok host-sync
    return sizes[idx] * LANES


def _sorted_post_sparse(sum_jobs, mm_jobs, ord_modes, keys, valid, payloads,
                        space: int, cap: int,
                        out: Dict[str, jax.Array]) -> None:
    """Sparse sorted post (q4.3 contract): emit (group_idx, value) pairs
    straight from the ONE lexicographic sort instead of densifying to
    (space,) arrays and compacting them afterwards.

    At SSB q4.3's 1.75M group space the dense outputs dominated the
    kernel (space-sized searchsorted probes + several (space,) arrays
    for ~13 live groups). Here run boundaries come from the sorted
    keys themselves: first-occurrence flags -> unique ranks -> one
    searchsorted of the rank vector. What the cost follows: the sort,
    the flags and the cumsums the compacted rows; the per-group tail
    (the boundary search and every gather behind it) the LIVE GROUPS.
    The tail runs at a probe count picked on the device from n_live
    (_sparse_post_sizes: a searchsorted is log2(rows) serial gathers of
    one element a probe, so cap probes for a few hundred live groups
    was most of the kernel) and pads its rows to (cap,).
    Output contract matches _compact_group_xfer exactly (group_idx
    holds dense space ids, sentinel rows carry count 0, group_overflow
    flags >cap live groups for the dense retry), so extraction and the
    batched dispatch are oblivious to which path produced it."""
    from .compact import LANES

    acc_f = float_acc_dtype()
    cnt_dtype = int_acc_dtype()

    sorted_ops, sum_slots, mm_slots, base = _sorted_post_common(
        sum_jobs, mm_jobs, keys, payloads)
    sk = sorted_ops[0]
    n_rows = sk.shape[0]

    # every live-key row is valid by construction (garbage slots were
    # re-sentineled to space before the sort), so run lengths ARE the
    # group counts and the valid column never needs to ride the sort
    live = sk < jnp.int32(space)
    uniq = live & jnp.concatenate(
        [jnp.ones(1, jnp.bool_), sk[1:] != sk[:-1]])
    ranks = chunked_cumsum(uniq.astype(jnp.int32)).astype(jnp.int32)
    n_live = ranks[-1]
    n_matched = jnp.searchsorted(sk, jnp.int32(space)).astype(jnp.int32)

    # everything whose cost goes by rows stays out here: a tail branch
    # holds one search and a handful of probe-sized gathers
    sums_cs: Dict[Tuple[int, bool], jax.Array] = {}
    for _i, spec, slot in sum_jobs:
        if (slot, spec.integral) not in sums_cs:
            dtype = int_acc_dtype() if spec.integral else acc_f
            sv = sorted_ops[base + sum_slots.index(slot)]
            sums_cs[(slot, spec.integral)] = jnp.concatenate(
                [jnp.zeros(1, dtype), chunked_cumsum(sv.astype(dtype))])
    sorted_orderable = _sorted_orderables(keys, payloads, mm_slots,
                                          sorted_ops)

    def tail(size: int):
        probes = size * LANES

        def pad(v, fill):
            if probes == cap:
                return v
            return jnp.concatenate(
                [v, jnp.full(cap - probes, fill, v.dtype)])

        @jax.named_scope(ph.SCOPE_GROUP_TAIL)
        def branch() -> Dict[str, jax.Array]:
            o: Dict[str, jax.Array] = {}
            rids = jnp.arange(1, probes + 1, dtype=jnp.int32)
            starts = jnp.searchsorted(ranks, rids,
                                      side="left").astype(jnp.int32)
            # ranks are integers, so the run of rank r ends where the run
            # of r + 1 starts: one search gives both edges, and only the
            # last probe's successor is looked up apart
            past = jnp.searchsorted(ranks, jnp.int32(probes + 1),
                                    side="left").astype(jnp.int32)
            ends = jnp.minimum(
                jnp.concatenate([starts[1:], past[None]]), n_matched)
            alive = rids <= n_live
            o["group_idx"] = pad(jnp.where(
                alive,
                sk.at[jnp.minimum(starts, n_rows - 1)].get(mode="clip"),
                jnp.int32(space)), space)
            counts = jnp.where(alive, (ends - starts).astype(cnt_dtype), 0)
            o["group_count"] = pad(counts, 0)

            sums_done: Dict[Tuple[int, bool], jax.Array] = {}
            for i, spec, slot in sum_jobs:
                name = _agg_name(i, spec)
                s = sums_done.get((slot, spec.integral))
                if s is None:
                    cs = sums_cs[(slot, spec.integral)]
                    s = pad(jnp.where(alive, cs[ends] - cs[starts], 0), 0)
                    sums_done[(slot, spec.integral)] = s
                if spec.kind == "avg":
                    o[name + "_sum"] = s
                    o[name + "_cnt"] = o["group_count"]
                else:
                    o[name] = s

            pos_min = jnp.minimum(starts, n_rows - 1)
            pos_max = jnp.clip(ends - 1, 0, n_rows - 1)
            for i, spec, slot in mm_jobs:
                name = _agg_name(i, spec)
                pos = pos_min if spec.kind == "min" else pos_max
                picked = sorted_orderable[slot].at[pos].get(mode="clip")
                acc = _acc_dtype(spec)
                vals = _from_orderable64(
                    picked, ord_modes[slot], acc_f).astype(acc)
                extreme = _extreme(acc, 1 if spec.kind == "min" else -1)
                o[name] = pad(jnp.where(counts > 0, vals, extreme),
                              extreme)
            return o

        return branch

    # n_live > cap takes the largest branch and flags the dense retry
    out.update(_ladder_switch(_sparse_post_sizes(cap), n_live, tail))
    out["group_overflow"] = (n_live > cap).astype(jnp.int32)


# ---------------------------------------------------------------------------
# full-scan group-by (strategy 'scan': every row, nothing compacted)
# ---------------------------------------------------------------------------

# a float SUM / AVG on the scan strategy: a value the planner bounded
# under 2^bits (AggSpec.bits, from the column's min/max) is summed as the
# integer round(x * 2^(SCAN_FIXED_BITS - bits)), |q| <= 2^SCAN_FIXED_BITS,
# cut into two halves of SCAN_HALF_BITS that ride the int8 limb matmul
# (or the sort's int64 cumsums) exactly. What is not exact is the
# rounding of each value to the fixed point, 2^-SCAN_FIXED_BITS of the
# bound a value (4e-19 of it), far under the 48 bits XLA:TPU holds of a
# float64 (PERF.md section 6)
SCAN_FIXED_BITS = 61
SCAN_HALF_BITS = 31


def scan_float_ok(spec: AggSpec) -> bool:
    """Whether the scan strategy can sum this float aggregate exactly:
    its magnitude is bounded (bits 63 is the planner's 'unprofiled')."""
    return spec.bits < 63


@jax.named_scope(ph.SCOPE_GROUP_SCAN)
def _scan_group_aggs(plan: KernelPlan, mask, cols, params, bucket: int,
                     out: Dict[str, jax.Array]) -> None:
    """Group aggregation over every row of the segment, for a group space
    over the dense one-hot budget (query/planner.py): the keys are
    computed in the kernel (dictionary ids, expression keys or both), so
    nothing is compacted and an expression key needs no key column. The
    rows go through the compact strategy's posts as they are: the
    factorized one-hot matmul, a lax.scan over blocks of rows (no (rows,
    space / 128) operand of the whole segment), up to
    FACTORIZED_GROUP_LIMIT groups, the sorted post above it.

    Integral sums ride the posts' exact int8 limbs (or int64 cumsums).
    A float sum rides them as a fixed-point integer in two halves
    (SCAN_FIXED_BITS) and comes back a float64: every SUM and AVG here is
    wide, on every backend (float_acc_forms)."""
    space = plan.group_space
    mask, keys_s = _group_keys_sentinel(plan, mask, cols, params)
    payloads: List[jax.Array] = []
    jobs: List[Tuple[int, AggSpec, int]] = []
    slots: Dict[ValueExpr, int] = {}
    fixed: Dict[ValueExpr, Tuple[int, int, int]] = {}
    floats: List[Tuple[int, AggSpec]] = []
    half = AggSpec("sum", None, True, bits=SCAN_HALF_BITS, signed=False)
    for i, spec in enumerate(plan.aggs):
        if spec.kind == "count":
            continue
        if spec.kind not in ("sum", "avg"):
            raise ValueError(f"scan group-by cannot lower {spec.kind!r}")
        if spec.integral:
            slot = slots.get(spec.value)
            if slot is None:
                v = _eval_value(spec.value, cols, params, promote=True)
                slot = slots[spec.value] = len(payloads)
                payloads.append(jnp.where(mask, v, 0))
            jobs.append((i, spec, slot))
            continue
        if spec.value not in fixed:
            shift = SCAN_FIXED_BITS - spec.bits
            with jax.named_scope(ph.SCOPE_FLOAT_ACC):
                x = _eval_value(spec.value, cols, params).astype(jnp.float64)
                q = jnp.round(x * jnp.float64(2.0 ** shift)).astype(jnp.int64)
                q = jnp.where(mask, q, jnp.int64(0))
            # the names of the two halves' sums: past every real aggregate
            j = len(plan.aggs) + 2 * len(fixed)
            fixed[spec.value] = (j, j + 1, shift)
            jobs.append((j, dc_replace(half, signed=spec.signed),
                         len(payloads)))
            jobs.append((j + 1, half, len(payloads) + 1))
            payloads += [q >> SCAN_HALF_BITS,
                         q & jnp.int64((1 << SCAN_HALF_BITS) - 1)]
        floats.append((i, spec))
    if space <= FACTORIZED_GROUP_LIMIT:
        _factorized_post(jobs, keys_s, mask, tuple(payloads), space, bucket,
                         out)
    else:
        _sorted_post(jobs, [], {}, keys_s, mask, tuple(payloads), space,
                     out)
    with jax.named_scope(ph.SCOPE_FLOAT_ACC):
        totals = {}
        for value, (j_hi, j_lo, shift) in fixed.items():
            hi = out.pop(_agg_name(j_hi, half)).astype(jnp.float64)
            lo = out.pop(_agg_name(j_lo, half)).astype(jnp.float64)
            totals[value] = ((hi * jnp.float64(2.0 ** SCAN_HALF_BITS) + lo)
                             * jnp.float64(2.0 ** -shift)).astype(
                                 float_acc_dtype())
        for i, spec in floats:
            name = _agg_name(i, spec)
            if spec.kind == "avg":
                out[name + "_sum"] = totals[spec.value]
                out[name + "_cnt"] = out["group_count"]
            else:
                out[name] = totals[spec.value]


# ---------------------------------------------------------------------------
# kernel assembly
# ---------------------------------------------------------------------------

def build_kernel(plan: KernelPlan, bucket: int,
                 slots_cap: Optional[int] = None,
                 platform: Optional[str] = None,
                 xfer_compact: bool = True,
                 local_segments: int = 1,
                 scatter: bool = False,
                 two_pass_mode: Optional[str] = None,
                 ladder_min: Optional[int] = None):
    """Return fn(cols, n_docs, params) -> dict of partial aggregation states.

    Shape contract: every cols[i] has the same (bucket,) length; n_docs is a
    traced scalar; outputs have static shapes derived only from the plan
    (scalars, or (group_space,) arrays) — never from the data. bucket is
    static (plans may bind zero columns, e.g. COUNT(*) with an IS NULL
    filter, so it can't be derived from cols).

    slots_cap sizes the compaction output for the 'compact' strategy
    (default: ops/compact.default_slots_cap(bucket)); the returned dict's
    "overflow" entry tells the executor to retry with full capacity.
    """

    total = bucket * local_segments

    def kernel(cols: Tuple[jax.Array, ...], n_docs: jax.Array,
               params: Tuple[jax.Array, ...]) -> Dict[str, jax.Array]:
        with jax.named_scope(ph.SCOPE_MASK):
            if local_segments == 1:
                valid = jnp.arange(total, dtype=jnp.int32) < n_docs
            else:
                # cols are local_segments same-bucket segments
                # concatenated along the row axis (the mesh path's
                # per-device shard); n_docs is (local_segments,)
                iota = jax.lax.broadcasted_iota(
                    jnp.int32, (local_segments, bucket), 1)
                valid = (iota < n_docs[:, None]).reshape(total)
            mask = valid & _eval_pred(plan.pred, cols, params, total)
        out: Dict[str, jax.Array] = {}
        if plan.is_group_by and plan.strategy == "compact":
            from .compact import default_slots_cap, sorted_default_slots_cap
            # scatter mode compacts exactly (XLA nonzero), so the tight
            # sorted-path cap applies: smaller gathers + scatter inputs,
            # and the overflow retry covers dense matches
            cap = slots_cap or (sorted_default_slots_cap(total)
                                if _needs_sort(plan) or scatter
                                else default_slots_cap(total))
            # sparse sorted post (q4.3): the sorted core emits
            # (group_idx, value) pairs directly at big spaces, so the
            # densify-then-compact _compact_group_xfer never runs there
            sparse = takes_sparse_post(plan, xfer_compact, scatter)
            _compact_group_aggs(plan, mask, cols, params, total, cap, out,
                                platform, scatter, two_pass_mode,
                                ladder_min, xfer_sparse=sparse)
            # scatter implies CPU execution, where the "transfer" the
            # device-side live-group compaction optimizes is free — the
            # nonzero over a big space only adds kernel time there
            if xfer_compact and not scatter and not sparse:
                _compact_group_xfer(plan, out)
            return out
        with jax.named_scope(ph.SCOPE_AGGREGATE):
            out["matched"] = jnp.sum(mask, dtype=int_acc_dtype())
        if plan.is_group_by and plan.strategy == "scan" and not scatter:
            _scan_group_aggs(plan, mask, cols, params, total, out)
            if xfer_compact:
                _compact_group_xfer(plan, out)
        elif plan.is_group_by:
            # a scatter backend's core reads every row already: the scan
            # strategy is the dense scatter core there
            _group_aggs(plan, mask, cols, params, total, out, scatter)
            if xfer_compact and not scatter:
                _compact_group_xfer(plan, out)
        else:
            for i, spec in enumerate(plan.aggs):
                _scalar_agg(i, spec, mask, cols, params, out)
        return out

    return kernel


def over_segments(plan: KernelPlan, fn, *stacked):
    """``fn`` over the leading (segment) axis of each of ``stacked``: one
    program for a stack of segments, on the one-chip batched launch
    (engine/batch.py) and on a mesh device's shard (parallel/
    distributed.py) alike. The scan strategy maps the segments in turn
    (lax.map): vmap batched its blocked one-hot contraction off the MXU,
    and for the taxi cell's zone tile XLA:TPU generated 765 MB of code in
    158 s and ran it in 160 ms a request (PERF.md section 6). Every other
    strategy vmaps."""
    if plan.strategy == "scan":
        return jax.lax.map(lambda a: fn(*a), stacked)
    return jax.vmap(fn)(*stacked)


# the compactor's grid steps by form (ops/compact.py: narrow or wide),
# both passes together; the host counts them where it reads "matched"
COMPACT_STEP_OUTPUTS = ("compact_steps_narrow", "compact_steps_wide")
# a kernel's outputs that count rows or steps and are no group arrays
COUNT_OUTPUTS = ("matched", "overflow") + COMPACT_STEP_OUTPUTS

# group outputs over this space reach the host as (group_idx, value) rows
# of the non-empty groups, GROUP_XFER_CAP of them: from the sorted core's
# sparse post, from the mesh's list of its devices' ids (parallel/
# distributed._gather_live_groups) or, dense outputs, _compact_group_xfer
GROUP_XFER_SPACE = 1 << 15
GROUP_XFER_CAP = 1 << 15


def takes_sparse_post(plan: KernelPlan, xfer_compact: bool = True,
                      scatter: bool = False) -> bool:
    """Whether a compact plan's group rows leave the kernel through the
    sparse sorted post (_sorted_post_sparse) and not through dense
    outputs and _compact_group_xfer: the builders' rule, and the host's
    where it counts what the post did (engine/executor.run_kernel)."""
    return (xfer_compact and not scatter and _needs_sort(plan)
            and plan.group_space >= GROUP_XFER_SPACE)


@jax.named_scope(ph.SCOPE_XFER_COMPACT)
def _compact_group_xfer(plan: KernelPlan, out: Dict[str, jax.Array]) -> None:
    """Replace dense (space,) group outputs with gathered non-empty rows:
    group_idx holds the dense space ids (sentinel=space past the count),
    group_overflow flags >GROUP_XFER_CAP live groups (executor retries with
    xfer_compact=False). All-or-nothing: any 2-D output (grouped
    DISTINCTCOUNT presence) disables compaction for the whole result, since
    extract_partial indexes every output with one positions array."""
    space = plan.group_space
    if space < GROUP_XFER_SPACE:
        return
    dense = {k: v for k, v in out.items() if k not in COUNT_OUTPUTS}
    if not all(v.ndim == 1 and v.shape[0] == space for v in dense.values()):
        return
    counts = out["group_count"]
    live = counts > 0
    idx, = jnp.nonzero(live, size=GROUP_XFER_CAP, fill_value=space)
    out["group_idx"] = idx.astype(jnp.int32)
    out["group_overflow"] = (
        jnp.sum(live, dtype=jnp.int32) > GROUP_XFER_CAP).astype(jnp.int32)
    for k, v in dense.items():
        out[k] = jnp.where(idx < space, v.at[idx].get(mode="clip"),
                           jnp.zeros((), dtype=v.dtype))


def _pred_col_indices(p) -> set:
    """Stored-column indices a predicate references."""
    if isinstance(p, (EqId, IdRange, InSet, InBitmap)):
        return {p.col}
    if isinstance(p, Cmp):
        return _value_col_indices(p.lhs)
    if isinstance(p, (And, Or)):
        return set().union(*[_pred_col_indices(c) for c in p.children])
    if isinstance(p, Not):
        return _pred_col_indices(p.child)
    return set()


def build_select_kernel(plan: SelectPlan, bucket: int):
    """fn(cols, n_docs, params) -> {"sel_<i>": (k,) stored values,
    "ord_<j>": (k,) order-key ids/values, "matched": scalar}.

    The composite order key packs the (col, desc, card) entries most-
    significant-first into one int64; lax.top_k picks the winners in one
    fused pass (LinearSelectionOrderByOperator's heap, TPU-shaped).
    Rows beyond min(matched, k) are garbage — extract slices by matched.
    """
    def kernel(cols: Tuple[jax.Array, ...], n_docs: jax.Array,
               params: Tuple[jax.Array, ...]) -> Dict[str, jax.Array]:
        with jax.named_scope(ph.SCOPE_MASK):
            mask = (jnp.arange(bucket, dtype=jnp.int32) < n_docs) \
                & _eval_pred(plan.pred, cols, params, bucket)
        with jax.named_scope(ph.SCOPE_TOPK):
            return _select_topk(plan, bucket, mask, cols)

    return kernel


def _select_topk(plan: SelectPlan, bucket: int, mask, cols
                 ) -> Dict[str, jax.Array]:
    """Composite order key, top_k and the winners' gathers."""
    if plan.order:
        key = jnp.zeros(bucket, dtype=jnp.int64)
        for col, desc, card in plan.order:
            v = cols[col].astype(jnp.int64)
            if card:  # dict ids: sorted dictionary => id order
                if desc:
                    v = jnp.int64(card - 1) - v
                key = key * jnp.int64(card) + v
            else:     # raw integral key — the planner only emits it
                # alone (card-free values can't pack into a radix)
                key = -v if desc else v
        # ascending composite wins smallest; top_k wants max -> negate
        sort_key = jnp.where(mask, -key, jnp.iinfo(jnp.int64).min)
    else:
        # doc order: earliest rows win
        iota = jnp.arange(bucket, dtype=jnp.int64)
        sort_key = jnp.where(mask, -iota, jnp.iinfo(jnp.int64).min)
    _, idx = jax.lax.top_k(sort_key, plan.k)
    out: Dict[str, jax.Array] = {
        "matched": jnp.sum(mask, dtype=int_acc_dtype()),
    }
    for i, col in enumerate(plan.select_cols):
        out[f"sel_{i}"] = jnp.take(cols[col], idx, axis=0)
    for j, (col, _d, _c) in enumerate(plan.order):
        out[f"ord_{j}"] = jnp.take(cols[col], idx)
    return out


@functools.lru_cache(maxsize=512)
def jitted_select_kernel(plan: SelectPlan, bucket: int):
    from ..utils.compileplane import kernel_jit, staged
    return staged(kernel_jit(build_select_kernel(plan, bucket),
                             ph.SELECT_TOPK),
                  "select_kernel", ("select", plan, bucket))


def _dict_value_cols(plan: KernelPlan) -> Dict[int, int]:
    """col index -> dict-values param index, for every Col(dict_param=..)
    referenced by an aggregation value expression."""
    found: Dict[int, int] = {}

    def walk(ve):
        if isinstance(ve, Col) and ve.dict_param is not None:
            found[ve.col] = ve.dict_param
        elif isinstance(ve, Bin):
            walk(ve.lhs)
            walk(ve.rhs)

    for spec in plan.aggs:
        if spec.value is not None:
            walk(spec.value)
    return found


@functools.lru_cache(maxsize=1024)
def _dict_value_params(plan: KernelPlan) -> Tuple[int, ...]:
    """params index of each dictionary-decoded value column of the plan
    (the plan walked once, not at every launch)."""
    return tuple(_dict_value_cols(plan).values())


def dict_decode_forms(plan: KernelPlan, params,
                      segmented: bool = False) -> Tuple[int, int]:
    """(select, gather): how many of the plan's dictionary-decoded value
    columns a launch with these params decodes in each form of
    _decode_dict. The form is the helper's own predicate on the length it
    sees: the last axis of the (stacked) dictionary, or all S*K entries
    where the segmented compact kernel flattens it."""
    n_select = 0
    pis = _dict_value_params(plan)
    for pi in pis:
        shape = params[pi].shape
        n_select += _decodes_by_select(
            shape[0] * shape[1] if segmented else shape[-1])
    return n_select, len(pis) - n_select


# aggregates whose state is a float accumulator when their value is not
# integral (the sketches' mergeable summaries are not sums of the rows)
_FLOAT_ACC_KINDS = frozenset({"sum", "avg", "min", "max"})


def float_acc_forms(plan: KernelPlan, platform: Optional[str] = None
                    ) -> Tuple[int, int]:
    """(wide, narrow): how many float aggregates (SUM, AVG, MIN or MAX of
    a value that is not integral) a launch of ``plan`` on ``platform``
    keeps at float64 from the column to the partial it hands the host,
    its sums blocked (_float_sums), and how many do not. Counted beside
    dict_decode_forms (launch_forms, utils/spans.count_dispatch):
    float_acc_wide and float_acc_narrow. Narrow is:

    - every one where float_acc_dtype() is float32 (x64 off);
    - on a backend that emulates float64 (every one but the CPU:
      compact.f64_bitcast_ok), a grouped plan of the compact strategy
      (ops/compact.compact carries a float64 payload as float32 there,
      having no bit view of the pair) and a dense group-by over more
      than FLOAT_UNROLL_GROUPS groups (_group_float_sums: an unblocked
      dot_general through the emulation, held to no bound).

    The scalar scan, the dense group-by of a few groups (TPC-H Q6 and
    Q1) and the scan strategy's fixed-point sums (_scan_group_aggs) are
    wide everywhere."""
    from .compact import f64_bitcast_ok
    n = sum(1 for s in plan.aggs if s.kind in _FLOAT_ACC_KINDS
            and s.value is not None and not s.integral)
    narrow = n and (float_acc_dtype() != jnp.float64 or (
        not f64_bitcast_ok(platform) and plan.is_group_by
        and (plan.strategy == "compact"
             or (plan.strategy == "dense"
                 and plan.group_space > FLOAT_UNROLL_GROUPS))))
    return (0, n) if narrow else (n, 0)


def launch_forms(plan: KernelPlan, params, segmented: bool = False,
                 platform: Optional[str] = None
                 ) -> Tuple[Tuple[int, int], Tuple[int, int]]:
    """(dict_decode_forms, float_acc_forms) of one launch: what every
    launch site hands utils/spans.count_dispatch after the family."""
    return (dict_decode_forms(plan, params, segmented),
            float_acc_forms(plan, platform))


def segmented_compact_ok(plan: KernelPlan) -> bool:
    """Whether a compact group-by plan can run the segmented batch kernel:
    no column may serve as both a group key and a dictionary-value source
    (the segment offsetting of dict ids would corrupt the group keys)."""
    if not (plan.is_group_by and plan.strategy == "compact"):
        return False
    key_cols = {ci for ci, _ in plan.group_keys}
    return not (key_cols & set(_dict_value_cols(plan)))


# Most rows one segmented program may hold when its combined group space
# (S x space) puts it on the SORT core. On a TPU v5e (jax 0.9.0 / libtpu
# 0.0.34, chip runs of PR 21) the sort core compiled and ran exact on one
# 2^24-row segment (bench.py, every sorted SSB query) and per segment at
# 2^23 rows in 18 s, but as ONE program over 8 x 2^24 rows XLA rejected
# it ("Ran out of memory in memory space vmem ... reduce-window ...
# 19.07M and limit 16.00M"). 2^24 is the largest size shown to compile;
# nothing between was tried.
SEGMENTED_SORT_ROW_LIMIT = 1 << 24


def sort_core_fits(plan: KernelPlan, rows: int, space_factor: int = 1,
                   row_limit: Optional[int] = None) -> bool:
    """The one rule for how many rows a compact program may hold: any
    number on the factorized core, at most SEGMENTED_SORT_ROW_LIMIT
    (``row_limit`` where a caller was constructed with its own) on the
    sort core. ``space_factor`` is what the program multiplies the
    plan's group space by: the segment count in the segmented batch
    kernel, 1 on a mesh shard (shared dictionaries, one space)."""
    if row_limit is None:
        row_limit = SEGMENTED_SORT_ROW_LIMIT
    return not _needs_sort(plan, space_factor) or rows <= row_limit


def segmented_compact_fits(plan: KernelPlan, bucket: int,
                           n_segments: int) -> bool:
    """Whether S same-plan compact segments fuse into ONE segmented
    program (engine/batch.py launches them per segment otherwise). The
    segment index multiplies the group space, so a plan that is
    factorized on one segment can land on the sort core as a batch —
    and the sort core does not compile at every batch size
    (sort_core_fits)."""
    if n_segments * plan.group_space > COMPACT_GROUP_LIMIT:
        return False
    return sort_core_fits(plan, n_segments * bucket, n_segments)


def build_segmented_compact_kernel(plan: KernelPlan, bucket: int,
                                   n_segments: int,
                                   slots_cap: Optional[int] = None,
                                   platform: Optional[str] = None,
                                   xfer_compact: bool = True,
                                   scatter: bool = False,
                                   two_pass_mode: Optional[str] = None,
                                   ladder_min: Optional[int] = None):
    """Multi-segment compact group-by as ONE device program.

    Reference parity: GroupByCombineOperator.java:125 runs the same
    group-by executor across segments on a thread pool; the TPU-native
    combine concatenates S same-bucket segments along the row axis and
    makes the segment index the leading group-key factor, so one Pallas
    compaction + one group pass serve the whole batch:

    - predicate masks evaluate vmapped (per-segment params: dict-id
      ranges differ across segment dictionaries);
    - per-segment dictionary-value params (S, card) flatten to (S*card,)
      and the referencing dict-id columns are offset by seg*card, so
      value decodes hit the right segment's dictionary after rows mix
      (_decode_dict sees all S*card entries and picks its form by that);
    - group space becomes S*space; the executor slices (S, space) rows
      apart host-side and decodes each against its own dictionaries.

    Inputs: cols tuple of (S, bucket); n_docs (S,); params tuple of
    (S, ...)-stacked arrays. Outputs: dense (S*space,) group arrays plus
    per-segment "matched" (S,).
    """
    from dataclasses import replace as dc_replace

    seg_col = 1 + max(
        [ci for ci, _ in plan.group_keys]
        + [c for s in plan.aggs if s.value is not None
           for c in _value_col_indices(s.value)]
        + list(_pred_col_indices(plan.pred)) + [-1])
    plan2 = dc_replace(plan, group_keys=((seg_col, n_segments),)
                       + plan.group_keys)
    dict_cols = _dict_value_cols(plan)
    total = n_segments * bucket

    def kernel(cols: Tuple[jax.Array, ...], n_docs: jax.Array,
               params: Tuple[jax.Array, ...]) -> Dict[str, jax.Array]:
        def pred_one(c, n, p):
            valid = jnp.arange(bucket, dtype=jnp.int32) < n
            return valid & _eval_pred(plan.pred, c, p, bucket)

        with jax.named_scope(ph.SCOPE_MASK):
            masks = jax.vmap(pred_one)(cols, n_docs, params)  # (S, bucket)
        seg2d = jax.lax.broadcasted_iota(jnp.int32, (n_segments, bucket), 0)

        flat_cols: List[jax.Array] = []
        with jax.named_scope(ph.SCOPE_DECODE_DICT):
            for ci, c in enumerate(cols):
                pi = dict_cols.get(ci)
                if pi is not None:  # offset ids into the flat dictionary
                    card = params[pi].shape[1]
                    c = c.astype(jnp.int32) + seg2d * jnp.int32(card)
                flat_cols.append(c.reshape(total))
        while len(flat_cols) <= seg_col:
            flat_cols.append(jnp.zeros(total, dtype=jnp.int32))
        flat_cols[seg_col] = seg2d.reshape(total)

        dict_pis = set(dict_cols.values())
        vparams = tuple(
            p.reshape((-1,) + p.shape[2:]) if i in dict_pis else p[0]
            for i, p in enumerate(params))

        from .compact import default_slots_cap, sorted_default_slots_cap
        cap = slots_cap or (sorted_default_slots_cap(total)
                            if _needs_sort(plan2)
                            else default_slots_cap(total))
        out: Dict[str, jax.Array] = {}
        sparse = takes_sparse_post(plan2, xfer_compact, scatter)
        _compact_group_aggs(plan2, masks.reshape(total), tuple(flat_cols),
                            vparams, total, cap, out, platform, scatter,
                            two_pass_mode, ladder_min, xfer_sparse=sparse)
        out["matched"] = masks.sum(axis=1, dtype=int_acc_dtype())  # (S,)
        if xfer_compact and not scatter and not sparse:
            # live-group gather over the combined S*space — the executor
            # splits segments host-side via group_idx // space
            _compact_group_xfer(plan2, out)
        return out

    return kernel


@functools.lru_cache(maxsize=256)
def _jitted_segmented_cached(plan, bucket, n_segments, slots_cap, platform,
                             xfer_compact, scatter, two_pass_mode,
                             ladder_min):
    from ..utils.compileplane import kernel_jit, staged
    key = ("segc", plan, bucket, n_segments, slots_cap, platform,
           xfer_compact, scatter, two_pass_mode, ladder_min)
    return staged(kernel_jit(build_segmented_compact_kernel(
        plan, bucket, n_segments, slots_cap, platform, xfer_compact,
        scatter, two_pass_mode, ladder_min), ph.COMPACT_SEGMENTED),
        "segmented_kernel", key)


def jitted_segmented_compact(plan: KernelPlan, bucket: int,
                             n_segments: int,
                             slots_cap: Optional[int] = None,
                             platform: Optional[str] = None,
                             xfer_compact: bool = True,
                             scatter: Optional[bool] = None):
    if scatter is None:
        scatter = cpu_scatter_default(platform)
    return _jitted_segmented_cached(plan, bucket, n_segments, slots_cap,
                                    platform, xfer_compact, scatter,
                                    _two_pass_mode(), _ladder_min_elems())


# the env-flag wrapper keeps the lru_cache introspection surface
# (tests/tpu_hw_script assert cache hits across the retry ladder)
jitted_segmented_compact.cache_info = _jitted_segmented_cached.cache_info
jitted_segmented_compact.cache_clear = _jitted_segmented_cached.cache_clear


@functools.lru_cache(maxsize=1024)
def _jitted_kernel_cached(plan, bucket, slots_cap, platform, xfer_compact,
                          scatter, two_pass_mode, ladder_min):
    from ..utils.compileplane import kernel_jit, staged
    key = ("kern", plan, bucket, slots_cap, platform, xfer_compact,
           scatter, two_pass_mode, ladder_min)
    return staged(kernel_jit(build_kernel(plan, bucket, slots_cap, platform,
                                          xfer_compact, scatter=scatter,
                                          two_pass_mode=two_pass_mode,
                                          ladder_min=ladder_min),
                             ph.plan_family(plan)),
                  "kernel", key)


def jitted_kernel(plan: KernelPlan, bucket: int,
                  slots_cap: Optional[int] = None,
                  platform: Optional[str] = None,
                  xfer_compact: bool = True,
                  scatter: Optional[bool] = None):
    """jit once per (plan structure, bucket, capacity, target platform,
    aggregation core) — platform keys the cache because f64-bitcast
    support and the Pallas gate differ per backend (mesh execution may
    target a platform other than the process default); scatter=None
    resolves from the platform + PINOT_CPU_FAST_GROUPBY at call time
    (cpu_scatter_default) so the flag is part of the cache key, and the
    compact-path knobs (PINOT_COMPACT_TWO_PASS / _LADDER_MIN) resolve
    here for the same reason — flipping the env between calls must not
    hit a stale cached kernel."""
    if scatter is None:
        scatter = cpu_scatter_default(platform)
    return _jitted_kernel_cached(plan, bucket, slots_cap, platform,
                                 xfer_compact, scatter,
                                 _two_pass_mode(), _ladder_min_elems())


jitted_kernel.cache_info = _jitted_kernel_cached.cache_info
jitted_kernel.cache_clear = _jitted_kernel_cached.cache_clear
