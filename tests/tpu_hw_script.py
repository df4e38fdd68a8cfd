"""Per-lowering hardware checks: a library, not a script.

``chip_smoke.py`` imports it and calls ``run_hardware_checks`` on the
real chip; tests/test_tpu_hw.py runs the selectivity grid on the CPU as a
digest sweep. It covers the lowering classes that have historically
compiled on CPU but crashed on the chip (f64 bitcast-convert through the
X64 rewriter, Pallas Mosaic lowering):

1. compact() Pallas kernel — exact multisets per dtype class (INT, LONG,
   FLOAT, DOUBLE) and at an odd (tail-padded) size;
2. one compact-strategy group-by query per dtype class through the full
   broker path, checked against a numpy oracle, plus the device sketch
   lowerings against the host registry;
3. one query through every device path that otherwise only runs on the
   CPU suite: two-pass/ladder compaction, the selectivity x group-space
   grid, device CASE/CAST/datetime + dateTrunc group keys, expression
   group keys, ROUND / FLOOR beside every tie and the civil date fields
   of int64 milliseconds against numpy, dictionary-evaluated string predicates, device top_k
   selection (kselect), segmented multi-segment compact batching, and a
   pipelined over-HBM-budget scan.

Each check asserts that the PLAN engaged the device lowering (not a host
fallback) and that the answers match a numpy oracle; none asserts a
speed. A failed check raises.
"""
from __future__ import annotations

import os
import tempfile

# every query here is a first run: its kernel compiles inside the query's
# time budget, and on the chip one compile outlasts the 10 s default
# (a YEAR(ts) group key took 70 s to compile on a v5e, chip run of PR 21)
COLD = " OPTION(timeoutMs=600000)"


def run_hardware_checks(checks: list) -> None:
    """Run every check on the current (TPU) backend, appending the name
    of each one that passed to ``checks``; raises on the first failure."""
    out = {"checks": checks}
    broker, seg, srcs, k = check_compact_dtypes(out)
    check_compact_queries(out, broker, seg, srcs, k)
    check_two_pass_ladder(out, broker, seg, srcs, k)
    run_selectivity_grid(1 << 21, out=out)
    check_device_transforms(out)
    check_whole_numbers_and_dates(out)
    check_string_predicates(out)
    check_kselect(out)
    check_segmented_batch(out)
    check_pipelined_scan(out)


def check_compact_dtypes(out):
    """Pallas compaction per dtype class and at an odd size; returns the
    (broker, segment, sources, key) table the query checks share."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pinot_tpu.ops import compact as C
    from pinot_tpu.spi import DataType, FieldSpec, FieldType

    rng = np.random.default_rng(11)
    n = 1 << 16
    mask_np = rng.random(n) < 0.15
    mask = jnp.asarray(mask_np)
    srcs = {
        "int": rng.integers(-1000, 1000, n).astype(np.int32),
        "long": rng.integers(-(2**40), 2**40, n),
        "float": rng.standard_normal(n).astype(np.float32),
        "double": rng.standard_normal(n),
    }
    cols = tuple(jnp.asarray(v) for v in srcs.values())
    cap = C.default_slots_cap(n)
    assert C._use_pallas(n), "Pallas path must engage on the chip"
    valid, outs, _nv, matched, ovf, _ = jax.device_get(
        C.compact(mask, cols, cap))
    if int(matched) != int(mask_np.sum()) or int(ovf) != 0:
        raise AssertionError(
            f"matched {int(matched)} != {mask_np.sum()} ovf={int(ovf)}")
    for (name, src), got_col in zip(srcs.items(), outs):
        got = np.sort(np.asarray(got_col)[valid])
        exp = np.sort(src[mask_np].astype(got.dtype))
        if not np.array_equal(got, exp):
            raise AssertionError(f"compact multiset mismatch for {name}")
        out["checks"].append(f"compact:{name}")

    # odd (non-multiple-of-STEP*LANES) sizes must still take the Pallas
    # path via tail padding
    n_odd = 40_000
    assert C._use_pallas(n_odd), "odd sizes must engage Pallas via padding"
    m_odd = rng.random(n_odd) < 0.2
    x_odd = rng.integers(-500, 500, n_odd).astype(np.int32)
    v2, (o2,), _nv2, m2, ov2, _ = jax.device_get(C.compact(
        jnp.asarray(m_odd), (jnp.asarray(x_odd),),
        C.full_slots_cap(n_odd)))
    if int(m2) != int(m_odd.sum()) or int(ov2) != 0 or not np.array_equal(
            np.sort(np.asarray(o2)[v2]), np.sort(x_odd[m_odd])):
        raise AssertionError("odd-size padded compact mismatch")
    out["checks"].append("compact:odd_size")

    k = rng.integers(0, 1000, n).astype(np.int32)
    broker, seg = _mini_table("t", [
        FieldSpec("k", DataType.INT, FieldType.DIMENSION),
        FieldSpec("i", DataType.INT, FieldType.METRIC),
        FieldSpec("l", DataType.LONG, FieldType.METRIC),
        FieldSpec("f", DataType.FLOAT, FieldType.METRIC),
        FieldSpec("d", DataType.DOUBLE, FieldType.METRIC),
    ], {"k": k, "i": srcs["int"], "l": srcs["long"],
        "f": srcs["float"], "d": srcs["double"]})
    return broker, seg, srcs, k


def check_compact_queries(out, broker, seg, srcs, k) -> None:
    """Full-path compact-strategy queries per dtype class, then the
    device sketch lowerings: HLL registers and theta hashes must be
    BIT-identical to the host registry on the real chip; percentile
    centroids within sketch tolerance."""
    m0 = k == 0
    cases = [
        ("SELECT k, SUM(i), COUNT(*) FROM t GROUP BY k ORDER BY k LIMIT 1",
         (0, int(srcs["int"][m0].sum()), int(m0.sum())), None),
        ("SELECT k, SUM(l) FROM t GROUP BY k ORDER BY k LIMIT 1",
         (0, int(srcs["long"][m0].sum())), None),
        ("SELECT k, MIN(f), MAX(f) FROM t GROUP BY k ORDER BY k LIMIT 1",
         (0, float(srcs["float"][m0].min()),
          float(srcs["float"][m0].max())), 1e-6),
        ("SELECT k, SUM(d), MIN(d), MAX(d) FROM t GROUP BY k "
         "ORDER BY k LIMIT 1",
         (0, float(srcs["double"][m0].sum()),
          float(srcs["double"][m0].min()),
          float(srcs["double"][m0].max())), 1e-4),
    ]
    for sql, expect, tol in cases:
        plan = assert_plan(seg, sql, "kernel")
        if plan.kernel_plan.strategy != "compact":
            raise AssertionError(f"{sql!r} planned "
                                 f"{plan.kernel_plan.strategy}, want compact")
        res = broker.query(sql + COLD)
        got = res.rows[0]
        for g, e in zip(got, expect):
            if tol is None:
                ok = g == e
            else:
                ok = abs(g - e) <= tol * max(1.0, abs(e))
            if not ok:
                raise AssertionError(f"{sql!r}: got {got}, want {expect}")
        out["checks"].append(f"query:{sql.split('(')[1].split(')')[0]}")

    sk_cases = [
        ("SELECT DISTINCTCOUNTHLL(k) FROM t", None),
        ("SELECT DISTINCTCOUNTTHETASKETCH(k, 512) FROM t", None),
        ("SELECT PERCENTILEKLL(d, 50) FROM t", 0.02),
    ]
    for sql, tol in sk_cases:
        assert_plan(seg, sql, "kernel")
        dev = broker.query(sql + COLD).rows[0][0]
        host = broker.query(
            sql + " OPTION(forceHostExecution=true,"
            "timeoutMs=600000)").rows[0][0]
        if tol is None:
            ok = dev == host
        else:
            spread = float(srcs["double"].max() - srcs["double"].min())
            ok = abs(dev - host) <= tol * spread
        if not ok:
            raise AssertionError(f"{sql!r}: device {dev} vs host {host}")
        out["checks"].append(f"sketch:{sql.split('(')[0].split()[-1]}")


def check_two_pass_ladder(out, broker, seg, srcs, k) -> None:
    """Round-5 compact-path rework on the REAL chip: force the second
    compaction pass + lax.switch size ladder (they self-enable only at
    full capacity scale) and require exact agreement with the
    default-path answer for a sparse and a dense filter."""
    import numpy as np

    from pinot_tpu.ops.kernels import jitted_kernel

    saved = {k2: os.environ.get(k2) for k2 in
             ("PINOT_COMPACT_TWO_PASS", "PINOT_COMPACT_LADDER_MIN")}
    try:
        for sql, mask in [
            ("SELECT k, SUM(i), COUNT(*) FROM t WHERE k = 7 "
             "GROUP BY k ORDER BY k LIMIT 10", k == 7),       # sparse
            ("SELECT k, SUM(i) FROM t WHERE k < 900 "
             "GROUP BY k ORDER BY k LIMIT 1", k < 900),       # dense
        ]:
            os.environ.pop("PINOT_COMPACT_TWO_PASS", None)
            os.environ.pop("PINOT_COMPACT_LADDER_MIN", None)
            jitted_kernel.cache_clear()
            base = broker.query(sql + COLD).rows
            os.environ["PINOT_COMPACT_TWO_PASS"] = "1"
            os.environ["PINOT_COMPACT_LADDER_MIN"] = "0"
            jitted_kernel.cache_clear()
            forced = broker.query(sql + COLD).rows
            if base != forced or not base:
                raise AssertionError(
                    f"two-pass/ladder mismatch for {sql!r}: "
                    f"{forced} vs {base}")
            g = base[0][0]
            exp = int(np.asarray(srcs["int"])[np.asarray(mask)
                                              & (k == g)].sum())
            if base[0][1] != exp:
                raise AssertionError(
                    f"{sql!r}: group {g} sum {base[0][1]} != {exp}")
        out["checks"].append("compact:two_pass_ladder")
    finally:
        jitted_kernel.cache_clear()
        for k2, v in saved.items():
            if v is None:
                os.environ.pop(k2, None)
            else:
                os.environ[k2] = v


# ---------------------------------------------------------------------------
# selectivity x group-space grid: the q2.2 / q2.3 / q3.2 / q3.4 / q4.3
# shapes as a synthetic sweep, digest-exact vs the numpy oracle on every
# backend (tests/test_tpu_hw.py runs it on the CPU).
# ---------------------------------------------------------------------------

def grid_cases():
    """(name, group_cols, sel_permille) mirroring the SSB sub-5x shapes:
    2-key 7x1000 (q2.x), 3-key 250x250x7 (q3.2/q3.4), 3-key 7x250x1000
    (q4.3); selectivities from 'almost nothing' through the edges."""
    return [
        ("q2.2-ish", ["k7", "k1000"], 2),
        ("q2.3-ish", ["k7", "k1000"], 16),
        ("q3.2-ish", ["k250a", "k250b", "k7"], 1),
        ("q3.4-ish", ["k250a", "k250b", "k7"], 30),
        ("q4.3-ish", ["k7", "k250a", "k1000"], 1),
        ("empty",    ["k7", "k1000"], 0),
        ("all-rows", ["k250a", "k7"], 1000),
    ]


def build_grid_table(n: int, seed: int = 53):
    """One flat segment with every key cardinality the grid needs plus a
    selectivity dial column (uniform 0..999)."""
    import numpy as np

    from pinot_tpu.spi import DataType, FieldSpec, FieldType

    rng = np.random.default_rng(seed)
    data = {
        "k7": rng.integers(0, 7, n).astype(np.int32),
        "k250a": rng.integers(0, 250, n).astype(np.int32),
        "k250b": rng.integers(0, 250, n).astype(np.int32),
        "k1000": rng.integers(0, 1000, n).astype(np.int32),
        "dial": rng.integers(0, 1000, n).astype(np.int32),
        "v": rng.integers(-100_000, 100_000, n).astype(np.int32),
    }
    fields = [FieldSpec(c, DataType.INT,
                        FieldType.METRIC if c == "v"
                        else FieldType.DIMENSION) for c in data]
    b, seg = _mini_table("grid", fields, data)
    return b, seg, data


def _grid_oracle(data, gcols, sel_permille):
    """Single-threaded numpy group-by; returns {key: (cnt, sum)}.
    INT dimension dictionaries are sorted and dense over the value range,
    so dict ids == values and the broker rows compare directly."""
    import numpy as np

    m = data["dial"] < sel_permille
    key = np.zeros(m.sum(), dtype=np.int64)
    cards = []
    for c in gcols:
        card = int(data[c].max()) + 1
        cards.append(card)
        key = key * card + data[c][m]
    cnts = np.bincount(key)
    sums = np.bincount(key, weights=data["v"][m].astype(np.float64))
    idxs = np.nonzero(cnts)[0]
    oracle = {}
    for i in idxs:
        rem, kv = int(i), []
        for card in reversed(cards):
            kv.append(rem % card)
            rem //= card
        oracle[tuple(reversed(kv))] = (int(cnts[i]), int(sums[i]))
    return oracle


def run_selectivity_grid(n: int, out: dict = None):
    """Sweep the grid; assert digest-exactness per case."""
    broker, seg, data = build_grid_table(n)
    for name, gcols, sel in grid_cases():
        sql = (f"SELECT {', '.join(gcols)}, COUNT(*), SUM(v) FROM grid "
               f"WHERE dial < {sel} GROUP BY {', '.join(gcols)} "
               "LIMIT 1000000")
        # sel == 0 legitimately folds to a pruned plan (metadata range
        # pruning); the zero-match KERNEL path is covered by the runtime
        # sel parameter sweep in tests/test_strategy_differential.py
        plan = assert_plan(seg, sql, "kernel" if sel > 0 else None)
        oracle = _grid_oracle(data, gcols, sel)
        res = broker.query(sql + COLD)
        got = {tuple(r[:len(gcols)]): (r[len(gcols)], r[len(gcols) + 1])
               for r in res.rows}
        if got != oracle:
            strat = plan.kernel_plan.strategy if plan.kernel_plan \
                else plan.kind
            raise AssertionError(
                f"grid {name} (sel {sel}/1000, strategy {strat}): "
                f"{len(got)} groups vs oracle {len(oracle)} — "
                "digests differ")
        if out is not None:
            out["checks"].append(f"grid:{name}")


def _mini_table(name, schema_fields, data):
    """Build a one-segment table; returns (broker, seg)."""
    from pinot_tpu.broker import Broker
    from pinot_tpu.segment import ImmutableSegment, SegmentBuilder
    from pinot_tpu.server import TableDataManager
    from pinot_tpu.spi import Schema, TableConfig

    tmp = tempfile.mkdtemp()
    d = SegmentBuilder(Schema(name, schema_fields),
                       TableConfig(name)).build(data, tmp, "seg_0")
    seg = ImmutableSegment.load(d)
    dm = TableDataManager(name)
    dm.add_segment(seg)
    b = Broker()
    b.register_table(dm)
    return b, seg


def assert_plan(seg, sql, want_kind):
    """Plan ``sql`` against ``seg``; raise unless the plan kind is
    ``want_kind`` (None: any). Returns the plan."""
    from pinot_tpu.query.context import build_query_context
    from pinot_tpu.query.planner import SegmentPlanner
    from pinot_tpu.query.sql import parse_sql

    plan = SegmentPlanner(build_query_context(parse_sql(sql)), seg).plan()
    if want_kind is not None and plan.kind != want_kind:
        raise AssertionError(
            f"{sql!r} planned {plan.kind!r}, want {want_kind!r} — the "
            "device lowering did not engage on hardware")
    return plan


def check_device_transforms(out) -> None:
    """Device CASE/CAST/datetime + dateTrunc/expression group keys
    (round-3 device transforms — tests/test_device_transforms.py run
    CPU-only; this certifies the same lowerings compile on the chip)."""
    import numpy as np

    from pinot_tpu.spi import DataType, FieldSpec, FieldType

    rng = np.random.default_rng(29)
    n = 20_000
    # narrow ~60-day span keeps dateTrunc('day') keys on the kernel path
    ts = rng.integers(1_700_000_000_000, 1_705_184_000_000, n) \
        .astype(np.int64)
    amt = rng.integers(1, 100, n).astype(np.int64)
    price = rng.uniform(0.5, 99.5, n)
    b, seg = _mini_table("tx", [
        FieldSpec("ts", DataType.LONG, FieldType.DIMENSION),
        FieldSpec("amt", DataType.LONG, FieldType.METRIC),
        FieldSpec("price", DataType.DOUBLE, FieldType.METRIC)],
        {"ts": ts, "amt": amt, "price": price})

    # expression group key: YEAR(ts)
    sql = ("SELECT YEAR(ts), COUNT(*) FROM tx GROUP BY 1 "
           "ORDER BY 1 LIMIT 100000")
    assert_plan(seg, sql, "kernel")
    years = (ts.astype("datetime64[ms]").astype("datetime64[Y]")
             .astype(np.int64) + 1970)
    uniq, cnt = np.unique(years, return_counts=True)
    got = {r[0]: r[1] for r in b.query(sql + COLD).rows}
    if got != {int(u): int(c) for u, c in zip(uniq, cnt)}:
        raise AssertionError("YEAR(ts) group key mismatch on chip")
    out["checks"].append("device:year_group_key")

    # dateTrunc('day') group key
    sql = ("SELECT DATETRUNC('day', ts), COUNT(*) FROM tx GROUP BY 1 "
           "ORDER BY 1 LIMIT 100000")
    assert_plan(seg, sql, "kernel")
    oracle = np.floor_divide(ts, 86_400_000) * 86_400_000
    uniq, cnt = np.unique(oracle, return_counts=True)
    got = {r[0]: r[1] for r in b.query(sql + COLD).rows}
    if got != {int(u): int(c) for u, c in zip(uniq, cnt)}:
        raise AssertionError("dateTrunc('day') group key mismatch on chip")
    out["checks"].append("device:datetrunc_group_key")

    # CASE WHEN aggregation + filter on a datetime expression
    sql = ("SELECT SUM(CASE WHEN amt > 75 THEN 2 WHEN amt > 25 THEN 1 "
           "ELSE 0 END) FROM tx WHERE MONTH(ts) = 12")
    assert_plan(seg, sql, "kernel")
    d = ts.astype("datetime64[ms]")
    months = (d.astype("datetime64[M]")
              - d.astype("datetime64[Y]")).astype(np.int64) + 1
    m = months == 12
    exp = int(2 * (amt[m] > 75).sum()
              + ((amt[m] > 25) & (amt[m] <= 75)).sum())
    if b.query(sql + COLD).rows[0][0] != exp:
        raise AssertionError("CASE WHEN + MONTH filter mismatch on chip")
    out["checks"].append("device:case_when_month_filter")

    # CAST in a value expression (f64 division on chip)
    sql = "SELECT SUM(CAST(amt AS DOUBLE) / 4), SUM(CAST(price AS LONG)) " \
          "FROM tx"
    assert_plan(seg, sql, "kernel")
    r = b.query(sql + COLD).rows[0]
    if abs(r[0] - float((amt / 4).sum())) > 1e-6 * abs(r[0]) \
            or r[1] != int(np.trunc(price).sum()):
        raise AssertionError("CAST value expression mismatch on chip")
    out["checks"].append("device:cast")


# doubles at and beside the ties of ROUND, where XLA:TPU's own rounding
# of an emulated float64 was wrong (PERF.md section 6), past 2^52, NaN
# and the infinities. The TPU's double is a pair of float32 with their
# exponent range: it holds nothing past 3.4e38 (1e300 arrives as inf)
NEAR_TIES = (
    2.5, 3.5, -2.5, -3.5, 0.5, -0.5, 1.5, 199.5, 198.5, 2.51, 2.49,
    2.5000000001, 2.4999999999, 0.49999999999, -0.49999999999,
    2.9999999999, -2.9999999999, 0.0, -0.0, 1e-3, 12.34, 399.99,
    2.0 ** 47 + 0.5, 2.0 ** 52 - 0.5, 2.0 ** 52, -2.0 ** 53, 2.0 ** 100,
    float("nan"), float("inf"), float("-inf"))
# the taxi table's first and last pickup (benchmark/taxi/data.py)
TAXI_MS = (1_230_768_000_000, 1_451_606_399_999)


def whole_number_misses(name: str, ref):
    """The doubles whose device ``name`` (round | floor, the kernel's
    _eval_func) differs from numpy's ``ref`` of the same double as the
    device holds it: NEAR_TIES and 8,192 drawn values, half of them
    ties."""
    import jax
    import numpy as np

    from pinot_tpu.ops import kernels
    rng = np.random.default_rng(11)
    xs = np.concatenate([np.array(NEAR_TIES), rng.normal(0, 1e3, 4096),
                         np.round(rng.normal(0, 1e3, 4096)) + 0.5])
    xs = np.asarray(jax.device_put(xs))
    got = np.asarray(jax.jit(lambda x: kernels._eval_func(name, [x]))(xs))
    want = ref(xs)
    return xs[~((got == want) | (np.isnan(got) & np.isnan(want)))]


def civil_date_misses(name: str):
    """The int64 milliseconds whose device ``name`` (year | month | day |
    quarter) differs from numpy's datetime64, or is no int64: over
    +-2^52 ms, +-10^13 ms, the day edges and TAXI_MS."""
    import jax
    import numpy as np

    from pinot_tpu.ops import kernels
    rng = np.random.default_rng(12)
    ms = np.concatenate([
        rng.integers(-2 ** 52, 2 ** 52, 4096),
        rng.integers(-10 ** 13, 10 ** 13, 4096),
        np.array([0, -1, 1, 86_399_999, 86_400_000, -86_400_000,
                  -86_400_001, 951_782_400_000, 951_868_800_000,
                  *TAXI_MS])]).astype(np.int64)
    dt = ms.astype("datetime64[ms]")
    month = dt.astype("datetime64[M]").astype(np.int64) % 12 + 1
    want = {"year": dt.astype("datetime64[Y]").astype(np.int64) + 1970,
            "month": month, "quarter": (month - 1) // 3 + 1,
            "day": (dt.astype("datetime64[D]")
                    - dt.astype("datetime64[M]")).astype(np.int64) + 1}[name]
    got = np.asarray(jax.jit(lambda x: kernels._eval_func(name, [x]))(ms))
    return ms if got.dtype != np.int64 else ms[got != want]


def check_whole_numbers_and_dates(out) -> None:
    """The scan strategy's key functions in the taxi statements: ROUND
    (half to even) and FLOOR of a double, and the civil date fields of
    int64 milliseconds, against numpy on the current backend."""
    import numpy as np
    for name, ref in (("round", np.round), ("floor", np.floor)):
        miss = whole_number_misses(name, ref)
        if miss.size:
            raise AssertionError(f"device {name} differs from numpy at "
                                 f"{miss[:8].tolist()}")
        out["checks"].append(f"device:{name}_beside_ties")
    for name in ("year", "month", "day", "quarter"):
        miss = civil_date_misses(name)
        if miss.size:
            raise AssertionError(f"device {name} differs from numpy at "
                                 f"{miss[:8].tolist()} ms")
        out["checks"].append(f"device:{name}_of_int64_ms")


def check_string_predicates(out) -> None:
    """Dictionary-evaluated string-transform predicates (round-3 final
    commit) on the chip: the predicate evaluates on the host dictionary
    but the doc-mask scan runs in the device kernel."""
    import numpy as np

    from pinot_tpu.spi import DataType, FieldSpec, FieldType

    rng = np.random.default_rng(31)
    n = 20_000
    cities = rng.choice(["Amsterdam", "berlin", "Chicago", "denver",
                         "Boston"], n)
    v = rng.integers(0, 100, n).astype(np.int64)
    b, seg = _mini_table("st", [
        FieldSpec("city", DataType.STRING, FieldType.DIMENSION),
        FieldSpec("v", DataType.LONG, FieldType.METRIC)],
        {"city": cities, "v": v})
    cities = cities.astype(str)
    for cond, m in [
            ("LOWER(city) = 'amsterdam'",
             np.char.lower(cities) == "amsterdam"),
            ("startsWith(city, 'B')", np.char.startswith(cities, "B")),
            ("LENGTH(city) > 6", np.char.str_len(cities) > 6)]:
        sql = f"SELECT COUNT(*), SUM(v) FROM st WHERE {cond}"
        assert_plan(seg, sql, "kernel")
        if tuple(b.query(sql + COLD).rows[0]) != (int(m.sum()), int(v[m].sum())):
            raise AssertionError(f"string predicate {cond!r} wrong on chip")
    out["checks"].append("device:string_transform_predicates")


def check_kselect(out) -> None:
    """Device selection/order-by via lax.top_k (round-3 item 5b)."""
    import numpy as np

    from pinot_tpu.spi import DataType, FieldSpec, FieldType

    rng = np.random.default_rng(37)
    n = 20_000
    data = {
        "city": rng.choice(["nyc", "sf", "austin", "la"], n),
        "year": rng.integers(2018, 2024, n).astype(np.int32),
        "salary": rng.integers(1000, 100000, n).astype(np.int64),
    }
    b, seg = _mini_table("ks", [
        FieldSpec("city", DataType.STRING, FieldType.DIMENSION),
        FieldSpec("year", DataType.INT, FieldType.DIMENSION),
        FieldSpec("salary", DataType.LONG, FieldType.METRIC)], data)
    sql = ("SELECT city, year, salary FROM ks WHERE year >= 2020 "
           "ORDER BY salary DESC LIMIT 5")
    assert_plan(seg, sql, "kselect")
    m = data["year"] >= 2020
    order = np.argsort(-data["salary"][m], kind="stable")[:5]
    exp = [(str(data["city"][m][i]), int(data["year"][m][i]),
            int(data["salary"][m][i])) for i in order]
    if [tuple(r) for r in b.query(sql + COLD).rows] != exp:
        raise AssertionError("kselect top_k selection mismatch on chip")
    out["checks"].append("device:kselect_top_k")


def check_segmented_batch(out) -> None:
    """Segmented multi-segment compact batching: same-plan compact
    segments must run as ONE device program on the chip."""
    import numpy as np

    from pinot_tpu.broker import Broker
    from pinot_tpu.ops import kernels as K
    from pinot_tpu.segment import SegmentBuilder
    from pinot_tpu.server import TableDataManager
    from pinot_tpu.spi import (DataType, FieldSpec, FieldType, Schema,
                               TableConfig)

    rng = np.random.default_rng(41)
    n_seg, rows, card_a, card_b = 4, 1500, 40, 210
    schema = Schema("sb", [
        FieldSpec("ka", DataType.STRING, FieldType.DIMENSION),
        FieldSpec("kb", DataType.STRING, FieldType.DIMENSION),
        FieldSpec("price", DataType.INT, FieldType.METRIC)])
    tmp = tempfile.mkdtemp()
    dm = TableDataManager("sb")
    chunks = []
    for i in range(n_seg):
        chunk = {
            "ka": np.array([f"a{k:02d}" for k in
                            rng.integers(0, card_a, rows)]),
            "kb": np.array([f"b{k:03d}" for k in
                            rng.integers(0, card_b, rows)]),
            "price": rng.integers(0, 10_000, rows).astype(np.int64),
        }
        chunk["ka"][:card_a] = [f"a{k:02d}" for k in range(card_a)]
        chunk["kb"][:card_b] = [f"b{k:03d}" for k in range(card_b)]
        chunks.append(chunk)
        dm.add_segment_dir(SegmentBuilder(schema, TableConfig("sb"))
                           .build(chunk, tmp, f"seg_{i}"))
    b = Broker()
    b.register_table(dm)
    before = K.jitted_segmented_compact.cache_info().misses
    sql = ("SELECT ka, kb, SUM(price) FROM sb GROUP BY ka, kb "
           "ORDER BY ka, kb LIMIT 100000")
    got = {(r[0], r[1]): r[2] for r in b.query(sql + COLD).rows}
    after = K.jitted_segmented_compact.cache_info().misses
    if after <= before:
        raise AssertionError("segmented compact batch kernel did not run")
    ka = np.concatenate([c["ka"] for c in chunks]).astype(str)
    kb = np.concatenate([c["kb"] for c in chunks]).astype(str)
    price = np.concatenate([c["price"] for c in chunks])
    exp = {}
    for a, bb, p in zip(ka, kb, price):
        exp[(a, bb)] = exp.get((a, bb), 0) + int(p)
    if got != exp:
        raise AssertionError("segmented compact batch mismatch on chip")
    out["checks"].append("device:segmented_compact_batch")


def check_pipelined_scan(out) -> None:
    """Pipelined over-HBM-budget scan: a 1-byte budget reroutes dense
    groups through the double-buffered streaming path on the chip."""
    import numpy as np

    from pinot_tpu.broker import Broker
    from pinot_tpu.engine import pipeline
    from pinot_tpu.segment import SegmentBuilder
    from pinot_tpu.server import TableDataManager
    from pinot_tpu.spi import (DataType, FieldSpec, FieldType, Schema,
                               TableConfig)

    rng = np.random.default_rng(43)
    n_seg, rows = 3, 4000
    schema = Schema("pl", [
        FieldSpec("g", DataType.INT, FieldType.DIMENSION),
        FieldSpec("x", DataType.LONG, FieldType.METRIC)])
    tmp = tempfile.mkdtemp()
    dm = TableDataManager("pl")
    gs, xs = [], []
    for i in range(n_seg):
        g = rng.integers(0, 50, rows).astype(np.int32)
        x = rng.integers(0, 1000, rows).astype(np.int64)
        gs.append(g)
        xs.append(x)
        dm.add_segment_dir(SegmentBuilder(schema, TableConfig("pl"))
                           .build({"g": g, "x": x}, tmp, f"seg_{i}"))
    b = Broker()
    b.register_table(dm)
    before = pipeline.STATS["pipelined_groups"]
    os.environ["PINOT_HBM_BUDGET_BYTES"] = "1"
    try:
        sql = ("SELECT g, SUM(x), COUNT(*) FROM pl GROUP BY g "
               "ORDER BY g LIMIT 100000")
        rows_out = b.query(sql + COLD).rows
    finally:
        del os.environ["PINOT_HBM_BUDGET_BYTES"]
    if pipeline.STATS["pipelined_groups"] <= before:
        raise AssertionError("over-budget scan did not take the "
                             "pipelined path")
    g = np.concatenate(gs)
    x = np.concatenate(xs)
    exp = [(int(u), int(x[g == u].sum()), int((g == u).sum()))
           for u in np.unique(g)]
    if [tuple(r) for r in rows_out] != exp:
        raise AssertionError("pipelined scan mismatch on chip")
    out["checks"].append("device:pipelined_over_budget_scan")
