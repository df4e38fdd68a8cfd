"""Differential group-by strategy test (round-6 satellite): dense,
compact-factorized, compact-sorted, and compact-scatter cores must
produce BYTE-IDENTICAL digests for the same query across the whole
selectivity range — including the empty-result and all-rows-match edges.

The selectivity is a runtime parameter (Cmp against params), so one
compiled kernel per (strategy, core) serves every selectivity: the sweep
costs compiles-per-strategy, not compiles-per-point. Digests cover
COUNT + exact integer SUM + MIN/MAX, which are order-independent, hence
byte-comparable across cores (float sums are order-dependent by design
and are covered with tolerances elsewhere)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pinot_tpu.ops import kernels as K
from pinot_tpu.ops.ir import AggSpec, Cmp, Col, KernelPlan

N = 1 << 13
CARD_A, CARD_B = 40, 50          # space 2000
SPACE = CARD_A * CARD_B

# per-mille thresholds: 0 = empty result, 1000 = all rows match
SELS = [0, 1, 10, 100, 500, 900, 1000]


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(31)
    return {
        "ka": rng.integers(0, CARD_A, N).astype(np.int32),
        "kb": rng.integers(0, CARD_B, N).astype(np.int32),
        "sel": rng.integers(0, 1000, N).astype(np.int32),
        "v": rng.integers(-1000, 1000, N).astype(np.int32),
    }


def _plan(with_minmax: bool, strategy: str) -> KernelPlan:
    aggs = [AggSpec(kind="sum", value=Col(3), integral=True,
                    bits=11, signed=True),
            AggSpec(kind="count", value=None)]
    if with_minmax:
        aggs += [AggSpec(kind="min", value=Col(3), integral=True),
                 AggSpec(kind="max", value=Col(3), integral=True)]
    return KernelPlan(pred=Cmp(Col(2), "<", 0), aggs=tuple(aggs),
                      group_keys=((0, CARD_A), (1, CARD_B)),
                      strategy=strategy)


def _digest(out: dict) -> dict:
    keep = {}
    for k, v in out.items():
        # how the compactor stepped differs by strategy, not the answer
        if k in ("overflow",) + K.COMPACT_STEP_OUTPUTS:
            continue
        keep[k] = np.asarray(v).tobytes()
    return keep


def _run(fn, cols, sel_permille):
    out = fn(cols, np.int32(N), (jnp.asarray(np.int32(sel_permille)),))
    return {k: np.asarray(v) for k, v in out.items()}


def _oracle(data, sel_permille):
    m = data["sel"] < sel_permille
    keys = data["ka"].astype(np.int64) * CARD_B + data["kb"]
    cnts = np.bincount(keys[m], minlength=SPACE)
    sums = np.bincount(keys[m], weights=data["v"][m].astype(np.float64),
                       minlength=SPACE).astype(np.int64)
    return m, cnts, sums


@pytest.mark.parametrize("with_minmax", [False, True],
                         ids=["sums", "minmax"])
def test_strategies_byte_identical(data, with_minmax, monkeypatch):
    # default ladder knobs: the production single-branch MXU post plus
    # the always-on scatter ladder (the forced-ladder sweep lives in
    # test_compact_ladder.py — re-forcing it here would multiply every
    # kernel's traced branch count for no extra coverage)
    cols = tuple(jnp.asarray(data[k]) for k in ("ka", "kb", "sel", "v"))

    variants = {
        "dense": jax.jit(K.build_kernel(
            _plan(with_minmax, "dense"), N, scatter=False)),
        "compact-scatter": jax.jit(K.build_kernel(
            _plan(with_minmax, "compact"), N, scatter=True)),
    }
    if with_minmax:
        # min/max forces the sorted post on the MXU core
        variants["compact-sorted"] = jax.jit(K.build_kernel(
            _plan(with_minmax, "compact"), N, scatter=False))
    else:
        variants["compact-factorized"] = jax.jit(K.build_kernel(
            _plan(with_minmax, "compact"), N, scatter=False))
        # shrink the factorized limit so the SAME sums-only plan takes
        # the sorted post — the third strategy of the differential
        monkeypatch.setattr(K, "FACTORIZED_GROUP_LIMIT", 1)
        variants["compact-sorted"] = jax.jit(K.build_kernel(
            _plan(with_minmax, "compact"), N, scatter=False))
        monkeypatch.undo()

    for sel in SELS:
        m, cnts, sums = _oracle(data, sel)
        outs = {name: _run(fn, cols, sel)
                for name, fn in variants.items()}
        # every strategy against the numpy oracle
        for name, out in outs.items():
            assert int(out["matched"]) == int(m.sum()), (name, sel)
            assert np.array_equal(out["group_count"], cnts), (name, sel)
            assert np.array_equal(out["agg0_sum"], sums), (name, sel)
        # and byte-identical against each other (counts, sums, min/max)
        ref_name = sorted(outs)[0]
        ref = _digest(outs[ref_name])
        for name, out in outs.items():
            d = _digest(out)
            for key in ref:
                if key == "matched":
                    continue
                assert d[key] == ref[key], \
                    f"{name} vs {ref_name} differ on {key} at sel={sel}"


def test_empty_and_all_match_edges(data):
    """The sel=0 (FalseP-like) and sel=1000 (all-match) edges through the
    compact path: empty results must produce all-zero dense outputs and
    matched=0; all-match must agree with a dense all-rows oracle."""
    cols = tuple(jnp.asarray(data[k]) for k in ("ka", "kb", "sel", "v"))
    # jitted_kernel: value-equal plans share one compile with the main
    # differential (lru keyed on the frozen dataclass)
    fn = K.jitted_kernel(_plan(True, "compact"), N, scatter=False)
    out = _run(fn, cols, 0)
    assert int(out["matched"]) == 0
    assert not out["group_count"].any()
    assert not out["agg0_sum"].any()
    out = _run(fn, cols, 1000)
    _m, cnts, sums = _oracle(data, 1000)
    assert np.array_equal(out["group_count"], cnts)
    assert np.array_equal(out["agg0_sum"], sums)
    live = cnts > 0
    keys = data["ka"].astype(np.int64) * CARD_B + data["kb"]
    mins = np.full(SPACE, np.iinfo(np.int64).max)
    maxs = np.full(SPACE, np.iinfo(np.int64).min)
    np.minimum.at(mins, keys, data["v"].astype(np.int64))
    np.maximum.at(maxs, keys, data["v"].astype(np.int64))
    assert np.array_equal(out["agg2_min"][live], mins[live])
    assert np.array_equal(out["agg3_max"][live], maxs[live])
