"""AOT TPU lowering of the Pallas compact path — no chip required.

The interpret-mode tests (test_compact_pallas.py) validate kernel
SEMANTICS on CPU but bypass the Mosaic compiler entirely; a kernel edit
can pass the whole CPU suite and still fail to lower on the real chip
(layout/op-support rejections happen at lowering, before execution).
jax.export with platforms=["tpu"] runs the Mosaic frontend on any host,
so this is the suite's compile-time hardware gate: if these exports
succeed, the kernels the SSB bench runs (two-pass compaction + size
ladder, sorted and factorized post-aggregation) are lowerable on TPU.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pinot_tpu.ops import compact as C
from pinot_tpu.ops.ir import And, AggSpec, Bin, Col, EqId, IdRange, \
    KernelPlan
from pinot_tpu.ops.kernels import build_kernel

N = 1 << 24


def _export_tpu(fn, *args):
    from jax import export
    return export.export(jax.jit(fn), platforms=["tpu"])(*args)


@pytest.mark.parametrize("k_sub", [C.K_MIN, C.K_MAX])
@pytest.mark.parametrize("n_cols", [1, 2, 3])
def test_compact_kernel_lowers_for_tpu(n_cols, k_sub):
    """The kernel, with its loop over slot-row chunks sized by each
    step's advance, lowers for TPU."""
    n = C.K_MAX * C.R * C.LANES * 2
    cap = C.sorted_default_slots_cap(n)

    def fn(mask, *cols):
        return C._compact_pallas(mask, cols, n, cap, k_sub, False)

    _export_tpu(fn, jax.ShapeDtypeStruct((n,), jnp.bool_),
                *[jax.ShapeDtypeStruct((n,), jnp.int32)] * n_cols)


@pytest.mark.parametrize("shape", ["sorted_q3", "factorized_q2"])
def test_full_compact_query_kernel_lowers_for_tpu(monkeypatch, shape):
    """The whole jitted query program (predicates -> Pallas compaction ->
    second pass -> lax.switch ladder -> sort/matmul post-aggregation ->
    transfer compaction) must lower for TPU. lax.switch traces EVERY
    ladder branch, so one export covers the full ladder."""
    monkeypatch.setenv("PINOT_COMPACT_LADDER_MIN", str(1 << 20))
    if shape == "sorted_q3":
        plan = KernelPlan(
            pred=And((EqId(0, 0), EqId(1, 1), IdRange(2, 2, 3))),
            aggs=(AggSpec(kind="sum", value=Col(3), integral=True,
                          bits=23, signed=False),),
            group_keys=((0, 250), (1, 250), (2, 7)),   # 437.5k: sort path
            strategy="compact",
        )
        n_cols = 4
    else:
        plan = KernelPlan(
            pred=And((EqId(0, 0), IdRange(1, 1, 2))),
            aggs=(AggSpec(kind="sum", value=Bin("-", Col(2), Col(3)),
                          integral=True, bits=24, signed=True),),
            group_keys=((0, 7), (1, 1000)),            # 7k: factorized
            strategy="compact",
        )
        n_cols = 4
    fn = build_kernel(plan, N, platform="tpu")
    cols = tuple(jax.ShapeDtypeStruct((N,), jnp.int32)
                 for _ in range(n_cols))
    params = tuple(jax.ShapeDtypeStruct((), jnp.int32) for _ in range(4))
    _export_tpu(fn, cols, jax.ShapeDtypeStruct((), jnp.int32), params)


def test_scan_kernel_over_segments_lowers_for_tpu():
    """The zone tile's scan-strategy program as the batched launch builds
    it (ops/kernels.over_segments: 8 segments of 2^23 rows, 265 groups,
    COUNT and a float AVG as an exact fixed point) lowers for TPU, and
    maps the segments in turn rather than batching them."""
    from pinot_tpu.ops.ir import TrueP
    from pinot_tpu.ops.kernels import over_segments
    plan = KernelPlan(pred=TrueP(),
                      aggs=(AggSpec("count", None, True),
                            AggSpec("avg", Col(1), False, bits=9)),
                      group_keys=((0, 265),), strategy="scan")
    seg_rows, n_seg = 1 << 23, 8
    kern = build_kernel(plan, seg_rows, platform="tpu")

    def fn(cols, n_docs, params):
        return over_segments(plan, kern, cols, n_docs, params)
    exp = _export_tpu(
        fn, (jax.ShapeDtypeStruct((n_seg, seg_rows), jnp.int32),
             jax.ShapeDtypeStruct((n_seg, seg_rows), jnp.float64)),
        jax.ShapeDtypeStruct((n_seg,), jnp.int32), ())
    # one loop over the segments, one over a segment's blocks of rows
    # (vmap would leave the blocks' loop alone, batched over segments)
    assert exp.mlir_module().count("stablehlo.while") == 2


@pytest.mark.parametrize("widths", [(25,), (25, 23)], ids=["one_lane",
                                                            "two_lanes"])
def test_topgroups_kernel_lowers_for_tpu(widths):
    """ClickBench q15's and q16's top-groups programs as the server
    launches them (8 segments of 2^23 rows, each key gathered through
    its segment's remap, one or two sort lanes, the top 5,000 by count)
    lower for TPU: one key sort, and top_k."""
    from pinot_tpu.ops.ir import TrueP
    from pinot_tpu.ops.kernels import build_topgroups_kernel
    seg_rows, n_seg = 1 << 23, 8
    keys = tuple(range(len(widths)))
    fn = build_topgroups_kernel((TrueP(),) * n_seg, (keys,) * n_seg,
                                (seg_rows,) * n_seg, widths, 5000, True)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    exp = _export_tpu(
        fn, tuple(tuple(i32(seg_rows) for _ in keys) for _ in range(n_seg)),
        i32(n_seg), tuple(() for _ in range(n_seg)),
        tuple(tuple(i32(1 << 22) for _ in keys) for _ in range(n_seg)))
    text = exp.mlir_module()
    assert text.count("stablehlo.sort") == 1 and text.count("top_k") == 1
