"""Masked row compaction — the TPU-native DocIdSet/Projection primitive.

Reference parity: pinot-core/.../operator/DocIdSetOperator.java:59-86
materializes filtered docIds in blocks, then ProjectionOperator.java:67-78
batch-gathers projected columns for them. The TPU analog cannot scatter
(no efficient per-lane scatter on the VPU), so compaction works lane-wise:

- the (N,) column is viewed as (N/128, 128) — 128 independent lane streams;
- per (R,128) tile, each lane compacts its matched rows to the top via a
  broadcast-compare scatter (dest[r,c] = exclusive in-lane count, an
  R x R strict-lower-triangular matmul, then sum_r [dest==s] * x — all
  VPU/MXU ops, no scatter);
- every lane stream advances by the same amount: the tile's max per-lane
  count. Short lanes pad with invalid slots (valid flags are compacted
  alongside), so the output is "loosely compacted": size ~ matched rows
  times a small inflation factor, never more than the input;
- a running slot offset carried in SMEM across the (sequential) TPU grid
  places each tile's rows; each DMA writes a full fixed-size staging
  block and the next tile's DMA overwrites the garbage tail;
- a grid step computes only the slot rows it can fill: the one-hot,
  the gather-sums and the placement run in chunks of NARROW = R/4 slot
  rows a subtile, as many chunks as the step's largest advance needs
  (each row's in-lane slot, the lane counts and the placement's offsets
  are taken once a step, ahead of the chunks). Where no subtile advances past NARROW (a narrow step: every step at
  SSB's selectivities, a few percent) that is one chunk, a quarter of
  the 32-row work; a dense step takes up to four. The rows past a
  subtile's advance are zeros, so the staging block is the same for any
  number of chunks that covers the advance; the kernel reports how many
  of its steps were narrow.

Order is NOT preserved — group-by / aggregation consumers don't need it.

Outputs are (slots_cap*128,) arrays + (n_slots, matched, overflow)
scalars + the (narrow, wide) grid step counts. Rows at index >=
n_slots*128 are uninitialized; consumers must mask with
`valid & (iota < n_slots*128)`. overflow != 0 means capacity
was exceeded and the result is incomplete — retry with full capacity
(`full_slots_cap(n)` can never overflow).

On CPU (tests, host fallback) an XLA nonzero-based implementation is used.
"""
from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp

from ..utils import phases as ph

LANES = 128
R = 32                 # sublane rows per subtile
K_MIN = 8              # minimum subtiles per grid step (gate + capacity math)
# Largest K the kernel is built with: what the installed toolchain
# compiles, whatever _choose_k's hand budget says. On jax 0.9.0 / libtpu
# 0.0.34 / TPU v5e the K=32 kernel needs 21.25 MB (1 column) to 23.95 MB
# (2 columns) of scoped VMEM against a 16 MiB limit and is rejected at
# compile time ("Ran out of memory in memory space vmem", chip run of
# PR 21) — the (R,R,128) one-hot and the per-byte partial sums are
# compiler temporaries the estimate does not count. K=16 compiled and
# ran exact for 1-6 columns at 2^24 rows in the same run.
K_MAX = 16
STEP = K_MIN * R       # minimum rows per grid step (pallas gate, caps)
STAGE = K_MIN * R + R  # staging rows at K_MIN (capacity math only)
NARROW = R // 4        # slot rows a subtile computes in one chunk of a step


def _interpret() -> bool:
    """Test-only escape hatch: run the Pallas kernel in interpret mode on
    CPU (trace-time; dedicated tests call compact() directly, so the
    jitted-kernel caches never see a stale value)."""
    return os.environ.get("PINOT_PALLAS_INTERPRET", "0") == "1"


def _choose_k(n_cols: int, n: int) -> int:
    """Subtiles per grid step: as large as VMEM comfortably allows.

    Larger K cuts the sequential grid (fewer DMA waits / SMEM carry
    round-trips) and deepens the placement matmul contraction from R=32
    to K*R (the 128x128 MXU is depth-starved at 32). Rough VMEM budget
    per column stream: double-buffered input block (2*K*R*LANES*4B) +
    staging ((K+1)*R*LANES*4B) + the bf16 part tiles; cap the estimate
    at ~10MB of the 16 MiB scoped-VMEM limit. The estimate leaves out
    the compiler's temporaries, which is why K_MAX bounds it from
    above."""
    k = K_MAX
    while k > K_MIN and k * R * LANES > n:
        k //= 2               # don't pad small inputs up to a giant step
    while k > K_MIN:
        in_blocks = 2 * k * R * LANES * 4 * (n_cols + 1)
        staging = (k + 1) * R * LANES * 4 * (n_cols + 1)
        parts = (4 * n_cols + 1) * k * R * LANES * 2
        stack = (k + 1) * R * k * R * 2
        if in_blocks + staging + parts + stack <= 10 << 20:
            break
        k //= 2
    # the grid consumes k*R*LANES rows per step; n is padded to that
    return k


# the full-capacity margin must cover the LARGEST staging block any
# chosen K can write ((K_MAX+1)*R rows) — the kernel's fits check is
# off+stage<=cap. It is sized for K up to 32, twice K_MAX, and is part
# of every full_slots_cap (hence of compiled shapes and plan-cache keys
# on every platform): do not re-tune it with the kernel's K_MAX. The
# DEFAULT caps keep the small K_MIN-based floor:
# compact() shrinks K until the staging block fits the cap, so a small
# cap simply runs a smaller grid step — quadrupling the floors would
# quadruple every small-segment kernel's post-aggregation for nothing
# (measured ~2x CPU kernel time at 200k rows).
STAGE_MAX = (32 + 1) * R

# smallest capacity the XLA fallback compaction accepts: it has no staging
# block, so the floor is only about keeping the ladder/post shapes sane.
# The cost model (multistage/costs.compact_slots_cap) clamps here when the
# selectivity estimate says almost nothing matches.
XLA_MIN_SLOTS = 8


def default_slots_cap(n: int) -> int:
    """Default output capacity (slot rows): 1/4 of the input, padded.

    The lane-wise compaction is loose — every subtile advances by its max
    per-lane count, so at selectivity p the slots consumed are ~E[max
    Binomial(R, p) over 128 lanes] / R, about 4-5x p for p around a few
    percent. 1/4 covers p <~ 8% without overflow; denser masks trigger the
    executor's full_slots_cap retry (engine/executor.py run_kernel)."""
    return max(n // (4 * LANES), 2 * STAGE) + STAGE


def sorted_default_slots_cap(n: int) -> int:
    """Default capacity for the sort-based group path: 1/16 of the input.

    Big-space group-bys are overwhelmingly low-selectivity (SSB Q3/Q4:
    0.01-0.5% matched), and the sort runs over the full static capacity,
    so a tighter cap is a direct kernel-time win. The loose-compaction
    advance floor is ~1 slot row per 32-row subtile with any match
    (~3.2%), so 1/16 (6.25%) keeps headroom; denser masks pay the
    full-capacity retry like everything else."""
    return max(n // (16 * LANES), 2 * STAGE) + STAGE


def full_slots_cap(n: int) -> int:
    """Capacity that can never overflow: total slot advance is bounded by
    one slot row per input row-of-128 plus one pad row per subtile, with
    margin for the largest staging block any K writes."""
    return n // LANES + n // (R * LANES) + STAGE_MAX


def f64_bitcast_ok(platform: str = None) -> bool:
    """XLA:TPU's x64 rewriter cannot lower f64 bitcast-convert (it legalizes
    s64/u64 as 32-bit pairs but has no rule for f64 bit views); emitting one
    crashes compilation on the real chip. CPU lowers it fine.

    platform: the platform the kernel will compile for — pass it whenever
    execution targets a mesh whose devices differ from the process default
    (e.g. a CPU dryrun mesh under a TPU default backend)."""
    return (platform or jax.default_backend()) == "cpu"


@jax.named_scope(ph.SCOPE_COMPACT)
def compact(mask: jax.Array, cols: Tuple[jax.Array, ...], slots_cap: int,
            platform: str = None):
    """Compact masked elements of 1-D arrays toward the front (lane-wise).

    mask: (N,) bool; cols: tuple of (N,) arrays. 64-bit columns are
    bit-split into int32 pairs around the kernel. float64 columns on
    backends without f64 bitcast support (TPU: a float64 is a pair of
    float32 there and has no bit view) are carried as float32: the one
    place a float aggregate's input still narrows. It is outside the
    1e-12 bound the dense strategy meets on the chip, and a launch that
    passes through here counts float_acc_narrow, not float_acc_wide
    (kernels.float_acc_forms); carrying the pair's two planes is open
    (PERF.md section 7).
    Returns (valid, out_cols, n_valid_rows, matched, overflow, steps)
    with valid/out_cols of length slots_cap*128 and steps the Pallas
    grid's (narrow, wide) step counts ((0, 0) on the XLA path).
    """
    n = mask.shape[0]
    # split 64-bit columns into int32 pairs (exact for int64 and float64)
    split_cols = []
    recipes = []  # (dtype, n_parts)
    for c in cols:
        if c.dtype == jnp.float64 and not f64_bitcast_ok(platform):
            c = c.astype(jnp.float32)
        if c.dtype.itemsize == 8:
            pair = jax.lax.bitcast_convert_type(c, jnp.int32)  # (N, 2)
            split_cols.extend([pair[:, 0], pair[:, 1]])
            recipes.append((c.dtype, 2))
        elif c.dtype.itemsize == 4:
            split_cols.append(jax.lax.bitcast_convert_type(c, jnp.int32))
            recipes.append((c.dtype, 1))
        else:
            split_cols.append(c.astype(jnp.int32))
            recipes.append((jnp.dtype(jnp.int32), 1))

    k_sub = _choose_k(len(split_cols), n)
    # the staging DMA writes (k_sub+1)*R rows; a cap smaller than one
    # staging block can't hold it (shape-invalid even when predicated
    # off) — shrink K, then fall back to XLA for pathological caps
    while (k_sub + 1) * R > slots_cap and k_sub > K_MIN:
        k_sub //= 2
    if _use_pallas(n, platform) and (k_sub + 1) * R <= slots_cap:
        # the kernel consumes k_sub*R*LANES rows per grid step; pad odd
        # sizes with unmatched rows (mask False) so every shape qualifies
        step_rows = k_sub * R * LANES
        rem = n % step_rows
        if rem:
            pad = step_rows - rem
            mask = jnp.pad(mask, (0, pad))
            split_cols = [jnp.pad(c, (0, pad)) for c in split_cols]
        valid, outs, n_slots, matched, overflow, steps = _compact_pallas(
            mask, tuple(split_cols),
            n + (step_rows - rem if rem else 0), slots_cap, k_sub,
            _interpret())
    else:
        valid, outs, n_slots, matched, overflow = _compact_xla(
            mask, tuple(split_cols), n, slots_cap)
        steps = (jnp.int32(0), jnp.int32(0))

    # recombine split columns
    out_cols = []
    i = 0
    for dtype, parts in recipes:
        if parts == 2:
            pair = jnp.stack([outs[i], outs[i + 1]], axis=-1)
            out_cols.append(jax.lax.bitcast_convert_type(pair, dtype))
            i += 2
        else:
            out_cols.append(jax.lax.bitcast_convert_type(outs[i], dtype)
                            if dtype != jnp.int32 else outs[i])
            i += 1
    n_valid = n_slots * LANES
    return valid, tuple(out_cols), n_valid, matched, overflow, steps


def _use_pallas(n: int, platform: str = None) -> bool:
    if n < STEP * LANES:
        return False
    if _interpret():
        return True            # test-only: interpret-mode kernel on CPU
    return (platform or jax.default_backend()) == "tpu"


def _compact_xla(mask, cols, n, slots_cap):
    """Fallback: exact dense compaction via cumsum + searchsorted + gather.

    Replaces the jnp.nonzero(size=...) formulation: XLA:CPU executed that
    lowering ~12x slower than one running-count cumsum plus a binary
    search for the k-th matched position (measured 14ms -> 1.2ms on a
    262k-row mask), and the cost now scales with the CAPACITY, not the
    input — the cost-model-tightened caps (multistage/costs.
    compact_slots_cap) make the search+gather nearly free at SSB
    selectivities."""
    cap = slots_cap * LANES
    size = min(cap, n)
    cs = jnp.cumsum(mask.astype(jnp.int32))
    # position of the (k+1)-th matched row = first index with cs == k+1;
    # k >= matched lands at n and is masked off below
    idx = jnp.searchsorted(cs, jnp.arange(1, size + 1, dtype=jnp.int32),
                           method="scan")
    valid_small = jnp.arange(size, dtype=jnp.int32) < cs[-1]
    outs = [jnp.where(valid_small, c.at[idx].get(mode="clip"), 0)
            for c in cols]
    if cap > size:
        pad = cap - size
        valid = jnp.concatenate(
            [valid_small, jnp.zeros(pad, dtype=jnp.bool_)])
        outs = [jnp.concatenate([o, jnp.zeros(pad, dtype=o.dtype)])
                for o in outs]
    else:
        valid = valid_small
    matched = cs[-1]
    overflow = (matched > cap).astype(jnp.int32)
    n_slots = jnp.minimum((matched + LANES - 1) // LANES,
                          jnp.int32(slots_cap))
    return valid, outs, n_slots, matched, overflow


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------

def _kernel(mask_ref, *rest, n_cols: int, slots_cap: int, n_steps: int,
            k_sub: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    stage_rows = (k_sub + 1) * R
    col_refs = rest[:n_cols]
    valid_out = rest[n_cols]
    col_outs = rest[n_cols + 1: 2 * n_cols + 1]
    nslots_ref = rest[2 * n_cols + 1]
    matched_ref = rest[2 * n_cols + 2]
    overflow_ref = rest[2 * n_cols + 3]
    narrow_ref = rest[2 * n_cols + 4]
    # SMEM (4,): [off, matched, narrow steps, this step's largest advance]
    carry = rest[2 * n_cols + 5]
    oflow = rest[2 * n_cols + 6]            # SMEM (1,)
    stages = rest[2 * n_cols + 7: 3 * n_cols + 8]   # VMEM staging per col
    sems = rest[3 * n_cols + 8]             # DMA sems (n_cols + 1,)

    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        # explicit int32 literals: weakly-typed Python ints re-canonicalize
        # to int64 when interpret mode's state discharge re-traces the
        # jaxpr under an x64-enabled process (dtype-mismatched ref swap)
        carry[0] = jnp.int32(0)
        carry[1] = jnp.int32(0)
        carry[2] = jnp.int32(0)
        oflow[0] = jnp.int32(0)

    # strict lower triangular (R x R): exclusive in-lane running count
    row_i = jax.lax.broadcasted_iota(jnp.int32, (R, R), 0)
    col_i = jax.lax.broadcasted_iota(jnp.int32, (R, R), 1)
    stril = (row_i > col_i).astype(jnp.int32).astype(jnp.float32)

    # Once a step, ahead of the chunks: each subtile's in-lane slot of
    # every row (dest via the stril matmul; -1 where the mask is off), its
    # lane counts and its advance (its largest in-lane count). The step's
    # largest advance says how many slot rows of a subtile can hold
    # anything at all.
    slots, cnts, offs = [], [], []
    local_off = jnp.int32(0)
    step_adv = jnp.int32(0)
    total = jnp.int32(0)
    for k in range(k_sub):
        m = mask_ref[k * R:(k + 1) * R, :] != 0        # (R, 128)
        mf = m.astype(jnp.int32).astype(jnp.float32)
        dest = jax.lax.dot_general(
            stril, mf, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)
        slots.append(jnp.where(m, dest, jnp.int32(-1)))
        # f32 reductions (exact: counts <= R=32): written for a Mosaic
        # that could not lower integer sum/max reductions; this form
        # compiles and runs exact on jax 0.9.0 / libtpu 0.0.34 (whether
        # the integer form lowers there now: not measured)
        cntf = jnp.sum(mf, axis=0, dtype=jnp.float32)  # (128,)
        cnts.append(cntf.astype(jnp.int32))
        adv = jnp.max(cntf).astype(jnp.int32)
        offs.append(local_off)
        local_off = local_off + adv
        step_adv = jnp.maximum(step_adv, adv)
        # f32 scalar sum (exact: <= 4096 per step); jnp.sum-to-scalar on
        # int32 sneaks an int64 intermediate past the Mosaic lowering
        total = total + jnp.sum(cntf, dtype=jnp.float32).astype(jnp.int32)

    # the placement's staging row for slot row j (< NARROW) of subtile k
    # in the first chunk: offs[k] + j at column k*NARROW + j; chunk c adds
    # c*NARROW
    shape = (stage_rows, k_sub * NARROW)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    sub = jax.lax.shift_right_logical(
        col, jnp.int32(NARROW.bit_length() - 1))      # col // NARROW
    dst0 = jax.lax.bitwise_and(col, jnp.int32(NARROW - 1))
    for k in range(1, k_sub):
        dst0 = jnp.where(sub == k, dst0 + offs[k], dst0)

    def stage_chunk(c, acc):
        """Add slot rows [c*NARROW, (c+1)*NARROW) of every subtile to the
        staging blocks. A subtile's slot rows at or past its advance are
        exact zeros, so the chunks up to the step's largest advance make
        the whole block, and each staging row takes one value or zeros.

        Per subtile: in-lane compaction (a one-hot gather-sum over
        (NARROW, R, 128) at the rows' slots). Placement into the staging
        block happens in ONE deep matmul per byte part across all k_sub
        subtiles:
            staging += stack_all @ vstack(subtile parts)
        stack_all (stage_rows, k_sub*NARROW) stacks each subtile's
        one-hot placement at its running offset; invalid slots are exact
        zeros, so overlapping garbage rows can't corrupt the sums. A deep
        contraction keeps the 128x128 MXU fed (per-subtile R=32-deep
        matmuls ran it at ~25% depth utilization). Values stay
        bf16-exact: columns are split into bytes (|v| <= 255) and
        recombined after f32 accumulation."""
        lo = c * jnp.int32(NARROW)                   # the chunk's first row
        out_iota = jax.lax.broadcasted_iota(
            jnp.int32, (NARROW, R, LANES), 0) + lo
        row_iota = jax.lax.broadcasted_iota(jnp.int32, (NARROW, LANES), 0) + lo
        valid_tiles = []
        part_tiles = [[[] for _ in range(4)] for _ in range(n_cols)]
        for k in range(k_sub):
            sl = slice(k * R, (k + 1) * R)
            scat = slots[k][None, :, :] == out_iota    # (NARROW, R, 128)
            valid_tiles.append(
                (row_iota < cnts[k][None, :])
                .astype(jnp.int32).astype(jnp.float32))
            for ci in range(n_cols):
                x = col_refs[ci][sl, :]
                # byte-split BEFORE the one-hot gather-sum so the
                # reduction runs in f32 (exact: one-hot selects a single
                # byte <= 255 per output slot) — no integer reduction
                for b in range(4):
                    if b < 3:
                        part = jax.lax.bitwise_and(
                            jax.lax.shift_right_logical(x, jnp.int32(8 * b)),
                            jnp.int32(0xFF))
                    else:
                        part = jax.lax.shift_right_arithmetic(
                            x, jnp.int32(24))
                    partf = part.astype(jnp.float32)
                    part_tiles[ci][b].append(jnp.sum(
                        jnp.where(scat, partf[None, :, :], jnp.float32(0)),
                        axis=1, dtype=jnp.float32))    # (NARROW, 128) f32

        # stack_all[s, k*NARROW + j] = 1 where slot row lo + j of subtile
        # k lands: staging row offs[k] + lo + j
        stack_all = (jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                     == dst0 + lo).astype(jnp.int32).astype(jnp.bfloat16)

        def place_all(tiles):
            # f32 tiles: (8, 128) is one whole f32 tile, half a bf16 one
            t = jnp.concatenate(tiles, axis=0).astype(jnp.bfloat16)
            return jax.lax.dot_general(
                stack_all, t, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        for ci in range(n_cols + 1):
            if ci == 0:
                val = place_all(valid_tiles).astype(jnp.int32)
            else:
                part = [place_all(part_tiles[ci - 1][b]) for b in range(4)]
                val = (((part[3].astype(jnp.int32) * jnp.int32(256)
                         + part[2].astype(jnp.int32)) * jnp.int32(256)
                        + part[1].astype(jnp.int32)) * jnp.int32(256)
                       + part[0].astype(jnp.int32))
            stages[ci][:] = stages[ci][:] + val
        return acc

    # as many chunks as the step's largest advance needs: one where no
    # subtile advances past NARROW (a narrow step), up to R // NARROW.
    # The trip count is read from SMEM: a scalar, not a vector lane.
    carry[3] = step_adv
    adv_s = carry[3]
    carry[2] = carry[2] + (adv_s <= NARROW).astype(jnp.int32)
    for ci in range(n_cols + 1):
        stages[ci][:] = jnp.zeros((stage_rows, LANES), jnp.int32)
    jax.lax.fori_loop(jnp.int32(0), (adv_s + (NARROW - 1)) // NARROW,
                      stage_chunk, jnp.int32(0))

    off = carry[0]
    fits = off + stage_rows <= slots_cap

    # DMA start + synchronous wait inside one conditional block: a skipped
    # step (overflow) skips both, so no semaphore imbalance across steps
    @pl.when(fits)
    def _():
        cps = []
        for ci in range(n_cols + 1):
            dst = valid_out if ci == 0 else col_outs[ci - 1]
            cp = pltpu.make_async_copy(
                stages[ci].at[:], dst.at[pl.ds(off, stage_rows)],
                sems.at[ci])
            cp.start()
            cps.append(cp)
        for cp in cps:
            cp.wait()
        carry[0] = off + local_off

    @pl.when(jnp.logical_not(fits))
    def _():
        oflow[0] = jnp.int32(1)

    carry[1] = carry[1] + total

    @pl.when(step == n_steps - 1)
    def _():
        nslots_ref[0, 0] = carry[0]
        matched_ref[0, 0] = carry[1]
        overflow_ref[0, 0] = oflow[0]
        narrow_ref[0, 0] = carry[2]


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _compact_pallas(mask, cols, n, slots_cap, k_sub, interp):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_cols = len(cols)
    step_rows = k_sub * R
    stage_rows = (k_sub + 1) * R
    n_steps = n // (step_rows * LANES)
    # int8, not uint8: written for a Mosaic whose ir_constant could not
    # emit uint8 literals (`mask_ref != 0` failed TPU lowering); int8
    # lowers on jax 0.9.0 / libtpu 0.0.34
    mask2d = mask.reshape(n // LANES, LANES).astype(jnp.int8)
    cols2d = [c.reshape(n // LANES, LANES) for c in cols]

    in_specs = [pl.BlockSpec((step_rows, LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)] * (n_cols + 1)
    out_shapes = ([jax.ShapeDtypeStruct((slots_cap, LANES), jnp.int32)]
                  * (n_cols + 1)
                  + [jax.ShapeDtypeStruct((1, 1), jnp.int32)] * 4)
    out_specs = ([pl.BlockSpec(memory_space=pl.ANY)] * (n_cols + 1)
                 + [pl.BlockSpec(memory_space=pltpu.SMEM)] * 4)

    kern = functools.partial(_kernel, n_cols=n_cols, slots_cap=slots_cap,
                             n_steps=n_steps, k_sub=k_sub)
    call = pl.pallas_call(
        kern,
        grid=(n_steps,),
        in_specs=in_specs,
        out_shape=out_shapes,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.SMEM((4,), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
        ] + [pltpu.VMEM((stage_rows, LANES), jnp.int32)] * (n_cols + 1)
          + [pltpu.SemaphoreType.DMA((n_cols + 1,))],
        interpret=interp,
        name="pinot_compact",
    )
    # the kernel is pure 32-bit; keep x64 promotion rules out of the trace
    with jax.enable_x64(False):
        outs = call(mask2d, *cols2d)

    valid2d = outs[0]
    col2d = outs[1: n_cols + 1]
    n_slots = outs[n_cols + 1][0, 0]
    matched = outs[n_cols + 2][0, 0]
    overflow = outs[n_cols + 3][0, 0]
    narrow = outs[n_cols + 4][0, 0]

    cap_rows = slots_cap * LANES
    row_ok = (jnp.arange(cap_rows, dtype=jnp.int32)
              < n_slots * LANES)
    valid = (valid2d.reshape(cap_rows) != 0) & row_ok
    out_cols = tuple(jnp.where(valid, c.reshape(cap_rows), 0)
                     for c in col2d)
    return (valid, out_cols, n_slots, matched, overflow,
            (narrow, jnp.int32(n_steps) - narrow))
