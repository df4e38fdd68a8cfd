"""Whole-plan mesh compilation: the join pipeline as ONE shard_map program.

Reference parity: "Query Processing on Tensor Computation Runtimes"
compiles entire relational plans into one tensor program; the mailbox
plane (exchange.py / dispatch.py — Pinot's MailboxService data plane)
pays a device->host->device round-trip at every stage boundary even when
all stage workers share one process and one mesh. This module removes
those boundaries for co-located plans: every stage boundary becomes an
explicit ``ops.ir.Exchange`` node, hash exchanges lower to the
``lax.all_to_all`` bucket collective (ops/join._shuffle_exchange_jit's
formulation, generalized to carry the pipeline state as payload) and
broadcast exchanges to build-side replication (the all_gather
degenerate), with every join body a ``device_equi_join`` sub-computation
of the single jit.

Execution model: the program never moves relation payloads — only int32
key codes and row indices. The pipeline state is, per joined table, a
gather index into that table's leaf relation (-1 = null-extended), plus
one canonical-position accumulator ``pos`` that composes each stage's
left-major dense layout (``pos' = pos * max_dup + slot``). After the
program returns, the host sorts by ``pos`` — restoring numpy
``hash_join``'s exact pair order without any device-side compaction —
and materializes the joined relation with one gather per column. The
final/window stages then run over that relation through the same host
evaluators as the mailbox plane, so results are byte-identical by
construction.

Fallback: any ineligibility (non-equi outer joins, key-cardinality or
state-size overflow, bucket overflow after a slack retry, a forced
``device.overflow`` chaos fault, a non-pow2 device count) returns None
and the executor re-runs the plan through the classic per-join path —
the mailbox plane stays the cross-host and chaos/failover story.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..analysis.plan_verify import check_fused_plan
from ..ops import ir
from ..utils import phases as ph
from ..utils.faults import fault_fires
from ..utils.spans import span
from ..utils.stats import make_bump
from . import device_join
from .join import _default_for, _key_nulls
from .relation import Relation

# thread-safe counters (utils/stats): tests assert exact routing
STATS = {"fused_plans": 0, "fused_fallbacks": 0, "fused_overflow": 0}
bump = make_bump(STATS)

_MAX_STATE_DEFAULT = 1 << 23   # dense state rows across the mesh


def _max_state_rows() -> int:
    return int(os.environ.get("PINOT_FUSED_MAX_STATE",  # jaxlint: ok host-sync
                              _MAX_STATE_DEFAULT))


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


# ---------------------------------------------------------------------------
# planning: SQL joins -> per-stage runtime arrays + the FusedPlan IR
# ---------------------------------------------------------------------------

class _Stage:
    """Host-side stage record: the FusedJoin statics plus the runtime
    arrays the program is parameterized with."""

    __slots__ = ("kind", "how", "max_dup", "owners", "cards",
                 "slot_codes", "build_codes", "build_ids", "cap",
                 "cap_b", "deferred")

    def __init__(self):
        self.deferred: List[Any] = []


def _slot_codes(lv: np.ndarray, rv: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Joint factorization of one key slot (join.py _composite_codes
    semantics: equal values share codes across sides).

    Dense-integer fast path: surrogate-key joins have tight value
    ranges, so ``value - min`` IS a joint code and the sort inside
    np.unique — the single most expensive host op of the whole fused
    pipeline — is skipped entirely. Codes only need to preserve
    equality; canonical order restoration rides ``pos``, never the
    code values themselves.
    """
    if lv.dtype.kind in "iu" and rv.dtype.kind in "iu" and \
            (len(lv) or len(rv)):
        mn = min(int(a.min()) for a in (lv, rv) if len(a))  # jaxlint: ok host-sync
        mx = max(int(a.max()) for a in (lv, rv) if len(a))  # jaxlint: ok host-sync
        width = mx - mn + 1
        if width <= max(4 * (len(lv) + len(rv)), 1024):
            return (lv.astype(np.int64) - mn,
                    rv.astype(np.int64) - mn, width)
    if lv.dtype == object or rv.dtype == object or \
            lv.dtype.kind in "US" or rv.dtype.kind in "US":
        lv = np.asarray(lv, dtype=object).astype(str)  # jaxlint: ok host-sync
        rv = np.asarray(rv, dtype=object).astype(str)  # jaxlint: ok host-sync
    both = np.concatenate([lv, rv])
    uniq, inv = np.unique(both, return_inverse=True)
    return inv[: len(lv)], inv[len(lv):], len(uniq)


def plan_fused(ex, ordered_joins: Sequence[Any], leafs: List[Relation],
               broadcast_threshold: int
               ) -> Tuple[Optional[ir.FusedPlan],
                          Optional[List[_Stage]], str]:
    """-> (FusedPlan IR, per-stage runtime arrays, fallback_reason).

    ``ex`` is the MultiStageExecutor (owner_of/_split_on reuse);
    ``leafs`` are the scanned leaf relations in execution order
    ([base] + one per ordered join). A None plan means the mailbox
    plane must serve this query; the reason is span-annotated.
    """
    import jax

    n_dev = jax.device_count()
    if n_dev & (n_dev - 1):
        return None, None, "non_pow2_devices"
    labels = [ex.tables[0].label] + [j.table.label for j in ordered_joins]
    ordinal = {lbl: i for i, lbl in enumerate(labels)}
    max_dup_bound = device_join._max_dup_bound()

    n_base = leafs[0].n_rows
    base_pad = n_dev * _pow2(max(-(-n_base // n_dev), 1))
    shard = base_pad // n_dev
    pos_bound = base_pad
    stages: List[_Stage] = []
    ir_stages: List[ir.FusedJoin] = []
    joined = {labels[0]}
    for i, j in enumerate(ordered_joins):
        label = j.table.label
        right = leafs[i + 1]
        if j.join_type not in ("inner", "left"):
            return None, None, f"join_type:{j.join_type}"
        equi, rest = ex._split_on(j.on, joined, label)
        joined.add(label)
        if not equi:
            return None, None, "no_equi_keys"
        if rest and j.join_type != "inner":
            # outer joins with non-equi ON conjuncts null-extend on
            # conjunct failure — that body is the executor's special
            # numpy path, not a fused sub-computation
            return None, None, "outer_non_equi"

        st = _Stage()
        st.how = j.join_type
        st.deferred = list(rest)
        owners: List[int] = []
        cards: List[int] = []
        slot_codes: List[np.ndarray] = []
        comb_r: Optional[np.ndarray] = None
        total_card = 1
        for lref, rref in equi:
            own_label = lref.split(".", 1)[0]
            owner = ordinal[own_label]
            lcol = leafs[owner].raw_values(lref)
            rcol = right.raw_values(rref)
            lc, rc, card = _slot_codes(lcol, rcol)
            lnull = _key_nulls(leafs[owner], [lref])
            if lnull is not None:
                lc = np.where(lnull, -1, lc)
            rnull = _key_nulls(right, [rref])
            if rnull is not None:
                rc = np.where(rnull, -1, rc)
            total_card *= max(card, 1)
            if total_card > 2**31 - 1:
                return None, None, "key_cardinality"
            owners.append(owner)
            cards.append(card)
            # pow2-pad the gather source (signature stability); pads
            # are never indexed (idx < n_rows) but carry the null code
            pad = _pow2(max(len(lc), 1))
            lc32 = np.full(pad, -1, dtype=np.int32)
            lc32[: len(lc)] = lc.astype(np.int32)
            slot_codes.append(lc32)
            comb_r = rc.astype(np.int64) if comb_r is None else \
                np.where((comb_r < 0) | (rc < 0), -1,
                         comb_r * card + rc)
        st.owners = tuple(owners)
        st.cards = np.asarray(cards, dtype=np.int32)  # jaxlint: ok host-sync
        st.slot_codes = slot_codes

        valid_r = comb_r >= 0
        bids = np.nonzero(valid_r)[0].astype(np.int32)
        bcodes = comb_r[valid_r].astype(np.int32)

        # hash (all_to_all repartition) only pays when the build side
        # is too big to replicate per device; below that, broadcast —
        # and when the joint code domain is dense enough, broadcast
        # lowers to a host-built CSR table so the device join body is
        # pure gathers with no device-side sort at all
        hash_min = max(broadcast_threshold,
                       int(os.environ.get("PINOT_FUSED_HASH_MIN",  # jaxlint: ok host-sync
                                          1 << 20)))
        csr_max = int(os.environ.get("PINOT_FUSED_MAX_CSR",  # jaxlint: ok host-sync
                                     1 << 22))
        if right.n_rows > hash_min and n_dev > 1 \
                and j.join_type == "inner":
            st.kind = "hash"
        elif total_card <= csr_max:
            st.kind = "csr"
        else:
            st.kind = "sort"
        if st.kind == "csr":
            counts = np.bincount(bcodes, minlength=total_card) \
                if len(bcodes) else np.zeros(total_card, dtype=np.int64)
            mc = int(counts.max()) if len(bcodes) else 1  # jaxlint: ok host-sync
            if mc > max_dup_bound:
                return None, None, "max_dup"
            md = _pow2(max(mc, 1))
        elif len(bcodes):
            md = device_join._bounded_max_dup(bcodes)
            if md is None:
                return None, None, "max_dup"
        else:
            md = 1
        st.max_dup = md

        if st.kind == "hash":
            # both sides pad to a device multiple and ride the bucket
            # all_to_all; bucket caps are pow2 statics. The slack is
            # deliberately tight: _splitmix32 mixes distinct codes
            # uniformly, so bucket load concentrates hard around
            # shard/n_dev and 1.25x (+ pow2 rounding) is dozens of
            # sigma of headroom — every doubling of cap doubles the
            # post-exchange state the rest of the program drags.
            # Genuine skew overflows retry once at 2x, then mailbox.
            slack = float(os.environ.get("PINOT_FUSED_SLACK",  # jaxlint: ok host-sync
                                         1.25))
            b_pad = n_dev * _pow2(max(-(-len(bcodes) // n_dev), 1))
            bc = np.full(b_pad, -1, dtype=np.int32)
            bc[: len(bcodes)] = bcodes
            bi = np.full(b_pad, -1, dtype=np.int32)
            bi[: len(bids)] = bids
            st.cap = _pow2(max(int(shard / n_dev * slack) + 16, 16))
            st.cap_b = _pow2(max(int((b_pad // n_dev) / n_dev * slack)
                                 + 16, 16))
            shard = n_dev * st.cap
        elif st.kind == "csr":
            # build side pre-sorted by code on the host: runs[c] ..
            # runs[c+1] index the build rows for code c in original
            # (stable) order, so the program probes with gathers only.
            # runs pads past the code domain hold the terminal offset
            # (empty run); sids pads are never reachable (cand < end)
            runs_core = np.zeros(total_card + 1, dtype=np.int64)
            np.cumsum(counts, out=runs_core[1:])
            r_pad = _pow2(total_card + 2)
            bc = np.full(r_pad, len(bcodes), dtype=np.int32)
            bc[: total_card + 1] = runs_core
            b_pad = _pow2(max(len(bids), 1))
            bi = np.full(b_pad, -1, dtype=np.int32)
            if mc <= 1:
                # unique build keys (the surrogate-key norm): each
                # present code's sorted position IS its prefix rank,
                # so a scatter replaces the argsort
                bi[runs_core[bcodes]] = bids
            else:
                bi[: len(bids)] = bids[np.argsort(bcodes,
                                                  kind="stable")]
            st.cap = 0
            st.cap_b = 0
        else:
            b_pad = _pow2(max(len(bcodes), 1))
            bc = np.full(b_pad, -2, dtype=np.int32)   # -2: matches no
            bc[: len(bcodes)] = bcodes                # probe code, -1
            bi = np.full(b_pad, -1, dtype=np.int32)   # (null) included
            bi[: len(bids)] = bids
            st.cap = 0
            st.cap_b = 0
        st.build_codes = bc
        st.build_ids = bi
        shard *= md
        pos_bound *= md
        if pos_bound > 2**31 - 1:
            return None, None, "pos_bound"
        if shard * n_dev > _max_state_rows():
            return None, None, "state_rows"
        stages.append(st)
        # csr and sort are both broadcast exchanges at the IR level —
        # the CSR table is just the replication-friendly lowering
        ir_stages.append(ir.FusedJoin(
            exchange=ir.Exchange(
                kind="hash" if st.kind == "hash" else "broadcast",
                partitions=n_dev, key_slots=st.owners,
                key_dtype="int32", cap=st.cap),
            how=st.how, max_dup=md, build_rows=b_pad))

    plan = ir.FusedPlan(stages=tuple(ir_stages), n_tables=len(labels),
                        base_rows=base_pad, partitions=n_dev,
                        pos_bound=pos_bound)
    return plan, stages, ""


# ---------------------------------------------------------------------------
# lowering: one staged shard_map program per fused plan shape
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _fused_program(spec: Tuple, n_dev: int):
    """One staged whole-plan executable per static chain spec. ``spec``
    entries: (kind, how, max_dup, n_slots, owners, cap, cap_b). Shape
    re-specializations of a warm wrapper stage per-signature inside the
    StagedFn (the device_join._jitted_equi_join cache granularity), so
    compile events, plan-shape ranking and the warmup-debt gate all see
    the fused executables."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..ops.join import SEG_AXIS, _splitmix32, device_equi_join
    from ..parallel.mesh import segment_mesh
    from ..utils.compileplane import staged

    mesh = segment_mesh()

    def _exchange(arrs, n_dev_, cap):
        """Hash-partition rows by arrs[0] (the key codes) across the
        mesh with ONE lax.all_to_all over the stacked payload."""
        c = arrs[0]
        m = c.shape[0]
        k = len(arrs)
        part = (_splitmix32(c) % jnp.uint32(n_dev_)).astype(jnp.int32)
        valid = c >= 0
        part_eff = jnp.where(valid, part, n_dev_).astype(jnp.int32)
        order = jnp.argsort(part_eff)
        sp_ = jnp.take(part_eff, order)
        run_start = jnp.searchsorted(sp_, sp_)
        within = jnp.arange(m, dtype=jnp.int32) \
            - run_start.astype(jnp.int32)
        live = sp_ < n_dev_
        ok = (within < cap) & live
        overflow = jnp.any((within >= cap) & live)
        tp = jnp.where(ok, sp_, n_dev_)
        stacked = jnp.stack([jnp.take(a, order) for a in arrs], axis=1)
        b = jnp.full((n_dev_, cap, k), -1, jnp.int32)
        b = b.at[tp, within].set(stacked, mode="drop")
        rb = jax.lax.all_to_all(b, SEG_AXIS, 0, 0, tiled=True)
        flat = rb.reshape(-1, k)
        return [flat[:, i] for i in range(k)], overflow

    def per_device(seed_pos, seed_idx, *args):
        pos = seed_pos
        idxs = [seed_idx]
        overflow = jnp.zeros((), dtype=bool)
        ai = 0
        for kind, how, max_dup, n_slots, owners, cap, cap_b in spec:
            slots = args[ai:ai + n_slots]
            cards = args[ai + n_slots]
            bcodes = args[ai + n_slots + 1]
            bids = args[ai + n_slots + 2]
            ai += n_slots + 3
            # probe key: gather each slot's code through its owner's
            # index column, combine by cartesian dict arithmetic
            pc = None
            ok = pos >= 0
            for s in range(n_slots):
                ix = idxs[owners[s]]
                src = slots[s]
                sc = jnp.take(src, jnp.clip(ix, 0, src.shape[0] - 1))
                sc = jnp.where(ix >= 0, sc, -1)
                ok = ok & (sc >= 0)
                pc = sc if pc is None else pc * cards[s] + sc
            pc = jnp.where(ok, pc, -1)
            d = max_dup
            if kind == "csr":
                # host pre-sorted the build by code: bcodes is the CSR
                # run-start table, bids the code-sorted build rows —
                # the join body is pure gathers, no device-side sort
                runs, sids = bcodes, bids
                safe = jnp.clip(pc, 0, runs.shape[0] - 2)
                start = jnp.take(runs, safe)
                end = jnp.take(runs, safe + 1)
                cand = start[:, None] \
                    + jnp.arange(d, dtype=jnp.int32)[None, :]
                match = (cand < end[:, None]) & (pc >= 0)[:, None]
                r_glob = jnp.take(
                    sids, jnp.clip(cand, 0, sids.shape[0] - 1))
            else:
                if kind == "hash":
                    # the collective stage boundary: state and build
                    # side repartition by key hash so equal codes
                    # co-locate
                    out, ovf_p = _exchange([pc, pos] + idxs, n_dev,
                                           cap)
                    pc, pos, idxs = out[0], out[1], out[2:]
                    bout, ovf_b = _exchange([bcodes, bids], n_dev,
                                            cap_b)
                    bcodes, bids = bout
                    # received fills are -1; remap build fills so a -1
                    # (null/dead) probe code can never match one
                    bcodes = jnp.where(bcodes >= 0, bcodes, -2)
                    overflow = overflow | ovf_p | ovf_b
                match, r_pos = device_equi_join(pc, bcodes, max_dup)
                match = match & (pc >= 0)[:, None]
                r_glob = jnp.take(bids, r_pos)
            slot_j = jnp.arange(d, dtype=jnp.int32)[None, :]
            if how == "left":
                nomatch = ~match.any(axis=1)
                keep = match.at[:, 0].set(
                    match[:, 0] | (nomatch & (pos >= 0)))
            else:
                keep = match
            new_r = jnp.where(match, r_glob, -1)
            pos = jnp.where(keep, pos[:, None] * d + slot_j,
                            -1).reshape(-1)
            idxs = [jnp.broadcast_to(ix[:, None],
                                     (ix.shape[0], d)).reshape(-1)
                    for ix in idxs]
            idxs.append(new_r.reshape(-1))
        return (pos, *idxs, overflow[None])

    in_specs: List[Any] = [P(SEG_AXIS), P(SEG_AXIS)]
    n_out = 2
    for kind, _how, _md, n_slots, _own, _cap, _cap_b in spec:
        in_specs.extend([P()] * (n_slots + 1))      # slot codes + cards
        side = P(SEG_AXIS) if kind == "hash" else P()
        in_specs.extend([side, side])               # build codes + ids
        n_out += 1
    out_specs = tuple([P(SEG_AXIS)] * (n_out + 1))

    fn = jax.shard_map(per_device, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=out_specs, check_vma=False)
    return staged(jax.jit(fn), "multistage", ("fused_plan", spec, n_dev))


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------

def _run_program(plan: ir.FusedPlan, stages: List[_Stage],
                 n_base: int) -> Optional[Tuple[np.ndarray, ...]]:
    """Stage + run the whole-plan program; None on bucket overflow."""
    import jax
    import jax.numpy as jnp

    n_dev = plan.partitions
    spec = tuple(
        (st.kind, st.how, st.max_dup, len(st.owners), st.owners,
         st.cap, st.cap_b) for st in stages)
    seed = np.full(plan.base_rows, -1, dtype=np.int32)
    seed[:n_base] = np.arange(n_base, dtype=np.int32)
    args: List[Any] = [jnp.asarray(seed), jnp.asarray(seed)]
    for st in stages:
        args.extend(jnp.asarray(a) for a in st.slot_codes)
        args.append(jnp.asarray(st.cards))
        args.append(jnp.asarray(st.build_codes))
        args.append(jnp.asarray(st.build_ids))
    out = _fused_program(spec, n_dev)(*args)
    out = jax.device_get(out)  # jaxlint: ok host-sync
    if bool(np.any(np.asarray(out[-1]))):  # jaxlint: ok host-sync
        bump("fused_overflow")
        return None
    return tuple(np.asarray(a) for a in out[:-1])  # jaxlint: ok host-sync


def execute_fused(ex, ordered_joins: Sequence[Any],
                  needed: Dict[str, set], pushed: Dict[str, List[Any]],
                  broadcast_threshold: int) -> Optional[Relation]:
    """Run the join pipeline as one fused mesh program; None routes the
    executor back to the classic (mailbox-fallback) per-join path."""
    from ..engine import host_eval
    from .executor import _and

    with span(ph.FUSED_PLAN, joins=len(ordered_joins)) as fsp:
        leafs: List[Relation] = []
        for tref in [ex.tables[0]] + [j.table for j in ordered_joins]:
            with span(ph.LEAF_SCAN, table=tref.label) as sp:
                rel = ex.leaf_scan(tref, needed[tref.label],
                                   _and(pushed[tref.label]))
                if sp is not None:
                    sp.annotate(rows=rel.n_rows)
            leafs.append(rel)
        if leafs[0].n_rows == 0:
            # an empty probe seed joins to the empty relation on every
            # plane; materialize it without a device round-trip
            return _materialize(leafs, [np.empty(0, dtype=np.int64)
                                        for _ in leafs])

        # stage planning is span-visible per exchange: the host-side
        # factorization IS the bytes that ride each collective
        with span(ph.COLLECTIVE_EXCHANGE, stages=len(ordered_joins)):
            plan, stages, reason = plan_fused(ex, ordered_joins, leafs,
                                              broadcast_threshold)
        if plan is None:
            bump("fused_fallbacks")
            if fsp is not None:
                fsp.annotate(fallback=reason)
            return None
        check_fused_plan(plan)   # PV2xx fail-fast before staging
        if fsp is not None:
            fsp.annotate(stages=[(s.kind, s.max_dup) for s in stages],
                         partitions=plan.partitions,
                         base_rows=plan.base_rows)

        if fault_fires("device.overflow", "multistage.fused"):
            # chaos: a forced bucket overflow must take the real
            # fallback edge — the mailbox plane serves the query
            bump("fused_fallbacks")
            if fsp is not None:
                fsp.annotate(fallback="device.overflow")
            return None

        with span(ph.FUSED_EXECUTE, partitions=plan.partitions) as esp:
            out = _run_program(plan, stages, leafs[0].n_rows)
            if out is None:
                # one skew retry at 2x bucket slack, then mailbox
                retry = _retry_with_slack(ex, ordered_joins, leafs,
                                          broadcast_threshold)
                if retry is None:
                    bump("fused_fallbacks")
                    if fsp is not None:
                        fsp.annotate(fallback="bucket_overflow")
                    return None
                plan, stages, out = retry
            if esp is not None:
                esp.annotate(rows=int(plan.base_rows))

        pos = out[0]
        sel = np.nonzero(pos >= 0)[0]
        if any(st.kind == "hash" for st in stages):
            order = sel[np.argsort(pos[sel], kind="stable")]
        else:
            # without a hash exchange nothing ever permutes the state:
            # the seed shards are contiguous slices and every stage's
            # row-major slot expansion is monotone in pos, so the
            # program output is already in canonical order
            order = sel
        final_idx = [np.asarray(ix)[order].astype(np.int64)  # jaxlint: ok host-sync
                     for ix in out[1:]]
        rel = _materialize(leafs, final_idx)
        # deferred non-equi inner conjuncts: filtering the materialized
        # relation commutes with the downstream joins' pair formation
        # (inner never preserves, left never drops probe rows)
        for st in stages:
            for conj in st.deferred:
                m = host_eval.eval_filter(conj, rel)
                rel = rel.take(np.nonzero(m)[0])
        bump("fused_plans")
        if fsp is not None:
            fsp.annotate(rows=rel.n_rows)
        return rel


def _retry_with_slack(ex, ordered_joins, leafs, broadcast_threshold):
    """One bucket-overflow retry at doubled slack (mesh_shuffle_join's
    ladder); returns (plan, stages, out) or None."""
    prev = os.environ.get("PINOT_FUSED_SLACK")
    os.environ["PINOT_FUSED_SLACK"] = str(
        2.0 * float(prev if prev is not None else 2.0))
    try:
        plan, stages, reason = plan_fused(ex, ordered_joins, leafs,
                                          broadcast_threshold)
        if plan is None:
            return None
        check_fused_plan(plan)
        out = _run_program(plan, stages, leafs[0].n_rows)
        if out is None:
            return None
        return plan, stages, out
    finally:
        if prev is None:
            os.environ.pop("PINOT_FUSED_SLACK", None)
        else:
            os.environ["PINOT_FUSED_SLACK"] = prev


def _materialize(leafs: List[Relation],
                 final_idx: List[np.ndarray]) -> Relation:
    """Gather the joined relation in canonical order (materialize_join
    + null_extend semantics: -1 indices take the column default with
    the null mask set)."""
    total = len(final_idx[0]) if final_idx else 0
    data: Dict[str, np.ndarray] = {}
    nulls: Dict[str, np.ndarray] = {}
    name_parts = []
    for leaf, ix in zip(leafs, final_idx):
        name_parts.append(leaf.name)
        m = ix >= 0
        safe = np.where(m, ix, 0)
        all_matched = bool(m.all())  # jaxlint: ok host-sync
        for k, v in leaf.data.items():
            col = v[safe] if len(v) else np.zeros(total, dtype=v.dtype)
            nm = leaf.nulls.get(k)
            nm = nm[safe] if nm is not None and len(v) else None
            if not all_matched:
                col = col.copy()
                col[~m] = _default_for(col.dtype)
                nm = (np.zeros(total, dtype=bool) if nm is None
                      else nm.copy()) | ~m
            if nm is not None and nm.any():
                nulls[k] = nm
            data[k] = col
    return Relation(data, nulls, "*".join(name_parts) or "fused")
