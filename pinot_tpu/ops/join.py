"""On-device equi-join over dict-encoded keys.

Reference parity: pinot-query-runtime/.../runtime/operator/
HashJoinOperator.java (build table on the right, probe with the left).
A hash table is the wrong shape for a TPU, so the device formulation is
sort + bounded-run probe, all static shapes:

- sort the right side's key column once (argsort keeps row identity);
- each probe row binary-searches its run start (jnp.searchsorted — the
  vectorized 'hash lookup');
- the run is materialized as max_dup candidate slots per probe row
  (max_dup = the right side's maximum key multiplicity, a static bound
  the caller takes from dictionary/build stats — 1 for PK joins), with
  a match mask killing slots past the run.

Output is a dense (L, max_dup) pair matrix + mask — the shape-preserving
analog of the dynamic match list, ready for gathers of payload columns
and for the same masked aggregation kernels every other operator uses.

mesh_equi_join shards the PROBE side over the mesh and replicates the
build side (broadcast join): each device joins its left shard against
the full right relation with zero collectives in the probe loop — the
all-to-all hash-exchange alternative only pays when the build side is
too big to replicate, which dict-encoded dimension tables are not.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SEG_AXIS = "seg"   # matches parallel.mesh.SEG_AXIS (ops cannot import
# parallel without a cycle; segment_mesh builds the same axis name)


def device_equi_join(lk: jax.Array, rk: jax.Array, max_dup: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """-> (match (L, max_dup) bool, r_idx (L, max_dup) int32).

    Pair (i, r_idx[i, j]) is a join match iff match[i, j]. Rows of rk
    with a key multiplicity beyond max_dup are silently truncated —
    callers size max_dup from build-side stats so that cannot happen.
    """
    n_r = rk.shape[0]
    order = jnp.argsort(rk)
    rs = jnp.take(rk, order)
    start = jnp.searchsorted(rs, lk)                      # (L,)
    cand = start[:, None] + jnp.arange(max_dup,
                                       dtype=jnp.int32)[None, :]
    cand_c = jnp.clip(cand, 0, max(n_r - 1, 0))
    match = (jnp.take(rs, cand_c) == lk[:, None]) & (cand < n_r)
    r_idx = jnp.take(order, cand_c).astype(jnp.int32)
    return match, r_idx


@functools.partial(jax.jit, static_argnums=(2, 3))
def _mesh_join_jit(lk, rk, max_dup, mesh):
    def per_device(lk_shard, rk_full):
        return device_equi_join(lk_shard, rk_full, max_dup)

    return jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P("seg"), P()),
        out_specs=(P("seg"), P("seg")),
        check_vma=False)(lk, rk)


def _splitmix32(x):
    """Device-side mix so hash partitioning is uniform even for
    sequential dict codes (skew would overflow a bucket)."""
    x = x.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _shuffle_exchange_jit(codes, ids, n_dev, cap, mesh):
    """Hash-partition (code, id) pairs across the mesh with ONE
    lax.all_to_all over ICI (SURVEY 2.9: the HashExchange ->
    on-device all-to-all mapping — this is that collective, not a
    comment). Returns per-device received (n_dev*cap,) codes/ids with
    -1 padding, plus an overflow flag (bucket capacity exceeded ->
    caller falls back)."""
    def per_device(c, i):
        m = c.shape[0]
        part = (_splitmix32(c) % jnp.uint32(n_dev)).astype(jnp.int32)
        # invalid rows (-1 code, padding) route to pseudo-partition
        # n_dev: they sort LAST (no real partition's rank inflates) and
        # every write lands out of bounds -> dropped, never clobbering
        # a live slot
        valid = c >= 0
        part_eff = jnp.where(valid, part, n_dev).astype(jnp.int32)
        order = jnp.argsort(part_eff)
        sp = jnp.take(part_eff, order)
        sc = jnp.take(jnp.where(valid, c, -1), order)
        si = jnp.take(i, order)
        # rank within each partition run = position - run start
        run_start = jnp.searchsorted(sp, sp)
        within = jnp.arange(m, dtype=jnp.int32) \
            - run_start.astype(jnp.int32)
        live = sp < n_dev
        ok = (within < cap) & live
        overflow = jnp.any((within >= cap) & live)
        buckets_c = jnp.full((n_dev, cap), -1, dtype=c.dtype)
        buckets_i = jnp.full((n_dev, cap), -1, dtype=ids.dtype)
        tp = jnp.where(ok, sp, n_dev)     # non-ok writes drop (OOB)
        buckets_c = buckets_c.at[tp, within].set(sc, mode="drop")
        buckets_i = buckets_i.at[tp, within].set(si, mode="drop")
        # the collective: bucket d of every device lands on device d
        rc = jax.lax.all_to_all(buckets_c, SEG_AXIS, 0, 0, tiled=True)
        ri = jax.lax.all_to_all(buckets_i, SEG_AXIS, 0, 0, tiled=True)
        return rc.reshape(-1), ri.reshape(-1), overflow[None]

    return jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P(SEG_AXIS), P(SEG_AXIS)),
        out_specs=(P(SEG_AXIS), P(SEG_AXIS), P(SEG_AXIS)),
        check_vma=False)(codes, ids)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _partition_join_jit(lk, lids, rk, rids, max_dup, mesh):
    """Per-device partition join after the exchange: every device joins
    its hash partition locally (zero collectives in the probe)."""
    def per_device(lc, li, rc, ri):
        match, r_pos = device_equi_join(lc, rc, max_dup)
        match = match & (lc >= 0)[:, None]       # dead probe entries
        r_glob = jnp.take(ri, r_pos)
        return match, jnp.broadcast_to(li[:, None], match.shape), r_glob

    return jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P(SEG_AXIS), P(SEG_AXIS), P(SEG_AXIS), P(SEG_AXIS)),
        out_specs=(P(SEG_AXIS), P(SEG_AXIS), P(SEG_AXIS)),
        check_vma=False)(lk, lids, rk, rids)


def mesh_shuffle_join(mesh: Mesh, lk: np.ndarray, rk: np.ndarray,
                      max_dup: int, slack: float = 2.0
                      ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Distributed hash-shuffle INNER join: both key arrays shard over
    the mesh, ONE all_to_all redistributes (code, row_id) pairs so equal
    codes land on the same device, then every device joins its
    partition locally. Returns global (l_idx, r_idx) matched pairs, or
    None when a hash bucket overflowed its capacity (caller retries
    with more slack or falls back to the host join).

    Reference mapping: HashExchange.java + HashJoinOperator — the
    repartitioning rides the ICI collective instead of mailboxes."""
    n_dev = mesh.devices.size

    def shard(arr, fill):
        pad = (-len(arr)) % n_dev
        if pad:
            arr = np.concatenate(
                [arr, np.full(pad, fill, dtype=arr.dtype)])
        return arr

    out = []
    for keys in (lk, rk):
        codes = shard(keys, -1)
        ids = shard(np.arange(len(keys), dtype=np.int64), -1)
        m = len(codes) // n_dev
        cap = max(int(m / n_dev * slack) + 16, 16)
        cap = 1 << (cap - 1).bit_length()   # pow2 bucket: bounded XLA
        # program count (cap is a jit static arg)
        c_d = jax.device_put(codes, NamedSharding(mesh, P(SEG_AXIS)))
        i_d = jax.device_put(ids, NamedSharding(mesh, P(SEG_AXIS)))
        rc, ri, ovf = _shuffle_exchange_jit(c_d, i_d, n_dev, cap, mesh)
        if bool(np.any(jax.device_get(ovf))):
            return None
        out.append((rc, ri))
    (lc, li), (rc, ri) = out
    match, l_glob, r_glob = _partition_join_jit(lc, li, rc, ri,
                                                max_dup, mesh)
    match = np.asarray(match)
    l_glob = np.asarray(l_glob)
    r_glob = np.asarray(r_glob)
    pairs = np.nonzero(match)
    l_idx = l_glob[pairs]
    r_idx = r_glob[pairs]
    keep = (l_idx >= 0) & (r_idx >= 0)
    l_idx = l_idx[keep]
    r_idx = r_idx[keep]
    # restore hash_join's exact output order (left-major; within a left
    # row matches share one code, and the stable build sort emits them
    # by ascending original right index) so every backend stays
    # byte-identical downstream
    o = np.lexsort((r_idx, l_idx))
    return l_idx[o], r_idx[o]


def mesh_equi_join(mesh: Mesh, lk: np.ndarray, rk: np.ndarray,
                   max_dup: int) -> Tuple[np.ndarray, np.ndarray]:
    """Broadcast join over a mesh: probe keys sharded on the 'seg' axis,
    build keys replicated. Returns host (L, max_dup) match/r_idx (the
    probe shard axis is padded to a device multiple and trimmed back)."""
    n = len(lk)
    n_dev = mesh.devices.size
    pad = (-n) % n_dev
    lk_p = np.concatenate([lk, np.full(pad, -1, dtype=lk.dtype)]) \
        if pad else lk
    lk_d = jax.device_put(lk_p, NamedSharding(mesh, P("seg")))
    rk_d = jax.device_put(rk, NamedSharding(mesh, P()))
    match, r_idx = _mesh_join_jit(lk_d, rk_d, max_dup, mesh)
    return np.asarray(match)[:n], np.asarray(r_idx)[:n]
