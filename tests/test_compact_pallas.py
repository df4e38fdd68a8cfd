"""The Pallas compaction kernel itself, on CPU via interpret mode.

Until round 5 the Pallas path (ops/compact._compact_pallas) only ever
executed on real TPU hardware — the CPU suite covered the XLA fallback
alone, so a kernel regression could only be caught on the chip.
PINOT_PALLAS_INTERPRET=1 routes
compact() through pl.pallas_call(interpret=True): the same kernel
trace, DMA emulation included, executable on the CPU backend.

Covers: multiset correctness across dtypes (int32/int64/float64),
sparse + dense masks, the loose-compaction slot accounting
(n_valid >= matched, rows past n_slots*LANES masked off), overflow
flagging, and agreement with the XLA fallback.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pinot_tpu.ops import compact as C


@pytest.fixture()
def interp(monkeypatch):
    monkeypatch.setenv("PINOT_PALLAS_INTERPRET", "1")


def _compact(mask, cols, cap):
    return C.compact(jnp.asarray(mask),
                     tuple(jnp.asarray(c) for c in cols), cap)


def _multiset(valid, out_cols):
    valid = np.asarray(valid)
    return sorted(zip(*[np.asarray(c)[valid].tolist() for c in out_cols]))


N = C.K_MAX * C.R * C.LANES * 2      # two grid steps at the largest K


@pytest.mark.parametrize("p", [0.001, 0.03, 0.25])
def test_pallas_kernel_multiset(interp, p):
    rng = np.random.default_rng(int(p * 1000))
    mask = rng.random(N) < p
    a = rng.integers(-2**31, 2**31, N, dtype=np.int32)
    b = rng.integers(-2**62, 2**62, N, dtype=np.int64)
    f = rng.normal(0, 1e9, N)
    # dense masks overflow the default cap by design (the executor
    # retries at full capacity); test the no-overflow contract there
    cap = C.default_slots_cap(N) if p < 0.1 else C.full_slots_cap(N)
    valid, (ac, bc, fc), n_valid, matched, ov = _compact(
        mask, (a, b, f), cap)
    assert int(ov) == 0
    assert int(matched) == int(mask.sum())
    v = np.asarray(valid)
    assert v.sum() == mask.sum()                 # loose slots are invalid
    assert int(n_valid) >= int(mask.sum())       # but cover every match
    assert not v[int(n_valid):].any()
    assert _multiset(v, (ac, bc, fc)) == \
        sorted(zip(a[mask].tolist(), b[mask].tolist(), f[mask].tolist()))


def test_pallas_kernel_matches_xla_fallback(interp, monkeypatch):
    rng = np.random.default_rng(9)
    mask = rng.random(N) < 0.01
    a = rng.integers(0, 1000, N).astype(np.int32)
    cap = C.sorted_default_slots_cap(N)
    valid_p, (ap,), _, m_p, ov_p = _compact(mask, (a,), cap)
    monkeypatch.setenv("PINOT_PALLAS_INTERPRET", "0")
    valid_x, (ax,), _, m_x, ov_x = _compact(mask, (a,), cap)
    assert int(m_p) == int(m_x)
    assert int(ov_p) == int(ov_x) == 0
    assert _multiset(valid_p, (ap,)) == _multiset(valid_x, (ax,))


def test_pallas_kernel_overflow_flag(interp):
    mask = np.ones(N, bool)
    a = np.arange(N, dtype=np.int32)
    tight = N // (2 * C.LANES)                   # half the needed rows
    *_, ov = _compact(mask, (a,), tight)
    assert int(ov) == 1
    valid, (ac,), _, matched, ov = _compact(mask, (a,),
                                            C.full_slots_cap(N))
    assert int(ov) == 0
    assert np.array_equal(np.sort(np.asarray(ac)[np.asarray(valid)]), a)


def test_pallas_kernel_empty_and_ragged(interp):
    # non-multiple-of-step length exercises the pad path
    n = C.K_MIN * C.R * C.LANES + 12345
    rng = np.random.default_rng(4)
    mask = rng.random(n) < 0.02
    a = rng.integers(-500, 500, n).astype(np.int32)
    cap = C.default_slots_cap(n)
    valid, (ac,), _, matched, ov = _compact(mask, (a,), cap)
    assert int(matched) == int(mask.sum())
    assert sorted(np.asarray(ac)[np.asarray(valid)].tolist()) == \
        sorted(a[mask].tolist())
    valid, (ac,), _, matched, ov = _compact(np.zeros(n, bool), (a,), cap)
    assert int(matched) == 0
    assert not np.asarray(valid).any()


def test_choose_k_respects_vmem_budget():
    assert C._choose_k(1, 1 << 27) == C.K_MAX
    assert C._choose_k(3, 1 << 27) >= C.K_MIN
    assert C._choose_k(12, 1 << 27) >= C.K_MIN
    for n_cols in (1, 3, 6, 12):
        k = C._choose_k(n_cols, 1 << 27)
        in_blocks = 2 * k * C.R * C.LANES * 4 * (n_cols + 1)
        staging = (k + 1) * C.R * C.LANES * 4 * (n_cols + 1)
        parts = (4 * n_cols + 1) * k * C.R * C.LANES * 2
        stack = (k + 1) * C.R * k * C.R * 2
        assert k == C.K_MIN or \
            in_blocks + staging + parts + stack <= 10 << 20
    # K is clamped to the input size: no padding a step-sized input 4x
    assert C._choose_k(1, C.K_MIN * C.R * C.LANES) == C.K_MIN
