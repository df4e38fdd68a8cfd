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
    valid, (ac, bc, fc), n_valid, matched, ov, _ = _compact(
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
    valid_p, (ap,), _, m_p, ov_p, _ = _compact(mask, (a,), cap)
    monkeypatch.setenv("PINOT_PALLAS_INTERPRET", "0")
    valid_x, (ax,), _, m_x, ov_x, _ = _compact(mask, (a,), cap)
    assert int(m_p) == int(m_x)
    assert int(ov_p) == int(ov_x) == 0
    assert _multiset(valid_p, (ap,)) == _multiset(valid_x, (ax,))


def test_pallas_kernel_overflow_flag(interp):
    mask = np.ones(N, bool)
    a = np.arange(N, dtype=np.int32)
    tight = N // (2 * C.LANES)                   # half the needed rows
    *_, ov, _ = _compact(mask, (a,), tight)
    assert int(ov) == 1
    valid, (ac,), _, matched, ov, _ = _compact(mask, (a,),
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
    valid, (ac,), _, matched, ov, _ = _compact(mask, (a,), cap)
    assert int(matched) == int(mask.sum())
    assert sorted(np.asarray(ac)[np.asarray(valid)].tolist()) == \
        sorted(a[mask].tolist())
    valid, (ac,), _, matched, ov, _ = _compact(np.zeros(n, bool), (a,), cap)
    assert int(matched) == 0
    assert not np.asarray(valid).any()


BLOCK = C.K_MAX * C.R          # mask rows of 128 lanes in one grid step
NARROW = C.NARROW              # as the kernel has it (the fixture below
#                                patches the module's)


def _lane_run(mask, step: int, count: int, lane: int = 5):
    """Set ``count`` rows of one lane in the first subtile of grid step
    ``step``: that subtile's advance, and the step's, is then ``count``
    (the sparse background advances at most 2 a subtile)."""
    m2 = mask.reshape(-1, C.LANES)
    m2[step * BLOCK: step * BLOCK + C.R, lane] = False
    m2[step * BLOCK: step * BLOCK + count, lane] = True
    return mask


def _background(seed: int, p: float = 0.001):
    mask = np.random.default_rng(seed).random(N) < p
    lanes = mask.reshape(-1, C.R, C.LANES).sum(axis=1)
    assert lanes.max() <= 2           # never close to the narrow limit
    return mask


def _case(name):
    """(mask, slots_cap, (narrow, wide) steps the mask implies): two
    grid steps of K_MAX subtiles; a step is narrow iff no subtile of it
    advances past NARROW slot rows."""
    full = C.full_slots_cap(N)
    if name.startswith("p"):
        p = float(name.split("_")[0][1:])
        rng = np.random.default_rng(int(p * 1e4))
        mask = rng.random(N) < p
        if name.endswith("mixed"):
            # the second step sparse where the first is dense, and back
            mask[N // 2:] = rng.random(N // 2) < (0.001 if p > 0.1 else 0.6)
        return mask, full, None
    if name == "lane_8":
        return _lane_run(_background(1), 0, NARROW), full, (2, 0)
    if name == "lane_9":
        return _lane_run(_background(1), 0, NARROW + 1), full, (1, 1)
    if name == "overflow_narrow":
        # every step narrow; the second step's staging block no longer
        # fits behind the first step's advance
        return (np.random.default_rng(3).random(N) < 0.03,
                (C.K_MAX + 1) * C.R + 4, (2, 0))
    raise KeyError(name)


FORM_CASES = [f"p{p}_{layout}" for p in (0.001, 0.03, 0.2, 0.6)
              for layout in ("uniform", "mixed")] + [
    "lane_8", "lane_9", "overflow_narrow"]


@pytest.fixture(scope="module")
def forms():
    """Every case through the kernel as it is, through the kernel held
    to all R slot rows in every step (NARROW = R: one chunk of 32 rows,
    the 32-row form) and through the XLA fallback. One compile a form
    and capacity: the results are computed here once."""
    rng = np.random.default_rng(17)
    a = rng.integers(-2**31, 2**31, N, dtype=np.int32)
    b = rng.integers(-2**62, 2**62, N, dtype=np.int64)
    cols = (a, b)
    res = {}
    with pytest.MonkeyPatch.context() as mp:
        for form, narrow, interp in (("kernel", NARROW, "1"),
                                     ("r32", C.R, "1"), ("xla", None, "0")):
            mp.setenv("PINOT_PALLAS_INTERPRET", interp)
            if narrow is not None:
                mp.setattr(C, "NARROW", narrow)
            C._compact_pallas.clear_cache()
            for name in FORM_CASES:
                mask, cap, _ = _case(name)
                res[form, name] = jax.device_get(_compact(mask, cols, cap))
        C._compact_pallas.clear_cache()
    return cols, res


@pytest.mark.parametrize("name", FORM_CASES)
def test_narrow_form_byte_identical_to_32_row_form(forms, name):
    cols, res = forms
    got, ref = res["kernel", name], res["r32", name]
    mask, _cap, steps = _case(name)
    # valid, columns, n_valid, matched, overflow: the same bytes
    for x, y in zip(jax.tree.leaves(got[:5]), jax.tree.leaves(ref[:5])):
        assert np.asarray(x).dtype == np.asarray(y).dtype
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert int(got[3]) == int(mask.sum())
    n_steps = N // (BLOCK * C.LANES)
    assert sum(int(s) for s in got[5]) == n_steps
    if steps is not None:
        assert tuple(int(s) for s in got[5]) == steps
    if int(got[4]):
        assert name == "overflow_narrow"
        return
    xla = res["xla", name]
    assert int(xla[4]) == 0 and int(xla[3]) == int(got[3])
    assert _multiset(got[0], got[1]) == _multiset(xla[0], xla[1]) == \
        sorted(zip(cols[0][mask].tolist(), cols[1][mask].tolist()))


def _loose_reference(mask, cols, cap, k_sub=C.K_MAX):
    """The loose layout the module docstring states, in numpy and apart
    from the kernel: per subtile of R rows, each lane's matched rows in
    row order from the running slot offset, the offset then advancing by
    the subtile's largest lane count; a grid step of k_sub subtiles whose
    staging block would pass ``cap`` is dropped and flags overflow.
    Returns (valid, columns, n_valid, matched, overflow)."""
    sub = mask.reshape(-1, C.R, C.LANES)
    csub = [c.reshape(-1, C.R, C.LANES) for c in cols]
    valid = np.zeros((cap, C.LANES), bool)
    outs = [np.zeros((cap, C.LANES), c.dtype) for c in cols]
    off = overflow = 0
    for s in range(sub.shape[0] // k_sub):
        if off + (k_sub + 1) * C.R > cap:
            overflow = 1
            continue
        for t in range(s * k_sub, (s + 1) * k_sub):
            for lane in np.flatnonzero(sub[t].any(axis=0)):
                rows = np.flatnonzero(sub[t][:, lane])
                valid[off:off + rows.size, lane] = True
                for o, c in zip(outs, csub):
                    o[off:off + rows.size, lane] = c[t][rows, lane]
            off += int(sub[t].sum(axis=0).max())
    return (valid.reshape(-1), [o.reshape(-1) for o in outs],
            off * C.LANES, int(mask.sum()), overflow)


@pytest.mark.parametrize("name", FORM_CASES)
def test_kernel_bytes_match_the_loose_layout_reference(forms, name):
    """Every slot of every output, not only the multiset: the kernel's
    chunked form writes the layout a plain per-subtile compaction
    writes."""
    cols, res = forms
    got = res["kernel", name]
    mask, cap, _ = _case(name)
    valid, outs, n_valid, matched, overflow = _loose_reference(
        mask, cols, cap)
    assert np.array_equal(np.asarray(got[0]), valid)
    for x, y in zip(got[1], outs):
        assert np.asarray(x).dtype == y.dtype
        assert np.array_equal(np.asarray(x), y)
    assert (int(got[2]), int(got[3]), int(got[4])) == (
        n_valid, matched, overflow)


def test_narrow_form_engages_by_selectivity(forms):
    """Sparse steps are narrow (one chunk), dense ones wide: the mixed
    layouts hold one of each."""
    _cols, res = forms
    steps = {name: tuple(int(s) for s in res["kernel", name][5])
             for name in FORM_CASES if name.startswith("p")}
    assert steps["p0.001_uniform"] == steps["p0.03_uniform"] == (2, 0)
    assert steps["p0.2_uniform"] == steps["p0.6_uniform"] == (0, 2)
    for p in (0.001, 0.03, 0.2, 0.6):
        assert steps[f"p{p}_mixed"] == (1, 1)
    assert tuple(int(s) for s in res["xla", "lane_9"][5]) == (0, 0)


def test_overflow_flag_under_narrow_form(forms):
    _cols, res = forms
    got = res["kernel", "overflow_narrow"]
    assert int(got[4]) == 1 and int(res["r32", "overflow_narrow"][4]) == 1
    assert tuple(int(s) for s in got[5]) == (2, 0)
    # the second step was skipped: only the first step's rows are kept
    mask, _cap, _ = _case("overflow_narrow")
    assert np.asarray(got[0]).sum() == mask[:N // 2].sum() < mask.sum()


@pytest.mark.parametrize("name,steps", [("lane_8", (2, 0)),
                                        ("lane_9", (1, 1))])
def test_compact_step_counters(interp, name, steps):
    """A compact group-by's kernel puts the steps of its compaction in
    its outputs, and the host counts them where it reads ``matched``."""
    from pinot_tpu.engine.executor import count_compact_steps
    from pinot_tpu.ops import kernels as K
    from pinot_tpu.ops.ir import AggSpec, Cmp, Col, KernelPlan
    from pinot_tpu.utils.metrics import global_metrics

    mask, _cap, _ = _case(name)
    plan = KernelPlan(
        pred=Cmp(Col(2), "<", 0),
        aggs=(AggSpec(kind="sum", value=Col(3), integral=True, bits=11,
                      signed=True),),
        group_keys=((0, 16), (1, 16)), strategy="compact")
    rng = np.random.default_rng(5)
    cols = (rng.integers(0, 16, N).astype(np.int32),
            rng.integers(0, 16, N).astype(np.int32),
            np.where(mask, 0, 1).astype(np.int32),
            rng.integers(-1000, 1000, N).astype(np.int32))
    fn = jax.jit(K.build_kernel(plan, N, C.full_slots_cap(N),
                                scatter=False))
    host = jax.device_get(fn(tuple(map(jnp.asarray, cols)), np.int32(N),
                             (jnp.asarray(np.int32(1)),)))
    assert int(host["matched"]) == int(mask.sum())
    assert (int(host["compact_steps_narrow"]),
            int(host["compact_steps_wide"])) == steps

    def counters():
        c = global_metrics.snapshot()["counters"]
        return tuple(c.get(k, 0) for k in K.COMPACT_STEP_OUTPUTS)

    before = counters()
    count_compact_steps(host)
    assert tuple(x - y for x, y in zip(counters(), before)) == steps
    assert not set(K.COMPACT_STEP_OUTPUTS) & set(host)


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
