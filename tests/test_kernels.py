"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles,
swept over shapes and dtypes (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

RNG = np.random.default_rng(0)


def _randn(shape, dtype):
    x = RNG.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


TOL = {jnp.float32: 3e-5, jnp.bfloat16: 4e-2}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,hkv,d,window", [
    (2, 256, 4, 2, 64, None),
    (1, 256, 4, 4, 64, 128),
    (2, 384, 6, 2, 64, None),
    (1, 512, 8, 1, 32, 256),
])
def test_flash_attention_sweep(b, s, h, hkv, d, window, dtype):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    q = _randn((b, s, h, d), dtype)
    k = _randn((b, s, hkv, d), dtype)
    v = _randn((b, s, hkv, d), dtype)
    o = flash_attention(q, k, v, causal=True, window=window, interpret=True)
    r = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_flash_attention_matches_model_layer_math():
    """Kernel semantics == the model's attention (same masking rules)."""
    from repro.kernels.flash_attention.ref import flash_attention_ref
    from repro.models.layers import attention_full
    b, s, h, hkv, d = 2, 128, 4, 2, 32
    q = _randn((b, s, h, d), jnp.float32)
    k = _randn((b, s, hkv, d), jnp.float32)
    v = _randn((b, s, hkv, d), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    o1 = attention_full(q, k, v, pos, pos, causal=True)
    o2 = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5,
                               rtol=2e-5)


# ---------------------------------------------------------------------------
# paged decode attention + gather
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hkv,g,d,npages,page,p", [
    (3, 2, 4, 64, 16, 8, 4),
    (2, 1, 8, 32, 8, 16, 3),
    (1, 4, 1, 128, 32, 8, 8),
])
def test_paged_decode_attention_sweep(b, hkv, g, d, npages, page, p, dtype):
    from repro.kernels.decode_attention.ops import paged_decode_attention
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    q = _randn((b, hkv, g, d), dtype)
    kp = _randn((npages, page, hkv, d), dtype)
    vp = _randn((npages, page, hkv, d), dtype)
    tbl = RNG.permutation(npages)[: b * p].reshape(b, p).astype(np.int32)
    tbl[0, -1] = -1  # a hole (non-resident block)
    lens = np.minimum(RNG.integers(1, p * page, b), p * page).astype(np.int32)
    o = paged_decode_attention(q, kp, vp, jnp.asarray(tbl),
                               jnp.asarray(lens), interpret=True)
    r = paged_decode_attention_ref(q, kp, vp, jnp.asarray(tbl),
                                   jnp.asarray(lens))
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_medic_gather(dtype):
    from repro.kernels.medic_gather.ops import medic_gather
    from repro.kernels.medic_gather.ref import medic_gather_ref
    pool = _randn((12, 8, 2, 32), dtype)
    tbl = jnp.asarray([[0, 5, -1], [3, -1, 11]], jnp.int32)
    o = medic_gather(pool, tbl, interpret=True)
    r = medic_gather_ref(pool, tbl)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(r))


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,w,bt,bw", [
    (2, 64, 256, 16, 128),
    (1, 128, 128, 32, 64),
    (3, 48, 384, 16, 128),
])
def test_rg_lru_sweep(b, s, w, bt, bw):
    from repro.kernels.rg_lru.ops import rg_lru
    from repro.kernels.rg_lru.ref import rg_lru_ref
    a = jnp.asarray(RNG.uniform(0.8, 0.999, (b, s, w)), jnp.float32)
    x = jnp.asarray(RNG.standard_normal((b, s, w)) * 0.1, jnp.float32)
    h0 = jnp.asarray(RNG.standard_normal((b, w)), jnp.float32)
    o = rg_lru(a, x, h0, bw=bw, bt=bt, interpret=True)
    r = rg_lru_ref(a, x, h0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-5,
                               rtol=1e-5)


def test_rg_lru_matches_model_scan():
    from repro.kernels.rg_lru.ref import rg_lru_ref
    from repro.models.recurrent import rglru_scan
    a = jnp.asarray(RNG.uniform(0.8, 0.999, (2, 32, 64)), jnp.float32)
    b = jnp.asarray(RNG.standard_normal((2, 32, 64)), jnp.float32)
    r1 = rg_lru_ref(a, b, jnp.zeros((2, 64)))
    r2 = rglru_scan(a, b)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), atol=1e-5,
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,dk,dv,chunk", [
    (2, 128, 2, 32, 64, 32),
    (1, 64, 4, 16, 32, 16),
    (2, 96, 1, 64, 64, 32),
])
def test_mlstm_kernel_sweep(b, s, h, dk, dv, chunk):
    from repro.kernels.mlstm.ops import mlstm
    from repro.kernels.mlstm.ref import mlstm_ref
    q = _randn((b, s, h, dk), jnp.float32)
    k = _randn((b, s, h, dk), jnp.float32)
    v = _randn((b, s, h, dv), jnp.float32)
    li = _randn((b, s, h), jnp.float32)
    lf = jnp.log(jax.nn.sigmoid(_randn((b, s, h), jnp.float32) + 2))
    o = mlstm(q, k, v, li, lf, chunk=chunk, interpret=True)
    r = mlstm_ref(q, k, v, li, lf)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=5e-4,
                               rtol=5e-3)


def test_mlstm_chunkwise_matches_recurrent():
    """Model chunkwise form == exact recurrent form (state carrying)."""
    from repro.models.xlstm import mlstm_chunkwise, mlstm_recurrent_ref
    b, s, h, dk, dv = 2, 128, 2, 16, 32
    q = _randn((b, s, h, dk), jnp.float32)
    k = _randn((b, s, h, dk), jnp.float32)
    v = _randn((b, s, h, dv), jnp.float32)
    li = _randn((b, s, h), jnp.float32)
    lf = jnp.log(jax.nn.sigmoid(_randn((b, s, h), jnp.float32) + 2))
    o1, st1 = mlstm_chunkwise(q, k, v, li, lf, chunk=32)
    o2, st2 = mlstm_recurrent_ref(q, k, v, li, lf)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=5e-4,
                               rtol=5e-3)
    np.testing.assert_allclose(np.asarray(st1[0]), np.asarray(st2[0]),
                               atol=5e-4, rtol=5e-3)


# ---------------------------------------------------------------------------
# wavefront segmented queue recovery
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as hyp_st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

_WS_KW = dict(banks=8, channels=4, l2_svc=4.0, l2_lat=20.0,
              occ_rowhit=4.0, occ_rowmiss=10.0)


def _wave_case(rng, n, dyadic=True, empty=False, banks=8, channels=4,
               warm_carry=True):
    """One fuzzed wave: sorted arrivals, random queue membership, random
    cross-wave carry (some queues never-touched: -inf anchors)."""
    step = 0.25 if dyadic else 0.7
    t_s = jnp.asarray(np.cumsum(rng.integers(0, 4, n)) * step, jnp.float32)
    bank = jnp.asarray(rng.integers(0, banks, n), jnp.int32)
    ch = jnp.asarray(rng.integers(0, channels, n), jnp.int32)
    row = jnp.asarray(rng.integers(0, 6, n), jnp.int32)
    if empty:
        valid = np.zeros(n, bool)
    else:
        valid = rng.random(n) < 0.9
    byp = (rng.random(n) < 0.2) & valid
    hit = (rng.random(n) < 0.4) & valid & ~byp
    use_l2 = jnp.asarray(valid & ~byp)
    go_dram = jnp.asarray(valid & (byp | ~hit))
    hp = jnp.asarray(rng.random(n) < 0.5)

    def qvec(q, lo, hi):
        return jnp.asarray(rng.uniform(lo, hi, q) * (4 if dyadic else 1),
                           jnp.float32)
    neg = jnp.asarray(np.where(rng.random(channels) < 0.3, -np.inf, 0.0),
                      jnp.float32)
    negb = jnp.asarray(np.where(rng.random(banks) < 0.3, -np.inf, 0.0),
                       jnp.float32)
    if not warm_carry:
        negb = jnp.full((banks,), -jnp.inf)
        neg = jnp.full((channels,), -jnp.inf)
    from repro.kernels.wavefront_scan.ref import QueueCarry
    carry = QueueCarry(
        bank_free=qvec(banks, 0, 30), bank_ts=qvec(banks, 0, 20) + negb,
        hp_free=qvec(channels, 0, 40), hp_ts=qvec(channels, 0, 20) + neg,
        hp_sa=qvec(channels, 0, 20) + neg,
        lp_free=qvec(channels, 0, 40), lp_ts=qvec(channels, 0, 20) + neg,
        lp_sa=qvec(channels, 0, 20) + neg,
        cur_row=jnp.asarray(rng.integers(-1, 6, channels), jnp.int32))
    return (t_s, bank, use_l2, ch, row, go_dram, jnp.asarray(byp), hp,
            carry)


def _recover(args, backend, exact=False, **shape):
    from repro.kernels.wavefront_scan.ops import wave_queue_recovery
    return wave_queue_recovery(*args, exact=exact, backend=backend,
                               **dict(_WS_KW, **shape))


def _assert_wave_equal(a, b, slots_exactly=True, go_dram=None):
    """Compare (t_head, t0, row_hit, carry) across backends. ``t0`` is
    compared only where the contract defines it (``go_dram`` slots)."""
    ta, t0a, rha, ca = a
    tb, t0b, rhb, cb = b
    np.testing.assert_array_equal(np.asarray(ta), np.asarray(tb))
    gd = np.asarray(go_dram) if go_dram is not None else \
        np.ones(np.asarray(t0a).shape, bool)
    np.testing.assert_array_equal(np.asarray(t0a)[gd], np.asarray(t0b)[gd])
    np.testing.assert_array_equal(np.asarray(rha), np.asarray(rhb))
    for f, va, vb in zip(ca._fields, ca, cb):
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb),
                                      err_msg=f"carry field {f}")


#: the benchmark cell's wave: 512 warps x 16 lanes over 12 L2 banks and 6
#: DRAM channels (bench/configs/medic_bfs_64k.json)
_CELL = dict(n=8192, banks=12, channels=6)


def _assert_fused_equal(rng, n, dyadic, warm=True, batch=1, exact=False,
                        banks=8, channels=4):
    """fused == ref on ``batch`` fuzzed waves; several waves run as one
    vmapped batch, as the engine runs its policy batch."""
    cases = [_wave_case(rng, n, dyadic=dyadic, banks=banks,
                        channels=channels, warm_carry=warm)
             for _ in range(batch)]
    kw = dict(banks=banks, channels=channels)
    if batch == 1:
        (args,) = cases
        _assert_wave_equal(_recover(args, "ref", exact, **kw),
                           _recover(args, "fused", exact, **kw),
                           go_dram=args[5])
        return
    stacked = jax.tree.map(lambda *x: jnp.stack(x), *cases)
    out = [jax.vmap(lambda *a, b=b: _recover(a, b, exact, **kw))(*stacked)
           for b in ("ref", "fused")]
    for i, args in enumerate(cases):
        ref, fused = (jax.tree.map(lambda x: x[i], o) for o in out)
        _assert_wave_equal(ref, fused, go_dram=args[5])


@pytest.mark.parametrize("dyadic", [True, False])
@pytest.mark.parametrize("shape", [
    *[pytest.param(dict(n=n), id=str(n)) for n in (1, 3, 17, 96, 256, 600)],
    pytest.param(dict(_CELL, warm=True), id="cell-warm"),
    pytest.param(dict(_CELL, warm=False), id="cell-cold"),
    pytest.param(dict(_CELL, warm=True, batch=4), id="cell-warm-vmap4"),
])
def test_wavefront_scan_fused_bitwise(shape, dyadic):
    """The fused queue-major path is bit-for-bit equal to the unfused
    oracle — including on non-dyadic floats (same elementwise ops on the
    same values; max exactly associative; integer-valued cumsums exact),
    which is what lets the engine default to it under 1e-6 goldens. Up
    to the benchmark cell's wave (8,192 slots, 12 banks, 6 channels),
    warm and cold, and vmapped over a 4-policy batch."""
    rng = np.random.default_rng(shape["n"] * 2 + dyadic)
    _assert_fused_equal(rng, dyadic=dyadic, **shape)


@pytest.mark.parametrize("exact,shape", [
    pytest.param(True, {}, id="True"), pytest.param(False, {}, id="False"),
    *[pytest.param(exact, dict(_CELL, warm=warm, dyadic=dyadic),
                   id=f"{exact}-cell-{'warm' if warm else 'cold'}-"
                      f"{'dyadic' if dyadic else 'nondyadic'}")
      for exact in (True, False) for warm in (True, False)
      for dyadic in (True, False)],
])
def test_wavefront_scan_fused_bitwise_exact_mode(exact, shape):
    """Both carry-floor modes (plain busy-until vs backlog interp), up
    to the benchmark cell's wave."""
    rng = np.random.default_rng(7)
    _assert_fused_equal(rng, exact=exact, **{"n": 64, "dyadic": False,
                                              **shape})


@pytest.mark.parametrize("n", [1, 5, 96, 256, 600, 1024])
def test_wavefront_scan_pallas_interpret(n):
    """The chunked Pallas kernel (interpret mode on CPU) is exactly
    equal on dyadic inputs — the chunk re-association of the prefix sums
    is exact on integer-valued occupancies — across single- and
    multi-chunk sizes (chunk = 256)."""
    rng = np.random.default_rng(n)
    args = _wave_case(rng, n, dyadic=True)
    _assert_wave_equal(_recover(args, "ref"),
                       _recover(args, "pallas"),
                       go_dram=args[5])


def test_wavefront_scan_pallas_nondyadic_close():
    """Non-dyadic inputs: chunk re-association may round differently, so
    the kernel is allclose, not bitwise."""
    rng = np.random.default_rng(11)
    args = _wave_case(rng, 600, dyadic=False)
    tr, t0r, rhr, cr = _recover(args, "ref")
    tp, t0p, rhp, cp = _recover(args, "pallas")
    gd = np.asarray(args[5])
    np.testing.assert_allclose(np.asarray(tr), np.asarray(tp), atol=1e-3)
    np.testing.assert_allclose(np.asarray(t0r)[gd], np.asarray(t0p)[gd],
                               atol=1e-3)
    np.testing.assert_array_equal(np.asarray(rhr), np.asarray(rhp))


@pytest.mark.parametrize("backend", ["fused", "pallas"])
def test_wavefront_scan_empty_wave(backend):
    """A wave with no valid slot is a no-op: the carry round-trips
    bitwise (this is what makes the engine's early-exit while_loop
    byte-identical to running the dead tail waves)."""
    rng = np.random.default_rng(13)
    args = _wave_case(rng, 48, dyadic=False, empty=True)
    ref = _recover(args, "ref")
    out = _recover(args, backend)
    _assert_wave_equal(ref, out, go_dram=args[5])
    for f, va, vb in zip(ref[3]._fields, args[8], out[3]):
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb),
                                      err_msg=f"carry field {f} changed "
                                              "on an empty wave")


@pytest.mark.parametrize("backend", ["fused", "pallas"])
def test_wavefront_scan_single_slot(backend):
    """n=1 waves (single-slot: one warp, one lane) across every request
    species: L2-only, DRAM hp, DRAM lp, bypass-direct."""
    from repro.kernels.wavefront_scan.ref import QueueCarry
    rng = np.random.default_rng(17)
    base = _wave_case(rng, 1, dyadic=False)
    for use, go, byp, hp in [(True, False, False, False),
                             (True, True, False, True),
                             (True, True, False, False),
                             (False, True, True, True)]:
        args = (base[0], base[1], jnp.asarray([use]), base[3], base[4],
                jnp.asarray([go]), jnp.asarray([byp]), jnp.asarray([hp]),
                base[8])
        _assert_wave_equal(_recover(args, "ref"), _recover(args, backend),
                           go_dram=args[5])


def test_wavefront_scan_cold_carry():
    """All-virgin queues (-inf anchors, as at t=0) don't poison the
    fused path's gathered floors."""
    rng = np.random.default_rng(23)
    args = _wave_case(rng, 96, dyadic=False, warm_carry=False)
    _assert_wave_equal(_recover(args, "ref"), _recover(args, "fused"),
                       go_dram=args[5])
    _assert_wave_equal(_recover(args, "ref"), _recover(args, "pallas"),
                       go_dram=args[5])


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(n=hyp_st.integers(1, 300), seed=hyp_st.integers(0, 2**31),
           dyadic=hyp_st.booleans(), empty=hyp_st.booleans())
    def test_wavefront_scan_fused_hypothesis(n, seed, dyadic, empty):
        """Fuzz mask patterns (incl. empty queues / single-slot waves):
        fused stays bitwise-equal to the oracle."""
        rng = np.random.default_rng(seed)
        args = _wave_case(rng, n, dyadic=dyadic, empty=empty)
        _assert_wave_equal(_recover(args, "ref"), _recover(args, "fused"),
                           go_dram=args[5])


# ---------------------------------------------------------------------------
# wavefront cache pass
# ---------------------------------------------------------------------------

def _cache_case(rng, n_warps, b, lanes, prm, pa, addr_hi=60, empty=False):
    """One fuzzed cache-pass wave over a warmed state. The warmed tags
    honor the engine invariant the fused backend relies on: non-(-1)
    tags are unique within a set (a line lives in at most one way —
    allocation only happens on miss)."""
    from repro.core.engine.state import init_state
    from repro.policy import ops as POL
    sets = prm.sets
    st = init_state(n_warps, prm)
    pool = np.argsort(rng.random((sets, 4 * prm.ways + addr_hi)),
                      axis=1)[:, :prm.ways]
    tags_np = np.where(rng.random((sets, prm.ways)) < 0.25, -1, pool)
    st = st._replace(
        tags=jnp.asarray(tags_np, jnp.int32),
        rrip=jnp.asarray(rng.integers(0, prm.rrip_max + 1,
                                      (sets, prm.ways)), jnp.int32),
        meta_type=jnp.asarray(rng.integers(0, 3, (sets, prm.ways)),
                              jnp.int32),
        eaf=jnp.asarray(rng.integers(0, 2, prm.eaf_bits), jnp.int32),
        eaf_ctr=jnp.asarray(rng.integers(0, prm.eaf_capacity), jnp.int32),
        pc_hits=jnp.asarray(rng.integers(0, 50, prm.pc_entries), jnp.int32),
        pc_acc=jnp.asarray(rng.integers(50, 100, prm.pc_entries),
                           jnp.int32),
        pc_req=jnp.asarray(rng.integers(0, 100, prm.pc_entries), jnp.int32))
    st = st._replace(clf=st.clf._replace(
        accesses=jnp.asarray(rng.integers(0, 64, n_warps), jnp.int32),
        hits=jnp.asarray(rng.integers(0, 32, n_warps), jnp.int32),
        sampled=jnp.asarray(rng.integers(0, 64, n_warps), jnp.int32)))
    w_sel = jnp.asarray(rng.choice(n_warps, b, replace=False), jnp.int32)
    clf_b0 = jax.tree.map(lambda a: a[w_sel], st.clf)
    tokens_b = POL.pcal_tokens(pa, n_warps)[w_sel]
    t0 = jnp.sort(jnp.asarray(rng.uniform(0, 50, b), jnp.float32))
    addr_lb = jnp.asarray(rng.integers(-1, addr_hi, (lanes, b)), jnp.int32)
    pc_b = jnp.asarray(rng.integers(0, 64, b), jnp.int32)
    owt_b = jnp.asarray(rng.integers(0, 3, b), jnp.int32)
    slot_ok = jnp.zeros(b, bool) if empty \
        else jnp.asarray(rng.random(b) < 0.9)
    if empty:
        addr_lb = jnp.full_like(addr_lb, -1)
    return st, (clf_b0, tokens_b, t0, addr_lb, pc_b, owt_b, slot_ok)


def _cache_run(st, args, prm, pa, backend):
    from repro.kernels.cache_pass.ops import wave_cache_pass
    return wave_cache_pass(st, *args, prm, pa, backend=backend)


def _cache_assert_equal(a, b):
    ra = jax.tree_util.tree_leaves_with_path(a)
    rb = jax.tree_util.tree_leaves_with_path(b)
    for (p, va), (_, vb) in zip(ra, rb):
        np.testing.assert_array_equal(
            np.asarray(va), np.asarray(vb),
            err_msg=f"leaf {jax.tree_util.keystr(p)}")


# (sets, wave width B, lanes, addr_hi): sets=1 collapses EVERY request
# into one set (maximal conflict chains); sets=2 makes every conflict a
# neighbor of the adjacent set's chain; B >= 128 engages the wide-wave
# dense-table construction, (1, 128, ...) with every request of the wide
# wave in one set; (512, 200, ...) is the sparse regime (aliasing only
# through the hash) and (384, 512, 16, ...) the bfs_2k cell's shape.
_CACHE_GRIDS = [(1, 8, 16, 40), (2, 8, 16, 40), (4, 12, 5, 30),
                (8, 160, 16, 60), (512, 200, 16, 4000), (1, 128, 4, 40),
                (384, 512, 16, 6000)]


@pytest.mark.parametrize("sets,b,lanes,addr_hi", _CACHE_GRIDS)
def test_cache_pass_fused_bitwise_aliasing_grids(sets, b, lanes, addr_hi):
    """Deterministic worst-case same-set aliasing: the fused sweep's
    last-write-wins conflict resolution must reproduce the sequential
    ref scan bitwise on state, classifier, and records."""
    from repro.core import baselines as BL
    from repro.core.engine.state import SimParams
    from repro.policy import to_arrays
    prm = SimParams(sets=sets)
    rng = np.random.default_rng(sets * 1000 + b)
    for pol in (BL.BASELINE, BL.MEDIC, BL.PCAL, BL.WBYP):
        pa = to_arrays(pol)
        st, args = _cache_case(rng, max(2 * b, b + 1), b, lanes, prm, pa,
                               addr_hi=addr_hi)
        _cache_assert_equal(_cache_run(st, args, prm, pa, "ref"),
                            _cache_run(st, args, prm, pa, "fused"))


def test_cache_pass_fused_bitwise_wide_extreme_tags():
    """A wide wave at 16 ways whose tags are all -1 or within 124 of
    2^31 - 1: the wide construction reads and writes the tag table as
    byte planes, and these tags set every bit of the top byte (the sign
    byte of -1 included) as well as the low ones."""
    from repro.core import baselines as BL
    from repro.core.engine.state import SimParams
    from repro.policy import to_arrays
    prm = SimParams(sets=8, ways=16)
    base = 2**31 - 1 - (4 * prm.ways + 60)
    rng = np.random.default_rng(31)
    for pol in (BL.MEDIC, BL.PCAL):
        pa = to_arrays(pol)
        st, args = _cache_case(rng, 320, 160, 8, prm, pa, addr_hi=60)
        st = st._replace(tags=jnp.where(st.tags >= 0, st.tags + base, -1))
        addr_lb = args[3]
        args = args[:3] + (jnp.where(addr_lb >= 0, addr_lb + base, -1),) \
            + args[4:]
        ref = _cache_run(st, args, prm, pa, "ref")
        assert int(jnp.max(ref[0].tags)) > 2**31 - 200
        _cache_assert_equal(ref, _cache_run(st, args, prm, pa, "fused"))


def test_cache_pass_fused_bitwise_empty_wave():
    """No valid slot: the pass must be a state no-op, bitwise, in both
    backends and in both fused constructions (what makes the engine's
    dead tail waves free)."""
    from repro.core import baselines as BL
    from repro.core.engine.state import SimParams
    from repro.policy import to_arrays
    prm = SimParams(sets=8)
    pa = to_arrays(BL.MEDIC)
    rng = np.random.default_rng(5)
    for b in (6, 160):                                # narrow, wide
        st, args = _cache_case(rng, 2 * b + 4, b, 8, prm, pa, empty=True)
        ref = _cache_run(st, args, prm, pa, "ref")
        _cache_assert_equal(ref, _cache_run(st, args, prm, pa, "fused"))
        for k in ("tags", "rrip", "meta_type", "eaf", "pc_req"):
            np.testing.assert_array_equal(np.asarray(getattr(ref[0], k)),
                                          np.asarray(getattr(st, k)))


def test_cache_pass_pallas_interpret_tiny():
    """The lane-chunked Pallas kernel (interpret mode on CPU) against
    both jnp backends — integer/select arithmetic throughout, so the
    claim is bitwise. ONE tiny case: interpret mode runs the lane grid
    in Python and compiles slowly."""
    from repro.core import baselines as BL
    from repro.core.engine.state import SimParams
    from repro.policy import to_arrays
    prm = SimParams(sets=8, ways=2, eaf_bits=32, eaf_capacity=8,
                    pc_entries=8)
    pa = to_arrays(BL.MEDIC)
    rng = np.random.default_rng(9)
    st, args = _cache_case(rng, 12, 3, 4, prm, pa, addr_hi=40)
    ref = _cache_run(st, args, prm, pa, "ref")
    _cache_assert_equal(ref, _cache_run(st, args, prm, pa, "pallas"))


if HAVE_HYPOTHESIS:
    @settings(max_examples=12, deadline=None)
    @given(seed=hyp_st.integers(0, 2**31),
           weights=hyp_st.tuples(*([hyp_st.integers(0, 2)] * 4)),
           boost=hyp_st.floats(2.0, 8.0),
           pool=hyp_st.sampled_from([8, 16, 32]))
    def test_cache_pass_fused_hypothesis_aliasing_traces(
            seed, weights, boost, pool):
        """Engine-level fuzz: TraceSpecs engineered so wave members pile
        into few cache sets (tiny set count, small shared pool, boosted
        shared fractions, pool-heavy mixes) must stay fused == ref
        bitwise on every reported metric. Shape is held fixed so every
        example reuses one compiled executable per backend."""
        from repro.core import baselines as BL
        from repro.core import tracegen as TG
        from repro.core.simulator import SimParams as SP, simulate_sweep
        # weight the pool-visiting archetypes; all_miss streams past the
        # pool so it keeps its default weight
        mix = np.asarray((0.0,) + tuple(float(w) for w in weights),
                         np.float64)
        mix[3] += 1.0                          # ensure a pool-heavy floor
        spec = TG.TraceSpec(
            name="alias", mix=tuple(mix / mix.sum()), intensity=0.9,
            n_warps=16, n_instr=10, lines_per_instr=8, n_pcs=6,
            shared_pool_lines=pool, shared_boost=boost)
        tr = TG.generate(spec, seed=seed)
        args = (jnp.asarray(tr["lines"]), jnp.asarray(tr["pcs"]),
                jnp.asarray(tr["compute_gap"]))
        prm = SP(sets=4)
        outs = {
            be: simulate_sweep(args[0], args[1], args[2],
                               (BL.MEDIC, BL.WBYP), n_warps=16, lanes=8,
                               prm=prm, engine="wavefront",
                               cache_backend=be)
            for be in ("ref", "fused")}
        for k in outs["ref"]:
            assert np.array_equal(np.asarray(outs["ref"][k]),
                                  np.asarray(outs["fused"][k]),
                                  equal_nan=True), k
