"""Kernel-level validation of kernels/fused_timestep.py and the ops.py
padding paths (non-block-multiple shapes, M=1, odd K), plus the
spike-word bitpacking round trip in core/zspe.py.

The fused kernel's oracle is the composite it replaces: dequant ->
`spikes @ w` -> `core.neuron.lif_step` with the connectivity touch mask,
jitted as one program (jit-for-jit the float programs are identical, so
comparisons are exact equality, not tolerance).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core.neuron import LIFParams, LIFState, lif_step, touch_mask
from repro.core.zspe import (SPIKE_WORD_BITS, empty_spike_words,
                             pack_spike_words, spike_word_count,
                             unpack_spike_words)
from repro.kernels import ops


def _case(rng, m, k, n, density=0.2, levels=16, zero_level=True):
    s = jnp.asarray(rng.random((m, k)) < density, jnp.float32)
    cb = np.sort(rng.normal(0, 0.3, levels)).astype(np.float32)
    if zero_level:
        cb[np.argmin(np.abs(cb))] = 0.0
    idx = jnp.asarray(rng.integers(0, levels, (k, n)), jnp.int8)
    cbw = jnp.asarray(np.broadcast_to(cb[:, None], (levels, n)).copy())
    w = jnp.asarray(cb)[idx.astype(jnp.int32)]
    v = jnp.asarray(rng.normal(0, 0.3, (m, n)), jnp.float32)
    el = jnp.asarray(rng.integers(0, 4, (m, n)), jnp.int32)
    return s, idx, cbw, w, v, el


def _oracle(s, w, v, el, threshold=1.0, leak=0.9):
    p = LIFParams(threshold=threshold, leak=leak)

    @jax.jit
    def run(s, v, el):
        cur = s @ w
        st, spk, upd = lif_step(
            LIFState(v, el), cur, p,
            touched=touch_mask(s, (w != 0).astype(jnp.float32)))
        return st.v, st.elapsed, spk, upd

    return run(s, v, el)


# ---------------------------------------------------------------------------
# spike-word bitpacking (core/zspe.py)
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(m=st.integers(1, 9), k=st.integers(1, 200),
       density=st.floats(0.0, 0.6))
def test_spike_word_round_trip(m, k, density):
    rng = np.random.default_rng(m * 211 + k)
    s = jnp.asarray(rng.random((m, k)) < density, jnp.float32)
    packed = pack_spike_words(s)
    assert packed.dtype == jnp.uint16
    assert packed.shape == (m, spike_word_count(k))
    np.testing.assert_array_equal(np.asarray(unpack_spike_words(packed, k)),
                                  np.asarray(s))
    # popcount survives packing (padding bits are zero)
    unpadded = np.asarray(s).sum(axis=1)
    np.testing.assert_array_equal(
        np.asarray(unpack_spike_words(packed)).sum(axis=1), unpadded)


def test_empty_spike_words_oracle():
    rng = np.random.default_rng(0)
    s_np = np.zeros((4, 70), np.float32)          # 5 words, last 6 bits pad
    s_np[0, 0] = 1.0                              # word 0 occupied
    s_np[1, 65] = 1.0                             # word 4 (padded) occupied
    s_np[3, :] = rng.random(70) < 0.5
    packed = pack_spike_words(jnp.asarray(s_np))
    got = np.asarray(empty_spike_words(packed))
    expected = []
    for r in range(4):
        row = np.zeros(80, np.float32)
        row[:70] = s_np[r]
        expected.append(sum(
            row[i * 16:(i + 1) * 16].sum() == 0 for i in range(5)))
    np.testing.assert_array_equal(got, expected)


# ---------------------------------------------------------------------------
# fused timestep kernel vs the composite oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(1, 17, 10), (8, 100, 37), (4, 256, 64),
                                   (3, 16, 1), (2, 1, 5)])
def test_fused_timestep_codebook_matches_oracle(m, k, n):
    """Untiled (engine configuration), including M=1, odd K, and K < one
    spike word: spikes and every integer output are exact; v matches the
    oracle exactly when K is word-aligned, and to ulp tolerance otherwise
    (zero-padding K can regroup a tiny gemv's reduction)."""
    rng = np.random.default_rng(m * 7 + k + n)
    s, idx, cbw, w, v, el = _case(rng, m, k, n)
    vo, eo, sp, tc, nnz, ew = ops.fused_timestep(s, idx, v, el, codebook=cbw)
    ov, oe, osp, oupd = _oracle(s, w, v, el)
    np.testing.assert_array_equal(np.asarray(sp), np.asarray(osp))
    if k % SPIKE_WORD_BITS == 0:
        np.testing.assert_array_equal(np.asarray(vo), np.asarray(ov))
    else:
        np.testing.assert_allclose(np.asarray(vo), np.asarray(ov),
                                   rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(eo), np.asarray(oe))
    np.testing.assert_array_equal(np.asarray(tc),
                                  np.asarray(oupd).astype(np.int32))
    np.testing.assert_array_equal(np.asarray(nnz),
                                  np.asarray(s).sum(axis=1).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(ew), np.asarray(empty_spike_words(pack_spike_words(s))))


@pytest.mark.parametrize("m,k,n", [(8, 100, 37), (1, 33, 12), (4, 96, 12)])
def test_fused_timestep_dense_matches_oracle(m, k, n):
    rng = np.random.default_rng(k)
    s, _, _, w, v, el = _case(rng, m, k, n)
    vo, eo, sp, tc, nnz, ew = ops.fused_timestep(s, w, v, el)
    ov, oe, osp, oupd = _oracle(s, w, v, el)
    np.testing.assert_array_equal(np.asarray(sp), np.asarray(osp))
    if k % SPIKE_WORD_BITS == 0:
        np.testing.assert_array_equal(np.asarray(vo), np.asarray(ov))
    else:
        np.testing.assert_allclose(np.asarray(vo), np.asarray(ov),
                                   rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(eo), np.asarray(oe))


def test_fused_timestep_tiled_blocks():
    """(bm, bn) tiling (the TPU configuration): padded/tiled output equals
    the oracle — spikes and integer counters exactly, currents to float
    tolerance (tiling regroups the reductions) — and the skip counters
    keep excluding padding (they count only the real ceil(K/16) words)."""
    rng = np.random.default_rng(3)
    m, k, n = 6, 75, 50                    # pads M 6->8, K 75->80, N 50->64
    s, idx, cbw, w, v, el = _case(rng, m, k, n, density=0.1)
    vo, eo, sp, tc, nnz, ew = ops.fused_timestep(
        s, idx, v, el, codebook=cbw, block=(4, 32))
    ov, oe, osp, oupd = _oracle(s, w, v, el)
    np.testing.assert_array_equal(np.asarray(sp), np.asarray(osp))
    np.testing.assert_array_equal(np.asarray(eo), np.asarray(oe))
    np.testing.assert_allclose(np.asarray(vo), np.asarray(ov),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(nnz),
                                  np.asarray(s).sum(axis=1).astype(np.int32))
    # padding rows/words contribute nothing to the skip telemetry
    assert ew.shape == (m,)
    np.testing.assert_array_equal(
        np.asarray(ew), np.asarray(empty_spike_words(pack_spike_words(s))))


def test_fused_timestep_zero_input_skip_branch():
    """All-empty spike words take the pl.when skip branch: no touches, no
    spikes, elapsed accrues, v untouched — and every word is counted."""
    rng = np.random.default_rng(1)
    _, idx, cbw, w, v, el = _case(rng, 4, 64, 16)
    s = jnp.zeros((4, 64), jnp.float32)
    vo, eo, sp, tc, nnz, ew = ops.fused_timestep(s, idx, v, el, codebook=cbw)
    np.testing.assert_array_equal(np.asarray(vo), np.asarray(v))
    np.testing.assert_array_equal(np.asarray(eo), np.asarray(el) + 1)
    assert float(jnp.abs(sp).max()) == 0.0
    assert int(jnp.abs(tc).max()) == 0
    np.testing.assert_array_equal(np.asarray(nnz), np.zeros(4, np.int32))
    np.testing.assert_array_equal(np.asarray(ew), np.full(4, 4, np.int32))


def test_fused_timestep_full_update_mode():
    """partial_update=False: the traditional dense update scheme."""
    rng = np.random.default_rng(9)
    s, idx, cbw, w, v, el = _case(rng, 5, 48, 20)
    vo, eo, sp, tc, *_ = ops.fused_timestep(s, idx, v, el, codebook=cbw,
                                            partial_update=False)
    p = LIFParams(partial_update=False)

    @jax.jit
    def oracle(s, v, el):
        st, spk, upd = lif_step(LIFState(v, el), s @ w, p)
        return st.v, st.elapsed, spk, upd

    ov, oe, osp, oupd = oracle(s, v, el)
    np.testing.assert_array_equal(np.asarray(sp), np.asarray(osp))
    np.testing.assert_array_equal(np.asarray(vo), np.asarray(ov))
    np.testing.assert_array_equal(np.asarray(eo), np.asarray(oe))
    assert int(tc.min()) == 1                 # every neuron updated


# ---------------------------------------------------------------------------
# ops.py padding paths for the pre-existing kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(1, 7, 5), (1, 129, 30), (3, 31, 1),
                                   (13, 257, 99)])
def test_zspe_spmm_padding_matches_ref(m, k, n):
    """Non-block-multiple (M, K, N), including M=1 and odd K: the padded
    kernel output equals the reference on the real region."""
    rng = np.random.default_rng(m * 13 + k + n)
    s = jnp.asarray(rng.random((m, k)) < 0.3, jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    out = ops.zspe_spmm(s, w)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ops.zspe_spmm_ref(s, w)),
                               rtol=1e-4, atol=1e-4 * k)


def test_zspe_skip_counters_exclude_padding_tiles():
    """Padding never *creates* skipped K-tiles: `_pick_block` guarantees
    the K pad is < one tile, so a tile counts as skipped iff its REAL
    spike region is empty.  Oracle: popcount over the real columns of
    each padded-grid K-tile."""
    rng = np.random.default_rng(4)
    m, k, n = 64, 200, 64                  # bk=128 -> K pads 200->256
    s_np = np.zeros((m, k), np.float32)
    s_np[5, 3] = 1.0                       # K-tile 0 occupied
    # K-tile 1 (cols 128..199 real, 200..255 pad) left empty -> skipped
    out, skipped = ops.zspe_spmm(jnp.asarray(s_np),
                                 jnp.asarray(rng.normal(size=(k, n)),
                                             jnp.float32),
                                 with_stats=True)
    bm, bk, bn = 64, 128, 64
    expected = np.zeros((m // bm, n // bn), np.int32)
    for i in range(m // bm):
        for kk in range(2):                # padded K grid: 2 tiles
            real = s_np[i * bm:(i + 1) * bm, kk * bk:min((kk + 1) * bk, k)]
            if np.count_nonzero(real) == 0:
                expected[i, :] += 1
    np.testing.assert_array_equal(np.asarray(skipped), expected)
    assert int(skipped.sum()) == expected.sum() > 0


@pytest.mark.parametrize("m,k,n", [(1, 9, 6), (5, 130, 3), (2, 64, 200)])
def test_codebook_matmul_padding_matches_ref(m, k, n):
    rng = np.random.default_rng(m + k + n)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 16, (k, n)), jnp.int8)
    cb = jnp.sort(jnp.asarray(rng.normal(size=16), jnp.float32))
    out = ops.codebook_matmul(x, idx, cb)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ops.codebook_matmul_ref(x, idx, cb)),
                               rtol=1e-4, atol=1e-3)


def test_lif_update_padding_matches_ref():
    rng = np.random.default_rng(6)
    b, n = 1, 37                            # pads to the (8, 128) tile
    v = jnp.asarray(rng.normal(size=(b, n)), jnp.float32)
    el = jnp.asarray(rng.integers(0, 5, (b, n)), jnp.int32)
    cur = jnp.asarray(np.where(rng.random((b, n)) < 0.4,
                               rng.normal(size=(b, n)), 0.0), jnp.float32)
    got = ops.lif_update(v, el, cur, threshold=1.0, leak=0.9)
    want = ops.lif_update_ref(v, el, cur, threshold=1.0, leak=0.9, reset=0.0)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=1e-5, atol=1e-6)


def test_interpret_default_cached():
    """The backend resolution is cached (one lookup per process)."""
    from repro.kernels.ops import interpret_default

    assert interpret_default() is interpret_default()
    info = interpret_default.cache_info()
    assert info.hits >= 1


# ---------------------------------------------------------------------------
# word layout (integer table words x a per-column step) vs select decode
# ---------------------------------------------------------------------------

def _exact_step_tables(rng, n, step_kind, zero_word):
    """(16, n) int words and (n,) f32 steps, two per-core tables across
    the columns: each level word x step is an exact f32 number."""
    if step_kind == "11bit":        # odd 11-bit mantissas, as the bench's
        steps = [np.float32(m * 2.0 ** -19) for m in (1531, 1093)]
    else:
        steps = [np.float32(2.0 ** -5), np.float32(2.0 ** -6)]
    tables = []
    for _ in steps:
        mags = np.sort(rng.choice(np.arange(1, 128), 8, replace=False))
        words = np.concatenate([-mags[::-1], mags])
        if zero_word:
            words[7] = 0
        tables.append(words)
    half = n // 2
    words = np.concatenate([np.broadcast_to(t[:, None], (16, w))
                            for t, w in zip(tables, (half, n - half))], 1)
    step = np.concatenate([np.full(half, steps[0], np.float32),
                           np.full(n - half, steps[1], np.float32)])
    return words, step


WORD_CASES = {
    # id: (step, streams, all_nonzero, partial_update, density, block)
    "11bit-aligned": ("11bit", (256,), True, True, 0.03, None),
    "pow2-unaligned-zero-words": ("pow2", (100,), False, True, 0.2, None),
    "11bit-recurrent-700+1024": ("11bit", (700, 1024), True, True, 0.03,
                                 None),
    "11bit-recurrent-full-update": ("11bit", (700, 1024), False, False,
                                    0.03, None),
    "11bit-tiled": ("11bit", (300,), False, True, 0.05, (4, 128)),
    "pow2-spike-free": ("pow2", (75,), True, True, 0.0, None),
}


@pytest.mark.parametrize("case", sorted(WORD_CASES))
def test_word_layout_equals_select_decode(case):
    """Exact-step tables: the word layout's `(spikes @ words) * step` and
    the select decode's f32 dot over the levels are the same current, so
    v', elapsed', spikes, the touched mask, nnz and the empty words are
    bit-equal.  A recurrent input is two word-padded spike streams (700
    -> 704 rows, then 1024), concatenated as the engine feeds them."""
    from repro.kernels.fused_timestep import (fused_timestep_codebook,
                                              fused_timestep_words)

    step_kind, streams, nonzero, partial, density, block = WORD_CASES[case]
    rng = np.random.default_rng(len(case))
    m, n = 8, 256
    packed = jnp.concatenate(
        [pack_spike_words(jnp.asarray(rng.random((m, s)) < density,
                                      jnp.float32)) for s in streams], -1)
    k = packed.shape[1] * SPIKE_WORD_BITS
    table, step = _exact_step_tables(rng, n, step_kind, zero_word=not nonzero)
    idx = rng.integers(0, 16, (k, n))
    words = np.take_along_axis(table, idx, axis=0)
    assert (words != 0).all() == nonzero
    levels = table.astype(np.float32) * step
    assert np.array_equal(table * step.astype(np.float64), levels)
    v = jnp.asarray(rng.normal(0, 0.3, (m, n)), jnp.float32)
    el = jnp.asarray(rng.integers(0, 4, (m, n)), jnp.int32)
    kw = dict(threshold=0.25, leak=0.9, partial_update=partial,
              all_nonzero=nonzero, block=block)
    want = fused_timestep_codebook(packed, jnp.asarray(idx, jnp.int8),
                                   jnp.asarray(levels), v, el, **kw)
    got = fused_timestep_words(packed, jnp.asarray(words, jnp.int8),
                               jnp.asarray(step[None, :]), v, el, **kw)
    for name, g, w in zip(("v", "elapsed", "spikes", "touched", "nnz",
                           "empty_words"), got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    fired = int(np.asarray(got[2]).sum())
    assert (fired > 0) == (density > 0), fired
