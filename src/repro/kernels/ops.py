"""Public jit'd entry points for the Pallas kernels.

Handle padding to block multiples, interpret-mode selection (interpret
exactly when the backend is not a TPU), and custom VJPs where the kernels
appear in training graphs.
"""
from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import codebook_matmul as _cbm
from repro.kernels import fused_timestep as _fused
from repro.kernels import lif_update as _lif
from repro.kernels import zspe_spmm as _zspe
from repro.kernels import ref as _ref


@functools.lru_cache(maxsize=1)
def interpret_default() -> bool:
    """Whether Pallas kernels run in interpret mode: exactly when the
    backend is not a TPU, so a chip always runs the compiled Mosaic
    kernels.  Resolved once per process (cached): the backend cannot
    change under a running program, and asking on every kernel dispatch
    showed up in the fused-engine hot path.
    """
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, mults: tuple[int, ...], value=0) -> jax.Array:
    pads = []
    for dim, m in zip(x.shape, mults):
        rem = (-dim) % m
        pads.append((0, rem))
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads, constant_values=value)


def _pick_block(m: int, k: int, n: int) -> tuple[int, int, int]:
    """MXU-aligned blocks, shrunk for small problems (tests / smoke nets)."""
    def pick(d, pref):
        for c in (pref, 256, 128, 64, 32, 16, 8):
            if c <= pref and d >= c:
                return c
        return 8
    return (pick(m, 128), pick(k, 128), pick(n, 128))


# ---------------------------------------------------------------------------
# codebook matmul
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(3,))
def codebook_matmul(x: jax.Array, idx: jax.Array, codebook: jax.Array,
                    interpret: bool | None = None) -> jax.Array:
    """x (..., K) @ codebook[idx (K, N)] with arbitrary shapes (padded)."""
    return _codebook_matmul_fwd_impl(x, idx, codebook, interpret)


def _codebook_matmul_fwd_impl(x, idx, codebook, interpret):
    interp = interpret_default() if interpret is None else interpret
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = idx.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    bm, bk, bn = _pick_block(m, k, n)
    xp = _pad_to(x2, (bm, bk))
    ip = _pad_to(idx, (bk, bn))
    out = _cbm.codebook_matmul(xp, ip, codebook.astype(jnp.float32),
                               block=(bm, bk, bn), interpret=interp)
    return out[:m, :n].reshape(*lead, n)


def _cbm_fwd(x, idx, codebook, interpret):
    return _codebook_matmul_fwd_impl(x, idx, codebook, interpret), (x, idx, codebook)


def _cbm_bwd(interpret, res, g):
    x, idx, codebook = res
    w = _dequant(idx, codebook)
    gx = jnp.einsum("...n,kn->...k", g, w).astype(x.dtype)
    # codebook grad: dL/dcb[l] = sum over positions with idx==l of (x^T g)
    xtg = jnp.einsum("...k,...n->kn", x.astype(jnp.float32), g.astype(jnp.float32))
    one_hot = jax.nn.one_hot(idx.astype(jnp.int32), codebook.shape[0],
                             dtype=jnp.float32)
    gcb = jnp.einsum("kn,knl->l", xtg, one_hot).astype(codebook.dtype)
    return gx, None, gcb


codebook_matmul.defvjp(_cbm_fwd, _cbm_bwd)


def _dequant(idx: jax.Array, codebook: jax.Array) -> jax.Array:
    return codebook[idx.astype(jnp.int32)]


# ---------------------------------------------------------------------------
# zero-skip spike matmul
# ---------------------------------------------------------------------------

def zspe_spmm(spikes: jax.Array, weights: jax.Array,
              interpret: bool | None = None,
              with_stats: bool = False):
    """spikes (..., K) {0,1} x weights (K, N).

    with_stats=True additionally returns the skipped-tile counters used to
    drive the energy model with measured skip rates.
    """
    interp = interpret_default() if interpret is None else interpret
    lead = spikes.shape[:-1]
    k = spikes.shape[-1]
    n = weights.shape[-1]
    s2 = spikes.reshape(-1, k)
    m = s2.shape[0]
    bm, bk, bn = _pick_block(m, k, n)
    sp = _pad_to(s2, (bm, bk))
    wp = _pad_to(weights, (bk, bn))
    out, skipped = _zspe.zspe_spmm(sp, wp, block=(bm, bk, bn), interpret=interp)
    out = out[:m, :n].reshape(*lead, n)
    if with_stats:
        return out, skipped
    return out


# ---------------------------------------------------------------------------
# fused LIF update
# ---------------------------------------------------------------------------

def lif_update(v, elapsed, current, *, threshold=1.0, leak=0.9, reset=0.0,
               interpret: bool | None = None):
    """(..., N) fused partial-update LIF step via the Pallas kernel."""
    interp = interpret_default() if interpret is None else interpret
    lead = v.shape[:-1]
    n = v.shape[-1]
    v2 = v.reshape(-1, n)
    e2 = elapsed.reshape(-1, n)
    c2 = current.reshape(-1, n)
    b = v2.shape[0]
    bb = 8 if b >= 8 else b
    bn = 128 if n >= 128 else n
    vp, ep, cp = (_pad_to(a, (bb, bn)) for a in (v2, e2, c2))
    vo, eo, sp, upd = _lif.lif_update(
        vp, ep, cp, threshold=threshold, leak=leak, reset=reset,
        block=(bb, bn), interpret=interp)
    crop = lambda a: a[:b, :n].reshape(*lead, n)
    return crop(vo), crop(eo), crop(sp), crop(upd)


# ---------------------------------------------------------------------------
# fused ZSPE -> dequant -> LIF timestep
# ---------------------------------------------------------------------------

def fused_timestep(spikes, weights, v, elapsed, *, codebook=None,
                   threshold=1.0, leak=0.9, reset=0.0,
                   partial_update: bool = True,
                   block: tuple[int, int] | None = None,
                   interpret: bool | None = None):
    """One fused layer-timestep with arbitrary (M, K, N) shapes.

    `spikes` is (M, K) {0,1} f32 — packed to uint16 words here (the
    engine keeps trains packed across the whole scan and calls the raw
    kernel directly).  `weights` is either a dense (K, N) f32 matrix or,
    with `codebook` given as an (n_levels, N) per-column level table, a
    (K, N) int8 index matrix.  Padding (K to the 16-spike word, M/N to
    `block` multiples) is applied and cropped here; padded spike bits are
    zero so counters and currents are unaffected, and padded columns are
    dropped before the caller sees them.

    Returns (v', elapsed', spikes_out, touched, nnz_rows, empty_words)
    with `empty_words` counting only the ceil(K/16) real spike words.
    """
    from repro.core.zspe import pack_spike_words, spike_word_count

    interp = interpret_default() if interpret is None else interpret
    m, k = spikes.shape
    n = v.shape[-1]
    kw = spike_word_count(k)
    packed = pack_spike_words(jnp.asarray(spikes, jnp.float32))
    kp = kw * _fused.SPIKE_WORD_BITS

    bm, bn = (m, n) if block is None else block
    packed = _pad_to(packed, (bm, kw))
    vp = _pad_to(v, (bm, bn))
    ep = _pad_to(elapsed, (bm, bn))
    if codebook is not None:
        w0 = _pad_to(jnp.asarray(weights, jnp.int8), (kp, bn))
        cbw = _pad_to(jnp.asarray(codebook, jnp.float32), (1, bn))
        outs = _fused.fused_timestep_codebook(
            packed, w0, cbw, vp, ep, threshold=threshold, leak=leak,
            reset=reset, partial_update=partial_update, gather=interp,
            block=(bm, bn), interpret=interp)
    else:
        w0 = _pad_to(jnp.asarray(weights, jnp.float32), (kp, bn))
        outs = _fused.fused_timestep_dense(
            packed, w0, vp, ep, threshold=threshold, leak=leak,
            reset=reset, partial_update=partial_update,
            block=(bm, bn), interpret=interp)
    vo, eo, sp, tc, nnz, ew = outs
    crop = lambda a: a[:m, :n]
    return (crop(vo), crop(eo), crop(sp), crop(tc), nnz[:m, 0], ew[:m, 0])


# Re-export oracles for convenience
codebook_matmul_ref = _ref.codebook_matmul_ref
zspe_spmm_ref = _ref.zspe_spmm_ref
lif_update_ref = _ref.lif_update_ref
