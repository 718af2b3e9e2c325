"""LIF neuron model with partial membrane-potential (MP) update (paper C2).

The chip's neuron updater integrates synaptic current into the membrane
potential, applies leak, fires and resets.  The *partial update* optimization
only touches neurons that received at least one valid input spike in the
current timestep; untouched neurons pay no update energy (their leak is
folded into the next touched step on-chip via a timestamp delta — we model
the exact equivalent: lazy leak accumulation).

All functions are pure and `jax.jit`/`jax.lax.scan` friendly.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class LIFParams:
    """Neuron configuration (the chip's per-core register-table fields)."""

    threshold: float = 1.0
    leak: float = 0.9            # multiplicative leak alpha in [0, 1]
    reset: float = 0.0           # reset potential after a spike
    reset_mode: str = "hard"     # "hard" (V<-reset) or "soft" (V<-V-theta)
    partial_update: bool = True  # paper C2: skip neurons with no input
    surrogate_beta: float = 4.0  # steepness of the surrogate gradient


class LIFState(NamedTuple):
    """Carry for a population of LIF neurons."""

    v: jax.Array            # membrane potential, f32 (..., n)
    elapsed: jax.Array      # int32 timesteps since last touch (lazy leak)


def init_state(n: int, dtype=jnp.float32) -> LIFState:
    return LIFState(v=jnp.zeros((n,), dtype), elapsed=jnp.zeros((n,), jnp.int32))


def init_batch_state(batch: int, n: int, dtype=jnp.float32) -> LIFState:
    return LIFState(
        v=jnp.zeros((batch, n), dtype),
        elapsed=jnp.zeros((batch, n), jnp.int32),
    )


@jax.custom_vjp
def spike_fn(v_minus_theta: jax.Array, beta: float) -> jax.Array:
    """Heaviside spike with fast-sigmoid surrogate gradient."""
    return (v_minus_theta >= 0.0).astype(v_minus_theta.dtype)


def _spike_fwd(x, beta):
    return spike_fn(x, beta), (x, beta)


def _spike_bwd(res, g):
    x, beta = res
    # fast sigmoid surrogate: d/dx [x / (1 + beta|x|)] = 1 / (1 + beta|x|)^2
    surr = 1.0 / (1.0 + beta * jnp.abs(x)) ** 2
    return (g * surr, None)


spike_fn.defvjp(_spike_fwd, _spike_bwd)


def decay_table(leak: float, steps: int) -> tuple[float, ...]:
    """`leak**n` for n = 0..steps, each the correctly rounded f32 power
    (NumPy's float32 power).  XLA's `pow` is not: on the CPU it is an ulp
    off at n = 4, 17, 18, 19, ..., and a TPU's differs elsewhere, so a
    membrane near the threshold can fire on one device and not another.
    A run of `steps` timesteps never has more than `steps` pending."""
    n = np.arange(steps + 1, dtype=np.float32)
    return tuple(float(x) for x in np.float32(leak) ** n)


def lif_step(
    state: LIFState, current: jax.Array, p: LIFParams,
    touched: jax.Array | None = None, decays: tuple | None = None,
) -> tuple[LIFState, jax.Array, jax.Array]:
    """One LIF timestep.

    Returns (new_state, spikes, updated_mask).  `updated_mask` marks neurons
    whose MP was actually touched this step (the partial-update set); the
    energy model charges `e_upd` only for those.

    `touched` optionally supplies the partial-update mask explicitly —
    the chip's updater is driven by the ZSPE's spike-indexed work, i.e. a
    neuron is touched when any valid spike reaches one of its nonzero
    synapses.  The simulators pass that connectivity mask (see
    `touch_mask`); it is integer-exact, so it cannot flip when a float
    current cancels to exactly zero under a different summation order.
    Without it the mask falls back to ``current != 0`` (equivalent except
    on such exact-cancellation ties).

    With ``partial_update`` the semantics are *identical* to the dense
    update: untouched neurons accumulate pending leak steps in ``elapsed``
    and apply ``leak**elapsed`` lazily when next touched (or when read out).
    This mirrors the chip, where the updater stores a timestep stamp.
    `decays` (`decay_table`) gives `leak**pending` exactly, in place of
    XLA's `pow`.
    """
    has_input = (current != 0.0) if touched is None else touched
    if p.partial_update:
        pending = state.elapsed + 1
        leak_n = (p.leak ** pending.astype(state.v.dtype) if decays is None
                  else jnp.asarray(decays, state.v.dtype)[
                      jnp.minimum(pending, len(decays) - 1)])
        # Lazy leak: apply alpha**pending only for touched neurons.
        decay = jnp.where(has_input, leak_n, 1.0)
        v_int = state.v * decay + current
        # Untouched neurons keep raw v and bump `elapsed`.
        new_elapsed = jnp.where(has_input, 0, pending)
        # A neuron can only fire when touched (its readout happens on touch).
        v_eff = jnp.where(has_input, v_int, -jnp.inf)
        spikes = spike_fn(v_eff - p.threshold, p.surrogate_beta)
        updated = has_input
    else:
        v_int = state.v * p.leak + current
        spikes = spike_fn(v_int - p.threshold, p.surrogate_beta)
        new_elapsed = jnp.zeros_like(state.elapsed)
        updated = jnp.ones_like(has_input)

    if p.reset_mode == "hard":
        v_reset = jnp.where(spikes > 0, p.reset, jnp.where(updated, v_int, state.v))
    else:  # soft reset
        v_after = v_int - spikes * p.threshold
        v_reset = jnp.where(updated, v_after, state.v)

    return LIFState(v=v_reset, elapsed=new_elapsed), spikes, updated


def touch_mask(spikes: jax.Array, nonzero_w: jax.Array) -> jax.Array:
    """Connectivity-driven partial-update mask.

    `nonzero_w` is ``(w != 0)`` as float; the product counts the valid
    spikes reaching each post-neuron through nonzero synapses.  The
    counts are small integers, exact in f32 under any summation order —
    so the mask is bit-identical between the interpretive and the
    compiled (scan/vmap) execution engines.
    """
    return (spikes @ nonzero_w) > 0


def settle_state(state: LIFState, p: LIFParams) -> LIFState:
    """Flush pending lazy leak (used at readout / end of sample)."""
    decay = p.leak ** state.elapsed.astype(state.v.dtype)
    return LIFState(v=state.v * decay, elapsed=jnp.zeros_like(state.elapsed))


def dense_reference_step(
    state: LIFState, current: jax.Array, p: LIFParams
) -> tuple[LIFState, jax.Array]:
    """Traditional (baseline) scheme: full MP update every step.

    Used as the oracle to prove partial update is semantics-preserving and
    as the energy baseline (the paper's '2.69x' comparison point).
    """
    dense = dataclasses.replace(p, partial_update=False)
    new_state, spikes, _ = lif_step(state, current, dense)
    return new_state, spikes


@partial(jax.jit, static_argnames=("p",))
def run_timesteps(
    state: LIFState, currents: jax.Array, p: LIFParams
) -> tuple[LIFState, jax.Array, jax.Array]:
    """Scan `lif_step` over a (T, ..., n) current tensor.

    Returns (final_state, spikes (T, ..., n), updates_per_step (T,)).
    """

    def body(carry, cur):
        st, spk, upd = lif_step(carry, cur, p)
        return st, (spk, upd.sum())

    final, (spikes, upd_counts) = jax.lax.scan(body, state, currents)
    return final, spikes, upd_counts
