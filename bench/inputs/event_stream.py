"""NMNIST-like event-camera spike trains (copied from the program's
`data.synthetic.EventStream`, so that no later change to the program can
change what the benchmark feeds it).

A gaussian blob moves across a 34x34 sensor along a class-specific
direction; ON events fire at its leading edge and OFF events at its
trailing edge, as a DVS sees motion.  Input density is about 2%.
"""
from __future__ import annotations

import numpy as np


def _sample(rng: np.random.Generator, label: int, spec: dict,
            timesteps: int) -> np.ndarray:
    h, w, n_classes = int(spec["height"]), int(spec["width"]), \
        int(spec["n_classes"])
    t = np.arange(timesteps)[:, None, None]
    ys, xs = np.mgrid[0:h, 0:w]
    angle = 2 * np.pi * label / n_classes
    cy = h / 2 + (t - timesteps / 2) * 0.8 * np.sin(angle)
    cx = w / 2 + (t - timesteps / 2) * 0.8 * np.cos(angle)
    d2 = (ys - cy) ** 2 + (xs - cx) ** 2
    intensity = np.exp(-d2 / (2 * 2.5 ** 2))
    vel = intensity - np.roll(intensity, 1, axis=0)
    p_on = np.clip(vel * 4.0, 0, 0.9)
    p_off = np.clip(-vel * 4.0, 0, 0.9)
    on = rng.random(p_on.shape) < p_on
    off = rng.random(p_off.shape) < p_off
    return np.stack([on, off], axis=-1).reshape(timesteps, -1)


def make(spec: dict, n: int, timesteps: int, seed: int) -> np.ndarray:
    """`n` trains (n, T, H*W*2) f32 in {0, 1}, a pure function of `seed`."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xE5]))
    labels = rng.integers(0, int(spec["n_classes"]), n)
    return np.stack([_sample(rng, int(l), spec, timesteps)
                     for l in labels]).astype(np.float32)
