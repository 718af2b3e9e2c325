"""SHD-shaped spike trains: spoken digits seen through a cochlea model.

The Spiking Heidelberg Digits (Cramer et al., arXiv:1910.07407) feed 700
channels of a cochlea model, over about 1 s, to a 20-class classifier
(the digits 0-9 in English and German).  Here each class has 2 or 3
frequency bands, like formants, that drift across the channels while the
word lasts; each band fires in Poisson fashion (at most one spike per
channel and time bin) around its centre, and every channel fires
sparsely at a background rate outside the word.  A sample jitters its
class's band positions, its speaking rate and its onset.  Nothing is
read from the real data set; input density is about 4%.

Spec keys: `channels`, `n_classes`, `band_sigma` (channels),
`band_rate` (spike probability per bin at a band's centre),
`background_rate` (per channel and bin).
"""
from __future__ import annotations

import numpy as np


def _class_bands(label: int, channels: int) -> np.ndarray:
    """(n_bands, 3): start channel, drift over the word (channels), and
    relative strength of each band of one class; fixed per class."""
    rng = np.random.default_rng(np.random.SeedSequence([0x5D, label]))
    n_bands = int(rng.integers(2, 4))
    starts = np.sort(rng.uniform(0.08, 0.85, n_bands)) * channels
    drift = rng.uniform(-0.15, 0.15, n_bands) * channels
    strength = rng.uniform(0.7, 1.0, n_bands)
    return np.stack([starts, drift, strength], axis=-1)


def _sample(rng: np.random.Generator, label: int, spec: dict,
            timesteps: int) -> np.ndarray:
    channels = int(spec["channels"])
    sigma = float(spec["band_sigma"])
    bands = _class_bands(label, channels)
    # speaking rate, onset and band positions vary per utterance
    length = timesteps * rng.uniform(0.55, 0.85)
    onset = rng.uniform(0.0, timesteps - length)
    shift = rng.normal(0.0, 0.02 * channels, len(bands))
    t = np.arange(timesteps, dtype=np.float64)[:, None]
    phase = (t - onset) / length                       # 0..1 inside the word
    inside = (phase >= 0.0) & (phase <= 1.0)
    ch = np.arange(channels, dtype=np.float64)[None, :]
    rate = np.zeros((timesteps, channels))
    for (start, drift, strength), dx in zip(bands, shift):
        centre = start + dx + drift * np.clip(phase, 0.0, 1.0)
        profile = np.exp(-0.5 * ((ch - centre) / sigma) ** 2)
        rate = np.maximum(rate, strength * profile * inside)
    p = np.clip(float(spec["band_rate"]) * rate, 0.0, 1.0)
    p = np.maximum(p, float(spec["background_rate"]))
    return rng.random((timesteps, channels)) < p


def make(spec: dict, n: int, timesteps: int, seed: int) -> np.ndarray:
    """`n` trains (n, T, channels) f32 in {0, 1}, a pure function of
    `seed`."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xC0C]))
    labels = rng.integers(0, int(spec["n_classes"]), n)
    return np.stack([_sample(rng, int(l), spec, timesteps)
                     for l in labels]).astype(np.float32)
