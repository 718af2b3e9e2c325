"""Compiler IR: the network description the mapping compiler consumes.

A `NetworkGraph` abstracts every supported frontend (dense SNN MLPs from
models/snn.py, conv SNNs from models/snn_conv.py, raw weight lists) into
the only facts the mapper needs: per-layer neuron counts, fan-in, the
expected spike traffic each layer emits per timestep, and which layers
feed their own spikes back to themselves (a recurrent self-edge, whose
spikes reach the layer one timestep later).  Spike rates can be
*measured* (by running the net on event data — see `measure_spike_rates`)
or *estimated* from the input stream's sparsity with a geometric
attenuation per layer, which is how real toolchains bootstrap placement
before profiling data exists.

`ChipSpec` is the hardware side: core count/capacity per level-1 domain,
how many domains the deployment may scale up to, and the router/energy
constants used to price routes.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import numpy as np

from repro.core import energy as E
from repro.core import noc as NOC
from repro.core.conv import ConvGeometry


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One mappable layer.  `index` 0 is the input population (never placed
    on a core); placed layers start at index 1.  A conv layer may carry
    its geometry (`conv`); its neurons then take input only from the
    band of the layer before that their receptive fields cover."""

    index: int
    n_neurons: int
    fan_in: int
    kind: str = "dense"          # "input" | "dense" | "conv"
    name: str = ""
    conv: ConvGeometry | None = None

    def __post_init__(self):
        if self.n_neurons <= 0:
            raise ValueError(f"layer {self.index}: n_neurons must be > 0")


@dataclasses.dataclass(frozen=True)
class NetworkGraph:
    """Layers in feed-forward order, each feeding the next.  A layer
    index in `recurrent` also has a self-edge: its spikes of step t-1
    reach every one of its own neurons at step t, at the layer's own
    spike rate (`self_edges`)."""

    layers: tuple[LayerSpec, ...]
    spike_rates: tuple[float, ...]   # spikes/timestep emitted by each layer
    recurrent: tuple[int, ...] = ()  # placed layers with a self-edge

    def __post_init__(self):
        if len(self.layers) < 2:
            raise ValueError("need an input layer and >= 1 placed layer")
        if len(self.spike_rates) != len(self.layers):
            raise ValueError("one spike rate per layer required")
        if self.layers[0].kind != "input":
            raise ValueError("layer 0 must be the input population")
        bad = [p for p in self.recurrent
               if not 1 <= int(p) < len(self.layers)]
        if bad:
            raise ValueError(f"recurrent layers {bad} are not placed layers "
                             f"(1..{len(self.layers) - 1})")

    def self_edges(self) -> tuple[tuple[int, float], ...]:
        """(layer index, spikes/timestep) of every recurrent self-edge."""
        return tuple((int(p), float(self.spike_rates[p]))
                     for p in sorted(set(self.recurrent)))

    @property
    def placed_layers(self) -> tuple[LayerSpec, ...]:
        return self.layers[1:]

    @property
    def total_neurons(self) -> int:
        return sum(l.n_neurons for l in self.placed_layers)

    def layer_sizes(self) -> tuple[int, ...]:
        return tuple(l.n_neurons for l in self.layers)

    def conv_geometries(self) -> dict[int, ConvGeometry]:
        """{layer index: geometry} of the layers that carry one."""
        return {l.index: l.conv for l in self.layers if l.conv is not None}


# Default traffic estimate: event inputs fire at ~10% (the NMNIST regime);
# each LIF stage attenuates traffic — deep layers both shrink and sparsify.
DEFAULT_INPUT_RATE = 0.10
DEFAULT_LAYER_FIRING = 0.08


def estimate_spike_rates(layer_sizes: Sequence[int],
                         input_rate: float = DEFAULT_INPUT_RATE,
                         layer_firing: float = DEFAULT_LAYER_FIRING
                         ) -> tuple[float, ...]:
    """Spikes/timestep per layer when no measurements are available."""
    rates = [input_rate * layer_sizes[0]]
    rates += [layer_firing * n for n in layer_sizes[1:]]
    return tuple(float(r) for r in rates)


def from_layer_sizes(layer_sizes: Sequence[int],
                     spike_rates: Sequence[float] | None = None,
                     kinds: Sequence[str] | None = None,
                     recurrent: Sequence[int] = (),
                     conv: dict | None = None) -> NetworkGraph:
    """A chain of `layer_sizes` (input first); layer indices in
    `recurrent` add a self-edge, which widens their fan-in by their own
    width; `conv` maps layer indices to the `ConvGeometry` of a conv
    layer, whose widths it must match."""
    sizes = [int(s) for s in layer_sizes]
    rec = tuple(sorted({int(p) for p in recurrent}))
    conv = dict(conv or {})
    for i, g in conv.items():
        if not 1 <= i < len(sizes) or (g.n_in, g.n_out) != (
                sizes[i - 1], sizes[i]):
            raise ValueError(f"conv layer {i} {g} does not fit layer sizes "
                             f"{sizes}")
        if i in rec:
            raise ValueError(f"conv layer {i} cannot be recurrent")
    kinds = list(kinds) if kinds is not None else (
        ["input"] + ["conv" if i in conv else "dense"
                     for i in range(1, len(sizes))])
    layers = tuple(
        LayerSpec(index=i, n_neurons=n,
                  fan_in=(0 if i == 0 else conv[i].fan_in if i in conv
                          else sizes[i - 1] + (n if i in rec else 0)),
                  kind=kinds[i], name=f"L{i}", conv=conv.get(i))
        for i, n in enumerate(sizes))
    rates = (tuple(float(r) for r in spike_rates) if spike_rates is not None
             else estimate_spike_rates(sizes))
    return NetworkGraph(layers=layers, spike_rates=rates, recurrent=rec)


def from_weights(weights: Sequence,
                 spike_rates: Sequence[float] | None = None) -> NetworkGraph:
    """Dense SNN described by per-layer weight matrices [(n_pre, n_post)]."""
    sizes = [int(weights[0].shape[0])] + [int(w.shape[1]) for w in weights]
    return from_layer_sizes(sizes, spike_rates)


def from_snn_config(cfg, spike_rates: Sequence[float] | None = None
                    ) -> NetworkGraph:
    """models/snn.py SNNConfig frontend."""
    return from_layer_sizes(cfg.layer_sizes, spike_rates)


def from_conv_config(cfg, spike_rates: Sequence[float] | None = None
                     ) -> NetworkGraph:
    """models/snn_conv.py ConvSNNConfig frontend.

    Conv layers map onto cores im2col-style: a stage with C_out channels at
    H x W spatial resolution is H*W*C_out neurons with k*k*C_in fan-in.
    Average-pool halves H and W between stages; the dense head follows.
    """
    h, w, c_in = cfg.in_shape
    sizes = [h * w * c_in]
    fan_ins = [0]
    kinds = ["input"]
    for c_out in cfg.channels:
        sizes.append(h * w * c_out)
        fan_ins.append(cfg.kernel * cfg.kernel * c_in)
        kinds.append("conv")
        h, w, c_in = h // 2, w // 2, c_out
    sizes.append(cfg.n_classes)
    fan_ins.append(h * w * c_in)
    kinds.append("dense")
    layers = tuple(
        LayerSpec(index=i, n_neurons=n, fan_in=f, kind=k, name=f"L{i}")
        for i, (n, f, k) in enumerate(zip(sizes, fan_ins, kinds)))
    rates = (tuple(float(r) for r in spike_rates) if spike_rates is not None
             else estimate_spike_rates(sizes))
    return NetworkGraph(layers=layers, spike_rates=rates)


def parse_conv_spec(spec: str, padding: str = "same"
                    ) -> tuple[int, list]:
    """SLAYER's network notation (Shrestha & Orchard, NeurIPS 2018), e.g.
    `34x34x2-12c5-2a-64c5-2a-10o` -> (input width, weight layers), each
    a `ConvGeometry` or a dense width:

    * `HxWxC`: the input map, first;
    * `Nck`: a k x k conv to N channels at stride 1, `padding` "same"
      (k // 2 on each side, k odd) or "valid";
    * `ka`: a spiking k x k sum-pool of stride k, no padding (a conv
      whose filter is diagonal over channels);
    * `No`: a dense layer of N neurons (flattening the map), last.
    """
    if padding not in ("same", "valid"):
        raise ValueError(f"padding must be 'same' or 'valid', got "
                         f"{padding!r}")
    head, *tokens = spec.split("-")
    try:
        h, w, c = (int(v) for v in head.split("x"))
    except ValueError:
        raise ValueError(f"{spec!r}: the input must be HxWxC") from None
    if not tokens:
        raise ValueError(f"{spec!r} has no layer")
    n_in = h * w * c
    layers: list = []
    shape: tuple[int, int, int] | None = (h, w, c)
    for i, tok in enumerate(tokens):
        if shape is None:
            raise ValueError(f"{spec!r}: {tok!r} follows the dense output")
        h, w, c = shape
        if "c" in tok:
            n, k = (int(v) for v in tok.split("c"))
            if padding == "same" and k % 2 == 0:
                raise ValueError(f"{tok!r}: same padding needs an odd kernel")
            g = ConvGeometry(h, w, c, n, k, 1,
                             k // 2 if padding == "same" else 0)
        elif tok.endswith("a"):
            k = int(tok[:-1])
            g = ConvGeometry(h, w, c, c, k, k, 0, pool=True)
        elif tok.endswith("o"):
            layers.append(int(tok[:-1]))
            shape = None
            continue
        else:
            raise ValueError(f"{spec!r}: cannot read layer {tok!r}")
        layers.append(g)
        shape = (g.out_height, g.out_width, g.c_out)
    return n_in, layers


def from_conv_spec(spec: str, padding: str = "same",
                   spike_rates: Sequence[float] | None = None
                   ) -> NetworkGraph:
    """A network in SLAYER's notation (`parse_conv_spec`), its conv and
    pool layers carrying their geometry."""
    n_in, layers = parse_conv_spec(spec, padding)
    sizes = [n_in] + [g.n_out if isinstance(g, ConvGeometry) else g
                      for g in layers]
    conv = {i + 1: g for i, g in enumerate(layers)
            if isinstance(g, ConvGeometry)}
    return from_layer_sizes(sizes, spike_rates, conv=conv)


def measure_spike_rates(weights: Sequence, spike_train,
                        lif=None) -> tuple[float, ...]:
    """Run a dense SNN on a real spike train (T, n_in) and measure the mean
    spikes/timestep each layer emits — the profile-guided traffic input to
    placement."""
    import jax.numpy as jnp

    from repro.core.neuron import LIFParams, init_state, lif_step

    lif = lif or LIFParams()
    spike_train = jnp.asarray(spike_train, jnp.float32)
    T = int(spike_train.shape[0])
    states = [init_state(int(w.shape[1])) for w in weights]
    totals = [float(jnp.sum(spike_train))] + [0.0] * len(weights)
    for t in range(T):
        spikes = spike_train[t]
        for li, w in enumerate(weights):
            st, out, _ = lif_step(states[li], spikes @ jnp.asarray(w), lif)
            states[li] = st
            totals[li + 1] += float(jnp.sum(out))
            spikes = out
    return tuple(tot / max(T, 1) for tot in totals)


# ---------------------------------------------------------------------------
# Hardware target
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """The mapping target: one or more 20-core fullerene domains."""

    n_cores: int = NOC.N_CORES                  # per level-1 domain
    neurons_per_core: int = E.NEURONS_PER_CORE
    max_domains: int = 1
    router: NOC.RouterParams = NOC.RouterParams()
    interconnect: E.InterconnectEnergyModel | None = None

    def __post_init__(self):
        if self.interconnect is None:
            # derive level-1 hop prices from the router so the placement
            # cost and the replayed NoC energy always agree
            object.__setattr__(
                self, "interconnect",
                E.InterconnectEnergyModel.from_router(self.router))

    def capacity(self, n_domains: int | None = None) -> int:
        d = self.max_domains if n_domains is None else n_domains
        return d * self.n_cores * self.neurons_per_core

    def domains_needed(self, n_groups: int) -> int:
        return max(1, math.ceil(n_groups / self.n_cores))

    def validate_network(self, net: NetworkGraph) -> None:
        need = net.total_neurons
        cap = self.capacity()
        if need > cap:
            raise ValueError(
                f"network needs {need} neurons but chip capacity is {cap} "
                f"({self.max_domains} domain(s) x {self.n_cores} cores x "
                f"{self.neurons_per_core} neurons/core)")
