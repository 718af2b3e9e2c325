"""SoC-level model (paper C5 + Fig. 7): 20 neuromorphic cores + fullerene
NoC + RISC-V control plane, with network->core mapping, a functional
simulator and full energy/power/cycle accounting.

This is the "chip in software": an SNN (from models/snn.py) is *mapped*
onto cores (each core holds <= 8192 neurons and one shared weight codebook
-- paper C3), spikes travel between cores over the fullerene NoC (C4), the
ZSPE/SPE cycle model prices each core-timestep (C1/C2), and the RISC-V
duty-cycle model prices the control plane.  Numbers in Table I /
Figs. 3,5,6 are reproduced by the benchmarks from this simulator plus the
calibrated models in core/energy.py.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import energy as E
from repro.core import noc as NOC
from repro.core.quant import CodebookConfig
from repro.core import zspe as Z
from repro.core.zspe import CoreGeometry, CycleModel


@dataclasses.dataclass(frozen=True)
class RegisterTable:
    """Per-core configuration registers (Fig. 1).

    `codebook_words` holds the core's shared weight table exactly as the
    chip stores it: N signed W-bit integers; `codebook_scale` is the
    fixed-point step.  `codebook()` reconstructs the float table the SPEs
    dequantize against — bit-exact against the `QuantizedTensor` the
    compiler lowered (see quant.codebook_to_words / words_to_codebook).
    """

    core_id: int
    enabled: bool = True
    threshold: float = 1.0
    leak: float = 0.9
    reset: float = 0.0
    weight_levels: int = 16       # N in {4,8,16}
    weight_bits: int = 8          # W in {4,8,16}
    codebook_words: tuple = ()    # N signed W-bit ints ((), if unprogrammed)
    codebook_scale: float = 1.0

    def __post_init__(self):
        if self.codebook_words:
            if len(self.codebook_words) != self.weight_levels:
                raise ValueError(
                    f"core {self.core_id}: {len(self.codebook_words)} codebook "
                    f"words for N={self.weight_levels}")
            lim = 2 ** (self.weight_bits - 1)
            bad = [w for w in self.codebook_words
                   if not (-lim <= int(w) <= lim - 1)]
            if bad:
                raise ValueError(
                    f"core {self.core_id}: codebook words {bad} exceed signed "
                    f"{self.weight_bits}-bit range")

    def codebook(self) -> np.ndarray:
        """The (N,) f32 weight table the SPEs read (words * scale)."""
        return (np.asarray(self.codebook_words, np.float32)
                * np.float32(self.codebook_scale))


def register_table_bytes(table: RegisterTable) -> int:
    """Configuration payload the host DMAs to program one core.

    Codebook: N words of W bits each (packed).  Neuron registers:
    threshold/leak/reset plus the codebook scale, one 32-bit word each,
    plus one 32-bit control word (enable bit, N/W fields, core id) — the
    Fig. 1 register file as the host interface sees it.
    """
    codebook_bits = table.weight_levels * table.weight_bits
    neuron_regs_bytes = 4 * 4          # threshold, leak, reset, scale
    control_bytes = 4
    return (codebook_bits + 7) // 8 + neuron_regs_bytes + control_bytes


@dataclasses.dataclass(frozen=True)
class HostDmaModel:
    """Host↔chip DMA interface model (SpikeHard-style packetized DMA).

    SpikeHard's host stack moves spikes and configuration over a
    descriptor-driven AXI DMA: the driver sets up a transfer (descriptor
    write + doorbell), then the engine streams fixed-size word bursts,
    each burst carrying a small packet header.  We keep that shape —
    per-transfer setup cost plus per-word streaming cost with packet
    header overhead — and price it in the chip's units (pJ, cycles at
    `freq_hz` of the consumer).  The per-word energy is an off-chip-I/O
    estimate in the same spirit as `energy.LEVEL2_HOP_PJ` (an off-die
    word movement costs roughly an order of magnitude more than on-die),
    not a paper anchor.

    Three transfer kinds the serve tier prices:

    * **spike upload** — the input event train, bitpacked 16 spikes per
      chip word exactly as the NoC/fused engine carry them
      (`core.zspe.pack_spike_words`), two chip words per 32-bit DMA word;
    * **table load** — reconfiguration: the register tables of a model
      being made resident (`register_table_bytes` each) — the
      NPARAM.INIT path, and the runtime model-swap cost of multi-tenant
      serving;
    * **output read** — the OBUF.READ path, one 32-bit count per output
      neuron.
    """

    word_bits: int = 32            # DMA/AXI word
    words_per_packet: int = 64     # burst length between headers
    header_words: int = 1          # per-packet header (dst/len/kind)
    setup_cycles: float = 120.0    # descriptor write + doorbell, per transfer
    cycles_per_word: float = 1.0   # streaming rate, words per chip cycle
    pj_per_word: float = 3.2       # off-chip word movement (estimate)

    def packets(self, n_words: int) -> int:
        return -(-int(n_words) // self.words_per_packet) if n_words else 0

    def transfer(self, n_words: int) -> tuple[float, float]:
        """(energy_pj, cycles) for one packetized transfer of n_words."""
        n_words = int(n_words)
        if n_words <= 0:
            return 0.0, 0.0
        total = n_words + self.packets(n_words) * self.header_words
        return (total * self.pj_per_word,
                self.setup_cycles + total * self.cycles_per_word)

    def spike_upload(self, timesteps: int, n_in: int) -> tuple[float, float]:
        """Upload one (T, n_in) binary event train, bitpacked 16
        spikes/chip-word (the chip's native spike-word layout)."""
        chip_words_per_step = -(-int(n_in) // 16)
        dma_words_per_step = -(-chip_words_per_step
                               // (self.word_bits // 16))
        return self.transfer(int(timesteps) * dma_words_per_step)

    def table_load(self, tables: Sequence[RegisterTable]
                   ) -> tuple[float, float]:
        """Reconfiguration DMA: stream every table's register payload."""
        n_bytes = sum(register_table_bytes(t) for t in tables)
        return self.transfer(-(-n_bytes // (self.word_bits // 8)))

    def output_read(self, n_out: int) -> tuple[float, float]:
        """Read back one 32-bit spike count per output neuron (OBUF)."""
        return self.transfer(int(n_out))


@dataclasses.dataclass(frozen=True)
class CoreAssignment:
    """A slice of one SNN layer placed on one physical core."""

    core_id: int                  # NoC node id (12..31)
    layer: int
    neuron_lo: int
    neuron_hi: int

    @property
    def n_neurons(self) -> int:
        return self.neuron_hi - self.neuron_lo


@dataclasses.dataclass
class Mapping:
    assignments: list[CoreAssignment]
    layer_sizes: list[int]

    def cores_of_layer(self, layer: int) -> list[CoreAssignment]:
        return [a for a in self.assignments if a.layer == layer]

    def active_core_ids(self) -> list[int]:
        return sorted({a.core_id for a in self.assignments})


def validate_capacity(layer_sizes: Sequence[int],
                      neurons_per_core: int = E.NEURONS_PER_CORE,
                      n_cores: int = NOC.N_CORES) -> None:
    """Reject networks that cannot fit the chip before any placement runs."""
    need = sum(int(s) for s in layer_sizes[1:])
    cap = n_cores * neurons_per_core
    if need > cap:
        raise ValueError(
            f"network needs {need} neurons but chip capacity is {cap} "
            f"({n_cores} cores x {neurons_per_core} neurons/core); "
            f"layer sizes {tuple(layer_sizes)} — use the compiler's "
            f"multi-domain scale-up (repro.compiler.ChipSpec(max_domains=N)) "
            f"for larger networks")


def map_network(layer_sizes: Sequence[int],
                neurons_per_core: int = E.NEURONS_PER_CORE,
                strategy: str = "greedy", seed: int = 0,
                recurrent: Sequence[int] = ()) -> Mapping:
    """Place an SNN onto the 20 cores.

    strategy "greedy" is the legacy contiguous layout (layers onto cores in
    id order, traffic-blind, no spreading).  Any other value is forwarded
    to the mapping compiler (repro.compiler.compile_network), e.g.
    "anneal" — traffic-aware placement with simulated-annealing refinement,
    which also weighs the self-edges of the layer indices in `recurrent`.

    Layer 0 is the input population (not placed).  Raises ValueError when
    the network exceeds chip capacity.
    """
    validate_capacity(layer_sizes, neurons_per_core)
    if strategy != "greedy":
        from repro import compiler as CC

        spec = CC.ChipSpec(neurons_per_core=neurons_per_core)
        compiled = CC.compile_network(
            CC.from_layer_sizes(layer_sizes, recurrent=recurrent), spec,
            strategy=strategy, seed=seed)
        return compiled.to_soc_mapping()
    cores = list(NOC.core_ids())
    assignments: list[CoreAssignment] = []
    nxt = 0
    for layer, size in enumerate(layer_sizes[1:], start=1):
        placed = 0
        while placed < size:
            if nxt >= len(cores):
                raise ValueError(
                    f"network needs more than {len(cores)} cores "
                    f"({layer_sizes})")
            take = min(neurons_per_core, size - placed)
            assignments.append(CoreAssignment(
                core_id=int(cores[nxt]), layer=layer,
                neuron_lo=placed, neuron_hi=placed + take))
            placed += take
            nxt += 1
    return Mapping(assignments=assignments, layer_sizes=list(layer_sizes))


def remap_mapping_cores(mapping: "Mapping",
                        core_ids: Sequence[int]) -> "Mapping":
    """Re-home a mapping onto an explicit set of physical cores.

    Used by multi-tenant packing: each tenant's network is compiled
    independently (so every mapping starts from the same low core ids),
    then remapped onto its disjoint slice of the chip.  The mapping's
    distinct cores (sorted) are assigned to `core_ids` (sorted)
    one-for-one, preserving every neuron slice; raises when the set is
    too small or contains non-core node ids.
    """
    used = sorted({a.core_id for a in mapping.assignments})
    pool = sorted(int(c) for c in core_ids)
    if len(pool) < len(used):
        raise ValueError(
            f"mapping uses {len(used)} cores but only {len(pool)} "
            f"physical cores were offered")
    valid = set(int(c) for c in NOC.core_ids())
    bad = [c for c in pool if c not in valid]
    if bad:
        raise ValueError(f"not chip core ids: {bad} (cores are "
                         f"{min(valid)}..{max(valid)})")
    table = dict(zip(used, pool))
    return Mapping(
        assignments=[dataclasses.replace(a, core_id=table[a.core_id])
                     for a in mapping.assignments],
        layer_sizes=list(mapping.layer_sizes))


def build_register_tables(mapping: "Mapping", qweights=None, lif=None,
                          layer_cfgs=None,
                          default_cfg: CodebookConfig | None = None
                          ) -> list[RegisterTable]:
    """Lower a mapping (+ optional per-layer QuantizedTensors) to one
    programmed RegisterTable per core assignment — the single
    implementation behind ChipSimulator and the compiler.

    `layer_cfgs` supplies each placed layer's CodebookConfig; when absent
    it is inferred from the tensor (minimal W holding the words).  With no
    `qweights` the tables carry only the neuron registers.
    """
    from repro.core import quant as Q
    from repro.core.neuron import LIFParams

    lif = lif or LIFParams()
    default_cfg = default_cfg or CodebookConfig()
    tables = []
    for a in mapping.assignments:
        words: tuple = ()
        scale = 1.0
        cfg = default_cfg
        if qweights is not None:
            q = qweights[a.layer - 1]
            cfg = (layer_cfgs[a.layer - 1] if layer_cfgs is not None else
                   CodebookConfig(n_levels=int(q.codebook.shape[-1]),
                                  bit_width=Q.infer_bit_width(q)))
            words, scale = Q.register_entry_for_slice(
                q, cfg, a.neuron_lo, a.neuron_hi)
        tables.append(RegisterTable(
            core_id=a.core_id, threshold=lif.threshold, leak=lif.leak,
            reset=lif.reset, weight_levels=cfg.n_levels,
            weight_bits=cfg.bit_width, codebook_words=words,
            codebook_scale=scale))
    return tables


def _reject_index_like(w, layer: int, quant_cfg: CodebookConfig | None) -> None:
    """Catch codebook *indices* passed where weights belong.

    Integer arrays are always rejected.  In the codebook path (a quant_cfg
    is supplied) a float array whose values are all small non-negative
    integers below N is almost certainly `QuantizedTensor.idx` cast to
    float; silently re-fitting k-means over index values used to produce
    garbage weights — raise instead and point at the right API.

    The max >= 2 condition deliberately exempts binary {0, 1} matrices:
    those are plausible real weights (masks/connectivity), and k-means
    over {0, 1} reproduces them exactly, so no corruption is possible.
    """
    if isinstance(w, (int, float)) or not hasattr(w, "dtype"):
        raise TypeError(f"layer {layer}: expected a weight matrix, got {w!r}")
    if jnp.issubdtype(w.dtype, jnp.integer):
        raise TypeError(
            f"layer {layer}: integer weight array ({w.dtype}) looks like "
            f"codebook indices, not synaptic weights — pass the full "
            f"quant.QuantizedTensor (idx + codebook + scale) instead")
    if quant_cfg is not None:
        vals = np.asarray(w, np.float32)
        if (vals.size and np.all(vals == np.round(vals)) and vals.min() >= 0
                and 2 <= vals.max() <= quant_cfg.n_levels - 1):
            raise ValueError(
                f"layer {layer}: float weight array holds only integers in "
                f"[0, {quant_cfg.n_levels}) — these look like codebook "
                f"indices; re-fitting a codebook over index values would "
                f"silently corrupt the network. Pass the QuantizedTensor "
                f"from quant.quantize(), or the dequantized float weights")


def _layer_sizes(weights, recurrent: Sequence[int]) -> list[int]:
    """Population widths, input first, of weight layers that each feed
    the next; a weight layer in `recurrent` holds (n_pre + n_post,
    n_post) rows, its forward input's and then its own."""
    sizes = [int(w.shape[1]) for w in weights]
    n_in = int(weights[0].shape[0]) - (sizes[0] if 0 in recurrent else 0)
    sizes = [n_in] + sizes
    for li in recurrent:
        if not 0 <= li < len(weights):
            raise ValueError(f"recurrent layer {li} is not one of the "
                             f"{len(weights)} weight layers")
    for li, w in enumerate(weights):
        want = sizes[li] + (sizes[li + 1] if li in recurrent else 0)
        if int(w.shape[0]) != want or n_in <= 0:
            kind = "recurrent " if li in recurrent else ""
            raise ValueError(
                f"{kind}weight layer {li} has {int(w.shape[0])} rows; its "
                f"input needs {want} (layer sizes {sizes})")
    return sizes


@dataclasses.dataclass
class StepStats:
    """Per-timestep accounting gathered by the functional simulator."""

    nominal_sops: float = 0.0
    performed_sops: float = 0.0
    spikes_in: float = 0.0
    spikes_routed: float = 0.0
    neurons_touched: float = 0.0
    core_cycles: float = 0.0         # max over cores (parallel execution)
    noc_hops: float = 0.0
    noc_energy_pj: float = 0.0
    noc_contention_cycles: float = 0.0  # M/M/1 bottleneck-router wait cycles
    spike_words_skipped: float = 0.0  # ZSPE word-scan skips (fused engine)
    weight_writes: float = 0.0       # plasticity register-index writes
    recurrent_sops: float = 0.0      # performed SOPs of last-step spikes
                                     # fed back into recurrent layers
    back_noc_hops: float = 0.0       # of noc_hops, the recurrent trees'
                                     # hops beyond the next layer's tree

    @property
    def sparsity(self) -> float:
        if self.nominal_sops == 0:
            return 1.0
        return 1.0 - self.performed_sops / self.nominal_sops


@dataclasses.dataclass
class ChipReport:
    steps: int
    stats: StepStats                 # accumulated
    energy_pj: float
    core_energy_pj: float
    noc_energy_pj: float
    riscv_energy_pj: float
    wall_cycles: float
    freq_hz: float
    write_energy_pj: float = 0.0     # plasticity weight-write energy

    @property
    def pj_per_sop(self) -> float:
        return self.energy_pj / max(self.stats.nominal_sops, 1.0)

    @property
    def power_mw(self) -> float:
        t_s = self.wall_cycles / self.freq_hz
        return self.energy_pj * 1e-12 / max(t_s, 1e-12) * 1e3

    @property
    def gsops(self) -> float:
        t_s = self.wall_cycles / self.freq_hz
        return self.stats.nominal_sops / max(t_s, 1e-12) / 1e9


class ChipSimulator:
    """Functional + energy simulation of the whole SoC for an SNN
    described by per-layer weight matrices.

    Weight layer `li` feeds layer `li + 1`.  A weight layer listed in
    `recurrent` also feeds its own last-step spikes back to itself:

        I_t = W_in^T s_in[t] + W_rec^T s_out[t-1]        (s_out[-1] = 0)

    and its weight is the one (n_pre + n_post, n_post) matrix a core
    stores, forward rows first, under one codebook per core slice.  The
    layer's spikes travel one multicast tree per source core, to the next
    layer's cores and its own (its own core at zero hops), in the step
    they fire, and count as input spikes, performed SOPs and touches of
    the next step, like any other input spike.

    Three execution engines share one lowered mapping:

    * ``engine="compiled"`` (default) — `repro.core.engine.CompiledEngine`:
      the whole inference is one XLA program (`jax.lax.scan` over
      timesteps, `jax.vmap` over the batch), with the mapping, cycle and
      NoC models lowered to arrays.
    * ``engine="fused"`` — `repro.core.engine.FusedEngine`: each
      layer-step is one Pallas kernel (kernels/fused_timestep.py) fusing
      the ZSPE word scan (bitpacked uint16 spikes), in-register codebook
      dequant from the RegisterTable words, and the partial-update LIF
      step in a single VMEM pass; batches shard over available devices
      via shard_map.  This is the throughput path; bit-identical to
      ``compiled`` under interpret mode.
    * ``engine="sharded"`` — `repro.core.engine.ShardedEngine`: the
      compiled program shard_mapped along the CORES axis as well — each
      mesh device owns a contiguous run of level-1 domains (its weight
      columns + LIF-state slice) and shards exchange bitpacked spike
      words at domain boundaries each timestep, so a multi-chip board
      runs as one XLA program.  Spikes are bit-identical to
      ``compiled``; composes with batch sharding on a 2-D mesh.
    * ``engine="reference"`` — the original interpretive Python loop
      (one sample, one timestep, one layer at a time).  Kept as the
      differential-testing oracle; see tests/test_engine_equiv.py.
    """

    def __init__(
        self,
        weights: Sequence,                     # [(n_pre, n_post) arrays] or
                                               # [quant.QuantizedTensor, ...]
        quant_cfg: CodebookConfig | None = None,
        freq_hz: float = 100e6,
        geometry: CoreGeometry | None = None,
        zero_skip: bool = True,
        partial_update: bool = True,
        leak: float = 0.9,
        threshold: float = 1.0,
        mapping: Mapping | None = None,
        mapping_strategy: str = "anneal",
        engine: str = "compiled",
        register_tables: Sequence[RegisterTable] | None = None,
        lif=None,
        trace=None,                            # telemetry.TraceConfig
        faults=None,                           # faults.FaultConfig
        plasticity=None,                       # plasticity.PlasticityConfig
        recurrent: Sequence[int] = (),         # weight layers fed back
    ):
        from repro.core.neuron import LIFParams  # local import to avoid cycle
        from repro.core import quant as Q
        from repro.telemetry.trace import TraceConfig
        from repro.faults import model as FM

        weights = list(weights)
        n_quant = sum(isinstance(w, Q.QuantizedTensor) for w in weights)
        if 0 < n_quant < len(weights):
            raise TypeError(
                "weights mix QuantizedTensor and raw arrays — quantize every "
                "layer (or none) before building the simulator")
        self.qweights: list | None = None
        self._layer_qcfg: list | None = None
        if n_quant:
            # already-fitted codebooks: the chip runs the register-word
            # round trip of each table, never a re-fit.  N/W are per-core
            # register fields, so each layer gets its own (validated)
            # config — inferred per tensor, or checked against an explicit
            # quant_cfg at this API boundary with the layer named.
            self._layer_qcfg = []
            for li, q in enumerate(weights):
                n = int(q.codebook.shape[-1])
                wb = Q.infer_bit_width(q)
                if quant_cfg is not None:
                    if n != quant_cfg.n_levels:
                        raise ValueError(
                            f"layer {li}: codebook has {n} levels but "
                            f"quant_cfg says N={quant_cfg.n_levels}")
                    if wb > quant_cfg.bit_width:
                        raise ValueError(
                            f"layer {li}: codebook words need W={wb} bits "
                            f"but quant_cfg says W={quant_cfg.bit_width}")
                    wb = quant_cfg.bit_width
                self._layer_qcfg.append(
                    CodebookConfig(n_levels=n, bit_width=wb))
            quant_cfg = quant_cfg or self._layer_qcfg[0]
            self.qweights = weights
            self.weights = [Q.dequantize_via_registers(q, c.bit_width)
                            for q, c in zip(weights, self._layer_qcfg)]
        else:
            for li, w in enumerate(weights):
                _reject_index_like(w, li, quant_cfg)
            self.weights = [jnp.asarray(w, jnp.float32) for w in weights]
        self.recurrent = tuple(sorted({int(li) for li in recurrent}))
        sizes = _layer_sizes(self.weights, self.recurrent)
        self.n_in = sizes[0]
        self.mapping = mapping or map_network(
            sizes, strategy=mapping_strategy,
            recurrent=[li + 1 for li in self.recurrent])
        self.quant_cfg = quant_cfg or CodebookConfig(n_levels=16, bit_width=8)
        self.geom = geometry or CoreGeometry(freq_hz=freq_hz)
        self.freq_hz = freq_hz
        self.zero_skip = zero_skip
        self.partial_update = partial_update
        self.faults = faults if faults is not None else FM.NULL_FAULTS
        self.cycle_model = CycleModel(self.geom)
        self.core_model = E.calibrate_core()
        self.chip_model = E.calibrate_chip(self.core_model)
        self.riscv = E.RiscvPowerModel()
        self.router = NOC.RouterParams()
        # a mapping with core ids beyond one domain (from the compiler's
        # scale-up stage) runs on the matching multi-domain fabric, with
        # level-2 hops priced at the off-chip rate
        max_node = max(a.core_id for a in self.mapping.assignments)
        if max_node >= NOC.N_NODES:
            n_domains = max_node // NOC.DOMAIN_STRIDE + 1
            self.adj = NOC.multi_domain_adjacency(n_domains)
            self._level2 = frozenset(
                int(x) for x in NOC.level2_node_ids(n_domains))
            self.interconnect = E.InterconnectEnergyModel.from_router(self.router)
        else:
            self.adj = NOC.fullerene_adjacency()
            self._level2 = frozenset()
            self.interconnect = None
        if self.faults.rerouted and self.faults.topology_faults():
            # repaired chip: CMRouter tables are reprogrammed on the
            # surviving graph, so routes below detour around the faults
            # (and the replay prices the detours); unreachable pairs fail
            # loudly in _compile_layer_routes
            self.adj = FM.masked_adjacency(self.adj, self.faults)
        self.routing = NOC.RoutingTable(self.adj)
        # routes are compiled ONCE from the mapping; each timestep only
        # replays them (no BFS in the simulation loop)
        self._layer_routes, self._back_hops = self._compile_layer_routes()
        # a full LIFParams (e.g. the SNNConfig's, for train->deploy parity)
        # wins over the scalar threshold/leak conveniences
        self.lif = (dataclasses.replace(lif, partial_update=partial_update)
                    if lif is not None else
                    LIFParams(threshold=threshold, leak=leak,
                              partial_update=partial_update))
        if quant_cfg is not None and self.qweights is None:
            # float weights + a codebook config = post-training fit here
            self.qweights = [Q.quantize(w, quant_cfg) for w in self.weights]
            self._layer_qcfg = [quant_cfg] * len(self.weights)
            self.weights = [Q.dequantize_via_registers(q, quant_cfg.bit_width)
                            for q in self.qweights]
        self.register_tables = (list(register_tables)
                                if register_tables is not None
                                else self._build_register_tables())
        # static faults fold into the weights/tables HERE — before the
        # touch masks, so every engine inherits them with no lowering
        # changes; a null config returns without touching anything
        FM.apply_chip_faults(self)
        self.drop_plan = FM.build_drop_plan(self)
        self._dispatch_count = 0
        # connectivity masks for the partial-update touch set (see
        # neuron.touch_mask): computed AFTER quantization so both engines
        # see the synapses the chip actually programs
        self.nonzero_weights = [(w != 0).astype(jnp.float32)
                                for w in self.weights]
        if engine not in ("compiled", "fused", "sharded", "reference"):
            raise ValueError(f"engine must be 'compiled', 'fused', "
                             f"'sharded' or 'reference', got {engine!r}")
        self.engine = engine
        # opt-in per-timestep capture (repro.telemetry): threaded through
        # every engine; trace-off lowers zero extra scan outputs
        self.trace = trace or TraceConfig()
        # opt-in on-chip learning (core/plasticity.py): disabled lowers the
        # exact inference programs (jaxpr-asserted, like trace/faults)
        from repro.core.plasticity import NULL_PLASTICITY
        self.plasticity = (plasticity if plasticity is not None
                           else NULL_PLASTICITY)
        if self.recurrent and (self.plasticity.enabled
                               or self.drop_plan is not None):
            raise NotImplementedError(
                "recurrent layers run without plasticity and without NoC "
                "packet drop: those scan bodies are chain-only until they "
                "merge into one layer-step body")
        self.write_model = E.WeightWriteModel()
        self._plast_tables = None  # lazy lower_plasticity_tables result
        self._ref_learned = None   # reference-engine learned indexes
        self._ref_elig = None      # reference-engine eligibility traces
        self._last_trace = None  # reference-engine ChipTrace
        self._compiled = None    # CompiledEngine, built lazily
        self._fused = None       # FusedEngine, built lazily
        self._sharded = None     # ShardedEngine, built lazily

    def compiled_engine(self):
        """The lazily-built batched XLA engine for this mapping."""
        if self._compiled is None:
            from repro.core.engine import CompiledEngine
            self._compiled = CompiledEngine(self)
        return self._compiled

    def fused_engine(self):
        """The lazily-built fused-Pallas-kernel engine for this mapping."""
        if self._fused is None:
            from repro.core.engine import FusedEngine
            self._fused = FusedEngine(self)
        return self._fused

    def sharded_engine(self, n_shards: int | None = None):
        """The lazily-built cores-axis shard_map engine for this mapping.

        ``n_shards`` (first call only) overrides the default
        min(devices, domains) split along the domain axis."""
        if self._sharded is None:
            from repro.core.engine import ShardedEngine
            self._sharded = ShardedEngine(self, n_shards=n_shards)
        return self._sharded

    def array_engine(self):
        """The batched array engine selected at construction (compiled,
        fused or sharded); raises for the reference engine, which has no
        lowering."""
        if self.engine == "fused":
            return self.fused_engine()
        if self.engine == "sharded":
            return self.sharded_engine()
        if self.engine == "compiled":
            return self.compiled_engine()
        raise ValueError("the reference engine is interpretive — no "
                         "array lowering to return")

    def last_trace(self):
        """The ChipTrace captured by the most recent run (None when the
        simulator was built without `trace=TraceConfig(enabled=True)` or
        has not run yet).  Schema-identical across all three engines."""
        if self.engine in ("compiled", "fused", "sharded"):
            eng = {"fused": self._fused, "sharded": self._sharded,
                   "compiled": self._compiled}[self.engine]
            return eng.last_trace if eng is not None else None
        return self._last_trace

    def plasticity_tables(self):
        """Per-layer plasticity lowering: None for frozen layers, else the
        (idx0 int8, cbw f32 inf-padded) pair every engine AND the reference
        oracle learn over — one lowering, so initial state cannot drift."""
        if self._plast_tables is None:
            from repro.core.engine import lower_plasticity_tables
            self._plast_tables = lower_plasticity_tables(self)
        return self._plast_tables

    @property
    def last_learned(self):
        """Per-layer learned codebook indexes from the most recent
        plasticity-enabled run (None entries for frozen layers; batch axis
        leading for batched runs)."""
        if self.engine in ("compiled", "fused", "sharded"):
            eng = {"fused": self._fused, "sharded": self._sharded,
                   "compiled": self._compiled}[self.engine]
            return eng.last_learned if eng is not None else None
        return self._ref_learned

    def apply_reward(self, reward):
        """Reward-mode trial commit: turn the eligibility accumulated by
        the last run into priced register writes (see
        plasticity.commit_reward).  Returns the write-accounting dict."""
        if self.engine in ("compiled", "fused", "sharded"):
            return self.array_engine().apply_reward(reward)
        from repro.core import plasticity as PLC
        if self.plasticity.mode != "reward" or self._ref_elig is None:
            raise ValueError("apply_reward needs a completed reward-mode "
                             "run to commit")
        self._ref_learned, info = PLC.commit_reward(
            self.plasticity, self.plasticity_tables(), self._ref_learned,
            self._ref_elig, reward, self.write_model, self.cycle_model)
        self._ref_elig = None
        return info

    def _build_register_tables(self) -> list[RegisterTable]:
        """One programmed RegisterTable per core assignment.  With quantized
        weights the core's shared table is the layer codebook (the group
        covering the core's neuron slice when the tensor is group-quantized),
        lowered to W-bit words — the exact values `self.weights` dequantized
        through."""
        return build_register_tables(
            self.mapping, qweights=self.qweights, lif=self.lif,
            layer_cfgs=self._layer_qcfg, default_cfg=self.quant_cfg)

    def _compile_layer_routes(self) -> tuple[dict[int, list[NOC.FlowRoute]],
                                             dict[int, np.ndarray]]:
        """Static routes of every layer that fires into another, keyed by
        the firing layer: the spikes layer `li` fires travel from each of
        its cores to every core holding layer `li+1` and, for a recurrent
        layer, to every core holding `li` itself (a core's own delivery
        crosses no link), one multicast tree per source core.  With them,
        per recurrent layer, each tree's back-edge share of hops: those
        it has beyond the tree to layer `li+1` alone."""
        def flows(li: int, dst_layers: list[int]) -> list[NOC.FlowRoute]:
            srcs = [a.core_id for a in self.mapping.cores_of_layer(li)]
            dsts = sorted({a.core_id for d in dst_layers
                           for a in self.mapping.cores_of_layer(d)})
            return [NOC.compile_flow(self.routing, s, dsts, self._level2)
                    for s in srcs]

        L = len(self.weights)
        routes: dict[int, list[NOC.FlowRoute]] = {}
        back_hops: dict[int, np.ndarray] = {}
        for li in range(1, L + 1):
            forward = [li + 1] if li < L else []
            if li - 1 in self.recurrent:
                routes[li] = flows(li, forward + [li])
                alone = ([f.hops for f in flows(li, forward)] if forward
                         else 0)
                back_hops[li] = np.array([f.hops for f in routes[li]],
                                         np.int64) - alone
            elif forward:
                routes[li] = flows(li, forward)
        return routes, back_hops

    # -- execution ----------------------------------------------------------

    def _consume_transient_fault(self) -> None:
        """Raise `TransientChipFault` when this dispatch index is listed in
        `faults.transient_dispatches`.  Engines call it after the scan ran
        but before results are read back — a mid-flight loss, so a retry
        (same FaultConfig, next dispatch index) can succeed."""
        i = self._dispatch_count
        self._dispatch_count += 1
        if i in self.faults.transient_dispatches:
            from repro.faults.model import TransientChipFault
            raise TransientChipFault(
                f"injected transient fault at dispatch {i}")

    def run(self, spike_train: jax.Array,
            learned=None) -> tuple[np.ndarray | jax.Array, ChipReport]:
        """spike_train: (T, n_in) binary.  Returns (out_spike_counts, report);
        the array engines' counts are a host array.

        Dispatches to the engine selected at construction; all engines
        return identical spikes and matching accounting.  `learned`
        (plasticity only) warm-starts the learnable layers' codebook
        indexes, e.g. with a previous run's `last_learned`.
        """
        if self.engine in ("compiled", "fused", "sharded"):
            return self.array_engine().run(spike_train, learned=learned)
        return self.run_reference(spike_train, learned=learned)

    def run_batch(self, spike_trains: jax.Array,
                  learned=None) -> tuple[np.ndarray, list[ChipReport]]:
        """spike_trains: (B, T, n_in).  Returns ((B, n_out) f32 counts as a
        host array, one ChipReport per sample).  The array engines run the
        batch as a single XLA program and read the counts back in the same
        transfer as the counters; the reference engine loops samples.

        With plasticity enabled every sample starts from the same initial
        indexes (broadcast `learned`, or per-sample (B, ...) entries) and
        `last_learned` holds per-sample finals — matching the array
        engines' vmap semantics, NOT chaining learning across the batch.
        """
        if self.engine in ("compiled", "fused", "sharded"):
            return self.array_engine().run_batch(spike_trains,
                                                 learned=learned)
        outs, reports, traces, finals, eligs = [], [], [], [], []
        B = int(spike_trains.shape[0])
        for b in range(B):
            lb = None
            if learned is not None:
                lb = [None if l is None
                      else (l[b] if np.ndim(l) == 3 else l)
                      for l in learned]
            counts, rep = self.run_reference(spike_trains[b], learned=lb)
            outs.append(counts)
            reports.append(rep)
            if self._ref_learned is not None:
                finals.append(self._ref_learned)
                eligs.append(self._ref_elig)
            if self._last_trace is not None:
                traces.append(self._last_trace)
        self._consume_transient_fault()
        if traces:
            from repro.telemetry.trace import ChipTrace
            self._last_trace = ChipTrace.concat(traces)
        if finals:
            self._ref_learned = [
                None if finals[0][li] is None
                else jnp.stack([f[li] for f in finals])
                for li in range(len(finals[0]))]
            self._ref_elig = (None if eligs[0] is None else [
                None if eligs[0][li] is None
                else jnp.stack([e[li] for e in eligs])
                for li in range(len(eligs[0]))])
        return np.asarray(jnp.stack(outs)), reports

    def run_reference(self, spike_train: jax.Array,
                      learned=None) -> tuple[jax.Array, ChipReport]:
        """The interpretive per-timestep loop (differential-test oracle)."""
        from repro.core.neuron import init_state, lif_step, touch_mask

        plast = self.plasticity
        if learned is not None and not plast.enabled:
            raise ValueError("learned indexes passed but plasticity is off")
        idx = x_pre = x_post = elig = cbws = None
        if plast.enabled:
            ptables = self.plasticity_tables()
            cbws = [None if pt is None else jnp.asarray(pt[1])
                    for pt in ptables]
            idx, x_pre, x_post, elig = [], [], [], []
            for li, pt in enumerate(ptables):
                if pt is None:
                    idx.append(None)
                    x_pre.append(None)
                    x_post.append(None)
                    elig.append(None)
                    continue
                i0 = pt[0] if learned is None or learned[li] is None \
                    else learned[li]
                idx.append(jnp.asarray(i0, jnp.int8))
                n_pre, n_post = (int(s) for s in self.weights[li].shape)
                x_pre.append(jnp.zeros((n_pre,), jnp.float32))
                x_post.append(jnp.zeros((n_post,), jnp.float32))
                elig.append(jnp.zeros((n_pre, n_post), jnp.float32)
                            if plast.mode == "reward" else None)

        T = int(spike_train.shape[0])
        states = [init_state(int(w.shape[1])) for w in self.weights]
        # last step's spikes of each recurrent layer (s_out[-1] = 0)
        fed_back = {li: jnp.zeros((int(self.weights[li].shape[1]),),
                                  jnp.float32) for li in self.recurrent}
        out_counts = jnp.zeros((int(self.weights[-1].shape[1]),), jnp.float32)
        acc = StepStats()
        wall = 0.0
        traced = self.trace.enabled
        trace_skips = traced and self.trace.skip_words
        # raw trace counters (same four tensors the array engines emit);
        # every derived series comes from telemetry.build_trace
        rec_fired: list[list[float]] = []
        rec_touched: list[list[float]] = []
        rec_nnz: list[list[float]] = []
        rec_skip: list[list[float]] = []
        rec_writes: list[list[float]] = []

        for t in range(T):
            spikes = spike_train[t].astype(jnp.float32)
            per_core_cycles: dict[int, float] = {}
            step_load = np.zeros(self.adj.shape[0], np.float64)
            if traced:
                rec_fired.append([])
                rec_touched.append([])
                rec_nnz.append([])
                rec_skip.append([])
                rec_writes.append([])
            for li in range(len(self.weights)):
                learns = plast.enabled and idx[li] is not None
                if learns:
                    # live weights from the carried indexes — the SAME
                    # jnp expressions the array engines lower, so spikes
                    # and learned indexes stay bit-identical
                    from repro.core import plasticity as PLC
                    w = PLC.dequant_indices(idx[li], cbws[li])
                    nzw = (w != 0).astype(jnp.float32)
                else:
                    w = self.weights[li]
                    nzw = self.nonzero_weights[li]
                n_pre, n_post = int(w.shape[0]), int(w.shape[1])
                # a recurrent layer's input: the forward spikes, then its
                # own spikes of the last step (two spike-word streams)
                streams = ([spikes, fed_back[li]] if li in fed_back
                           else [spikes])
                x = jnp.concatenate(streams) if li in fed_back else spikes
                nnz = float(jnp.sum(x != 0))
                acc.spikes_in += nnz
                if li in fed_back:
                    acc.recurrent_sops += float(
                        jnp.sum(fed_back[li] != 0)) * n_post
                if traced:
                    rec_nnz[-1].append(nnz)
                    if trace_skips:
                        rec_skip[-1].append(sum(
                            float(Z.empty_spike_words(Z.pack_spike_words(s)))
                            for s in streams))
                current = jnp.matmul(x, w, precision=Z.CURRENT_PRECISION)
                st, out, touched = lif_step(
                    states[li], current, self.lif,
                    touched=touch_mask(x, nzw))
                states[li] = st
                acc.nominal_sops += n_pre * n_post
                acc.performed_sops += nnz * n_post
                acc.neurons_touched += float(jnp.sum(touched))
                touched_np = np.asarray(touched)
                out_np = np.asarray(out)
                col_ch = None
                if learns:
                    if plast.mode == "stdp":
                        nidx, xp, xq, changed = PLC.stdp_step(
                            plast, spikes, out, x_pre[li], x_post[li],
                            idx[li], cbws[li])
                        idx[li], x_pre[li], x_post[li] = nidx, xp, xq
                        col_ch = np.asarray(
                            jnp.sum(changed, axis=0), np.float64)
                        acc.weight_writes += float(col_ch.sum())
                    else:
                        xp, xq, e = PLC.elig_step(
                            plast, spikes, out, x_pre[li], x_post[li],
                            elig[li])
                        x_pre[li], x_post[li], elig[li] = xp, xq, e
                if traced:
                    rec_writes[-1].append(
                        float(col_ch.sum()) if col_ch is not None else 0.0)
                asn = self.mapping.cores_of_layer(li + 1)
                # cycles for each core holding a slice of this layer, from
                # the exact (integer) touched count of the core's slice
                for a in asn:
                    core_touched = float(
                        touched_np[a.neuron_lo:a.neuron_hi].sum())
                    cyc = self.cycle_model.timestep_cycles(
                        n_pre, a.n_neurons, nnz, core_touched,
                        self.zero_skip, self.partial_update,
                        writes=(float(
                            col_ch[a.neuron_lo:a.neuron_hi].sum())
                            if col_ch is not None else None))
                    per_core_cycles[a.core_id] = per_core_cycles.get(a.core_id, 0.0) + cyc
                    if traced:
                        rec_touched[-1].append(core_touched)
                        rec_fired[-1].append(
                            float(out_np[a.neuron_lo:a.neuron_hi].sum()))
                # NoC: the spikes each source core fired travel its own
                # precompiled flow (replay, no BFS here) — source-exact,
                # so where a spike fires from changes what it costs
                fired = float(out_np.sum())
                fired_per_src = [int(out_np[a.neuron_lo:a.neuron_hi].sum())
                                 for a in asn]
                if li in fed_back:
                    fed_back[li] = out
                if fired > 0 and li + 1 in self._layer_routes:
                    rep = NOC.replay_flows(
                        list(zip(self._layer_routes[li + 1], fired_per_src)),
                        self.router, n_nodes=self.adj.shape[0],
                        interconnect=self.interconnect)
                    acc.noc_hops += rep.total_hops
                    acc.noc_energy_pj += rep.energy_pj
                    acc.spikes_routed += fired
                    step_load += rep.router_load
                    if li + 1 in self._back_hops:
                        acc.back_noc_hops += float(
                            np.dot(fired_per_src, self._back_hops[li + 1]))
                # per-hop packet drop (faults.DropPlan): fired counters
                # above are pre-drop (the source committed the energy);
                # what the next layer integrates is post-drop
                if (self.drop_plan is not None
                        and self.drop_plan.keep_p[li] is not None):
                    spikes = out * self.drop_plan.mask(li, t)
                else:
                    spikes = out
            out_counts = out_counts + spikes
            core_wall = max(per_core_cycles.values()) if per_core_cycles else 1.0
            # bottleneck-router contention stalls the timestep barrier
            cont = float(NOC.contention_cycles(
                step_load.max(), core_wall, self.router))
            acc.noc_contention_cycles += cont
            wall += core_wall + cont

        if plast.enabled:
            self._ref_learned = idx
            self._ref_elig = elig if plast.mode == "reward" else None
        if traced:
            from repro.telemetry.trace import build_trace
            self._last_trace = build_trace(
                self,
                np.asarray(rec_fired, np.float64)[None],      # (1, T, S)
                np.asarray(rec_touched, np.float64)[None],
                np.asarray(rec_nnz, np.float64)[None],
                (np.asarray(rec_skip, np.float64)[None]
                 if trace_skips else None),
                weight_writes=(np.asarray(rec_writes, np.float64)[None]
                               if plast.enabled else None))
        return out_counts, self._report(T, acc, wall)

    def _report(self, steps: int, acc: StepStats, wall: float) -> ChipReport:
        # one pricing implementation for both engines (energy.price_batched;
        # the compiled engine calls it with batch arrays)
        priced = E.price_batched(
            self.core_model, self.riscv,
            nominal_sops=acc.nominal_sops, performed_sops=acc.performed_sops,
            noc_energy_pj=acc.noc_energy_pj, wall_cycles=wall, steps=steps,
            freq_hz=self.freq_hz, zero_skip=self.zero_skip,
            partial_update=self.partial_update,
            weight_writes=acc.weight_writes, write_model=self.write_model)
        return ChipReport(
            steps=steps, stats=acc,
            energy_pj=float(priced["total_pj"]),
            core_energy_pj=float(priced["core_pj"]),
            noc_energy_pj=acc.noc_energy_pj,
            riscv_energy_pj=float(priced["riscv_pj"]),
            wall_cycles=wall, freq_hz=self.freq_hz,
            write_energy_pj=float(priced["write_pj"]))


# ---------------------------------------------------------------------------
# ENU — extended neuromorphic instruction set (paper C5)
# ---------------------------------------------------------------------------

ENU_OPCODES = {
    "NPARAM.INIT": 0x0,   # network parameter initialization (DMA descriptors)
    "CORE.EN": 0x1,       # core enable mask -> register tables / clock gates
    "NET.START": 0x2,     # network startup (timestep engine go)
    "NET.WAIT": 0x3,      # sleep until network-computing-finish IRQ
    "TS.SYNC": 0x4,       # timestep-switch barrier
    "OBUF.READ": 0x5,     # read one of the 4 x 0.2 KB output buffers
}


@dataclasses.dataclass
class EnuInstruction:
    op: str
    arg: int = 0

    def encode(self) -> int:
        return (ENU_OPCODES[self.op] << 28) | (self.arg & 0x0FFFFFFF)


class EnuProgram:
    """A control program for one inference — used by the SoC timeline model
    to derive the RISC-V duty cycle (Fig. 6) instead of assuming it."""

    def __init__(self, instrs: list[EnuInstruction]):
        self.instrs = instrs

    @staticmethod
    def standard_inference(core_mask: int, timesteps: int) -> "EnuProgram":
        body = [EnuInstruction("NPARAM.INIT"), EnuInstruction("CORE.EN", core_mask),
                EnuInstruction("NET.START", timesteps)]
        body += [EnuInstruction("TS.SYNC", t) for t in range(timesteps)]
        body += [EnuInstruction("NET.WAIT"), EnuInstruction("OBUF.READ", 0)]
        return EnuProgram(body)

    def timeline(self, cycles_per_timestep: float,
                 cpu_cycles_per_instr: float = 40.0,
                 cpu_freq_hz: float = 16e6, net_freq_hz: float = 100e6
                 ) -> tuple[float, float]:
        """Returns (t_active_s, t_sleep_s) for the RISC-V core."""
        active_instr = [i for i in self.instrs if i.op not in ("NET.WAIT", "TS.SYNC")]
        t_active = len(active_instr) * cpu_cycles_per_instr / cpu_freq_hz
        n_wait = sum(1 for i in self.instrs if i.op in ("NET.WAIT", "TS.SYNC"))
        t_sleep = n_wait * cycles_per_timestep / net_freq_hz
        return t_active, t_sleep
