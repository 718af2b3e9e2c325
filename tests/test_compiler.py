"""Mapping-compiler tests: partition/place/route round-trips, the
greedy-vs-optimized cost guarantee, capacity validation, and multi-domain
scale-up with level-2 energy pricing."""
import numpy as np
import pytest

from repro import compiler as COMP
from repro.core import noc as NOC
from repro.core.soc import ChipSimulator, map_network, validate_capacity

NMNIST_SIZES = (2312, 4096, 1024, 10)


# ---------------------------------------------------------------------------
# stage 1: partition
# ---------------------------------------------------------------------------

def test_partition_places_every_neuron_exactly_once():
    cn = COMP.compile_network(list(NMNIST_SIZES))
    by_layer = {}
    for g in cn.groups:
        by_layer.setdefault(g.layer, []).append(g)
    for layer in cn.net.placed_layers:
        slices = sorted(by_layer[layer.index], key=lambda g: g.lo)
        assert slices[0].lo == 0
        assert slices[-1].hi == layer.n_neurons
        for a, b in zip(slices[:-1], slices[1:]):
            assert a.hi == b.lo            # contiguous, no gap, no overlap


def test_partition_respects_core_capacity():
    cn = COMP.compile_network([100, 3 * 8192 + 5, 10])
    for g in cn.groups:
        assert 0 < g.n_neurons <= cn.spec.neurons_per_core
    # one codebook per core: a group never spans layers
    assert len({(g.gid) for g in cn.groups}) == len(cn.groups)
    # placement is injective: one group per physical core
    cores = list(cn.placement.assignment.values())
    assert len(cores) == len(set(cores))


def test_partition_spread_uses_idle_cores():
    cn = COMP.compile_network(list(NMNIST_SIZES))
    assert len(cn.groups) == 20                  # all cores of one domain
    cn_min = COMP.compile_network(list(NMNIST_SIZES), spread=False)
    assert len(cn_min.groups) == 3               # capacity-driven minimum


# ---------------------------------------------------------------------------
# capacity validation (soc + compiler agree)
# ---------------------------------------------------------------------------

def test_oversized_network_raises_everywhere():
    too_big = [100, 21 * 8192]                   # > 20 cores x 8192
    with pytest.raises(ValueError, match="capacity"):
        map_network(too_big)
    with pytest.raises(ValueError, match="capacity"):
        validate_capacity(too_big)
    with pytest.raises(ValueError, match="capacity"):
        COMP.compile_network(too_big, COMP.ChipSpec(max_domains=1))
    rng = np.random.default_rng(0)
    w = [np.asarray(rng.normal(0, 0.1, (100, 21 * 8192)), np.float32)]
    with pytest.raises(ValueError, match="capacity"):
        ChipSimulator(w)


def test_too_many_tiny_layers_raises():
    # 21 one-neuron layers fit the neuron budget but not the core count
    sizes = [8] + [1] * 21
    with pytest.raises(ValueError, match="cores"):
        COMP.compile_network(sizes, COMP.ChipSpec(max_domains=1))


# ---------------------------------------------------------------------------
# stage 2: place — the optimization guarantee
# ---------------------------------------------------------------------------

def test_anneal_strictly_beats_contiguous_on_nmnist_scale():
    cn = COMP.compile_network(list(NMNIST_SIZES), strategy="anneal", seed=0)
    assert cn.cost < cn.baseline_cost            # strictly lower traffic cost
    assert cn.improvement > 1.0


def test_placement_cost_is_hop_weighted_traffic():
    cn = COMP.compile_network([64, 128, 10], spread=False)
    # two placed layers -> single flow L1 -> L2 at the L1 spike rate
    dist = NOC.bfs_distances(cn.routed.adjacency)
    (g1, g2) = cn.groups
    c1, c2 = cn.core_of_group(g1.gid), cn.core_of_group(g2.gid)
    expect = cn.net.spike_rates[1] * dist[c1, c2]
    assert abs(cn.cost - expect) < 1e-6


def test_anneal_deterministic_given_seed():
    a = COMP.compile_network(list(NMNIST_SIZES), seed=7)
    b = COMP.compile_network(list(NMNIST_SIZES), seed=7)
    assert a.placement.assignment == b.placement.assignment
    assert a.cost == b.cost


# ---------------------------------------------------------------------------
# stage 3: route — connection matrices reproduce BFS connectivity
# ---------------------------------------------------------------------------

def test_routed_tables_reproduce_bfs_paths():
    cn = COMP.compile_network(list(NMNIST_SIZES))
    COMP.verify_roundtrip(cn.routed)             # raises on any miss
    # spot-check: table walk == BFS path hop-for-hop
    rt = cn.routed.routing
    some = cn.routed.layer_flows[1][0]
    for dst in some.dsts[:5]:
        if dst == some.src:
            continue
        walked = cn.routed.router_tables.follow(some.src, dst)
        assert walked == rt.path(some.src, dst)


def test_flow_routes_match_simulate_traffic():
    """Replaying compiled routes must equal the legacy one-shot simulator."""
    rng = np.random.default_rng(3)
    adj = NOC.fullerene_adjacency()
    flows = NOC.uniform_random_flows(rng, 50, bcast_frac=0.3)
    legacy = NOC.simulate_traffic(adj, flows)
    rt = NOC.RoutingTable(adj)
    routed = [(NOC.compile_flow(rt, s, d), n) for s, d, n in flows]
    replay = NOC.replay_flows(routed, n_nodes=adj.shape[0])
    assert replay.total_hops == legacy.total_hops
    assert replay.spikes_delivered == legacy.spikes_delivered
    assert abs(replay.energy_pj - legacy.energy_pj) < 1e-9
    assert replay.mode_counts == legacy.mode_counts


# ---------------------------------------------------------------------------
# stage 4: scale-up
# ---------------------------------------------------------------------------

def test_scaleup_spans_two_domains_with_l2_pricing():
    spec = COMP.ChipSpec(max_domains=4)
    cn = COMP.compile_network((2312, 81920, 81920, 10), spec, verify=True)
    assert cn.n_domains_used >= 2
    assert cn.routed.total_l2_hops() > 0
    es = cn.energy_summary()
    assert es["l2_pj_per_step"] > 0
    assert es["level2_premium"] > 1.0
    # off-chip hops must be priced above the same count of on-chip hops
    ic = spec.interconnect
    assert ic.flow_pj(0, 10) > ic.flow_pj(10, 0)


def test_congestion_aware_placement_flattens_router_load():
    """With `congestion_weight > 0` the anneal objective trades a few
    hops for a lower bottleneck-router occupancy; every placement records
    its congestion, and the placement stays injective."""
    sizes = [256, 512, 512, 256, 10]
    base = COMP.compile_network(sizes, strategy="anneal", seed=0)
    aware = COMP.compile_network(sizes, strategy="anneal", seed=0,
                                 congestion_weight=2.0)
    assert base.placement.congestion > 0
    assert aware.placement.congestion < base.placement.congestion
    assert aware.placement.congestion_weight == 2.0
    cores = list(aware.placement.assignment.values())
    assert len(cores) == len(set(cores))
    # telemetry surfaces in the summary either way
    assert base.summary()["congestion"] == round(base.placement.congestion, 3)


def test_path_load_table_matches_flow_table_router_load():
    """The placement-side path-load prediction uses the same router-load
    convention the engines replay (`FlowTable.router_load`): each link
    charges its sending node."""
    from repro.compiler.place import path_load_table

    adj = NOC.fullerene_adjacency()
    load = path_load_table(adj)
    rt = NOC.RoutingTable(adj)
    cores = [int(c) for c in NOC.core_ids()]
    for src, dst in [(cores[0], cores[7]), (cores[3], cores[19])]:
        fr = NOC.compile_flow(rt, src, [dst])
        table = NOC.compile_flow_table([fr], n_nodes=adj.shape[0])
        np.testing.assert_array_equal(load[src, dst], table.router_load[0])


def test_single_domain_has_no_l2_hops():
    cn = COMP.compile_network(list(NMNIST_SIZES))
    assert cn.plan.n_domains == 1
    assert cn.routed.total_l2_hops() == 0
    assert cn.energy_summary()["l2_pj_per_step"] == 0


# ---------------------------------------------------------------------------
# end-to-end: compiled mapping through the ChipSimulator
# ---------------------------------------------------------------------------

def test_compiled_mapping_preserves_functional_output():
    """Placement must never change the math — only where it runs."""
    rng = np.random.default_rng(0)
    sizes = (128, 256, 10)
    w = [np.asarray(rng.normal(0, 0.4, (a, b)), np.float32)
         for a, b in zip(sizes[:-1], sizes[1:])]
    spikes = np.asarray(rng.random((6, sizes[0])) < 0.1, np.float32)
    out_greedy, rep_g = ChipSimulator(w, mapping_strategy="greedy").run(spikes)
    out_comp, rep_c = ChipSimulator(w, mapping_strategy="anneal").run(spikes)
    np.testing.assert_array_equal(np.asarray(out_greedy), np.asarray(out_comp))
    # compiled mapping spreads layers: strictly more cores, fewer wall cycles
    assert rep_c.wall_cycles <= rep_g.wall_cycles


def test_multi_domain_mapping_runs_in_simulator():
    """A compiled scale-up mapping must simulate on the matching
    multi-domain fabric with level-2 hops priced at the off-chip rate."""
    rng = np.random.default_rng(2)
    sizes = [8] + [4] * 21                       # 22 layers -> 2 domains
    w = [np.asarray(rng.normal(0, 1.2, (a, b)), np.float32)
         for a, b in zip(sizes[:-1], sizes[1:])]
    cn = COMP.compile_network(sizes, COMP.ChipSpec(max_domains=2))
    assert cn.n_domains_used >= 2
    sim = ChipSimulator(w, mapping=cn.to_soc_mapping())
    assert sim.interconnect is not None
    assert any(fr.l2_hops > 0
               for frs in sim._layer_routes.values() for fr in frs)
    spikes = np.asarray(rng.random((3, sizes[0])) < 0.5, np.float32)
    out, rep = sim.run(spikes)
    assert out.shape == (sizes[-1],)
    assert rep.noc_energy_pj >= 0


def test_map_network_greedy_fallback_is_legacy_contiguous():
    m = map_network([100, 8192 + 10, 50], strategy="greedy")
    cores = NOC.core_ids()
    assert [a.core_id for a in m.assignments] == [int(c) for c in cores[:3]]
    assert [(a.layer, a.neuron_lo, a.neuron_hi) for a in m.assignments] == \
        [(1, 0, 8192), (1, 8192, 8202), (2, 0, 50)]


def test_conv_frontend_partitions():
    from repro.models.snn_conv import ConvSNNConfig

    cfg = ConvSNNConfig(in_shape=(32, 32, 2), channels=(16, 32), timesteps=8)
    cn = COMP.compile_network(cfg)
    sizes = cn.net.layer_sizes()
    assert sizes[0] == 32 * 32 * 2
    assert sizes[1] == 32 * 32 * 16              # stage 1, pre-pool resolution
    assert sizes[2] == 16 * 16 * 32
    assert sizes[3] == cfg.n_classes
    assert cn.net.layers[1].kind == "conv"
    assert cn.net.layers[1].fan_in == 3 * 3 * 2


def test_measured_spike_rates_feed_placement():
    rng = np.random.default_rng(1)
    sizes = (64, 96, 10)
    w = [np.asarray(rng.normal(0, 0.5, (a, b)), np.float32)
         for a, b in zip(sizes[:-1], sizes[1:])]
    spikes = np.asarray(rng.random((8, 64)) < 0.2, np.float32)
    rates = COMP.measure_spike_rates(w, spikes)
    assert len(rates) == len(sizes)
    assert abs(rates[0] - float(spikes.sum()) / 8) < 1e-6
    graph = COMP.from_weights(w, spike_rates=rates)
    cn = COMP.compile_network(graph)
    assert cn.net.spike_rates == tuple(rates)


# ---------------------------------------------------------------------------
# recurrent self-edges: back-edges placed, routed and priced
# ---------------------------------------------------------------------------

RECURRENT_SIZES = (48, 64, 10)


def test_back_edge_flows_cover_every_slice_pair():
    net = COMP.from_layer_sizes(RECURRENT_SIZES, recurrent=[1])
    assert net.layers[1].fan_in == 48 + 64 and net.layers[2].fan_in == 64
    assert net.self_edges() == ((1, net.spike_rates[1]),)
    cn = COMP.compile_network(net, verify=True)
    hidden = [g for g in cn.groups if g.layer == 1]
    assert len(hidden) > 1
    flows = COMP.group_traffic(net, cn.groups)
    pairs = {(s, d) for s, d, _ in flows}
    for s in hidden:
        for d in hidden:
            assert ((s.gid, d.gid) in pairs) == (s.gid != d.gid)
    # one tree per slice carries its spikes to every readout core and
    # back to every hidden slice, its own core included; the readout
    # fires into no other layer
    cores = {cn.core_of_group(g.gid) for g in hidden}
    readout = {cn.core_of_group(g.gid) for g in cn.groups if g.layer == 2}
    trees = cn.routed.layer_flows[1]
    assert [f.src for f in trees] == [cn.core_of_group(g.gid) for g in hidden]
    assert all(list(f.dsts) == sorted(cores | readout) for f in trees)
    assert sorted(cn.routed.layer_flows) == [1] and cn.cost > 0
    chain = COMP.compile_network(list(RECURRENT_SIZES), verify=True)
    chain_readout = {chain.core_of_group(g.gid) for g in chain.groups
                     if g.layer == 2}
    assert all(list(f.dsts) == sorted(chain_readout)
               for f in chain.routed.layer_flows[1])


def test_back_edge_routes_agree_flat_and_hierarchical():
    spec = COMP.ChipSpec(neurons_per_core=8, max_domains=2)
    net = COMP.from_layer_sizes((40, 200, 10), recurrent=[1])
    hier = COMP.compile_network(net, spec, hierarchical=True)
    flat = R_route(hier, net)
    assert hier.n_domains_used == 2
    assert hier.routed.layer_flows == flat.layer_flows
    assert hier.routed.total_l2_hops() > 0
    COMP.verify_roundtrip(hier.routed)


def R_route(compiled, net):
    from repro.compiler import route as R

    return R.route(compiled.groups, compiled.placement.assignment,
                   compiled.plan.adjacency, compiled.plan.level2_nodes,
                   recurrent=net.recurrent)


def test_on_core_delivery_costs_zero_hops():
    """A recurrent layer held by one core delivers its spikes back to
    that core over no link: its tree is the path to the readout alone,
    and the back-edge's share of hops is zero."""
    spec = COMP.ChipSpec()
    net = COMP.from_layer_sizes((30, 40, 10), recurrent=[1])
    cn = COMP.compile_network(net, spec, spread=False, verify=True)
    (tree,) = cn.routed.layer_flows[1]
    (readout,) = {cn.core_of_group(g.gid) for g in cn.groups if g.layer == 2}
    assert readout != tree.src and set(tree.dsts) == {tree.src, readout}
    alone = NOC.compile_flow(cn.routed.routing_table(), tree.src, [readout])
    assert set(tree.links) == set(alone.links) and tree.hops == alone.hops > 0
    sim = ChipSimulator([np.ones((70, 40), np.float32) * 0.3,
                         np.ones((40, 10), np.float32) * 0.2],
                        engine="compiled", mapping=cn.to_soc_mapping(),
                        recurrent=(0,))
    assert sim.compiled_engine().tables.back_hops[0].tolist() == [0]
    x = np.zeros((1, 4, 30), np.float32)
    x[:, :, :5] = 1.0
    _, [rep] = sim.run_batch(x)
    assert rep.stats.recurrent_sops > 0 and rep.stats.spikes_routed > 0
    assert rep.stats.back_noc_hops == 0.0
    assert rep.stats.noc_hops == rep.stats.spikes_routed * alone.hops


def test_back_edge_share_of_a_tree_by_hand():
    """Each hidden slice's one tree reaches every readout and hidden
    core; its back-edge share is the links the tree has beyond the tree
    to the readout alone, and each fired spike is routed once."""
    net = COMP.from_layer_sizes(RECURRENT_SIZES, recurrent=[1])
    cn = COMP.compile_network(net, verify=True)
    from repro.telemetry.trace import TraceConfig

    sim = ChipSimulator([np.full((48 + 64, 64), 0.05, np.float32),
                         np.full((64, 10), 0.2, np.float32)],
                        engine="compiled", mapping=cn.to_soc_mapping(),
                        recurrent=(0,), trace=TraceConfig(enabled=True))
    rt = sim.routing
    hidden = [a.core_id for a in sim.mapping.cores_of_layer(1)]
    readout = sorted({a.core_id for a in sim.mapping.cores_of_layer(2)})

    def tree_links(src, dsts):
        return {link for d in dsts
                for link in zip(rt.path(src, d)[:-1], rt.path(src, d)[1:])}

    want = [len(tree_links(src, hidden + readout))
            - len(tree_links(src, readout)) for src in hidden]
    assert sim._back_hops[1].tolist() == want and sum(want) > 0
    x = np.zeros((1, 3, 48), np.float32)
    x[:, 0, :] = 1.0
    _, [rep] = sim.run_batch(x)
    tr = sim.last_trace()
    sel = np.asarray(tr.slice_layer) == 0
    assert np.asarray(tr.slice_core)[sel].tolist() == hidden
    per_slice = tr.fired[0][:, sel].sum(axis=0)
    assert rep.stats.spikes_routed == per_slice.sum() > 0
    assert rep.stats.back_noc_hops == float(per_slice @ np.array(want))
