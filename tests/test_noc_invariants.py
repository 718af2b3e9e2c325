"""NoC topology invariants (paper C4) pinned as tests: published graph
metrics, degree structure, all-pairs reachability, and routing-table
consistency on the single- and multi-domain fullerene fabrics."""
import numpy as np

from repro.core import noc as NOC


def test_published_graph_metrics():
    m = NOC.fullerene_metrics()
    assert m.n_nodes == 32
    assert abs(m.avg_degree - 3.75) < 1e-9           # paper: 3.75
    assert abs(m.degree_variance - 0.9375) < 1e-9    # paper: 0.93-0.94
    assert abs(m.avg_core_hops - 3.16) < 0.01        # paper: ~3.16 hops


def test_degree_structure():
    """20 cores of degree 3 (dodecahedron vertices), 12 CMRouters of
    degree 5 (faces); cores only attach to routers."""
    adj = NOC.fullerene_adjacency()
    deg = adj.sum(axis=1)
    assert (deg[NOC.core_ids()] == 3).all()
    assert (deg[NOC.router_ids()] == 5).all()
    cores = NOC.core_ids()
    assert adj[np.ix_(cores, cores)].sum() == 0      # no core-core links


def test_all_pairs_reachable():
    dist = NOC.bfs_distances(NOC.fullerene_adjacency())
    assert (dist >= 0).all()
    for n_domains in (2, 3):
        d = NOC.bfs_distances(NOC.multi_domain_adjacency(n_domains))
        assert (d >= 0).all()                        # level-2 bridges connect


def test_routing_table_paths_are_shortest():
    adj = NOC.fullerene_adjacency()
    rt = NOC.RoutingTable(adj)
    cores = NOC.core_ids()
    for a in cores:
        for b in cores:
            if a == b:
                continue
            p = rt.path(int(a), int(b))
            assert len(p) - 1 == rt.dist[a, b]
            for u, v in zip(p[:-1], p[1:]):          # every hop is a link
                assert adj[u, v] == 1


def test_multi_domain_ids_and_l2_accounting():
    n_domains = 2
    adj = NOC.multi_domain_adjacency(n_domains)
    cores = NOC.multi_domain_core_ids(n_domains)
    l2 = frozenset(int(x) for x in NOC.level2_node_ids(n_domains))
    assert len(cores) == n_domains * NOC.N_CORES
    assert all(adj[c].sum() == 3 for c in cores)
    rt = NOC.RoutingTable(adj)
    # a cross-domain route must traverse the level-2 bridge
    src, dst = int(cores[0]), int(cores[-1])
    fr = NOC.compile_flow(rt, src, [dst], l2)
    assert fr.l2_hops >= 3                           # in-link, bridge, out-link
    assert fr.l1_hops == fr.hops - fr.l2_hops
    # an intra-domain route never touches level 2
    fr_local = NOC.compile_flow(rt, int(cores[0]), [int(cores[5])], l2)
    assert fr_local.l2_hops == 0


def test_broadcast_forks_share_prefix_links():
    """A 1-to-N broadcast traverses the shared path prefix once (the
    connection-matrix fork), so charged hops < sum of per-dst path hops."""
    adj = NOC.fullerene_adjacency()
    rt = NOC.RoutingTable(adj)
    cores = [int(c) for c in NOC.core_ids()]
    src, dsts = cores[0], cores[5:11]
    fr = NOC.compile_flow(rt, src, dsts)
    per_dst = sum(len(rt.path(src, d)) - 1 for d in dsts)
    assert fr.mode == "broadcast"
    assert fr.hops < per_dst
    assert fr.hops == len(fr.links)


def test_self_flow_replay_and_contention_by_hand():
    """A flow whose destinations include its own source core (a recurrent
    layer's back-edge): the source's delivery crosses no link, so the
    replay charges only the links to the other cores."""
    adj = NOC.fullerene_adjacency()
    rt = NOC.RoutingTable(adj)
    cores = [int(c) for c in NOC.core_ids()]
    src = cores[0]
    other = max(cores, key=lambda c: len(rt.path(src, c)))
    fr = NOC.compile_flow(rt, src, [src, other])
    path = rt.path(src, other)
    n = len(path) - 1                           # the farthest core: 4 links
    assert fr.mode == "broadcast" and fr.hops == n >= 4
    assert set(fr.links) == set(zip(path[:-1], path[1:]))
    table = NOC.compile_flow_table([fr])
    hops, pj, load = NOC.replay_flows_exact(table, np.array([[5.0], [0.0]]))
    assert hops.tolist() == [5.0 * n, 0.0]
    assert pj[0] == 5 * (n * NOC.RouterParams().e_hop_bcast_pj) \
        and pj[1] == 0
    want = np.zeros(NOC.N_NODES)
    want[path[:-1]] = 5.0                      # each sender on the path
    np.testing.assert_array_equal(load[0], want)
    # one step: the busiest router holds 5 spikes at 0.4 spikes/cycle
    cyc = NOC.contention_cycles(load.max(axis=-1), np.array([50.0, 50.0]))
    assert cyc[0] == 12.5 + 12.5 ** 2 / 50.0 and cyc[1] == 0.0
    # a core's delivery to itself alone crosses nothing
    own = NOC.compile_flow_table([NOC.compile_flow(rt, src, [src])])
    h, e, ld = NOC.replay_flows_exact(own, np.array([9.0]))
    assert h == 0.0 and e == 0.0 and not ld.any()
