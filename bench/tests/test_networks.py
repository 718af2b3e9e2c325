"""The network hook (`registry.network`) gives what the chain code gave:
weights, trains, mapping plan, reference and control bit for bit, on a
seed under 2**32 and one above it."""
import hashlib
import json

import numpy as np
import pytest

from bench import registry, workload
import bench.run as R

GOLDEN = registry.load_json(registry.BENCH_DIR / "tests" / "fixtures"
                            / "nmnist_mlp_golden.json")


def digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_absent_network_key_is_the_dense_chain():
    cfg = registry.load_json(registry.BENCH_DIR / "configs"
                             / "nmnist_mlp.json")
    assert "network" not in cfg
    assert registry.network(cfg) is registry.network(
        dict(cfg, network="dense_chain"))
    assert registry.network(cfg).n_in(cfg) == 2312
    with pytest.raises(FileNotFoundError, match="no network named"):
        registry.network(dict(cfg, network="no_such_kind"))


@pytest.mark.parametrize("seed", sorted(GOLDEN["seeds"], key=int))
def test_hook_matches_the_chain_digests(seed):
    want = GOLDEN["seeds"][seed]
    seed = int(seed)
    cell = registry.files_cell(GOLDEN["config"], "closed_b32_compiled")
    cfg = cell.config
    net = registry.network(cfg)
    program, layers = net.make(cfg, seed)
    trains = workload.make_trains(cfg, 8, seed)
    _, sim, _, _, plan = R.build(cell, seed, {})
    del sim
    counts, fields = net.reference(layers, trains[:4], cfg, plan)
    c_counts, c_fields = net.reference(layers, trains[:4], cfg, plan,
                                       control=True)
    got = {
        "idx": digest(*[lc.idx for lc in layers]),
        "words": digest(*[lc.words for lc in layers]),
        "scales": digest(*[np.float32(lc.scale) for lc in layers]),
        "program": digest(*[x for q in program
                            for x in (q.idx, q.codebook, q.scale)]),
        "trains": digest(trains),
        "plan": hashlib.sha256(json.dumps(plan, sort_keys=True)
                               .encode()).hexdigest(),
        "reference": digest(counts, fields),
        "control": digest(c_counts, c_fields),
    }
    assert got == want
