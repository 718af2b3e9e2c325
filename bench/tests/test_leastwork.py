"""Least operations and bytes, and sop_mfu, against hand counts."""
import importlib.util
import types

import pytest

from bench import leastwork, registry

V5E = leastwork.peaks("TPU v5 lite")


def config(name):
    return registry.load_json(registry.BENCH_DIR / "configs" / f"{name}.json")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        leastwork.peaks("cpu")


@pytest.mark.parametrize("batch,by_hand", [
    # weights at 4 bits + 3 tables of 16 bytes + Bx20x2312 spike bits
    # + Bx10 int32 counts
    (32, (2312 * 4096 + 4096 * 1024 + 1024 * 10) / 2 + 48
     + 32 * 20 * 2312 / 8 + 32 * 10 * 4),
    (8, (2312 * 4096 + 4096 * 1024 + 1024 * 10) / 2 + 48
     + 8 * 20 * 2312 / 8 + 8 * 10 * 4),
])
def test_least_bytes_by_hand(batch, by_hand):
    cfg = config("nmnist_mlp")
    assert registry.network(cfg).least_bytes(cfg, batch) == by_hand


def test_least_time_picks_the_larger_bound():
    cfg = config("nmnist_mlp")
    t, bound = leastwork.least_time(cfg, 32, 32 * 8.66e6, V5E)
    assert bound == "bytes"
    assert t == pytest.approx(
        registry.network(cfg).least_bytes(cfg, 32) / 819e9)
    t, bound = leastwork.least_time(cfg, 32, 1e12, V5E)
    assert bound == "ops" and t == pytest.approx(2e12 / 197e12)


@pytest.mark.parametrize("sops,seconds", [(8.66e6 * 25000, 10.0),
                                          (4.64e6 * 80000, 10.0)])
def test_sop_mfu_by_hand(sops, seconds):
    spec = importlib.util.spec_from_file_location(
        "m", registry.BENCH_DIR / "metrics" / "sop_mfu.batch.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    run = types.SimpleNamespace(
        drive={"performed_sops": sops, "window_s": seconds}, peak=V5E)
    assert mod.read(run) == pytest.approx(100 * 2 * sops / seconds / 197e12)
