"""The batch path's profiler spans, recorded on the CPU.

`_EngineBase.run_batch` and `SnnServer` mark their phases with
`jax.profiler.TraceAnnotation` spans whose counts ride as span stats.
Here `jax.profiler.TraceAnnotation` is replaced by a recorder, so each
span's name, stats and parent are checked without a profiler, and the
engine's `np.asarray` is counted, so the readback span is held to the
arrays the function really reads.
"""
import jax
import numpy as np
import pytest

from repro.core import engine as ENG
from repro.core.soc import ChipSimulator
from repro.serve import SnnRequest, SnnServer

PHASES = ("snn.upload", "snn.dispatch", "snn.device_wait", "snn.readback",
          "snn.noc_replay", "snn.price")


class Recorder:
    """Stands in for `jax.profiler.TraceAnnotation`: each span entered is
    kept as {name, stats, parent} in order of entry."""

    def __init__(self):
        self.spans: list[dict] = []
        self.stack: list[dict] = []

    def __call__(self, name, **stats):
        rec = self

        class Span:
            def __enter__(self):
                entry = {"name": name, "stats": stats,
                         "parent": rec.stack[-1]["name"] if rec.stack
                         else None}
                rec.spans.append(entry)
                rec.stack.append(entry)

            def __exit__(self, *exc):
                rec.stack.pop()
                return False

        return Span()

    def active(self):
        return self.stack[-1]["name"] if self.stack else None


class CountingNumpy:
    """The engine's `np`, with each device array it converts counted
    under the span active at the time."""

    def __init__(self, rec: Recorder):
        self.rec = rec
        self.reads: list[tuple[str | None, int]] = []

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kw):
        if isinstance(a, jax.Array):
            self.reads.append((self.rec.active(), int(a.nbytes)))
        return np.asarray(a, *args, **kw)


@pytest.fixture
def rec(monkeypatch):
    r = Recorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", r)
    return r


def _net(seed=0, sizes=(8, 16, 4)):
    rng = np.random.default_rng(seed)
    return [rng.normal(0, 0.5, (a, b)).astype(np.float32)
            for a, b in zip(sizes[:-1], sizes[1:])]


@pytest.mark.parametrize("engine", ["compiled", "fused"])
def test_run_batch_emits_each_phase_once_per_call(rec, monkeypatch, engine):
    sim = ChipSimulator(_net(), engine=engine)
    eng = sim.array_engine()
    counting = CountingNumpy(rec)
    monkeypatch.setattr(ENG, "np", counting)
    trains = (np.random.default_rng(1).random((4, 3, 8)) < 0.4
              ).astype(np.float32)
    for _ in range(2):
        sim.run_batch(trains)

    calls = [s for s in rec.spans if s["name"] == "snn.run_batch"]
    assert [s["stats"] for s in calls] == [
        {"call": 1, "batch": 4, "steps": 3},
        {"call": 2, "batch": 4, "steps": 3}]
    assert all(s["parent"] is None for s in calls)
    inner = [s for s in rec.spans if s["name"] != "snn.run_batch"]
    assert [s["name"] for s in inner] == list(PHASES) * 2
    assert all(s["parent"] == "snn.run_batch" for s in inner)

    uploads = [s for s in inner if s["name"] == "snn.upload"]
    assert [s["stats"] for s in uploads] == [{"bytes": trains.nbytes}] * 2
    # every device array the call converts is read inside snn.readback
    assert {span for span, _ in counting.reads} == {"snn.readback"}
    readbacks = [s["stats"] for s in inner if s["name"] == "snn.readback"]
    # one conversion a call: the slab of counts and counters
    assert len(counting.reads) == 2
    assert [r["transfers"] for r in readbacks] == [1, 1]
    per_call = len(counting.reads) // 2
    assert readbacks == [{"transfers": per_call,
                          "bytes": sum(b for _, b in counting.reads[:per_call])
                          }] * 2
    flows = sum(ft.n_flows for ft in eng.tables.flows if ft is not None)
    assert flows > 0
    assert [s["stats"] for s in inner if s["name"] == "snn.noc_replay"] \
        == [{"flows": flows, "back_flows": 0}] * 2


@pytest.mark.parametrize("engine", ["compiled", "fused"])
def test_run_batch_span_counts_word_layers(rec, engine):
    """Tables of exact integer words x a power-of-two step: the fused
    engine runs both layers on the word layout and its call span says
    so (`word_layers`); the compiled engine's span has no such stat."""
    from repro.core.quant import CodebookConfig, QuantizedTensor

    rng = np.random.default_rng(0)
    words = np.concatenate([-np.arange(8, 0, -1), np.arange(1, 9)])
    weights = [QuantizedTensor(
        idx=jax.numpy.asarray(rng.integers(0, 16, (a, b)), "int8"),
        codebook=jax.numpy.asarray(words[None] * 2.0 ** -4, "float32"),
        scale=jax.numpy.asarray([2.0 ** -4], "float32"), group_axis_size=0)
        for a, b in ((8, 16), (16, 4))]
    sim = ChipSimulator(weights, engine=engine,
                        quant_cfg=CodebookConfig(16, 8))
    sim.run_batch((rng.random((2, 3, 8)) < 0.4).astype(np.float32))
    (call,) = [s for s in rec.spans if s["name"] == "snn.run_batch"]
    want = {"call": 1, "batch": 2, "steps": 3}
    if engine == "fused":
        want["word_layers"] = 2
    assert call["stats"] == want


@pytest.mark.parametrize("engine", ["compiled", "fused"])
def test_noc_replay_counts_back_flows(rec, engine):
    """A recurrent layer's flows, one tree per core slice to the readout
    and back to the layer, ride as the replay span's `back_flows` stat
    too."""
    w_in, w_out = _net()
    w_rec = np.random.default_rng(9).normal(0, 0.5, (16, 16))
    sim = ChipSimulator([np.concatenate([w_in, w_rec]).astype(np.float32),
                         w_out], engine=engine, recurrent=(0,))
    sim.run_batch((np.random.default_rng(1).random((2, 3, 8)) < 0.4
                   ).astype(np.float32))
    slices = len(sim.mapping.cores_of_layer(1))
    assert slices > 1
    assert [s["stats"] for s in rec.spans if s["name"] == "snn.noc_replay"] \
        == [{"flows": slices, "back_flows": slices}]


def test_device_array_input_uploads_nothing(rec):
    sim = ChipSimulator(_net(), engine="compiled")
    trains = jax.numpy.zeros((2, 3, 8), jax.numpy.float32)
    sim.run_batch(trains)
    assert [s["stats"] for s in rec.spans if s["name"] == "snn.upload"] \
        == [{"bytes": 0}]


def test_server_uploads_the_group_it_assembles(rec):
    srv = SnnServer(ChipSimulator(_net(), engine="compiled"), batch_slots=4)
    rng = np.random.default_rng(2)
    for uid in range(3):
        srv.submit(SnnRequest(uid=uid, events=(rng.random((5, 8)) < 0.3
                                               ).astype(np.float32)))
    served = srv.step()
    assert len(served) == 3

    names = [(s["name"], s["parent"]) for s in rec.spans]
    # the group's upload, outside the engine call, then the engine's own
    assert names[:3] == [("snn.upload", None), ("snn.run_batch", None),
                         ("snn.upload", "snn.run_batch")]
    group, engine_upload = rec.spans[0], rec.spans[2]
    assert group["stats"] == {"bytes": 4 * 5 * 8 * 4}    # slots x T x n_in
    assert engine_upload["stats"] == {"bytes": 0}
