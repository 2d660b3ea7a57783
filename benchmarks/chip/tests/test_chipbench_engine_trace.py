"""Readers of the serving engine's own records: device idle put down to the
engine's host work (`chipbench/engine_trace.py`), checked on a hand-built
trace whose answer is known, and the TTFT split by lifecycle stamps
(`chipbench/lifecycle.py`)."""

import types

import numpy as np
import pytest

from chipbench_testlib import DATA, REPO
from chipbench import engine_trace, lifecycle, serving, trace
from chipbench.spec import Bench
from chipbench.traffic import Item
from test_chipbench_trace import KERNELS, ev, plane


def engine_fake_trace(engine_spans=True):
    """Window [1000, 11000]. Steps [1000, 5000] and [6000, 10000] with a
    read-back at the end of each, and a step that runs past the window;
    device ops [500, 2000], [3000, 4500], [6500, 9500], [10500, 12000]."""
    host = [ev("bench.window", 1000, 10000), ev("bench.step", 1000, 4200),
            ev("bench.step", 6000, 4000)]
    if engine_spans:
        host += [ev("engine.step", 1000, 4000, tick=1),
                 ev("engine.prefill_chunk", 1500, 1000, rid=1, slot=0),
                 ev("engine.readback", 4000, 1000),
                 ev("engine.step", 6000, 4000, tick=2),
                 ev("engine.readback", 9000, 1000),
                 ev("engine.step", 10800, 700, tick=3)]
    ops = [ev("fusion.1", 500, 1500), ev("fusion.2", 3000, 1500),
           ev("fusion.3", 6500, 3000), ev("fusion.4", 10500, 1500)]
    dev = plane("/device:TPU:0", {trace.OPS_LINE: ops})
    return types.SimpleNamespace(planes=[plane("/host:CPU", {"py": host}),
                                         dev])


def test_host_idle_share_hand_built_trace():
    """Steps less read-backs: [1000, 4000] + [6000, 9000] + [10800, 11000];
    device idle within them: [2000, 3000] + [6000, 6500] = 1500 of 10000.
    The device's whole idle share is 4000 of 10000."""
    t = engine_fake_trace()
    assert engine_trace.host_idle_share(t) == pytest.approx(0.15)
    assert trace.reduce(t, KERNELS).idle_share == pytest.approx(0.4)


def test_host_idle_share_needs_the_engine_spans():
    """A program without engine spans (or a trace without its window)
    reads nothing."""
    assert engine_trace.host_idle_share(engine_fake_trace(False)) is None
    t = engine_fake_trace()
    t.planes[0].lines[0].events.pop(0)
    assert engine_trace.host_idle_share(t) is None


@pytest.mark.parametrize("a,b,want", [
    ([[0, 10]], [], [[0, 10]]),
    ([[0, 10]], [[2, 3], [5, 7]], [[0, 2], [3, 5], [7, 10]]),
    ([[0, 4], [6, 10]], [[3, 7]], [[0, 3], [7, 10]]),
    ([[2, 4]], [[0, 10]], []),
    ([[0, 2], [8, 10]], [[4, 6]], [[0, 2], [8, 10]]),
])
def test_interval_difference(a, b, want):
    assert engine_trace._minus(a, b) == want


def test_reader_finds_the_cells_trace(tmp_path, monkeypatch):
    """The reader opens the trace `run.py` wrote for the cell, and reads
    nothing for an untraced run or a cell with no trace."""
    monkeypatch.setattr(engine_trace, "TRACE_ROOT", tmp_path)
    monkeypatch.setattr(engine_trace, "load",
                        lambda path: engine_fake_trace())
    run = types.SimpleNamespace(cell={"name": "smollm-chat"},
                                trace=object())
    assert engine_trace.read(run) is None                 # no file
    d = tmp_path / "smollm-chat" / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
    assert engine_trace.read(run) == pytest.approx(15.0)
    run.trace = None
    assert engine_trace.read(run) is None


def _rec(**stamps):
    r = serving.Record(item=Item(due=0.0, prompt=np.zeros(4, np.int32),
                                 max_new=4))
    r.req = types.SimpleNamespace(**stamps)
    return r


def _fake_run(measured):
    return types.SimpleNamespace(measured=measured, records=measured,
                                 trace=None)


def test_lifecycle_readers_split_ttft():
    """Each reader takes the 95th percentile of its stamp difference over
    the requests that ran a chunk; each metric file reads the pair of
    stamps its docstring names."""
    recs = [_rec(t_enqueue=0.0, t_admitted=0.1 * i,
                 t_prefill_start=0.1 * i + 0.01 * i,
                 t_prefill_end=0.1 * i + 0.01 * i + 0.5)
            for i in range(1, 21)]
    # a full prefix hit ran no chunk, and a refused request has no Request
    recs.append(_rec(t_enqueue=0.0, t_admitted=9.0, t_prefill_start=None,
                     t_prefill_end=9.5))
    refused = serving.Record(item=recs[0].item)
    run = _fake_run(recs + [refused])
    bench = Bench(REPO)
    got = {m: bench.reader(m)(run) for m in (
        "queue_wait_p95_ms.ttft", "chunk_wait_p95_ms.ttft",
        "prefill_span_p95_ms.ttft")}
    assert got["queue_wait_p95_ms.ttft"] == pytest.approx(
        1e3 * np.percentile([0.1 * i for i in range(1, 21)], 95))
    assert got["chunk_wait_p95_ms.ttft"] == pytest.approx(
        1e3 * np.percentile([0.01 * i for i in range(1, 21)], 95))
    assert got["prefill_span_p95_ms.ttft"] == pytest.approx(500.0)


def test_lifecycle_readers_read_nothing_without_stamps():
    """No request ran a chunk, or the program's requests lack the stamps
    (a program from before them): no reading."""
    hit = _fake_run([_rec(t_enqueue=0.0, t_admitted=1.0,
                          t_prefill_start=None, t_prefill_end=2.0)])
    old = _fake_run([_rec(t_enqueue=0.0, t_first_token=1.0)])
    for run in (hit, old, _fake_run([])):
        assert lifecycle.p95_ms(run, "t_enqueue", "t_admitted") is None


def test_recorded_chip_trace_with_engine_spans():
    """A trace recorded on a v5e chip by the harness with the Python
    tracer off (tests/data/engine-trace.xplane.pb: 0.23 s of smollm-chat,
    two ticks): the engine's spans sit inside the harness's `bench.step`,
    each chunk span names its request, slot, start and length, and the
    device's idle time on the engine's host work is part of its idle
    time."""
    pdata = trace.load(str(DATA / "engine-trace.xplane.pb"))
    spans = trace.host_spans(pdata, (
        "bench.step", "engine.step", "engine.admit", "engine.prefill_chunk",
        "engine.decode", "engine.readback"))
    steps = [s for s in spans if s[0] == "bench.step"]
    engine = [s for s in spans if s[0].startswith("engine.")]
    assert {s[0] for s in engine} >= {"engine.step", "engine.prefill_chunk",
                                      "engine.decode", "engine.readback"}
    assert all(any(b[1] <= s[1] and s[2] <= b[2] for b in steps)
               for s in engine)
    chunks = [dict(ev.stats) for p in pdata.planes for ln in p.lines
              for ev in ln.events if ev.name == "engine.prefill_chunk"]
    assert chunks and all(set(c) == {"rid", "slot", "start", "length"}
                          for c in chunks)
    assert all(c["start"] % 64 == 0 and 0 < c["length"] <= 64
               for c in chunks)
    share = engine_trace.host_idle_share(pdata)
    assert 0 < share <= trace.reduce(pdata, KERNELS).idle_share
