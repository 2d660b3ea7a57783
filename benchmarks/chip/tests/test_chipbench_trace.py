"""The trace reducer: busy and idle time, per-kernel and per-program device
time, and idle gaps labelled by the host span they fall in. Checked on a
hand-built trace whose answers are known, and on a small trace recorded
on a v5e chip by the harness itself (tests/data/chip-trace.xplane.pb:
0.12 s of smollm-chat)."""

import os
import types

import pytest

from chipbench_testlib import DATA
from chipbench import trace

KERNELS = ("decode_attention", "flash_attention_paged", "int8_matmul")


def ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start,
                                 duration_ns=dur, stats=stats)


def plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k, events=v) for k, v in lines.items()])


def fake_trace():
    host = plane("/host:CPU", {"python": [
        ev("bench.window", 1000, 10000),
        ev("bench.step", 1000, 4000), ev("bench.wait", 5000, 3000),
        ev("bench.step", 8000, 3000)]})
    ops = [ev("fusion.1", 500, 1000),                  # starts before
           ev("custom-call.7", 2000, 1000,
              long_name="decode_attention"),
           ev("fusion.2", 2500, 1000),                 # overlaps the kernel
           ev("custom-call.9", 9000, 500, tf_op="int8_matmul.1"),
           ev("fusion.3", 10500, 2000)]                # runs past the end
    mods = [ev("jit__decode(3)", 1500, 2500), ev("jit__chunk(4)", 8500, 1500),
            ev("jit__decode(3)", 10500, 2000)]         # not inside: left out
    dev = plane("/device:TPU:0", {trace.OPS_LINE: ops,
                                  trace.MODULES_LINE: mods})
    return types.SimpleNamespace(planes=[host, dev])


def test_reduce_hand_built_trace():
    red = trace.reduce(fake_trace(), KERNELS)
    assert red.window_s == pytest.approx(10000e-9)
    # union within [1000, 11000]: [1000,1500] + [2000,3500] + [9000,9500]
    # + [10500,11000]
    assert red.busy_s == pytest.approx(3000e-9)
    assert red.idle_share == pytest.approx(0.7)
    assert red.kernel_s == {"decode_attention": pytest.approx(1000e-9),
                            "int8_matmul": pytest.approx(500e-9)}
    assert red.program_s == {"_decode": [pytest.approx(2500e-9)],
                             "_chunk": [pytest.approx(1500e-9)]}
    gaps = dict((round(s * 1e9), name) for name, s in red.idle_gaps)
    # [3500,9000] mid 6250 in bench.wait; [1500,2000] in the first step;
    # [9500,10500] in the second step
    assert gaps == {5500: "bench.wait", 500: "bench.step",
                    1000: "bench.step"}
    assert red.idle_gaps[0] == ("bench.wait", pytest.approx(5500e-9))
    assert red.device_ops[0][0] in ("fusion.1", "fusion.2", "custom-call.7")


def test_reduce_needs_one_window_span():
    t = fake_trace()
    t.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(t, KERNELS)


def test_reduce_recorded_chip_trace():
    path = DATA / "chip-trace.xplane.pb"
    red = trace.reduce(trace.load(str(path)), KERNELS)
    assert red.n_devices == 1
    assert 0.05 < red.window_s < 1.0
    assert 0 < red.busy_s < red.window_s
    assert red.kernel_s["decode_attention"] > 0
    assert red.kernel_s["flash_attention_paged"] > 0
    assert "int8_matmul" not in red.kernel_s          # a bf16 configuration
    assert sum(red.kernel_s.values()) < red.busy_s
    assert red.program_s["_decode"] and red.program_s["_chunk"]
    assert all(0 < s < red.window_s for v in red.program_s.values()
               for s in v)
    idle = red.window_s - red.busy_s
    assert 0 < sum(s for _, s in red.idle_gaps) <= idle * (1 + 1e-9)
    assert {name for name, _ in red.idle_gaps} <= set(
        trace.HOST_SPANS) | {"outside_spans"}


def test_load_reads_a_traced_run_once(tmp_path):
    """The reduction and the trace readers of one run share one load: the
    same file, named directly or by its directory, is read once; a file
    written anew is read again."""
    d = tmp_path / "cell" / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    f = d / "host.xplane.pb"
    f.write_bytes((DATA / "chip-trace.xplane.pb").read_bytes())
    first = trace.load(str(f))
    assert trace.load(str(tmp_path / "cell")) is first
    assert trace.reduce(first, KERNELS).n_devices == 1
    f.write_bytes((DATA / "chip-trace.xplane.pb").read_bytes())
    os.utime(f, (1, 1))
    assert trace.load(str(f)) is not first
