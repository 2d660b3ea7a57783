"""Device idle time put down to the serving engine's own host work.

The engine marks each tick with host spans on the profiler's clock
(`serve/engine.ServeEngine.step`): `engine.step` around the whole tick and
`engine.readback` around its host syncs on the decode step's results. In
the traced window, device 0 is idle on the engine's host work where it runs
no operation while the serving thread is inside an `engine.step` and not
inside an `engine.readback`: building inputs, dispatching, scheduling and
bookkeeping, but not waiting for the device's answer. That share is at
most the device's whole idle share.

The trace is the one `run.py` writes for the cell, under
`bench-out/trace/<cell>` at the root of the checkout.
"""

from __future__ import annotations

import pathlib
import re
from typing import List, Optional, Sequence, Tuple

from chipbench.trace import (
    OPS_LINE, WINDOW, _clip, _union, find_xplane, host_spans, load)

TRACE_ROOT = pathlib.Path(__file__).resolve().parents[3] / "bench-out" \
    / "trace"
STEP, READBACK = "engine.step", "engine.readback"

Intervals = List[List[float]]


def _minus(a: Intervals, b: Intervals) -> Intervals:
    """The parts of `a` outside `b` (both sorted and disjoint)."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > lo:
                out.append([lo, b[k][0]])
            lo = max(lo, b[k][1])
            k += 1
        if hi > lo:
            out.append([lo, hi])
    return out


def _total(a: Sequence[Sequence[float]]) -> float:
    return sum(hi - lo for lo, hi in a)


def _clipped(ivals: Sequence[Tuple[float, float]], w0: float,
             w1: float) -> Intervals:
    return _union([c for a, b in ivals
                   if (c := _clip(a, b, w0, w1)) is not None])


def host_idle_share(pdata) -> Optional[float]:
    """Share of the traced window in which device 0 ran no operation while
    the engine did host work (in `engine.step`, not in `engine.readback`);
    None where the trace holds no window, no engine step or no device."""
    windows = host_spans(pdata, (WINDOW,))
    if len(windows) != 1:
        return None
    _, w0, w1 = windows[0]
    spans = host_spans(pdata, (STEP, READBACK))
    steps = _clipped([(a, b) for n, a, b in spans if n == STEP], w0, w1)
    if not steps:
        return None
    reads = _clipped([(a, b) for n, a, b in spans if n == READBACK], w0, w1)
    device = next((p for p in pdata.planes
                   if re.match(r"^/device:TPU:\d+$", p.name)), None)
    if device is None:
        return None
    ops = [ln for ln in device.lines if ln.name == OPS_LINE]
    busy = _clipped([(ev.start_ns, ev.start_ns + ev.duration_ns)
                     for ln in ops for ev in ln.events], w0, w1)
    idle_in_work = _minus(_minus(steps, reads), busy)
    return _total(idle_in_work) / (w1 - w0)


def span_total(pdata, name: str, stat: str) -> Optional[float]:
    """The sum of the metadata `stat` over the engine's `name` spans that
    begin in the traced window; None where the trace holds no window or
    no such span."""
    windows = host_spans(pdata, (WINDOW,))
    if len(windows) != 1:
        return None
    _, w0, w1 = windows[0]
    found = None
    for plane in pdata.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == name and w0 <= ev.start_ns < w1:
                    found = (found or 0) + dict(ev.stats).get(stat, 0)
    return found


def cell_trace(run):
    """The profiler trace `run.py` wrote for the run's cell, loaded; None
    for an untraced run or a cell with no trace file."""
    if run.trace is None:
        return None
    try:
        path = find_xplane(str(TRACE_ROOT / run.cell["name"]))
    except FileNotFoundError:
        return None
    return load(path)


def read(run) -> Optional[float]:
    """`host_idle_share` of the run's traced window, in percent; None for
    a run without a trace or a trace without the engine's spans."""
    pdata = cell_trace(run)
    if pdata is None:
        return None
    share = host_idle_share(pdata)
    return None if share is None else 100.0 * share
