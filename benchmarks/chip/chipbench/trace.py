"""Reduce a profiler trace (`.xplane.pb`) to device busy time, per-kernel
and per-program device time, and idle gaps labelled by the host.

The serving loop marks the traced window with a host span (`WINDOW`) and each
call into the engine with spans of its own (`HOST_SPANS`), all on the
profiler's clock. On each device plane (`/device:TPU:<n>`), the "XLA Ops"
line holds every operation that ran and the "XLA Modules" line every
program execution. Within the window:

  busy_s     the union of the operations' intervals, averaged over devices
  kernel_s   summed device time of the ops whose name or HLO metadata
             names a kernel (`decode_attention`, ...; the cell's kernels,
             `run.kernel_names`), summed over devices
  program    device durations of each execution of a jitted program,
             keyed by the program's function name (`_decode`, `_chunk`)
  idle gaps  the intervals of device 0 with no operation, each labelled by
             the host span that covers its middle
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

WINDOW = "bench.window"
HOST_SPANS = ("bench.step", "bench.submit", "bench.wait")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_LABEL_STATS = ("long_name", "tf_op", "hlo_op", "name", "kernel_name")


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    n_devices: int
    kernel_s: Dict[str, float]
    program_s: Dict[str, List[float]]
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str):
    """The profile at `path`, a `.xplane.pb` or the newest under a
    directory. The last file loaded is kept, so that the reduction and the
    readers of one traced run share one load."""
    if os.path.isdir(path):
        path = find_xplane(path)
    return _load_file(os.path.realpath(path), os.path.getmtime(path))


@functools.lru_cache(maxsize=1)
def _load_file(path: str, mtime: float):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _stats(ev) -> Dict[str, object]:
    try:
        return dict(ev.stats)
    except Exception:           # an event with no readable stats
        return {}


def _label(ev) -> str:
    st = _stats(ev)
    return " ".join([ev.name] + [str(st[k]) for k in _LABEL_STATS if k in st])


def kernels_in(label: str, kernels: Sequence[str]) -> List[str]:
    """The kernels, in the order given, whose names `label` holds, less a
    name it holds only inside a longer one: a kernel named after another
    (`decode_attention_latent` beside `decode_attention`) is told apart
    from it."""
    hit = [k for k in kernels if k in label]
    return [k for k in hit if not any(k != o and k in o for o in hit)]


def _union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def _program_name(module: str) -> str:
    """`jit__decode(12)` -> `_decode`."""
    m = re.match(r"^jit_(.*?)(\(\d+\))?$", module)
    return m.group(1) if m else module


def host_spans(pdata, names: Sequence[str]) -> List[Tuple[str, float, float]]:
    out = []
    for plane in pdata.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def reduce(pdata, kernels: Sequence[str], *, top: int = 10) -> Reduced:
    windows = host_spans(pdata, (WINDOW,))
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found "
                         f"{len(windows)}")
    _, w0, w1 = windows[0]
    spans = sorted(host_spans(pdata, HOST_SPANS), key=lambda s: s[1])
    devices = [p for p in pdata.planes
               if re.match(r"^/device:TPU:\d+$", p.name)]
    if not devices:
        raise ValueError("the trace holds no TPU device plane: planes "
                         f"{[p.name for p in pdata.planes]}")
    busy_total = 0.0
    kernel_ns: Dict[str, float] = collections.defaultdict(float)
    op_ns: Dict[str, float] = collections.defaultdict(float)
    program_ns: Dict[str, List[float]] = collections.defaultdict(list)
    gaps: List[Tuple[float, float]] = []
    for d, plane in enumerate(devices):
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            raise ValueError(f"{plane.name} has no {OPS_LINE!r} line: "
                             f"{sorted(lines)}")
        ivals = []
        for ev in lines[OPS_LINE].events:
            c = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, w0, w1)
            if c is None:
                continue
            ivals.append(c)
            op_ns[ev.name] += c[1] - c[0]
            found = kernels_in(_label(ev), kernels)
            if found:
                kernel_ns[found[0]] += c[1] - c[0]
        merged = _union(ivals)
        busy_total += sum(b - a for a, b in merged)
        if MODULES_LINE in lines:
            for ev in lines[MODULES_LINE].events:
                if w0 <= ev.start_ns and ev.start_ns + ev.duration_ns <= w1:
                    program_ns[_program_name(ev.name)].append(
                        float(ev.duration_ns))
        if d == 0:
            edge = w0
            for a, b in merged:
                if a > edge:
                    gaps.append((edge, a))
                edge = b
            if w1 > edge:
                gaps.append((edge, w1))
    n = len(devices)

    def label_gap(a: float, b: float) -> str:
        mid = (a + b) / 2
        inside = [s for s in spans if s[1] <= mid <= s[2]]
        return inside[-1][0] if inside else "outside_spans"

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return Reduced(
        window_s=(w1 - w0) / 1e9,
        busy_s=busy_total / n / 1e9,
        n_devices=n,
        kernel_s={k: v / 1e9 for k, v in kernel_ns.items()},
        program_s={k: [x / 1e9 for x in v] for k, v in program_ns.items()},
        device_ops=[(name, ns / 1e9) for name, ns in sorted(
            op_ns.items(), key=lambda kv: kv[1], reverse=True)[:top]],
        idle_gaps=[(label_gap(a, b), (b - a) / 1e9) for a, b in gaps[:top]])
