"""The serving loop and its clock.

One thread drives the engine: it submits each request when it falls due,
runs one `engine.step()` and stamps every token that step returned with
the host clock after it (`step` reads its tokens back before it returns,
so that is when a user would see them). Every time is on one clock from
the start of the traffic; a request's latency counts from when it was
due, so a late submission costs the request and not the generator.

Host spans (`jax.profiler.TraceAnnotation`) mark the traced window and
each call into the engine: `bench.submit`, `bench.step`, and
`bench.wait` where the loop sleeps for the next arrival.

Every `step` that takes longer than `SLOW_STEP_S` is kept with the
counters it moved, and every garbage collection longer than `SLOW_GC_S`
with its generation: a stall in a tail is named, not guessed.
"""

from __future__ import annotations

import dataclasses
import gc
import heapq
import time
from typing import Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation

from chipbench.traffic import Item, Source

COUNTERS = ("decode_steps", "prefill_chunks", "tokens_out", "occupancy_sum",
            "prefix_hits", "prefix_hit_tokens", "prefill_tokens",
            "cow_copies", "preemptions", "prefills")
SLOW_STEP_S = 0.25
SLOW_GC_S = 0.05


@dataclasses.dataclass(eq=False)
class Record:
    item: Item
    req: object = None                 # the engine's Request
    stamps: List[float] = dataclasses.field(default_factory=list)
    error: Optional[str] = None


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    counters0: Dict[str, float]
    counters1: Optional[Dict[str, float]] = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def delta(self, name: str) -> float:
        return self.counters1[name] - self.counters0[name]


def counters(engine) -> Dict[str, float]:
    return {k: float(getattr(engine.stats, k)) for k in COUNTERS}


@dataclasses.dataclass
class Stalls:
    """Slow steps as (start on the run's clock, seconds, seconds of this
    thread's CPU time, counters moved): a stall that spent its time on
    the CPU was host work, one that did not was a wait. Slow garbage
    collections as (start, seconds, generation)."""
    steps: List[tuple] = dataclasses.field(default_factory=list)
    gcs: List[tuple] = dataclasses.field(default_factory=list)


class _GcClock:
    """Times the collector's passes through `gc.callbacks`."""

    def __init__(self, stalls: Stalls, start: float, clock):
        self.stalls, self.start, self.clock, self.t = stalls, start, clock, 0.0

    def __call__(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self.t = self.clock()
        elif self.clock() - self.t > SLOW_GC_S:
            self.stalls.gcs.append((self.t - self.start, self.clock() - self.t,
                                    info.get("generation")))


def slots_full(engine, records: List[Record]) -> bool:
    """Every slot holds a request, and as many requests as there are slots
    have begun to decode: the first fill of the batch is over."""
    started = sum(1 for r in records if r.stamps)
    return started >= engine.n_slots and all(
        r is not None for r in engine._slots)


def drive(engine, source: Source, *, seconds: float, lead_s: float,
          opens: str, grace_s: float = 60.0, open_by_s: float = 300.0,
          trace_s: float = 0.0,
          trace_start: Optional[Callable] = None,
          trace_stop: Optional[Callable] = None,
          clock=time.perf_counter):
    """Serve `source` until the window has closed and (open loop) every
    request due in it has finished or `grace_s` has passed. Returns
    (records, window, lateness, stalls)."""
    start = clock()
    stalls = Stalls()
    gc_clock = _GcClock(stalls, start, clock)
    gc.callbacks.append(gc_clock)
    try:
        return _serve(engine, source, seconds, lead_s, opens, grace_s,
                      open_by_s, trace_s, trace_start, trace_stop, clock,
                      start, stalls)
    finally:
        gc.callbacks.remove(gc_clock)


def _serve(engine, source, seconds, lead_s, opens, grace_s, open_by_s,
           trace_s, trace_start, trace_stop, clock, start, stalls):
    pending: list = []
    seq = 0
    for it in source.initial():
        heapq.heappush(pending, (it.due, seq, it))
        seq += 1
    records: List[Record] = []
    inflight: List[Record] = []
    lateness: List[float] = []
    window: Optional[Window] = None
    win_span = None
    tracing = False
    while True:
        now = clock() - start
        while pending and pending[0][0] <= now:
            _, _, it = heapq.heappop(pending)
            rec = Record(item=it)
            with TraceAnnotation("bench.submit"):
                try:
                    rec.req = engine.submit(it.prompt,
                                            max_new_tokens=it.max_new)
                except Exception as e:        # refused: counts as failed
                    rec.error = f"{type(e).__name__}: {e}"
            lateness.append(clock() - start - it.due)
            records.append(rec)
            if rec.req is not None:
                inflight.append(rec)
        if window is None:
            if (opens == "lead_end" and now >= lead_s) or (
                    opens == "slots_full" and slots_full(engine, records)):
                t0 = lead_s if opens == "lead_end" else now
                window = Window(t0=t0, t1=t0 + seconds,
                                counters0=counters(engine))
                if trace_s > 0:
                    trace_start()
                    win_span = TraceAnnotation("bench.window")
                    win_span.__enter__()
                    tracing = True
                    trace_t0 = clock() - start
        if tracing and now >= trace_t0 + trace_s:
            win_span.__exit__(None, None, None)
            trace_stop()
            tracing = False
        if inflight:
            before = counters(engine)
            t_step, cpu = clock(), time.thread_time()
            with TraceAnnotation("bench.step"):
                engine.step()
            t = clock() - start
            if t + start - t_step > SLOW_STEP_S:
                after = counters(engine)
                stalls.steps.append((
                    t_step - start, t + start - t_step,
                    time.thread_time() - cpu,
                    {k: after[k] - before[k] for k in COUNTERS
                     if after[k] != before[k]}))
            finished = []
            for r in inflight:
                if len(r.req.out_tokens) > len(r.stamps):
                    r.stamps.append(t)
                if r.req.done:
                    finished.append(r)
            for r in finished:
                inflight.remove(r)
                for it in source.on_done(r.item, r.req.out_tokens, t):
                    heapq.heappush(pending, (it.due, seq, it))
                    seq += 1
        elif pending:
            with TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(pending[0][0] - now, 0.05)))
        now = clock() - start
        if window is not None and window.counters1 is None \
                and now >= window.t1:
            window.counters1 = counters(engine)
        if window is not None and window.counters1 is not None \
                and not tracing:
            if opens == "slots_full":
                break
            due = [r for r in records if window.t0 <= r.item.due < window.t1]
            if all(r.req is None or r.req.done for r in due) and \
                    not [p for p in pending if p[0] < window.t1]:
                break
            if now >= window.t1 + grace_s:
                break
        if window is None and (now > open_by_s or not (inflight or pending)):
            raise RuntimeError(f"the window did not open ({opens}) in "
                               f"{now:.0f} s of traffic")
    return records, window, lateness, stalls
