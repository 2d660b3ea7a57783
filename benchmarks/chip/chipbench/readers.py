"""What the metric readers share (metrics/<name>.py each pick one).

Every reader takes the run (`run.RunData`) and returns a number, or None
where the run holds nothing to read: the harness then leaves the metric
out of the result line. Shares are in percent.
"""

from __future__ import annotations

from typing import Optional

from chipbench.stats import percentile


def ttft_p95_ms(run) -> Optional[float]:
    """95th percentile, over every request due in the window, of the time
    from when it was due to its first token."""
    v = [r.stamps[0] - r.item.due for r in run.measured if r.stamps]
    p = percentile(v, 95)
    return None if p is None else 1e3 * p


def tpot_p95_ms(run) -> Optional[float]:
    """95th percentile, over every request due in the window, of its time
    per output token after the first: (last - first) / (tokens - 1)."""
    v = [(r.stamps[-1] - r.stamps[0]) / (len(r.stamps) - 1)
         for r in run.measured if len(r.stamps) > 1]
    p = percentile(v, 95)
    return None if p is None else 1e3 * p


def prefix_hit_share(run) -> Optional[float]:
    """Prompt tokens served from cached pages, of all prompt tokens
    admitted in the window."""
    w = run.window
    hit = w.delta("prefix_hit_tokens")
    total = hit + w.delta("prefill_tokens")
    return None if total <= 0 else 100.0 * hit / total


def output_tokens_per_s(run) -> Optional[float]:
    """Tokens emitted in the window [t0, t1) by all requests, over the
    window's seconds."""
    w = run.window
    n = sum(1 for r in run.records for t in r.stamps if w.t0 <= t < w.t1)
    return n / w.seconds if n else None


def batch_occupancy(run) -> Optional[float]:
    """Mean share of the slots that decoded in each decode step of the
    window."""
    steps = run.window.delta("decode_steps")
    return None if steps <= 0 else \
        100.0 * run.window.delta("occupancy_sum") / steps


def program_ms(run, program: str) -> Optional[float]:
    """Mean device time of one execution of a jitted program."""
    if run.trace is None or not run.trace.program_s.get(program):
        return None
    v = run.trace.program_s[program]
    return 1e3 * sum(v) / len(v)


def device_idle_share(run) -> Optional[float]:
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
