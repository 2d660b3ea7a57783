"""Where a request's time to first token goes, from the engine's own
lifecycle stamps (`serve/engine.Request`, one monotonic clock):

  t_enqueue -> t_admitted        queue: waiting for a slot and its pages
  t_admitted -> t_prefill_start  chunk FIFO: behind older prompts' chunks
  t_prefill_start -> t_prefill_end  its own chunks, one a tick, with the
                                 decode batch between them

Each is read over the window's measured requests that ran at least one
prefill chunk. A program whose requests carry no such stamps gives None.
"""

from __future__ import annotations

from typing import Optional

from chipbench.stats import percentile


def p95_ms(run, since: str, until: str) -> Optional[float]:
    """95th percentile of `until - since`, in ms, over the measured
    requests that ran a prefill chunk and hold both stamps."""
    v = []
    for rec in run.measured:
        req = rec.req
        if getattr(req, "t_prefill_start", None) is None:
            continue
        a, b = getattr(req, since, None), getattr(req, until, None)
        if a is not None and b is not None:
            v.append(b - a)
    p = percentile(v, 95)
    return None if p is None else 1e3 * p
