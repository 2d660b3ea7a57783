"""Prefill span, 95th percentile: the request's first chunk to its slot
going live, one chunk a tick with decode between (scheduler)."""

from chipbench.lifecycle import p95_ms


def read(run):
    return p95_ms(run, "t_prefill_start", "t_prefill_end")
