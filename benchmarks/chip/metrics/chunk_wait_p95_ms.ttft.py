"""Chunk-FIFO wait, 95th percentile: admission to the request's first
prefill chunk, behind older prompts' chunks (scheduler)."""

from chipbench.lifecycle import p95_ms


def read(run):
    return p95_ms(run, "t_admitted", "t_prefill_start")
