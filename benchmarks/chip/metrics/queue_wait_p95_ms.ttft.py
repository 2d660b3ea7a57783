"""Queue wait, 95th percentile: submission to admission into a slot
(scheduler)."""

from chipbench.lifecycle import p95_ms


def read(run):
    return p95_ms(run, "t_enqueue", "t_admitted")
