"""Mean share of the slots decoding in each decode step of the window
(scheduler)."""

from chipbench.readers import batch_occupancy as read  # noqa: F401
