"""Time per output token, 95th percentile over the window's requests."""

from chipbench.readers import tpot_p95_ms as read  # noqa: F401
