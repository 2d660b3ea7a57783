"""Time to first token, 95th percentile over the window's requests."""

from chipbench.readers import ttft_p95_ms as read  # noqa: F401
