"""Share of the traced window with no device operation."""

from chipbench.readers import device_idle_share as read  # noqa: F401
