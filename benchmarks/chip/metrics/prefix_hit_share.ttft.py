"""Prompt tokens served from the prefix cache (scheduler)."""

from chipbench.readers import prefix_hit_share as read  # noqa: F401
