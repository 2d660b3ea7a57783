"""Output tokens per second: tokens emitted in the window by all requests,
over its seconds."""

from chipbench.readers import output_tokens_per_s as read  # noqa: F401
