"""Matmul operations of the tokens processed in the traced window, over the
window at the chip's int8 peak (model step)."""

from chipbench.costs import step_mfu as read  # noqa: F401
