"""int8_matmul's least time by the chip's peaks, over its device time
(kernels)."""

from chipbench.costs import int8_matmul_roofline as read  # noqa: F401
