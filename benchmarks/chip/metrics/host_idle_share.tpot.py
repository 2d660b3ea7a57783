"""Share of the traced window with the device idle while the engine did
host work: inside `engine.step`, outside `engine.readback` (scheduler)."""

from chipbench.engine_trace import read  # noqa: F401
