"""The chip benchmark's harness: one harness for every cell of BENCHMARK.json.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by its name (`spec.Bench`):

  configs/<config>.json   published config, serving settings, check limits
  traffic/<traffic>.json  parameters of the one generator (`traffic.py`)
  metrics/<metric>.py     `read(run) -> float | None`

The modules here are the yardstick: traffic generation (`traffic`), the
serving loop and its clock (`serving`), seeded weights (`weights`), the
plain float32 reference and the comparison that decides `correct`
(`reference`, `check`), and the reduction of a profiler trace to busy
time and kernel time (`trace`).
"""
