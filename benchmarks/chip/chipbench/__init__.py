"""The chip benchmark's harness: one harness for every cell of BENCHMARK.json.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by its name (`spec.Bench`):

  configs/<config>.json   published config, serving settings, check limits,
                          and the name of its reference
  references/<name>.py    an architecture: its sizes, plain float32 model,
                          program fields, weight rules and matmul shapes
                          (the interface is in `spec`)
  traffic/<traffic>.json  parameters of the one generator (`traffic.py`)
  metrics/<metric>.py     `read(run) -> float | None`

The modules here are the yardstick: traffic generation (`traffic`), the
serving loop and its clock (`serving`), seeded weights drawn by a
reference's rules (`weights`), the float32 pieces every reference shares
and the comparison that decides `correct` (`plain`, `check`), and the
reduction of a profiler trace to busy time and kernel time (`trace`).
"""
