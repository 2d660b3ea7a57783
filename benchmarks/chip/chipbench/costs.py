"""Operations and bytes of the program's matmuls, computed from shapes, and
the shares of the chip's peaks that they give.

`int8_matmul` (the program's `kernels/int8_matmul.py`) multiplies x (m, K)
in bf16 by w (K, N) in int8 with one float32 scale per output channel, and
writes (m, N) in bf16. Its least time on a chip is the larger of
2·m·K·N operations at the int8 peak and the bytes it must move at the
HBM peak: the int8 weights, the scales, the activations in and out.

A decode step dispatches every projection of every layer once with m the
engine's slot count; a prefill chunk with m its padded token count. Which
projections a layer has, and how many parameters a token multiplies, are
the architecture's: `projections` and `matmul_params` of the reference
that the run's configuration names (`run.ref`).

Peaks come from `peaks.json`, by the device kind JAX reports; a device
that is not there gives no share.
"""

from __future__ import annotations

from types import ModuleType
from typing import Dict, Optional

from chipbench import engine_trace
from chipbench.spec import BENCH_DIR, load_json

PEAKS = BENCH_DIR / "peaks.json"
X_BYTES = 2                 # bf16 activations in and out
SCALE_BYTES = 4             # float32 scale per output channel


def peaks(kind: str) -> Optional[Dict[str, float]]:
    return load_json(PEAKS)["devices"].get(kind)


def int8_matmul_ops(m: int, k: int, n: int) -> int:
    return 2 * m * k * n


def int8_matmul_bytes(m: int, k: int, n: int) -> int:
    return X_BYTES * m * k + k * n + SCALE_BYTES * n + X_BYTES * m * n


def int8_matmul_least_s(m: int, k: int, n: int, peak: Dict) -> float:
    return max(int8_matmul_ops(m, k, n) / peak["int8_ops"],
               int8_matmul_bytes(m, k, n) / peak["hbm_bytes_per_s"])


def step_int8_matmul_least_s(ref: ModuleType, shape, m: int,
                             peak: Dict) -> float:
    """Least int8_matmul time of one step (every layer's projections)."""
    return shape.n_layers * sum(int8_matmul_least_s(m, k, n, peak)
                                for k, n in ref.projections(shape))


def int8_matmul_roofline(run) -> Optional[float]:
    """Over the traced window: the decode and chunk programs' least
    int8_matmul time, over int8_matmul's device time, in percent."""
    peak = peaks(run.device.get("kind"))
    if run.trace is None or peak is None:
        return None
    kernel_s = run.trace.kernel_s.get("int8_matmul")
    if not kernel_s:
        return None
    shape, serve = run.ref.Shape.of(run.config["model"]), run.config["serve"]
    runs = {"_decode": serve["n_slots"],
            "_chunk": serve["chunk_pages"] * serve["page_size"]}
    least = sum(len(run.trace.program_s.get(prog, ()))
                * step_int8_matmul_least_s(run.ref, shape, m, peak)
                for prog, m in runs.items())
    return 100.0 * least / kernel_s


def step_mfu(run) -> Optional[float]:
    """2 x matmul parameters x tokens processed in the traced window (each
    decode step's active slots and each chunk's real tokens, from the
    engine's spans), over the window at the int8 peak, in percent.
    Attention's own operations are left out."""
    peak = peaks(run.device.get("kind"))
    pdata = engine_trace.cell_trace(run) if peak is not None else None
    if pdata is None:
        return None
    decoded = engine_trace.span_total(pdata, "engine.decode", "active")
    chunked = engine_trace.span_total(pdata, "engine.prefill_chunk",
                                      "length")
    tokens = (decoded or 0) + (chunked or 0)
    if not tokens:
        return None
    shape = run.ref.Shape.of(run.config["model"])
    ops = 2 * run.ref.matmul_params(shape) * tokens
    return 100.0 * ops / (run.trace.window_s * peak["int8_ops"])
