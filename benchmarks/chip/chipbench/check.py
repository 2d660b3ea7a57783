"""The comparison that decides `correct` for a served model.

Once the window has closed and the engine is freed, a sample of the
requests it finished, drawn from the seed, goes through the plain
reference (`reference.logits`), each prompt with the tokens that were
served after it. For every served token the gap is the reference's best
logit at that position minus its logit of the served token: 0 where they
agree, and larger the worse the served token is by the reference's own
judgement. Greedy decoding makes that the test: a served token may lose
to the reference's best only by what rounding at the stated precision can
move a logit.

Readings:
  max_gap    the widest gap of any served token in the sample
  mean_gap   the sum of the gaps over the number of tokens compared
  share_over_<tol>  the share of served tokens whose gap exceeds tol

The lower-precision control reads the same numbers, its served tokens
compared alike with the reference at the configuration's own precision.
For a bf16 configuration it is the program with its own lower-precision
path switched on (int8 weights and int8 KV); for an int8 one, which has
no lower path of its own, it is the program serving the same weights on
the int4 grid (`weights.LEVELS`).
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from chipbench import reference
from chipbench.reference import Shape

# logit tolerances of the `share_over_<tol>` readings: the share of served
# tokens that the reference ranks more than `tol` below its best
TOLERANCES = (0.02, 0.05, 0.075, 0.1, 0.15)


def sample(records: Sequence, rng: np.random.Generator,
           target_tokens: int) -> List:
    """Finished requests in an order drawn from the seed, until they hold
    `target_tokens` served tokens; the longest (prompt plus output) and
    the one most served from the prefix cache always among them."""
    done = [r for r in records if r.req.done and len(r.req.out_tokens)]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.req.prompt)
                  + len(r.req.out_tokens))
    cached = max(done, key=lambda r: r.req.cached_prompt_tokens)
    picked = [longest] + ([cached] if cached is not longest else [])
    tokens = sum(len(r.req.out_tokens) for r in picked)
    for i in rng.permutation(len(done)):
        if tokens >= target_tokens:
            break
        r = done[i]
        if any(r is p for p in picked):
            continue
        picked.append(r)
        tokens += len(r.req.out_tokens)
    return picked


def gaps(params, shape: Shape, kv_dtype: str,
         picked: Sequence) -> np.ndarray:
    """Per-token gaps of the served tokens of the picked requests."""
    served = []
    for r in picked:
        prompt = np.asarray(r.req.prompt, np.int32)
        out = np.asarray(r.req.out_tokens, np.int32)
        seq = np.concatenate([prompt, out[:-1]])
        rows = np.arange(prompt.shape[0] - 1, seq.shape[0])
        ref = reference.logits(params, shape, kv_dtype, seq, rows)
        served.append(ref.max(axis=-1) - ref[np.arange(out.shape[0]), out])
    return np.concatenate(served) if served else np.zeros(0)


def readings(gap: np.ndarray) -> Dict[str, float]:
    if gap.size == 0:
        return {}
    out = {"max_gap": float(gap.max()), "mean_gap": float(gap.mean()),
           "tokens": int(gap.size),
           "disagreements": int(np.count_nonzero(gap > 0))}
    for tol in TOLERANCES:
        out[f"share_over_{tol}"] = float(np.mean(gap > tol))
    return out


def judge(read: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every compared number within its limit (and something compared)."""
    return bool(limits) and all(
        name in read and read[name] <= limit for name, limit in
        limits.items())
