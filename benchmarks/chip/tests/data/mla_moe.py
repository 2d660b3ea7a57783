"""A reference brought as a file, for the CPU test of the harness's seams:
DeepSeek-V2-Lite's block as the program builds it (latent attention with
a direct query projection, routed experts stacked on an expert axis,
shared experts behind a gate). It holds the sizes, the program fields
and the weight rules of the leaves; the test puts a stand-in `logits` in
place of a plain model."""

from __future__ import annotations

import dataclasses
from typing import Dict

from chipbench import weights


@dataclasses.dataclass(frozen=True)
class Shape:
    n_layers: int
    d_model: int
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared_experts: int
    vocab: int
    tie_embeddings: bool
    rope_theta: float
    norm_eps: float

    @classmethod
    def of(cls, model: Dict) -> "Shape":
        return cls(**{f.name: model[f.name] for f in dataclasses.fields(cls)})


PROGRAM_FIELDS = {"n_layers": "n_layers", "d_model": "d_model",
                  "n_heads": "n_heads", "q_lora_rank": "q_lora_rank",
                  "kv_lora_rank": "kv_lora_rank",
                  "qk_nope_dim": "qk_nope_dim", "qk_rope_dim": "qk_rope_dim",
                  "v_head_dim": "v_head_dim", "n_experts": "n_experts",
                  "moe_top_k": "top_k", "d_ff_expert": "d_ff_expert",
                  "vocab_size": "vocab", "tie_embeddings": "tie_embeddings",
                  "rope_theta": "rope_theta"}


def derived_fields(arch) -> Dict[str, object]:
    """The program states the shared experts as one width."""
    return {"n_shared_experts": arch.d_ff_shared // arch.d_ff_expert}


# per-layer axes (the layer axis removed): wq (d, H, nope + rope), wkv_a
# (d, rank + rope), wk_b and wv_b (rank, H, .), wo (H, v, d); experts
# (E, d, f) and (E, f, d); router (d, E); shared gate (d, 1)
WEIGHTS = {
    "wq": weights.proj(0), "wkv_a": weights.proj(0),
    "wk_b": weights.proj(0), "wv_b": weights.proj(0),
    "wo": weights.proj(0, 1),
    "w1": weights.proj(1, stacked=(0,)), "w3": weights.proj(1, stacked=(0,)),
    "w2": weights.proj(1, stacked=(0,)),
    "shared_w1": weights.proj(0), "shared_w3": weights.proj(0),
    "shared_w2": weights.proj(0),
    "router": weights.router(0), "shared_gate": weights.router(0),
    "attn_norm": weights.NORM, "kv_norm": weights.NORM,
    "ffn_norm": weights.NORM, "final_norm": weights.NORM,
    "embed": weights.EMBED, "lm_head": weights.EMBED}
