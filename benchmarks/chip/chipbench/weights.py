"""Seeded random weights, made on the device in the type they are served in.

The program's parameter tree (`model.init`'s structure, read with
`jax.eval_shape`) is filled leaf by leaf, in one jitted call from the
seed: leaf i from the key folded with i. How each leaf is drawn is data,
the `WEIGHTS` table of the configuration's reference
(references/<name>.py), keyed by the leaf's name; a leaf the table lacks
is refused before anything is drawn. Each stacked layer weight is drawn
one layer at a time (`lax.map`), so the float32 draw of a whole stack is
never resident.

Projections are served in bf16, or in int8 as the program's quantized
leaf `{"int8_q": int8, "s": float32}`: symmetric, one scale per output
channel over the projection's contraction axes, the scale keeping the
weight's rank with those axes reduced to 1. The int8 weights are
quantized from the float32 draw inside the same `lax.map`, so no bf16 copy
of the model is ever made (`int4`, for the control, is the same draw
on a coarser grid). Every other leaf stays bf16 either way.

Scales are those of a trained model's activations: every projection is
drawn at its true fan-in, the product of its contraction axes (d_model
into q/k/v, heads x head_dim into the output projection), embeddings at
0.02. The program's own init takes the fan-in of a (d_model, heads,
head_dim) weight from its head axis, which saturates every softmax at
these widths.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02
BIAS_STD = 0.1
# quantized projections: the levels each side of zero of each grid, all
# stored as int8
LEVELS = {"int8": 127, "int4": 7}
KINDS = ("proj", "router", "norm", "embed", "bias")


@dataclasses.dataclass(frozen=True)
class Rule:
    """How one leaf is drawn.

    proj    normal at 1 / sqrt(fan-in), quantized where the deployment is;
            a stacked layer weight. `contract`: its contraction axes,
            counted with the layer axis removed, whose product is the
            fan-in. `stacked`: leading axes (after the layer axis) that
            are neither contracted nor an output, such as experts: each
            slice along them is its own matrix, drawn and quantized apart
    router  drawn as a projection, never quantized
    norm    ones
    embed   normal at EMBED_STD (embedding, untied output layer)
    bias    normal at BIAS_STD
    """
    kind: str
    contract: Tuple[int, ...] = ()
    stacked: Tuple[int, ...] = ()

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown weight kind {self.kind!r}")
        drawn = self.kind in ("proj", "router")
        if drawn != bool(self.contract):
            raise ValueError(f"a {self.kind} rule takes contraction axes "
                             f"only if it is drawn at a fan-in")
        if self.stacked != tuple(range(len(self.stacked))) or \
                set(self.stacked) & set(self.contract):
            raise ValueError(f"stacked axes {self.stacked} must lead and "
                             f"not be contracted ({self.contract})")


def proj(*contract: int, stacked: Tuple[int, ...] = ()) -> Rule:
    return Rule("proj", tuple(contract), tuple(stacked))


def router(*contract: int) -> Rule:
    return Rule("router", tuple(contract))


NORM, EMBED, BIAS = Rule("norm"), Rule("embed"), Rule("bias")


def jax_seed(seed: int) -> int:
    """A 32-bit key seed from any whole seed, however large."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


def quantize(w, axes: Tuple[int, ...], levels: int):
    """Symmetric quantization over `axes` (the contraction axes): ints in
    [-levels, levels] as int8, one float32 scale per output channel, kept
    at the weight's rank."""
    absmax = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
    s = jnp.maximum(absmax, 1e-8) / levels
    q = jnp.clip(jnp.round(w / s), -levels, levels).astype(jnp.int8)
    return {"int8_q": q, "s": s}


def _draw_matrix(key, shape, contract: Tuple[int, ...], dtype: str):
    fan_in = math.prod(shape[a] for a in contract)
    w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
    if dtype in LEVELS:
        return quantize(w, contract, LEVELS[dtype])
    if dtype == "bf16":
        return w.astype(jnp.bfloat16)
    raise ValueError(f"weights are made in bf16, int8 or int4, not "
                     f"{dtype!r}")


def _draw_stack(key, shape, rule: Rule, dtype: str):
    """A stacked layer weight of `shape` (layer axis first), one matrix
    at a time: one per layer, or per layer and stacked slice."""
    n_lead = 1 + len(rule.stacked)
    lead, inner = shape[:n_lead], shape[n_lead:]
    contract = tuple(a - len(rule.stacked) for a in rule.contract)
    if rule.kind == "router":
        dtype = "bf16"
    keys = jax.random.split(key, math.prod(lead))
    out = jax.lax.map(
        lambda kk: _draw_matrix(kk, inner, contract, dtype), keys)
    return jax.tree.map(lambda a: a.reshape(lead + a.shape[1:]), out)


def _check_rule(name: str, rule: Rule, shape) -> None:
    axes = rule.contract + rule.stacked
    if axes and max(axes) >= len(shape) - 1:
        raise ValueError(f"weight {name!r} of shape {shape} has no axis "
                         f"{max(axes)} after its layer axis")


def builder(model, rules: Dict[str, Rule], dtype: str = "bf16"):
    """key -> the program's parameter tree, seeded, each leaf drawn by
    `rules[leaf name]`, its projections in `dtype` (to be jitted). "int4"
    is the control of an int8 deployment: the same draw on the int4 grid
    of each channel (15 levels, a step of absmax / 7), stored as the
    program's int8 leaf so that the program serves it on its own int8
    path."""
    abstract = jax.eval_shape(model.init, jax.random.key(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [path[-1].key for path, _ in flat]
    missing = sorted({n for n in names if n not in rules})
    if missing:
        raise ValueError(f"no rule to make weight {missing[0]!r} "
                         f"(the table lacks {missing})")
    for name, (_, sds) in zip(names, flat):
        _check_rule(name, rules[name], sds.shape)

    def build(key):
        leaves = []
        for i, (name, (_, sds)) in enumerate(zip(names, flat)):
            rule = rules[name]
            k = jax.random.fold_in(key, i)
            if rule.kind in ("proj", "router"):
                leaves.append(_draw_stack(k, sds.shape, rule, dtype))
            elif rule.kind == "norm":
                leaves.append(jnp.ones(sds.shape, jnp.bfloat16))
            else:
                std = EMBED_STD if rule.kind == "embed" else BIAS_STD
                leaves.append((std * jax.random.normal(
                    k, sds.shape, jnp.float32)).astype(jnp.bfloat16))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build


def make_params(model, rules: Dict[str, Rule], seed: int,
                dtype: str = "bf16"):
    """The program's parameter tree, seeded, on the device, its
    projections in `dtype`."""
    params = jax.jit(builder(model, rules, dtype))(
        jax.random.key(jax_seed(seed)))
    jax.block_until_ready(params)
    return params
