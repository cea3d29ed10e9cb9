"""Operations and bytes of the served model, from its configuration's
shapes alone.

Parameters are counted leaf by leaf from the layout the configuration's
reference states (dense matrices, or butterfly and pixelfly factors under
the factorization policy), so the counts do not depend on how the program
implements the layers.

- A token's model FLOPs are 2 x every weight parameter it multiplies (all
  but the embedding table, the head included) plus 4 x layers x query heads
  x head size x the positions it attends to (scores and values).
- A decode step's fewest bytes are the weights it reads (all but the
  embedding table) plus the K/V of every live token, as one chip holds
  them: a tensor-parallel degree tp divides every matrix and the cache,
  and every chip keeps its own copy of the norm gains.
"""
from __future__ import annotations

import importlib
import math

BF16 = 2


def _layout(c: dict):
    ref = importlib.import_module(f"bench.refs.{c['reference']}")
    return ref.layer_layout(c), ref.top_layout(c)


def _size(shape) -> int:
    return math.prod(shape)


def _is_norm(path: str) -> bool:
    return "norm" in path.split("/")[-1]


def weight_params(c: dict) -> dict[str, int]:
    """Parameter counts: ``embed``, ``norms`` (all gains), ``matmul`` (every
    matrix a token multiplies, head included)."""
    layer, top = _layout(c)
    n_layers = c["num_hidden_layers"]
    out = {"embed": 0, "norms": 0, "matmul": 0}
    for path, shape in layer.items():
        out["norms" if _is_norm(path) else "matmul"] += n_layers * _size(shape)
    for path, shape in top.items():
        key = "embed" if path == "embed" else (
            "norms" if _is_norm(path) else "matmul")
        out[key] += _size(shape)
    return out


def weight_bytes(c: dict) -> int:
    """All weights in bf16, the embedding table included."""
    return BF16 * sum(weight_params(c).values())


def kv_bytes_per_token(c: dict) -> int:
    """K and V of one token, all layers, bf16."""
    return (BF16 * 2 * c["num_hidden_layers"] * c["num_key_value_heads"]
            * c["head_dim"])


def decode_weight_bytes_per_chip(c: dict, tp: int) -> float:
    p = weight_params(c)
    return BF16 * (p["matmul"] / tp + p["norms"])


def decode_min_bytes_per_chip(c: dict, tp: int, live_tokens: int) -> float:
    """Fewest bytes one chip reads in a decode step whose live sequences
    hold ``live_tokens`` cached positions in all."""
    return (decode_weight_bytes_per_chip(c, tp)
            + kv_bytes_per_token(c) * live_tokens / tp)


def attention_flops(c: dict, context: int) -> float:
    """Scores and weighted values of one token over ``context`` positions."""
    return (4.0 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"] * context)


def decode_token_flops(c: dict, context: int) -> float:
    """Model FLOPs of one decoded token that attends to ``context``
    positions (its own included)."""
    return 2.0 * weight_params(c)["matmul"] + attention_flops(c, context)


def prefill_flops(c: dict, length: int) -> float:
    """Model FLOPs of a causal prefill of ``length`` tokens."""
    return (2.0 * weight_params(c)["matmul"] * length
            + attention_flops(c, 1) * length * (length + 1) / 2)
