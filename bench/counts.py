"""Operations and bytes of the served model, from its configuration's
shapes alone.

Parameters are counted leaf by leaf from the layout the configuration's
reference states (dense matrices, or butterfly and pixelfly factors under
the factorization policy), so the counts do not depend on how the program
implements the layers.  The reference module (``refs/<reference>.py``,
found by :func:`bench.model.reference`) gives:

- ``layer_layout(c)``: ``{path: per-layer shape}`` of a layer that all
  ``num_hidden_layers`` share, or groups ``[(layers, {path: shape}), ...]``
  where layers differ (leading dense layers, then expert layers);
- ``top_layout(c)``: the leaves outside the layers;
- optionally ``token_share(c, path)``: the share of a layer leaf that one
  token multiplies on this chip (1 where absent).  A leaf with a share
  below 1 holds one slice per expert on its leading axis (the experts this
  chip holds); the reference then names ``EXPERTS_READ_STAT``, the run's
  window counter of (layer, expert) pairs its decode steps read;
- optionally ``kv_bytes_per_token(c)`` and ``attention_flops(c, context)``,
  in place of the GQA formulas of ``refs/qwen3.py``.

- A token's model FLOPs are 2 x every weight parameter it multiplies (all
  but the embedding table, the head included; an expert leaf at its token
  share) plus the attention's FLOPs over the positions it attends to.
- A decode step's fewest bytes are the weights it reads (all but the
  embedding table; of an expert leaf, the slices of the experts it reads)
  plus the K/V of every live token, as one chip holds them: a
  tensor-parallel degree tp divides every matrix and the cache, and every
  chip keeps its own copy of the norm gains.
"""
from __future__ import annotations

import math

from bench.model import reference
from bench.refs import qwen3 as gqa

BF16 = 2


def _groups(c: dict) -> list:
    """[(layers, {path: per-layer shape}), ...] of the reference's layers."""
    layout = reference(c).layer_layout(c)
    if isinstance(layout, dict):
        return [(c["num_hidden_layers"], layout)]
    return list(layout)


def _size(shape) -> int:
    return math.prod(shape)


def _is_norm(path: str) -> bool:
    return "norm" in path.split("/")[-1]


def _expert_leaves(c: dict) -> list:
    """(layers, per-layer shape, token share, group) of every layer leaf
    that one token multiplies only in part (``token_share`` of the
    reference)."""
    share = getattr(reference(c), "token_share", None)
    if share is None:
        return []
    return [(n_layers, shape, share(c, path), g)
            for g, (n_layers, layer) in enumerate(_groups(c))
            for path, shape in layer.items()
            if not _is_norm(path) and share(c, path) < 1]


def weight_params(c: dict) -> dict[str, int]:
    """Parameter counts: ``embed``, ``norms`` (all gains), ``matmul`` (every
    matrix a token may multiply, head included)."""
    out = {"embed": 0, "norms": 0, "matmul": 0}
    for n_layers, layer in _groups(c):
        for path, shape in layer.items():
            out["norms" if _is_norm(path) else "matmul"] += (
                n_layers * _size(shape))
    for path, shape in reference(c).top_layout(c).items():
        key = "embed" if path == "embed" else (
            "norms" if _is_norm(path) else "matmul")
        out[key] += _size(shape)
    return out


def expert_params(c: dict) -> int:
    """Parameters of every expert leaf, all layers."""
    return sum(n * _size(shape) for n, shape, _, _ in _expert_leaves(c))


def expert_read_bytes(c: dict) -> int:
    """Bytes of one expert of one layer: what a decode step reads per
    (layer, expert) pair it routes to (0 where there are no experts)."""
    per: dict[int, int] = {}
    for _, shape, _, g in _expert_leaves(c):
        per[g] = per.get(g, 0) + _size(shape) // shape[0]
    if len(set(per.values())) > 1:
        raise ValueError(f"{c['name']}: expert layers of different sizes")
    return BF16 * next(iter(per.values()), 0)


def token_matmul_params(c: dict):
    """Matrix parameters one token multiplies: every matrix, an expert
    leaf at its token share."""
    leaves = _expert_leaves(c)
    return (weight_params(c)["matmul"] - expert_params(c)
            + sum(n * _size(shape) * share for n, shape, share, _ in leaves))


def experts_read_per_step(c: dict, stats: dict):
    """(layer, expert) pairs a decode step reads, averaged over the window:
    0 where the model has no experts, None where the run lacks the counter
    the reference names."""
    if not expert_read_bytes(c):
        return 0
    stat = getattr(reference(c), "EXPERTS_READ_STAT", None)
    if stat is None or stat not in stats or not stats.get("decode_steps"):
        return None
    return stats[stat] / stats["decode_steps"]


def weight_bytes(c: dict) -> int:
    """All weights in bf16, the embedding table included."""
    return BF16 * sum(weight_params(c).values())


def kv_bytes_per_token(c: dict) -> int:
    """K and V (or what the attention caches) of one token, all layers."""
    return getattr(reference(c), "kv_bytes_per_token",
                   gqa.kv_bytes_per_token)(c)


def decode_weight_bytes_per_chip(c: dict, tp: int) -> float:
    """Weights a decode step reads on one chip, expert leaves left out."""
    p = weight_params(c)
    return BF16 * ((p["matmul"] - expert_params(c)) / tp + p["norms"])


def decode_min_bytes_per_chip(c: dict, tp: int, live_tokens: int,
                              experts_read: float = 0):
    """Fewest bytes one chip reads in a decode step whose live sequences
    hold ``live_tokens`` cached positions in all and which reads
    ``experts_read`` (layer, expert) pairs; None where the model has
    experts and ``experts_read`` is None."""
    need = (decode_weight_bytes_per_chip(c, tp)
            + kv_bytes_per_token(c) * live_tokens / tp)
    per_expert = expert_read_bytes(c)
    if not per_expert:
        return need
    if experts_read is None:
        return None
    return need + per_expert * experts_read / tp


def attention_flops(c: dict, context: int) -> float:
    """Scores and weighted values of one token over ``context`` positions."""
    return getattr(reference(c), "attention_flops", gqa.attention_flops)(
        c, context)


def decode_token_flops(c: dict, context: int) -> float:
    """Model FLOPs of one decoded token that attends to ``context``
    positions (its own included)."""
    return 2.0 * token_matmul_params(c) + attention_flops(c, context)


def prefill_flops(c: dict, length: int) -> float:
    """Model FLOPs of a causal prefill of ``length`` tokens."""
    return (2.0 * token_matmul_params(c) * length
            + attention_flops(c, 1) * length * (length + 1) / 2)
