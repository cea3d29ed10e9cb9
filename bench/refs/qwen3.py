"""Plain reference of the Qwen3 decoder: float32 at the highest matmul
precision, no cache, no batching tricks, no kernels.

It follows the published architecture (pre-norm blocks, GQA attention with
per-head RMS norm of q and k, rotary positions, SwiGLU MLP, final norm,
untied output head) with the departures the configuration file lists
under ``assumed``.  Linear layers hold their published dense matrices or,
under the configuration's factorization policy, the paper's structured
factors, which this module turns into dense matrices by its own reading of
their definitions:

- butterfly (block size b, padded width N = b * 2^k): k factors applied in
  order of block stride s = 1, 2, 4, ...; the factor with stride s maps
  input block j*2s + c*s + t to output block j*2s + r*s + t through the
  (b, b) block w[j, r, c, t];
- pixelfly: output block r sums input blocks r and r XOR 2^i (i < log2 of
  the block count) through blocks[r, 0] and blocks[r, 1 + i], plus the
  low-rank term x @ u @ v.

Weights are regenerated layer by layer from the run's seed
(:mod:`bench.weights`), so the reference never holds the whole model and
takes nothing from the program.  ``precision="fp8"`` is the control: the
same computation with every weight and every matmul input rounded to
float8 (e4m3, one scale per tensor).
"""
from __future__ import annotations

import fnmatch
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import leaf_value, seed_key


# ------------------------------------------------------------- layout ----
def _rule(policy: dict, site: str) -> dict:
    over = policy.get("overrides", {})
    if site in over:
        return over[site]
    for pattern, rule in over.items():
        if fnmatch.fnmatchcase(site, pattern):
            return rule
    return policy.get("default", {"kind": "dense"})


def _padded(n_in: int, n_out: int, block: int) -> tuple[int, int]:
    """(block, padded width) of a structured (n_in, n_out) layer: the block
    halves until it fits, and the width is block * 2^k >= max(n_in, n_out)."""
    m = max(n_in, n_out)
    b = block
    while b > 1 and 2 * b > m:
        b //= 2
    blocks = max(1, -(-m // b))
    return b, b * (1 << (blocks - 1).bit_length())


def linear_layout(policy: dict, site: str, n_in: int, n_out: int) -> dict:
    """{leaf name: per-layer shape} of one linear layer."""
    rule = _rule(policy, site)
    kind = rule.get("kind", "dense")
    if kind == "dense":
        return {"w": (n_in, n_out)}
    b, n = _padded(n_in, n_out, rule.get("block_size", 128))
    nb = n // b
    if kind == "butterfly":
        out, s, i = {}, 1, 0
        while s < nb:
            out[f"factors/{i}"] = (nb // (2 * s), 2, 2, s, b, b)
            s, i = 2 * s, i + 1
        return out
    if kind == "pixelfly":
        r = rule.get("rank", 16)
        return {"blocks": (nb, 1 + int(math.log2(nb)), b, b),
                "u": (n_in, r), "v": (r, n_out)}
    raise ValueError(f"reference has no {kind!r} linear")


def _dims(c: dict):
    d, hq, hkv, hd = (c["hidden_size"], c["num_attention_heads"],
                      c["num_key_value_heads"], c["head_dim"])
    return d, hq, hkv, hd, c["intermediate_size"]


def padded_vocab(c: dict) -> int:
    return -(-c["vocab_size"] // 256) * 256


def layer_layout(c: dict) -> dict:
    """{path under periods/slot0: per-layer shape} of one decoder layer."""
    d, hq, hkv, hd, ff = _dims(c)
    pol = c["policy"]
    sites = {"mixer/qkv": ("attn_qkv", d, (hq + 2 * hkv) * hd),
             "mixer/out": ("attn_out", hq * hd, d),
             "ffn/gate": ("mlp", d, ff), "ffn/up": ("mlp", d, ff),
             "ffn/down": ("mlp", ff, d)}
    out = {"norm1": (d,), "norm2": (d,), "mixer/q_norm": (hd,),
           "mixer/k_norm": (hd,)}
    for prefix, (site, n_in, n_out) in sites.items():
        for leaf, shape in linear_layout(pol, site, n_in, n_out).items():
            out[f"{prefix}/{leaf}"] = shape
    return out


def top_layout(c: dict) -> dict:
    d = c["hidden_size"]
    out = {"embed": (padded_vocab(c), d), "final_norm": (d,)}
    for leaf, shape in linear_layout(c["policy"], "head", d,
                                     padded_vocab(c)).items():
        out[f"head/{leaf}"] = shape
    return out


def kv_bytes_per_token(c: dict) -> int:
    """K and V of one token, all layers, bf16."""
    return (2 * 2 * c["num_hidden_layers"] * c["num_key_value_heads"]
            * c["head_dim"])


def attention_flops(c: dict, context: int) -> float:
    """Scores and weighted values of one token over ``context`` positions."""
    return (4.0 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"] * context)


# ------------------------------------------------------------ numerics ---
def _q(x, precision: str):
    """Round to float8 e4m3 with one scale per tensor (the control), or
    leave float32 as it is."""
    if precision == "f32":
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _butterfly_dense(factors: list, n_in: int, n_out: int, b: int, n: int):
    """Dense (n_in, n_out) matrix of a butterfly: the factors applied in
    turn to the rows of the identity."""
    nb = n // b
    x = jnp.eye(n_in, n, dtype=jnp.float32).reshape(n_in, nb, b)
    s = 1
    for w in factors:
        jj, tt = np.meshgrid(np.arange(nb // (2 * s)), np.arange(s),
                             indexing="ij")
        y = jnp.zeros_like(x)
        for r in range(2):
            dst = (jj * 2 * s + r * s + tt).reshape(-1)
            for cc in range(2):
                src = (jj * 2 * s + cc * s + tt).reshape(-1)
                blk = w[:, r, cc].reshape(-1, b, b)  # (j, t) row-major
                y = y.at[:, dst].add(jnp.einsum("mni,nio->mno",
                                                x[:, src], blk))
        x, s = y, 2 * s
    return x.reshape(n_in, n)[:, :n_out]


def _pixelfly_dense(blocks, u, v, n_in: int, n_out: int, b: int, n: int):
    nb = n // b
    w = jnp.zeros((nb, nb, b, b), jnp.float32)  # [in block, out block]
    rows = np.arange(nb)
    w = w.at[rows, rows].add(blocks[:, 0])
    for i in range(blocks.shape[1] - 1):
        w = w.at[rows ^ (1 << i), rows].add(blocks[:, 1 + i])
    dense = w.transpose(0, 2, 1, 3).reshape(n, n)[:n_in, :n_out]
    return dense + u @ v


def dense_matrix(policy, site, n_in, n_out, leaves: dict, precision: str):
    """A linear layer's weight as one dense (n_in, n_out) float32 matrix."""
    rule = _rule(policy, site)
    kind = rule.get("kind", "dense")
    lv = {k: _q(v.astype(jnp.float32), precision) for k, v in leaves.items()}
    if kind == "dense":
        return lv["w"]
    b, n = _padded(n_in, n_out, rule.get("block_size", 128))
    if kind == "butterfly":
        fs = [lv[f"factors/{i}"] for i in range(len(lv))]
        return _butterfly_dense(fs, n_in, n_out, b, n)
    return _pixelfly_dense(lv["blocks"], lv["u"], lv["v"], n_in, n_out, b, n)


def _rope(x, theta: float):
    """x (N, L, H, hd): rotate adjacent pairs by position * theta^(-2i/hd)."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


# -------------------------------------------------------------- passes ---
class Reference:
    """The reference for configuration dict ``c`` and the run's ``seed``."""

    def __init__(self, c: dict, seed: int, device=None):
        self.c = c
        self._spec = json.dumps(c, sort_keys=True)
        self.device = device or jax.devices()[0]
        with jax.default_device(self.device):
            self.key = seed_key(seed)
        self.layer_shapes = layer_layout(c)
        self.top_shapes = top_layout(c)

    # one compiled program per configuration, whatever the seed: the key
    # is an argument, never a constant
    def __hash__(self):
        return hash(self._spec)

    def __eq__(self, other):
        return isinstance(other, Reference) and other._spec == self._spec

    def _leaves(self, key, shapes: dict, prefix: str, layer):
        return {k: leaf_value(key, prefix + k, s, layer)
                for k, s in shapes.items()}

    @functools.partial(jax.jit, static_argnums=(0, 3))
    def _layer_weights(self, key, layer, precision: str):
        c = self.c
        d, hq, hkv, hd, ff = _dims(c)
        lv = self._leaves(key, self.layer_shapes, "periods/slot0/", layer)
        pol = c["policy"]

        def lin(prefix, site, n_in, n_out):
            sub = {k[len(prefix) + 1:]: v for k, v in lv.items()
                   if k.startswith(prefix + "/")}
            return dense_matrix(pol, site, n_in, n_out, sub, precision)

        f32 = lambda k: lv[k].astype(jnp.float32)  # noqa: E731
        return {"norm1": f32("norm1"), "norm2": f32("norm2"),
                "q_norm": f32("mixer/q_norm"), "k_norm": f32("mixer/k_norm"),
                "qkv": lin("mixer/qkv", "attn_qkv", d, (hq + 2 * hkv) * hd),
                "out": lin("mixer/out", "attn_out", hq * hd, d),
                "gate": lin("ffn/gate", "mlp", d, ff),
                "up": lin("ffn/up", "mlp", d, ff),
                "down": lin("ffn/down", "mlp", ff, d)}

    @functools.partial(jax.jit, static_argnums=(0, 3))
    def _layer(self, w, x, precision: str):
        """One decoder layer over x (N, L, d), causal over L."""
        c = self.c
        d, hq, hkv, hd, ff = _dims(c)
        eps = float(c["rms_norm_eps"])
        q_ = functools.partial(_q, precision=precision)
        n, length = x.shape[:2]
        h = _rms(x, w["norm1"], eps)
        qkv = q_(h) @ w["qkv"]
        q = qkv[..., :hq * hd].reshape(n, length, hq, hd)
        k = qkv[..., hq * hd:(hq + hkv) * hd].reshape(n, length, hkv, hd)
        v = qkv[..., (hq + hkv) * hd:].reshape(n, length, hkv, hd)
        q = _rope(_rms(q, w["q_norm"], eps), float(c["rope_theta"]))
        k = _rope(_rms(k, w["k_norm"], eps), float(c["rope_theta"]))
        g = hq // hkv
        k = jnp.repeat(k, g, axis=2)  # query head h reads kv head h // g
        v = jnp.repeat(v, g, axis=2)
        s = jnp.einsum("nqhd,nkhd->nhqk", q_(q), q_(k)) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((length, length), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("nhqk,nkhd->nqhd", q_(p), q_(v)).reshape(n, length, -1)
        x = x + q_(o) @ w["out"]
        h = q_(_rms(x, w["norm2"], eps))
        m = jax.nn.silu(h @ w["gate"]) * (h @ w["up"])
        return x + q_(m) @ w["down"]

    @functools.partial(jax.jit, static_argnums=(0, 2))
    def _top_weights(self, key, precision: str):
        lv = self._leaves(key, self.top_shapes, "", 0)
        c = self.c
        head = dense_matrix(c["policy"], "head", c["hidden_size"],
                            padded_vocab(c),
                            {k[5:]: v for k, v in lv.items()
                             if k.startswith("head/")}, precision)
        return {"embed": lv["embed"].astype(jnp.float32),
                "final_norm": lv["final_norm"].astype(jnp.float32),
                "head": head[:, :c["vocab_size"]]}

    @functools.partial(jax.jit, static_argnums=(0, 5))
    def _summary(self, top, hidden, rows, ids, precision: str):
        """Per hidden-state row: the best logit, the logits' standard
        deviation, the argmax and the logits at ``ids`` (rows, k)."""
        h = _rms(hidden[rows], top["final_norm"],
                 float(self.c["rms_norm_eps"]))
        lg = _q(h, precision) @ top["head"]
        return (lg.max(-1), lg.std(-1), lg.argmax(-1),
                jnp.take_along_axis(lg, ids, axis=1))

    def hidden(self, tokens: np.ndarray, precision: str = "f32",
               block: int = 4) -> jax.Array:
        """Final hidden states (N, L, d) of token rows (N, L), in blocks of
        ``block`` rows per attention call."""
        with jax.default_matmul_precision("highest"), \
                jax.default_device(self.device):
            top = self._top_weights(self.key, precision)
            ids = jnp.clip(jnp.asarray(tokens), 0, self.c["vocab_size"] - 1)
            x = top["embed"][ids]
            del top
            n = x.shape[0]
            pad = -n % block
            if pad:
                x = jnp.concatenate(
                    [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
            for layer in range(self.c["num_hidden_layers"]):
                w = self._layer_weights(self.key, layer, precision)
                x = jnp.concatenate([self._layer(w, x[i:i + block], precision)
                                     for i in range(0, x.shape[0], block)])
                del w
            return x[:n]

    def summary(self, hidden, rows: np.ndarray, ids: np.ndarray,
                precision: str = "f32", chunk: int = 512) -> dict:
        """Logit statistics at flat (row * L + position) indices of
        ``hidden``, in chunks: ``best``, ``std`` and ``argmax`` per index,
        and ``at`` (n, k): the logits of the k token ids ``ids[i]``."""
        with jax.default_matmul_precision("highest"), \
                jax.default_device(self.device):
            top = self._top_weights(self.key, precision)
            flat = hidden.reshape(-1, hidden.shape[-1])
            parts = []
            for i in range(0, len(rows), chunk):
                n = len(rows[i:i + chunk])
                idx = np.zeros(chunk, np.int32)
                idx[:n] = rows[i:i + chunk]
                tok = np.zeros((chunk, ids.shape[1]), np.int32)
                tok[:n] = ids[i:i + chunk]
                out = self._summary(top, flat, idx, tok, precision)
                parts.append([np.asarray(x)[:n] for x in out])
        names = ("best", "std", "argmax", "at")
        return {k: np.concatenate([p[j] for p in parts])
                for j, k in enumerate(names)}
