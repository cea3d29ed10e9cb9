"""Random weights from the run's seed, shared by the program and the
reference.

Every leaf is addressed by its path in the parameter tree (``embed``,
``periods/slot0/mixer/qkv/factors/0``, ...) and, under ``periods`` and the
architecture's other stacked prefixes (``STACKED`` of its
``archs/<model_type>.py``), by its layer.  Its values are a pure function
of (seed, path, layer): uniform draws of unit variance, scaled by a rule
on the path, the architecture's own ``scale_rule`` first.  The program's
weights are made from these in one jitted call, directly in bf16 and under
the program's own shardings; the reference regenerates each layer from the
same function when it needs it, so it takes nothing the program made.
Integer bits turned into floats by exact arithmetic keep the values
bit-identical however XLA fuses the generator.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

NORMS = ("final_norm", "norm1", "norm2", "q_norm", "k_norm")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (``PRNGKey`` keeps 32)."""
    seed %= 2 ** 64
    key = jax.random.PRNGKey(seed % 2 ** 32)
    return jax.random.fold_in(key, seed >> 32)


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def scale_rule(path: str, shape: tuple[int, ...],
               rule=None) -> tuple[float, float]:
    """(offset, scale) of a leaf whose per-layer shape is ``shape``: norm
    gains near 1, every matrix scaled so a product keeps unit variance.
    ``rule(path, shape)``, an architecture's own, is asked first; where it
    returns None the shared rules below apply."""
    if rule is not None:
        own = rule(path, shape)
        if own is not None:
            return own
    parts = path.split("/")
    last = parts[-1]
    if last in NORMS:
        return 1.0, 0.1
    if last == "embed" or parts[0] == "embed":
        return 0.0, 1.0
    if "factors" in parts:          # butterfly factor (J, 2, 2, S, b, b)
        return 0.0, 1.0 / math.sqrt(2 * shape[-1])
    if last == "blocks":            # pixelfly blocks (nb, k, b, b)
        return 0.0, 1.0 / math.sqrt(shape[-3] * shape[-1])
    if last in ("w", "u", "v"):     # (in, out) matrices
        return 0.0, 1.0 / math.sqrt(shape[-2])
    raise ValueError(f"no scale rule for leaf {path!r}")


def leaf_value(key: jax.Array, path: str, shape, layer,
               rule=None) -> jax.Array:
    """One layer's slice of leaf ``path`` (bf16); ``layer`` may be traced.
    ``rule`` is the architecture's ``scale_rule``, if it has one."""
    offset, scale = scale_rule(path, tuple(shape), rule)
    k = jax.random.fold_in(jax.random.fold_in(
        key, zlib.crc32(path.encode()) & 0x7FFFFFFF), layer)
    bits = jax.random.bits(k, tuple(shape), jnp.uint32)
    u = (bits >> 8).astype(jnp.float32) * (2.0 ** -24) - 0.5  # [-0.5, 0.5)
    return (offset + u * (scale * math.sqrt(12.0))).astype(jnp.bfloat16)


def make_like(seed: int, shapes, shardings=None, arch=None):
    """A tree shaped like ``shapes`` (ShapeDtypeStructs, as ``eval_shape``
    gives the program's parameters) filled from the seed, made on the
    device in one jitted call, in bf16, placed per ``shardings``.  Leaves
    under ``periods/`` and under the prefixes of ``arch.STACKED`` (the
    architecture module) are filled one layer of their leading axis at a
    time; ``arch.scale_rule`` is asked before the shared rules."""
    rule = getattr(arch, "scale_rule", None)
    stacked = tuple(p.rstrip("/") + "/" for p in
                    ("periods",) + tuple(getattr(arch, "STACKED", ())))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for p, leaf in flat:
            path = path_str(p)
            if path.startswith(stacked):
                per = leaf.shape[1:]
                out.append(jax.vmap(lambda l, path=path, per=per: leaf_value(
                    key, path, per, l, rule))(jnp.arange(leaf.shape[0])))
            else:
                out.append(leaf_value(key, path, leaf.shape, 0, rule))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))
