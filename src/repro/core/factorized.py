"""Unified linear-layer factory — the paper's technique as a composable feature.

Every linear layer in the model stack goes through ``Linear``; a
:class:`repro.core.policy.FactorizationPolicy` resolves the call-site to a
:class:`~repro.core.policy.Rule`, the :mod:`repro.core.registry` turns the
rule into a spec and (optionally) a kernel backend.  This is what makes
butterfly a first-class framework feature rather than a bolted-on layer —
and what lets one model mix structures per site ("pixelfly MLPs +
butterfly attention + dense head", the paper's Table-4 regime).

``FactorizationConfig`` survives as a deprecated shim that lowers to a
single-rule policy (see DESIGN.md section 7 for the migration table).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import registry
from repro.core.policy import (
    DENSE_POLICY,
    SITES,
    FactorizationPolicy,
    Rule,
)

# legacy alias: the registered built-in kinds (order matches the old tuple)
KINDS = ("dense", "butterfly", "pixelfly", "lowrank", "circulant", "fastfood")

DENSE = DENSE_POLICY


@dataclasses.dataclass(frozen=True)
class FactorizationConfig:
    """DEPRECATED single-structure config — use FactorizationPolicy.

    Keeps the old semantics (one kind/block_size/rank applied at ``sites``,
    dense elsewhere) by lowering to a single-rule policy via ``to_policy()``.
    Everything that accepts a policy also accepts this shim.
    """

    kind: str = "dense"
    block_size: int = 128
    rank: int = 16
    sites: tuple[str, ...] = ("mlp", "attn_qkv", "attn_out", "expert")
    use_kernel: bool = False
    permute: str = "none"

    def __post_init__(self):
        if not registry.is_registered(self.kind):
            raise ValueError(
                f"kind must be one of {registry.available_kinds()}, "
                f"got {self.kind!r}")
        for s in self.sites:
            if s not in SITES:
                raise ValueError(f"unknown site {s!r}; valid: {SITES}")
        warnings.warn(
            "FactorizationConfig is deprecated; use "
            "repro.core.policy.FactorizationPolicy (per-site Rules)",
            DeprecationWarning, stacklevel=3)

    def to_rule(self) -> Rule:
        return Rule(kind=self.kind, block_size=self.block_size, rank=self.rank,
                    permute=self.permute, use_kernel=self.use_kernel)

    def to_policy(self) -> FactorizationPolicy:
        return FactorizationPolicy.uniform(self.to_rule(), self.sites)

    def kind_for_site(self, site: str) -> str:
        return self.kind if site in self.sites else "dense"


def as_policy(fact) -> FactorizationPolicy:
    """Normalize policy / Rule / legacy FactorizationConfig to a policy."""
    if isinstance(fact, FactorizationPolicy):
        return fact
    if isinstance(fact, Rule):
        return FactorizationPolicy(default=fact)
    if isinstance(fact, FactorizationConfig):
        return fact.to_policy()
    raise TypeError(
        f"expected FactorizationPolicy, Rule or FactorizationConfig, "
        f"got {type(fact).__name__}")


def make_spec(
    fact,
    in_features: int,
    out_features: int,
    site: str = "other",
    bias: bool = False,
    dtype: Any = jnp.float32,
):
    """Build the registry spec for one call-site.

    ``fact`` may be a FactorizationPolicy, a bare Rule (applied regardless
    of site), or the deprecated FactorizationConfig shim.
    """
    rule = as_policy(fact).resolve(site)
    entry = registry.get_factorization(rule.kind)
    return entry.make_spec(rule, in_features, out_features, bias, dtype)


class Linear:
    """A (possibly factorized) linear layer bound to a registry spec.

    init(key) -> params pytree; (params, x) -> y.  ``batch_dims`` adds leading
    parameter batch axes (e.g. MoE experts): init/apply are vmapped.
    """

    def __init__(
        self,
        fact,
        in_features: int,
        out_features: int,
        site: str = "other",
        bias: bool = False,
        dtype: Any = jnp.float32,
        batch_dims: tuple[int, ...] = (),
    ):
        self.policy = as_policy(fact)
        self.rule = self.policy.resolve(site)
        self.entry = registry.get_factorization(self.rule.kind)
        self.spec = self.entry.make_spec(self.rule, in_features, out_features,
                                         bias, dtype)
        self.site = site
        self.batch_dims = tuple(batch_dims)
        # the named scope the site's ops carry in compiled programs (and so
        # in device traces): fact.<kind>.<site>, or dense.<site>
        self.scope = f"dense.{site}" if self.rule.kind == "dense" \
            else f"fact.{self.rule.kind}.{site}"
        if self.rule.use_kernel:
            # attach Pallas backends to the registry before the first apply
            registry.ensure_kernels_registered()

    # -- params -----------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        if not self.batch_dims:
            return self.spec.init(key)
        init = self.spec.init
        for _ in self.batch_dims:
            init = jax.vmap(init)
        nkeys = 1
        for d in self.batch_dims:
            nkeys *= d
        keys = jax.random.split(key, nkeys)
        # reshape only the leading key axis: typed PRNG keys are scalars
        # ((nkeys,) array), legacy uint32 keys carry a trailing (2,)
        keys = keys.reshape(self.batch_dims + keys.shape[1:])
        return init(keys)

    def param_count(self) -> int:
        n = self.spec.param_count()
        for d in self.batch_dims:
            n *= d
        return n

    def dense_param_count(self) -> int:
        n = self.spec.dense_param_count()
        for d in self.batch_dims:
            n *= d
        return n

    # -- forward ----------------------------------------------------------
    def _apply_one(self, params: dict, x: jax.Array) -> jax.Array:
        if self.entry.shard_tokens and x.ndim == 3:
            # distributed butterfly schedule: tokens shard over BOTH mesh
            # axes, features stay full — factor weights (data-sharded or
            # replicated) then apply without inter-factor activation
            # resharding (no-op without an installed mesh)
            from repro.parallel import context as pctx
            x = pctx.constrain(x, "dp", "tp", None)
        return self.entry.apply(self.spec, params, x,
                                use_kernel=self.rule.use_kernel)

    def __call__(self, params: dict, x: jax.Array) -> jax.Array:
        """params has leading batch_dims; x has matching leading dims."""
        apply = self._apply_one
        for _ in self.batch_dims:
            apply = jax.vmap(apply)
        with jax.named_scope(self.scope):
            return apply(params, x)
