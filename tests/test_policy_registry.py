"""Tests for the pluggable factorization registry + per-site policy API."""
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ButterflySpec,
    DenseSpec,
    FactorizationConfig,
    FactorizationPolicy,
    Linear,
    PixelflySpec,
    Rule,
    make_spec,
    registry,
)
from repro.core.registry import register_factorization

MIXED = FactorizationPolicy(
    default=Rule(kind="dense"),
    overrides={
        "mlp": Rule(kind="pixelfly", block_size=8, rank=4),
        "attn_qkv": Rule(kind="butterfly", block_size=8),
        "head": Rule(kind="dense"),
    })


# ------------------------------------------------------------- resolve ----


def test_resolve_exact_then_glob_then_default():
    pol = FactorizationPolicy(
        default=Rule(kind="lowrank", rank=2),
        overrides={
            "attn_qkv": Rule(kind="pixelfly", block_size=8, rank=4),
            "attn_*": Rule(kind="butterfly", block_size=8),
        })
    assert pol.resolve("attn_qkv").kind == "pixelfly"  # exact beats glob
    assert pol.resolve("attn_out").kind == "butterfly"  # glob
    assert pol.resolve("mlp").kind == "lowrank"  # default


def test_mixed_policy_matches_per_spec_reference():
    """A mixed policy's Linear at each site computes exactly what the
    corresponding spec computes standalone."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 64))
    cases = [
        ("mlp", PixelflySpec(64, 48, block_size=8, rank=4, bias=False)),
        ("attn_qkv", ButterflySpec(64, 48, block_size=8, bias=False)),
        ("head", DenseSpec(64, 48, bias=False)),
    ]
    for site, ref_spec in cases:
        lin = Linear(MIXED, 64, 48, site=site)
        assert type(lin.spec) is type(ref_spec), site
        params = lin.init(key)
        ref_params = ref_spec.init(key)
        got = lin(params, x)
        want = ref_spec.apply(ref_params, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6, err_msg=site)


def test_make_spec_accepts_policy_rule_and_shim():
    assert isinstance(make_spec(MIXED, 64, 32, site="mlp"), PixelflySpec)
    assert isinstance(make_spec(Rule(kind="butterfly", block_size=8), 64, 32),
                      ButterflySpec)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        fc = FactorizationConfig(kind="butterfly", block_size=8, sites=("mlp",))
    assert isinstance(make_spec(fc, 64, 32, site="mlp"), ButterflySpec)
    assert isinstance(make_spec(fc, 64, 32, site="head"), DenseSpec)


# --------------------------------------------------------- serialization ----


def test_policy_json_round_trip():
    blob = json.dumps(MIXED.to_dict())
    back = FactorizationPolicy.from_dict(json.loads(blob))
    assert back == MIXED
    for site in ("mlp", "attn_qkv", "attn_out", "head", "other"):
        assert back.resolve(site) == MIXED.resolve(site)


def test_from_budget_fits_and_is_json_stable():
    sites = {"mlp": (1024, 1024), "attn_qkv": (1024, 768), "head": (1024, 256)}
    budget = 1_200_000  # dense total is ~2.1M
    pol = FactorizationPolicy.from_budget(budget, sites)
    total = sum(
        make_spec(pol, n_in, n_out, site=s, bias=False).param_count()
        for s, (n_in, n_out) in sites.items())
    assert total <= budget
    assert FactorizationPolicy.from_dict(pol.to_dict()) == pol


def test_from_budget_dense_when_budget_is_loose():
    pol = FactorizationPolicy.from_budget(10**9, {"mlp": (64, 64)})
    assert pol.resolve("mlp").kind == "dense"


def test_from_budget_raises_when_unreachable():
    with pytest.raises(ValueError, match="cannot fit"):
        FactorizationPolicy.from_budget(10, {"mlp": (1024, 1024)})


# -------------------------------------------------------------- registry ----


def test_registry_rejects_duplicate_kind():
    with pytest.raises(ValueError, match="already registered"):
        register_factorization(
            "butterfly", lambda rule, i, o, b, d: DenseSpec(i, o, b, d))


def test_duplicate_override_pattern_rejected():
    """Duplicate patterns would collapse across a to_dict round-trip,
    changing which rule wins — refused at construction."""
    with pytest.raises(ValueError, match="duplicate override"):
        FactorizationPolicy(overrides=(
            ("attn_*", Rule(kind="butterfly", block_size=8)),
            ("attn_*", Rule(kind="pixelfly", block_size=8)),
        ))


def test_unknown_site_name_rejected():
    """A typo'd literal site would silently resolve everything to the
    default — refuse it at construction (globs stay unchecked)."""
    with pytest.raises(ValueError, match="unknown site"):
        FactorizationPolicy(overrides={"attn_kqv": Rule(kind="butterfly")})
    # glob patterns are allowed
    FactorizationPolicy(overrides={"attn_*": Rule(kind="butterfly")})


def test_registry_unknown_kind_errors():
    with pytest.raises(KeyError, match="unknown factorization"):
        registry.get_factorization("nope")
    with pytest.raises(ValueError, match="registered"):
        Rule(kind="nope")


def test_registry_extensible_with_custom_kind():
    """A new kind registers, serves a Linear end-to-end, and unknown kinds
    never hit an isinstance chain."""
    kind = "test-double-dense"
    register_factorization(
        kind, lambda rule, i, o, b, d: DenseSpec(i, o, b, d))
    try:
        lin = Linear(Rule(kind=kind), 16, 8, site="mlp")
        params = lin.init(jax.random.PRNGKey(0))
        y = lin(params, jnp.ones((2, 16)))
        assert y.shape == (2, 8)
    finally:
        del registry._REGISTRY[kind]  # keep the global registry pristine


def test_kernel_dispatch_through_registry():
    """use_kernel routes through the registered Pallas backend (interpret
    mode on CPU) and matches the jnp reference path."""
    rule = Rule(kind="butterfly", block_size=128, use_kernel=True)
    lin = Linear(rule, 256, 256, site="mlp")
    entry = registry.get_factorization("butterfly")
    assert entry.kernel_apply is not None  # kernels attached on demand
    assert entry.kernel_supports(lin.spec)  # lane-aligned: the kernel path
    params = lin.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 256))
    got = lin(params, x)
    want = lin.spec.apply(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-5)


def test_kernel_path_is_differentiable():
    """use_kernel rules train: kernel forward, reference backward — grads
    match the pure-jnp path within kernel tolerance."""
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 256))
    k_lin = Linear(Rule(kind="butterfly", block_size=128, use_kernel=True),
                   256, 256, site="mlp")
    r_lin = Linear(Rule(kind="butterfly", block_size=128), 256, 256,
                   site="mlp")
    params = k_lin.init(jax.random.PRNGKey(0))
    gk = jax.grad(lambda p: (k_lin(p, x) ** 2).sum())(params)
    gr = jax.grad(lambda p: (r_lin(p, x) ** 2).sum())(params)
    for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_kernel_supports_gating_falls_back():
    """Blocks the kernel does not compile for (not lane-aligned) take the
    jnp path, on every platform, without error."""
    rule = Rule(kind="butterfly", block_size=4, use_kernel=True)
    lin = Linear(rule, 32, 32, site="mlp")
    assert not registry.get_factorization("butterfly").kernel_supports(
        lin.spec)
    params = lin.init(jax.random.PRNGKey(0))
    y = lin(params, jnp.ones((2, 32)))
    assert y.shape == (2, 32)


# ------------------------------------------------------------------ shim ----


def test_shim_emits_deprecation_warning():
    with pytest.warns(DeprecationWarning, match="FactorizationConfig"):
        FactorizationConfig(kind="butterfly", block_size=8, sites=("mlp",))


def test_shim_produces_identical_params_to_policy_path():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        fc = FactorizationConfig(kind="butterfly", block_size=8,
                                 sites=("mlp", "attn_qkv"))
    pol = FactorizationPolicy.uniform(
        Rule(kind="butterfly", block_size=8), sites=("mlp", "attn_qkv"))
    key = jax.random.PRNGKey(7)
    for site in ("mlp", "attn_qkv", "head"):
        a = Linear(fc, 64, 48, site=site)
        b = Linear(pol, 64, 48, site=site)
        assert type(a.spec) is type(b.spec)
        pa, pb = a.init(key), b.init(key)
        for la, lb in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 64))
        np.testing.assert_array_equal(np.asarray(a(pa, x)),
                                      np.asarray(b(pb, x)))


def test_typed_prng_key_batched_init():
    """Linear.init works with BOTH legacy uint32 keys and new-style typed
    keys for batched (MoE expert) params."""
    lin = Linear(Rule(kind="butterfly", block_size=8), 32, 32,
                 site="expert", batch_dims=(3, 2))
    p_legacy = lin.init(jax.random.PRNGKey(0))
    p_typed = lin.init(jax.random.key(0))
    for leaf in jax.tree.leaves(p_typed):
        assert leaf.shape[:2] == (3, 2)
    # the two key styles derive the same subkey streams
    for a, b in zip(jax.tree.leaves(p_legacy), jax.tree.leaves(p_typed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
