"""Qwen3 through the per-architecture files reads exactly as it did when
its config, weight rules and counts were written for Qwen3 alone.  The
readings in ``qwen3_before_archs.json`` were taken from that harness."""
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import counts, weights
from bench.model import load_config, model_config
from conftest import tiny_config

BEFORE = json.loads((Path(__file__).parent
                     / "qwen3_before_archs.json").read_text())
CONFIGS = sorted(BEFORE["configs"])


def _shapes(c: dict):
    from repro.models import init_params
    cfg = model_config(c)
    return jax.eval_shape(lambda k: init_params(cfg, k),
                          jax.random.PRNGKey(0))


@pytest.mark.parametrize("name", CONFIGS)
def test_parameter_and_kv_counts(name):
    c, before = load_config(name), BEFORE["configs"][name]
    assert counts.weight_params(c) == before["weight_params"]
    assert counts.weight_bytes(c) == before["weight_bytes"]
    assert counts.kv_bytes_per_token(c) == before["kv_bytes_per_token"]
    for tp in BEFORE["tp"]:
        assert counts.decode_weight_bytes_per_chip(c, tp) == before[
            "decode_weight_bytes_per_chip"][str(tp)]
        for live in BEFORE["live"]:
            assert counts.decode_min_bytes_per_chip(c, tp, live) == before[
                "decode_min_bytes_per_chip"][f"{tp}/{live}"]


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("fn", ["attention_flops", "decode_token_flops",
                                "prefill_flops"])
def test_flops(name, fn):
    c, before = load_config(name), BEFORE["configs"][name][fn]
    for context in BEFORE["context"]:
        assert getattr(counts, fn)(c, context) == before[str(context)]


@pytest.mark.parametrize("name", CONFIGS)
def test_model_config(name):
    assert repr(model_config(load_config(name))) == BEFORE["configs"][name][
        "model_config"]


def test_scale_rule_of_every_leaf():
    shapes = _shapes(load_config("qwen3-4b-fact"))
    seen = {}
    for p, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        path = weights.path_str(p)
        per = leaf.shape[1:] if path.startswith("periods/") else leaf.shape
        seen[path] = [list(per), list(weights.scale_rule(path, tuple(per)))]
    assert seen == BEFORE["scale_rule"]


def test_seeded_weights_bit_identical():
    params = weights.make_like(2 ** 33 + 5, _shapes(tiny_config()))
    digests = {weights.path_str(p): hashlib.sha256(
        np.asarray(leaf).tobytes()).hexdigest()
        for p, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert digests == BEFORE["tiny_weights_sha256"]
