"""The plain reference against the program's own forward pass, and its
parameter layout against the program's at the cells' real sizes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.model import load_config, model_config
from bench.refs.qwen3 import Reference, layer_layout, top_layout
from bench.weights import make_like, path_str
from conftest import FACT, tiny_config


def program_layout(c):
    from repro.models import init_params
    cfg = model_config(c)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    layer = {path_str(p)[len("periods/slot0/"):]: tuple(x.shape[1:])
             for p, x in flat if path_str(p).startswith("periods/")}
    top = {path_str(p): tuple(x.shape) for p, x in flat
           if not path_str(p).startswith("periods/")}
    return shapes, layer, top


@pytest.mark.parametrize("name", ["qwen3-4b-fact", "qwen3-8b"])
def test_layout_matches_program_at_real_size(name):
    c = load_config(name)
    _, layer, top = program_layout(c)
    assert layer == {k: tuple(v) for k, v in layer_layout(c).items()}
    assert top == {k: tuple(v) for k, v in top_layout(c).items()}


@pytest.mark.parametrize("policy", [{"default": {"kind": "dense"},
                                     "overrides": {}}, FACT],
                         ids=["dense", "butterfly+pixelfly"])
def test_reference_logits_match_program_forward(policy):
    from repro.models import forward
    c = tiny_config(policy)
    shapes, _, _ = program_layout(c)
    seed = 2 ** 36 + 5
    params = make_like(seed, shapes)
    cfg = model_config(c)
    cfg32 = cfg.__class__(**{**cfg.__dict__, "dtype": jnp.float32})
    toks = np.random.default_rng(0).integers(0, 500, (3, 20))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward(
            jax.tree.map(lambda x: x.astype(jnp.float32), params), cfg32,
            jnp.asarray(toks)))[..., :500]
    ref = Reference(c, seed)
    flat = want.reshape(60, 500)
    probe = np.stack([flat.argmax(-1), np.arange(60) % 500], axis=1)
    got = ref.summary(ref.hidden(toks), np.arange(60), probe)
    picked = np.take_along_axis(flat, probe, axis=1)
    scale = np.abs(flat).max()
    assert np.abs(got["at"] - picked).max() < 1e-4 * scale
    assert np.abs(got["best"] - flat.max(-1)).max() < 1e-4 * scale
    assert np.abs(got["std"] - flat.std(-1)).max() < 1e-4 * scale
    assert np.array_equal(got["argmax"], flat.argmax(-1))
    # the float8 control departs from both by far more than rounding
    ctl = ref.summary(ref.hidden(toks, "fp8"), np.arange(60), probe, "fp8")
    assert np.abs(ctl["at"] - picked).max() > 0.05 * scale
