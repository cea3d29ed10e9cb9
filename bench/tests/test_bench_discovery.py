"""The harness finds a configuration, a traffic mix, a metric and a cell by
name, from new files and new BENCHMARK.json entries alone."""
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import harness
from bench.traffic_gen import load_traffic, make_plan

ROOT = Path(harness.ROOT)


def test_new_files_make_a_new_cell(tmp_path):
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "limits"):
        shutil.copytree(ROOT / "bench" / sub, bench / sub)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((bench / "configs" / "qwen3-8b.json").read_text())
    cfg["mesh"] = {"dp": 1, "tp": 1}
    (bench / "configs" / "qwen3-1x.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "chat.json").read_text())
    mix["rate_per_s"] = 1.5
    (bench / "traffic" / "slowchat.json").write_text(json.dumps(mix))
    (bench / "metrics" / "requests_seen.py").write_text(
        "def read(run):\n    return len(run.requests)\n")
    (bench / "limits" / "qwen3-1x.slowchat.json").write_text(
        json.dumps({"widest_gap": 0.5}))
    spec["configs"].append({**spec["configs"][0], "name": "qwen3-1x",
                            "file": "bench/configs/qwen3-1x.json"})
    spec["workloads"].append({"name": "qwen3-1x.slowchat",
                              "config": "qwen3-1x", "traffic": "slowchat",
                              "chips": 1, "why": "a test cell"})
    spec["per_layer"].append({"name": "requests_seen", "unit": "count",
                              "better": "higher",
                              "source": "program_counter",
                              "layer": "scheduler", "moves": "ttft_p50_ms",
                              "workloads": ["qwen3-1x.slowchat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("qwen3-1x.slowchat", root=tmp_path)
    assert cell.config["name"] == "qwen3-1x"
    assert cell.config["mesh"]["tp"] == 1
    assert cell.traffic["rate_per_s"] == 1.5
    assert cell.chips == 1 and cell.limits == {"widest_gap": 0.5}
    names = [m["name"] for m in cell.per_layer]
    assert "requests_seen" in names
    assert [m["name"] for m in cell.end_to_end] == [
        m["name"] for m in spec["end_to_end"] if "workloads" not in m]
    read = harness.reader("requests_seen", root=tmp_path)

    class FakeRun:
        requests = [1, 2, 3]
    assert read(FakeRun()) == 3
    # the cells that exist are found as before, without the new metric
    old = harness.load_cell("qwen3-4b-fact.chat", root=tmp_path)
    assert "requests_seen" not in [m["name"] for m in old.per_layer]


def _bucket(n, cap):
    return min(harness._pow2(n), harness._pow2(cap))


def test_warm_waves_reach_every_prefill_shape():
    t = load_traffic("chat")
    plan = make_plan(t, 3, 45, 32, 1000)
    lens, max_len, rows = plan.prompt_lens, t["max_len"], t["max_wave_rows"]
    waves = harness.warm_waves(lens, max_len, 32, rows)

    def shape(wave):
        w = _bucket(max(wave), max_len)
        rb = _bucket(len(wave), 32)
        return rb, w, len(wave) < rb or min(wave) < w
    warmed = {shape(w) for w in waves}
    assert {len(w) for w in waves} >= set(range(1, rows + 1))
    rng = np.random.default_rng(0)
    for _ in range(20000):
        wave = list(rng.choice(lens, size=rng.integers(1, rows + 1)))
        assert shape(wave) in warmed, wave
    for n in set(lens.tolist()):  # single rows of every exact length
        assert shape([n]) in warmed


# A mixture-of-experts architecture the benchmark has no file for: its
# program config, weight rules and reference come as new files alone.
TOY_ARCH = '''
import math

STACKED = ("dense_layers",)


def scale_rule(path, shape):
    if path.split("/")[-1] == "router":
        return 0.0, 1.0 / math.sqrt(shape[-2])
    return None


def program_config(c):
    import jax.numpy as jnp
    from repro.configs.base import ModelConfig
    from repro.core.policy import FactorizationPolicy
    lead = c["first_k_dense_replace"]
    return ModelConfig(
        name=c["name"], family="moe", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        d_ff=c["moe_intermediate_size"], vocab_size=c["vocab_size"],
        head_dim=c["head_dim"],
        pattern=(("attn", "dense"),) * lead + (("attn", "moe"),),
        num_experts=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
        num_shared_experts=c["n_shared_experts"], qk_norm=True,
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        fact=FactorizationPolicy.from_dict(c["policy"]),
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, remat=False)
'''

TOY_REF = '''
from bench.refs import qwen3

EXPERTS_READ_STAT = "experts_read"
top_layout = qwen3.top_layout


def _attention(c):
    d, hq, hkv, hd = (c["hidden_size"], c["num_attention_heads"],
                      c["num_key_value_heads"], c["head_dim"])
    return {"norm1": (d,), "norm2": (d,), "mixer/q_norm": (hd,),
            "mixer/k_norm": (hd,), "mixer/qkv/w": (d, (hq + 2 * hkv) * hd),
            "mixer/out/w": (hq * hd, d)}


def _mlp(prefix, d, ff, lead=()):
    return {f"{prefix}/gate/w": lead + (d, ff), f"{prefix}/up/w": lead + (d, ff),
            f"{prefix}/down/w": lead + (ff, d)}


def layer_layout(c):
    d, e = c["hidden_size"], c["n_routed_experts"]
    ff = c["moe_intermediate_size"]
    lead = c["first_k_dense_replace"]
    moe = {**_attention(c), "ffn/router": (d, e),
           **_mlp("ffn/experts", d, ff, (e,)),
           **_mlp("ffn/shared", d, ff * c["n_shared_experts"])}
    return [(lead, {**_attention(c), **_mlp("ffn", d, c["intermediate_size"])}),
            (c["num_hidden_layers"] - lead, moe)]


def token_share(c, path):
    if path.startswith("ffn/experts/"):
        return c["num_experts_per_tok"] / c["n_routed_experts"]
    return 1
'''

TOY_CONFIG = {
    "model_type": "toy_moe", "hidden_act": "silu", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 32, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "num_experts_per_tok": 2, "n_shared_experts": 1,
    "first_k_dense_replace": 1, "num_hidden_layers": 2, "vocab_size": 500,
    "rms_norm_eps": 1e-6, "rope_theta": 1e6, "reference": "toy_moe",
    "policy": {"default": {"kind": "dense"}, "overrides": {}},
    "mesh": {"dp": 1, "tp": 1}, "page_size": 8}


@pytest.fixture
def toy_root(tmp_path):
    """A copy of the benchmark's data with a toy MoE architecture, its
    reference, configuration, cell and limits added as new files."""
    from conftest import tiny_traffic
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "limits", "archs", "refs"):
        shutil.copytree(ROOT / "bench" / sub, bench / sub)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "archs" / "toy_moe.py").write_text(TOY_ARCH)
    (bench / "refs" / "toy_moe.py").write_text(TOY_REF)
    (bench / "configs" / "toy-moe.json").write_text(json.dumps(TOY_CONFIG))
    (bench / "traffic" / "tiny.json").write_text(
        json.dumps(tiny_traffic("closed")))
    (bench / "limits" / "toy-moe.tiny.json").write_text(
        json.dumps({"widest_gap": 0.5}))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({**spec["configs"][0], "name": "toy-moe",
                            "file": "bench/configs/toy-moe.json"})
    spec["workloads"].append({"name": "toy-moe.tiny", "config": "toy-moe",
                              "traffic": "tiny", "chips": 1,
                              "why": "a test cell"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    # no file the benchmark had was edited
    assert all(p.read_bytes() == b for p, b in before.items())
    return tmp_path


def test_new_architecture_builds_its_engine(toy_root):
    cell = harness.load_cell("toy-moe.tiny", root=toy_root)
    engine, cfg, mesh, plan, memory = harness.build(
        cell, 5, memory_bytes=50_000_000)
    assert cfg.family == "moe" and cfg.num_experts == 4 and cfg.top_k == 2
    assert cfg.pattern == (("attn", "dense"), ("attn", "moe"))
    assert mesh is None and engine.num_slots >= 1 and memory["pool"] > 0
    # the model's own weights: the router's scale comes from the
    # architecture's rule, the experts' from the shared one
    p = engine.core.executor.runner.params["periods"]["slot1"]["ffn"]
    router = np.asarray(p["router"], np.float32)
    assert router.std() == pytest.approx(1 / 8, rel=0.1)


def test_unknown_architecture_names_its_file(toy_root):
    from bench.model import load_config, model_config
    c = load_config("toy-moe", toy_root / "bench" / "configs")
    c["model_type"] = "no_such_arch"
    with pytest.raises(FileNotFoundError, match="archs/no_such_arch.py"):
        model_config(c)


def test_new_architecture_weights_per_layer(toy_root):
    import jax
    import jax.numpy as jnp
    from bench import weights
    from bench.model import arch, load_config, model_config
    from repro.models import init_params
    c = load_config("toy-moe", toy_root / "bench" / "configs")
    cfg = model_config(c)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    # a stack of two leading dense layers under a prefix of its own
    shapes["dense_layers"] = {"ffn": {"gate": {"w": jax.ShapeDtypeStruct(
        (2, 64, 32), jnp.bfloat16)}}}
    mod = arch(c)
    with pytest.raises(ValueError, match="router"):
        weights.make_like(3, shapes)
    got = weights.make_like(3, shapes, arch=mod)
    key = weights.seed_key(3)
    stack = got["dense_layers"]["ffn"]["gate"]["w"]
    for layer in (0, 1):
        want = weights.leaf_value(key, "dense_layers/ffn/gate/w", (64, 32),
                                  layer, mod.scale_rule)
        assert np.array_equal(np.asarray(stack[layer]), np.asarray(want))
    assert not np.array_equal(np.asarray(stack[0]), np.asarray(stack[1]))
    router = got["periods"]["slot1"]["ffn"]["router"]
    want = weights.leaf_value(key, "periods/slot1/ffn/router", (64, 4), 0,
                              mod.scale_rule)
    assert np.array_equal(np.asarray(router[0]), np.asarray(want))


def test_new_architecture_counts_experts_at_their_share(toy_root):
    from bench import counts
    from bench.model import load_config
    c = load_config("toy-moe", toy_root / "bench" / "configs")
    d, ff, e, k, v = 64, 32, 4, 2, 512
    attn = d * 128 + 64 * d
    dense = attn + 3 * d * ff
    experts = 3 * e * d * ff
    moe_shared = attn + d * e + 3 * d * ff
    head = d * v
    per_token = dense + moe_shared + experts * k / e + head
    ctx = 37
    attention = 4 * 2 * 4 * 16 * ctx
    assert counts.decode_token_flops(c, ctx) == pytest.approx(
        2 * per_token + attention)
    assert counts.weight_params(c)["matmul"] == (dense + moe_shared + experts
                                                 + head)
    assert counts.expert_read_bytes(c) == 2 * 3 * d * ff
    live = 12
    kv = 2 * 2 * 2 * 2 * 16
    norms = 2 * (2 * d + 2 * 16) + d
    dense_bytes = 2 * (dense + moe_shared + head + norms)
    assert counts.decode_min_bytes_per_chip(c, 1, live, None) is None
    assert counts.decode_min_bytes_per_chip(c, 1, live, 3) == pytest.approx(
        dense_bytes + kv * live + 3 * 2 * 3 * d * ff)


def test_expert_readers_need_the_expert_counter(toy_root):
    from bench import trace_reduce as tr
    from bench.counts import decode_min_bytes_per_chip
    from bench.model import load_config
    from bench.peaks import peaks_for
    c = load_config("toy-moe", toy_root / "bench" / "configs")
    dev = "/device:TPU:0"
    events = [tr.Event(dev, tr.MODULES_LINE, "jit_step_fn(1)", 0, 2e6),
              tr.Event(dev, tr.MODULES_LINE, "jit_step_fn(1)", 3e6, 2e6)]
    steps = [harness.Step(1.0, 1.01, [5, 7], [], None, None),
             harness.Step(1.02, 1.03, [6, 8], [], None, None)]

    def fake_run(stats):
        run = harness.Run(c, {}, 1, 1, peaks_for("TPU v5 lite"), 1.0,
                          (0.0, 10.0), [], steps, stats, 0)
        run.trace = {"events": events, "plane": dev, "span": (0.0, 10.0)}
        return run

    roofline = harness.reader("decode_hbm_roofline", root=toy_root)
    mfu = harness.reader("step_mfu_pct", root=toy_root)
    bare = fake_run({"decode_steps": 2})
    assert roofline(bare) is None and mfu(bare) is None
    counted = fake_run({"decode_steps": 2, "experts_read": 6})
    need = (decode_min_bytes_per_chip(c, 1, 12, 3)
            + decode_min_bytes_per_chip(c, 1, 14, 3)) / 2
    assert roofline(counted) == pytest.approx(
        100 * need / 819e9 / 2e-3)
    assert mfu(counted) > 0
