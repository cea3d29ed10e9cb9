import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

FACT = {"default": {"kind": "dense"},
        "overrides": {"mlp": {"kind": "pixelfly", "block_size": 16,
                              "rank": 4},
                      "attn_*": {"kind": "butterfly", "block_size": 16}}}


# The widest gap allowed at the tiny size: sound CPU runs of the tiny
# cells read 0 to 0.008 and the float8 control 0.107 to 0.249.
TINY_LIMIT = 0.05


def tiny_config(policy=FACT, tp=1) -> dict:
    """A Qwen3-shaped configuration a CPU runs in seconds."""
    return {"name": "tiny", "model_type": "qwen3", "hidden_act": "silu",
            "attention_bias": False, "hidden_size": 64,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "intermediate_size": 96, "vocab_size": 500,
            "num_hidden_layers": 2, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
            "tie_word_embeddings": False, "reference": "qwen3",
            "policy": policy, "mesh": {"dp": 1, "tp": tp}, "page_size": 8}


def tiny_traffic(loop="open") -> dict:
    return {"name": f"tiny-{loop}", "loop": loop, "rate_per_s": 30.0,
            "prompt": {"law": "lognormal", "median": 16, "sigma": 0.7,
                       "min": 4, "max": 32},
            "output": {"law": "lognormal", "median": 8, "sigma": 0.7,
                       "min": 2, "max": 16},
            "max_len": 48, "max_slots": 4, "ramp_seconds": 0.5,
            "max_wave_rows": 2, "check_requests": 4, "trace_seconds": 0.5}


def tiny_cell(loop="open", policy=FACT, tp=1, limit=TINY_LIMIT):
    """A cell of the tiny configuration, reporting the chat cell's metrics
    and, in a closed loop, the completed tokens per second."""
    from bench.harness import Cell, load_cell
    base = load_cell("qwen3-4b-fact.chat")
    e2e = list(base.end_to_end)
    if loop == "closed":
        e2e.append({"name": "output_tokens_per_s", "unit": "tokens/s"})
    return Cell(f"tiny.{loop}", tiny_config(policy, tp), tiny_traffic(loop),
                tp, e2e, base.per_layer, {"widest_gap": limit})
