"""The harness finds a configuration, a traffic mix, a metric and a cell by
name, from new files and new BENCHMARK.json entries alone."""
import json
import shutil
from pathlib import Path

import numpy as np

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
