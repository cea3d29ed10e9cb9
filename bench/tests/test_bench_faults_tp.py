"""The tensor-parallel cell's run at a tiny size on four simulated
devices: sound, it comes out correct; its float8 control, and each fault
planted under the timed path (the exchange between chips left out among
them), does not."""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

SCRIPT = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}, {here!r}]
from conftest import tiny_cell
from bench import faults
from bench.harness import run
dense = {{"default": {{"kind": "dense"}}, "overrides": {{}}}}
cell = tiny_cell("closed", dense, tp=4)
out = {{}}
runs = [("sound", None, False), ("control", None, True)] + [
    (name, fault, False) for name, fault in
    sorted({{**faults.FAULTS, **faults.FAULTS_TP}}.items())]
for name, fault, control in runs:
    r = run(cell, 77, 1.5, False, require_tpu=False,
            memory_bytes=50_000_000, fault=fault, control=control)
    out[name] = [r["correct"], r["checks"], r["metrics"]]
print(json.dumps(out))
"""


def test_tp_run_and_dropped_exchange():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SCRIPT.format(root=ROOT, src=os.path.join(ROOT, "src"), here=HERE)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["sound"][0], out["sound"]
    assert "output_tokens_per_s" in out["sound"][2]
    assert set(out) == {"sound", "control", "exchange_dropped",
                        "state_unchanged", "token_altered"}
    for name in set(out) - {"sound"}:
        assert not out[name][0], (name, out[name])
