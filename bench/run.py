"""The benchmark's command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the chips of this machine and prints
one JSON object as the last line of standard output.  Exits nonzero, with
no result, where JAX finds no TPU or fewer chips than the cell asks for.
JAX's persistent compilation cache lives at a fixed path inside the
checkout, ``.bench_cache/jax``, so only a cell's first run there compiles.
"""
import time

T_START = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".bench_cache",
                                                       "jax")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
