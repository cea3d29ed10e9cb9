"""Knee sweep of an open-loop cell: one engine, set up once, serves a
window at each offered rate, and each line says whether the backlog grew.

    python3 bench/sweep.py --workload qwen3-4b-fact.chat --rates 3,5,7 \\
        --seconds 20 --seed 1

One JSON line per rate on standard output.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".bench_cache",
                                                       "jax")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"

from bench import harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    b = harness.Bench(harness.load_cell(args.workload), args.seed,
                      t_start=T_START)
    print(json.dumps({"setup_s": b.setup_s, "slots": b.engine.num_slots,
                      "pages": b.engine.num_pages, "memory": b.memory,
                      "memory_peak_bytes": b.memory_peak()}), flush=True)
    for rate in (float(x) for x in args.rates.split(",")):
        run = b.window(args.seconds, args.seed, rate=rate)
        due = [r for r in run.requests if run.in_window(r.due)]
        late = [r.seq.t_first_token - run.window[1] for r in due
                if r.seq.t_first_token is not None
                and r.seq.t_first_token >= run.window[1]]
        waves = collections.Counter(len(x.prefill_lens) for x in run.steps
                                    if x.prefill_lens)
        row = {"rate": rate, "due": len(due),
               "no_first_token": sum(1 for r in due
                                     if r.seq.t_first_token is None),
               "first_token_after_close": len(late),
               "wait_after_close_s": max(late, default=0.0),
               "finished_in_window": sum(1 for r in run.requests
                                         if run.in_window(r.seq.t_finished)),
               "waves": dict(sorted(waves.items())),
               "decode_steps": run.stats["decode_steps"],
               "engine_decode_ms": (1e3 * run.stats["decode_time"]
                                    / max(1, run.stats["decode_steps"])),
               "memory_peak_bytes": b.memory_peak()}
        for m in ("ttft_tail_p95_ms", "itl_tail_p95_ms", "ttft_p50_ms",
                  "itl_p50_ms", "itl_mean_ms", "output_tokens_per_s",
                  "queue_wait_p50_ms", "decode_batch_mean",
                  "kv_pages_used_pct", "compiles_in_window"):
            row[m] = harness.reader(m)(run)
        print(json.dumps(row), flush=True)
        b.drain()


if __name__ == "__main__":
    main()
