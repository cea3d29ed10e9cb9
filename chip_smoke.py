#!/usr/bin/env python
"""Smoke run of the system on a TPU: kernels, serving and training.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the tensor-parallel path on four

One process drives every phase, and each phase fails the run on the first
error.  With no TPU the script exits nonzero before doing any work.

One chip:
  (a) device: the backend is a TPU; its kind and count are printed.
  (b) kernels: the fused butterfly (N = 4096) and pixelfly (N = 16384)
      Pallas kernels, b = 128, compiled natively at decode (M = 4) and
      prefill (M = 512) rows, against their jnp references.
  (c) paged attention: the paged decode attention kernel and the gather
      path it replaces at the chat cell's decode pool (32 rows, 96 pages
      of 16, 1485 blocks, 32 q heads and 8 kv heads of 128; ragged rows,
      some idle), compiled natively: their largest difference and each
      one's time per layer.
  (d) serve: qwen3-4b at its published widths, dense and under its
      recommended butterfly/pixelfly policy, through the serve CLI's own
      setup; 4 requests of 64 prompt tokens and 32 new tokens each over the
      paged cache, checked against the token-by-token reference.
  (e) train: three steps of butterfly-lm-100m at full width through the
      training launcher's loop, with no failed step tolerated.

--four-chips runs only: minitron-8b (about 19.8 GB of bf16 weights, more
than one chip holds) served at dp=1 x tp=4, checked against the reference
on the same mesh, with each chip's share of the weights printed.

Each phase prints its wall time, the time JAX spent compiling in it, and
the device's peak bytes in use.  The last line of standard output is one
JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

PROMPT_LEN, MAX_NEW, NUM_REQUESTS = 64, 32, 4
KERNEL_BLOCK = 128
# Kernel outputs against float32 references computed from the same bf16
# inputs.  The fused butterfly rounds its activation to bf16 before each of
# log2(32) = 5 factors and once on output; six roundings of at most 2^-9
# relative each bound the error near 1.2% of the output scale (unit here),
# so 2e-2 absolute plus 2e-2 relative leaves margin and still catches any
# wrong block pairing, which is an O(1) error.
KERNEL_TOL = dict(rtol=2e-2, atol=2e-2)
# served prefill logits against the reference: see compare_to_reference
LOGIT_RTOL = 0.1

# seconds JAX spent tracing, lowering and compiling ("compile"), the part
# of it in the backend compiler or reading the persistent cache
# ("backend"), and the persistent cache's requests and hits
_counts = dict(compile=0.0, backend=0.0, requests=0, hits=0)


def _on_duration(event: str, duration: float, **_) -> None:
    if event.startswith("/jax/core/compile/"):
        _counts["compile"] += duration
    if event == "/jax/core/compile/backend_compile_duration":
        _counts["backend"] += duration


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        _counts["requests"] += 1
    elif event == "/jax/compilation_cache/cache_hits":
        _counts["hits"] += 1


@contextlib.contextmanager
def phase(name: str):
    """Prints the phase's wall time, compile time, persistent-cache hits
    and the device's peak memory when the phase ends without an error."""
    t0, c0 = time.perf_counter(), dict(_counts)
    print(f"[{name}] start", flush=True)
    yield
    d = {k: _counts[k] - c0[k] for k in _counts}
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    print(f"[{name}] ok: wall {time.perf_counter() - t0:.2f} s, "
          f"compile {d['compile']:.2f} s (backend {d['backend']:.2f} s), "
          f"persistent cache hits {d['hits']} of {d['requests']}, "
          f"peak_bytes_in_use {peak} (device 0)", flush=True)


def check_device(count: int) -> dict:
    """(a) The backend must be a TPU with ``count`` devices."""
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX backend is "
                         f"{backend!r}); nothing was run")
    devices = jax.devices()
    if len(devices) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU devices, found "
                         f"{len(devices)}")
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    print(f"device: {dev['platform']} {dev['kind']} x {dev['count']}",
          flush=True)
    return dev


def run_kernels() -> None:
    """(b) Both Pallas kernels natively, against their references."""
    from repro.core.butterfly import init_factors
    from repro.kernels.butterfly.ops import fused_apply
    from repro.kernels.butterfly.ref import fused_butterfly_apply_ref
    from repro.kernels.pixelfly.ops import bsmm
    from repro.kernels.pixelfly.ref import pixelfly_bsmm_ref

    b = KERNEL_BLOCK
    key = jax.random.PRNGKey(0)
    for kind, n in (("butterfly", 4096), ("pixelfly", 16384)):
        nb = n // b
        kw, kx = jax.random.split(jax.random.fold_in(key, n))
        if kind == "butterfly":
            w = [f.astype(jnp.bfloat16) for f in init_factors(kw, n, b)]

            def kernel(x, w):
                return fused_apply(x, w, block_size=b, interpret=False)

            def ref(x, w):
                return fused_butterfly_apply_ref(
                    x, [f.astype(jnp.float32) for f in w], block_size=b)
        else:
            k = nb.bit_length()  # 1 + log2(nb) support blocks per row
            w = (jax.random.normal(kw, (nb, k, b, b))
                 / np.sqrt(k * b)).astype(jnp.bfloat16)

            def kernel(x, w):
                return bsmm(x, w, block_size=b, interpret=False)

            def ref(x, w):
                return pixelfly_bsmm_ref(x, w.astype(jnp.float32),
                                         block_size=b)
        for m in (4, 512):
            x = jax.random.normal(jax.random.fold_in(kx, m),
                                  (m, n)).astype(jnp.bfloat16)
            # weights are arguments, not closed-over constants: a constant
            # would be baked into the executable and its cache entry
            fn = jax.jit(kernel)
            t0 = time.perf_counter()
            got = jax.block_until_ready(fn(x, w))
            first = time.perf_counter() - t0
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x, w))
            warm = time.perf_counter() - t0
            assert "tpu_custom_call" in fn.lower(x, w).as_text(), kind
            with jax.default_matmul_precision("highest"):
                want = jax.jit(ref)(x.astype(jnp.float32), w)
            got, want = np.asarray(got, np.float32), np.asarray(want)
            np.testing.assert_allclose(got, want, **KERNEL_TOL,
                                       err_msg=f"{kind} N={n} M={m}")
            print(f"  {kind} N={n} b={b} M={m}: max |err| "
                  f"{np.abs(got - want).max():.3e} (|ref| max "
                  f"{np.abs(want).max():.3f}); first call {first:.3f} s, "
                  f"warm call {warm * 1e3:.3f} ms", flush=True)


def _per_call(fn, *args, calls: int = 20) -> float:
    """Seconds per call of a warm jitted ``fn``, over ``calls`` calls."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls


def run_paged_attention() -> None:
    """(c) The paged decode attention kernel against the gather path at
    the chat cell's pool: 21 live rows of ragged lengths (1 and 1536
    among them) and 11 idle ones, one layer's K and V.  The two differ by
    the bf16 rounding of the softmax weights into the MXU and of their
    outputs, well inside KERNEL_TOL; a wrong page or mask is O(1)."""
    from repro.kernels.paged_attention.ops import paged_attention
    from repro.kernels.paged_attention.ref import paged_decode_attention_ref

    rows, live_rows, max_pages, page, blocks = 32, 21, 96, 16, 1485
    hq, hkv, hd = 32, 8, 128
    rng = np.random.default_rng(0)
    lengths = np.zeros(rows, np.int32)
    live = np.sort(rng.choice(rows, live_rows, replace=False))
    lengths[live] = rng.integers(1, max_pages * page + 1, live_rows)
    lengths[live[:2]] = (1, max_pages * page)
    table = np.zeros((rows, max_pages), np.int32)
    ids = rng.permutation(np.arange(1, blocks))
    used = 0
    for r in live:
        n = -(-int(lengths[r]) // page)
        table[r, :n] = ids[used:used + n]
        used += n
    kk, kv, kq = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (blocks, page, hkv, hd)
    k = jax.random.normal(kk, shape, jnp.bfloat16)
    v = jax.random.normal(kv, shape, jnp.bfloat16)
    q = (jax.random.normal(kq, (rows, hq, hd)) * hd ** -0.5).astype(
        jnp.bfloat16)
    lens, tab = jnp.asarray(lengths), jnp.asarray(table)

    want = np.asarray(paged_decode_attention_ref(q, k, v, lens, tab),
                      np.float32)
    t_gather = _per_call(paged_decode_attention_ref, q, k, v, lens, tab)
    pages = int(sum(-(-int(n) // page) for n in lengths))
    print(f"  gather path: {t_gather * 1e3:.4f} ms per layer "
          f"({rows} x {max_pages} pages gathered)", flush=True)
    kernel = jax.jit(paged_attention)
    got = np.asarray(kernel(q, k, v, lens, tab), np.float32)
    t = _per_call(kernel, q, k, v, lens, tab)
    # K/V bytes of the pages walked, over the kernel's time
    rate = pages * 2 * page * hkv * hd * 2 / t / 1e9
    print(f"  kernel: {t * 1e3:.4f} ms per layer ({pages} pages walked, "
          f"{rate:.1f} GB/s of K/V); max |diff| against the gather path "
          f"{np.abs(got - want).max():.3e} (|ref| max "
          f"{np.abs(want).max():.3f})", flush=True)
    np.testing.assert_allclose(got, want, **KERNEL_TOL,
                               err_msg="paged attention")


def serve_and_check(argv: list[str], label: str):
    """(d) Build the engine exactly as the serve CLI does, serve
    NUM_REQUESTS greedy requests and check them against the reference.
    Returns the engine, which holds the served weights."""
    from repro.launch.serve import build_parser, setup
    from repro.models import prefill
    from repro.serving import Request, token_by_token_greedy

    args = build_parser().parse_args(
        argv + ["--batch", str(NUM_REQUESTS), "--prompt-len", str(PROMPT_LEN),
                "--max-new", str(MAX_NEW)])
    engine, cfg = setup(args)
    max_len = PROMPT_LEN + MAX_NEW
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, size=(NUM_REQUESTS, PROMPT_LEN),
                           dtype=np.int32)
    requests = [Request(f"{label}-{i}", tuple(int(t) for t in prompts[i]),
                        MAX_NEW) for i in range(NUM_REQUESTS)]
    t0 = time.perf_counter()
    outputs = engine.run(requests)
    print(f"  {label}: served {NUM_REQUESTS} x ({PROMPT_LEN} prompt + "
          f"{MAX_NEW} new) in {time.perf_counter() - t0:.2f} s (first run, "
          f"compiles included); decode steps {engine.stats.decode_steps}",
          flush=True)
    for out in outputs:
        assert len(out.tokens) == MAX_NEW, (out.request_id, len(out.tokens))
    compiles = engine.decode_compile_count()
    assert compiles == 1, f"decode compiled {compiles} times"

    # the served path's last-prompt-position logits (the batched prefill
    # the engine dispatches), which must be finite for every request
    params = engine.params
    last = jax.jit(lambda p, t: prefill(p, cfg, t, max_len)[0][:, -1])(
        params, jnp.asarray(prompts))
    last = np.asarray(last[:, : cfg.vocab_size], np.float32)
    assert np.isfinite(last).all(), f"{label}: non-finite logits"

    ref_tok, ref_logits = token_by_token_greedy(
        params, cfg, jnp.asarray(prompts), MAX_NEW, max_len,
        return_logits=True)
    ref_tok, ref_logits = np.asarray(ref_tok), np.asarray(ref_logits)
    compare_to_reference(label, outputs, last, ref_tok, ref_logits)
    return engine


def compare_to_reference(label, outputs, last, ref_tok, ref_logits) -> None:
    """Served tokens and prefill logits against the token-by-token oracle.

    The batched prefill and the one-token-at-a-time reference add the same
    bf16 products in different orders, so their logits differ by bf16
    rounding noise.  Each rounding is at most 2^-9 relative; a few hundred
    of them along 36 layers (more with factorized linears, which round
    between factors) random-walk to a few percent of the logits' RMS.  The
    logits must agree to 10% relative RMS: a wrong cache position, mask or
    weight moves them by their own scale (relative RMS near 1).  Greedy
    tokens must equal the reference's until a step where the reference's
    pick beats the served token by no more than twice the observed noise
    (the largest prefill-logit difference): a near tie either order may
    break, after which the two sequences are no longer comparable."""
    ref0 = ref_logits[:, 0]
    noise = float(np.abs(last - ref0).max())
    rel = float(np.linalg.norm(last - ref0) / np.linalg.norm(ref0))
    print(f"  {label}: prefill vs reference logits: max |diff| {noise:.4f}, "
          f"relative RMS {rel:.4f} (limit {LOGIT_RTOL})", flush=True)
    assert rel <= LOGIT_RTOL, f"{label}: logits differ, relative RMS {rel}"
    tie = 2 * noise
    for i, out in enumerate(outputs):
        served = np.asarray(out.tokens)
        diff = np.nonzero(served != ref_tok[i])[0]
        if len(diff) == 0:
            print(f"  {label}: request {i} equals the reference for all "
                  f"{len(served)} tokens", flush=True)
            continue
        j = int(diff[0])
        gap = float(ref_logits[i, j, ref_tok[i, j]]
                    - ref_logits[i, j, served[j]])
        print(f"  {label}: request {i} equals the reference for {j} tokens, "
              f"then a near tie (logit gap {gap:.4f}, limit {tie:.4f})",
              flush=True)
        assert gap <= tie, (
            f"{label}: request {i} diverged at token {j} with logit gap "
            f"{gap} > {tie}")


def run_train() -> None:
    """(e) Three full-width butterfly-lm-100m steps, no failure tolerated."""
    from repro.configs import get_config
    from repro.launch.train import train

    with tempfile.TemporaryDirectory() as ckpt:
        losses = train(get_config("butterfly-lm-100m"), steps=3, batch=8,
                       seq=512, ckpt_dir=ckpt, ckpt_every=1000,
                       max_failures=0)
    assert len(losses) == 3 and np.isfinite(losses).all(), losses
    print(f"  losses {losses}", flush=True)


def serve_qwen(argv: list[str], label: str) -> None:
    """One qwen3-4b policy; its weights are freed before the next phase,
    since the dense and factorized models do not fit one chip together."""
    serve_and_check(argv, label)
    gc.collect()


def run_four_chips() -> None:
    """minitron-8b at dp=1 x tp=4 against the reference on the same mesh;
    each chip must hold about a quarter of the weights."""
    engine = serve_and_check(["--arch", "minitron-8b", "--tp", "4"],
                             "minitron-8b-tp4")
    weights = sum(x.nbytes for x in jax.tree.leaves(engine.params))
    stats = [d.memory_stats() for d in jax.devices()]
    used = [s["bytes_in_use"] for s in stats]
    print(f"  weights {weights} bytes in all; bytes_in_use per device "
          f"{used}; peak_bytes_in_use per device "
          f"{[s['peak_bytes_in_use'] for s in stats]}", flush=True)
    # a quarter of the weights each, plus the small replicated leaves
    # (norms), the KV pool and the reference's caches
    for i, u in enumerate(used):
        assert 0.2 * weights <= u <= 0.35 * weights, (i, u, weights)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the minitron-8b tp=4 serving phase")
    args = ap.parse_args()
    dev = check_device(4 if args.four_chips else 1)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)

    if args.four_chips:
        with phase("serve minitron-8b dp=1 x tp=4"):
            run_four_chips()
    else:
        from repro.configs.qwen3_4b import FACT_POLICY
        with phase("kernels"):
            run_kernels()
        with phase("paged attention"):
            run_paged_attention()
        with phase("serve qwen3-4b dense"):
            serve_qwen(["--arch", "qwen3-4b"], "qwen3-4b")
        with tempfile.TemporaryDirectory() as tmp:
            policy = os.path.join(tmp, "qwen3_4b_fact.json")
            with open(policy, "w") as f:
                json.dump(FACT_POLICY.to_dict(), f)
            with phase("serve qwen3-4b fact"):
                serve_qwen(["--arch", "qwen3-4b", "--policy-json", policy],
                           "qwen3-4b-fact")
        with phase("train butterfly-lm-100m"):
            run_train()
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
