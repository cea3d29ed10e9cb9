"""Serving CLI: a thin shell over the continuous-batching ``Engine``.

Builds the architecture at its published widths, with random weights made
on the device in the compute dtype; ``--reduce`` swaps in the 128-wide
smoke preset that a CPU can run.  Requests are admitted into
decode slots over a PAGED KV cache by default (``--page-size`` blocks; the
scheduler admits against free pages, so short requests stop paying for
``max_len`` stripes — ``--fixed-slots`` falls back to the dense SlotCache),
prefill is ONE batched forward per prompt-length group (not a per-token
decode loop), and sampling (greedy / temperature / top-k / stop tokens) is
per-request.  The old token-by-token prefill path survives as
``repro.serving.reference.token_by_token_greedy`` — the parity oracle the
engine is tested against.

``--dp/--tp`` serve across a (data, model) mesh: decode becomes one SPMD
dispatch per step (DESIGN.md section 9).  On CPU, host devices are
simulated with XLA_FLAGS=--xla_force_host_platform_device_count=N.

``--http PORT`` switches from the closed-batch demo to an open HTTP
server over :class:`repro.serving.AsyncEngine` (DESIGN.md section 11):
stdlib ``asyncio`` networking only, no web framework.

  POST /generate   JSON body {"prompt": [ids], "max_new": n, and optional
                   "temperature", "top_k", "seed", "stop_tokens"}.
                   Responds 200 with Content-Type application/x-ndjson and
                   ``Connection: close``: one JSON object PER LINE, each a
                   TokenDelta {"request_id", "token", "index"}, the last
                   line adding "finish_reason"; the body ends (connection
                   closes) after the terminal line.  Tokens stream as the
                   step loop produces them — a second request POSTed while
                   the first is mid-stream interleaves, it does not wait.
  GET /stats       One JSON object: engine throughput counters, scheduler
                   occupancy, prefix-cache hit rates (--prefix-cache), and
                   TTFT/ITL aggregates over completed requests
                   (None-valued stages skipped, PR 4 rules).
  GET /healthz     Cheap liveness probe: {"status": "ok"} plus a
                   free_pages/free_slots/waiting snapshot — what a replica
                   router dispatches on.

``--prefix-cache`` turns on the radix-tree prefix cache over the paged
pool (DESIGN.md section 12): repeated prompt heads skip prefill for the
matched pages, bit-identical to the uncached stream.

``--chunk-size N`` turns on chunked prefill (DESIGN.md section 15): each
step composes every running slot's decode token with up to N prompt
tokens from the queue head into ONE mixed dispatch, so a long admission
no longer stalls running decodes — token streams stay bit-identical to
the unchunked path.

``--speculative`` turns on speculative decoding (DESIGN.md section 16): a
small dense draft — the target's first ``--draft-layers`` layers sharing
its embedding/head — proposes ``--spec-k`` tokens per slot per round and
ONE batched target dispatch verifies them all; the output stream stays
bit-identical to non-speculative decode at any temperature.  Conflicts
with ``--chunk-size`` and ``--swap``.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import itertools
import json
import logging

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.core.policy import FactorizationPolicy, uniform_policy
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_serving_mesh
from repro.serving import (AsyncEngine, Engine, LocalExecutor, Request,
                           RequestOutput, SamplingParams, init_serving_params,
                           make_requests, percentile, resolve_engine_spec)
from repro.serving.budget import plan_engine_report

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
log = logging.getLogger("repro.serve")


def resolve_policy(args) -> FactorizationPolicy | None:
    """--policy-json (a FactorizationPolicy.to_dict file) wins over --fact
    (uniform kind at the classic sites); None keeps the config's policy."""
    if args.policy_json:
        with open(args.policy_json) as f:
            return FactorizationPolicy.from_dict(json.load(f))
    if args.fact and args.fact != "dense":
        return uniform_policy(args.fact, block_size=args.fact_block)
    return None


def make_draft(cfg, params, draft_layers: int):
    """(draft_params, draft_cfg) for ``--speculative``: the target's first
    ``draft_layers`` layers with the embedding / final norm / head SHARED
    (zero extra bytes for those), under the same factorization policy so
    the sliced period params apply unchanged.  A distilled draft would
    load its own checkpoint here; the truncated-target draft is the
    zero-training stand-in with the right cost shape."""
    period = len(cfg.pattern)
    if draft_layers < period or draft_layers % period != 0 or \
            draft_layers >= cfg.num_layers:
        raise ValueError(
            f"--draft-layers must be a multiple of the pattern period "
            f"({period}) in [{period}, {cfg.num_layers}), got {draft_layers}")
    m = draft_layers // period
    draft_cfg = dataclasses.replace(cfg, num_layers=draft_layers)
    draft_params = dict(params)
    draft_params["periods"] = jax.tree.map(lambda x: x[:m],
                                           params["periods"])
    return draft_params, draft_cfg


def build_engine(args, cfg, params, max_len: int, mesh) -> Engine:
    """Engine construction shared by the closed-batch and HTTP modes.

    Construction goes through the Executor seam: args normalize into an
    :class:`EngineSpec` via ``resolve_engine_spec`` (the --dp/--tp mesh and
    single-device paths share this one code path — the spec owns the mesh
    rounding), a :class:`LocalExecutor` builds the runner, and the Engine
    facade wraps it."""
    page_size = None if (args.fixed_slots or not args.page_size) \
        else args.page_size
    prefix = bool(getattr(args, "prefix_cache", False))
    if prefix and page_size is None:
        raise SystemExit("--prefix-cache needs the paged KV cache; drop "
                         "--fixed-slots / set --page-size")
    overcommit = float(getattr(args, "overcommit", 1.0) or 1.0)
    swap = bool(getattr(args, "swap", False))
    if (overcommit > 1.0 or swap) and page_size is None:
        raise SystemExit("--overcommit/--swap need the paged KV cache; drop "
                         "--fixed-slots / set --page-size")
    chunk_size = int(getattr(args, "chunk_size", 0) or 0) or None
    if chunk_size is not None and page_size is None:
        raise SystemExit("--chunk-size needs the paged KV cache; drop "
                         "--fixed-slots / set --page-size")
    speculative = bool(getattr(args, "speculative", False))
    spec_k = int(getattr(args, "spec_k", 0) or 0) or None
    if speculative and chunk_size is not None:
        raise SystemExit("--speculative and --chunk-size are mutually "
                         "exclusive: a verify round is the step's whole "
                         "token budget")
    if speculative and swap:
        raise SystemExit("--speculative composes with drop-and-recompute "
                         "preemption only; drop --swap")
    draft_params = draft_cfg = None
    if speculative:
        try:
            draft_params, draft_cfg = make_draft(
                cfg, params, int(getattr(args, "draft_layers", 0)
                                 or len(cfg.pattern)))
        except ValueError as e:
            raise SystemExit(str(e))
    try:
        if args.memory_budget_mb:  # derived sizing; explicit flags conflict
            if args.slots or args.token_budget:
                raise SystemExit("--memory-budget-mb derives slots and token "
                                 "budget; drop --slots/--token-budget")
            budget = int(args.memory_budget_mb * 1e6)
            plan = plan_engine_report(cfg, budget, max_len, mesh=mesh,
                                      page_size=page_size,
                                      overcommit=overcommit,
                                      draft_cfg=draft_cfg)
            log.info("plan (per device): params %.2f MB, kv %.2f MB, "
                     "%d slots x %d shards -> %d total, token budget %s"
                     "%s",
                     plan.param_bytes_per_device / 1e6,
                     plan.kv_bytes_per_device / 1e6, plan.slots_per_device,
                     plan.dp_size, plan.num_slots, plan.token_budget,
                     f", {plan.num_pages} pages x {plan.page_size} tokens"
                     if plan.num_pages is not None else "")
            if draft_cfg is not None:
                savings = plan.dense_target_param_bytes_per_device - \
                    plan.param_bytes_per_device
                log.info("speculative plan: draft %.2f MB (dense-priced) + "
                         "%.2f MB/slot KV vs %.2f MB factorization "
                         "savings — %sfunded by compression",
                         plan.draft_param_bytes_per_device / 1e6,
                         plan.draft_slot_bytes_per_device / 1e6,
                         savings / 1e6,
                         "" if plan.draft_param_bytes_per_device <= savings
                         else "NOT ")
            # hand the spec the plan we just logged (num_slots is already a
            # dp multiple) instead of re-deriving it from the budget
            spec = resolve_engine_spec(
                cfg, max_len, num_slots=plan.num_slots,
                token_budget=(None if plan.num_pages is not None
                              else plan.token_budget),
                page_size=plan.page_size, num_pages=plan.num_pages,
                mesh=mesh, prefix_cache=prefix, overcommit=overcommit,
                swap=swap, chunk_size=chunk_size,
                speculative=speculative, spec_k=spec_k)
        else:
            spec = resolve_engine_spec(
                cfg, max_len, num_slots=(args.slots or min(args.batch, 8)),
                token_budget=args.token_budget or None, page_size=page_size,
                mesh=mesh, prefix_cache=prefix, overcommit=overcommit,
                swap=swap, chunk_size=chunk_size,
                speculative=speculative, spec_k=spec_k)
        executor = LocalExecutor(params, cfg, spec, mesh=mesh,
                                 draft_params=draft_params,
                                 draft_cfg=draft_cfg)
        return Engine.from_executor(executor)
    except ValueError as e:
        # e.g. --prefix-cache on a recurrent arch (needs pure attention)
        raise SystemExit(str(e))


def pooled_itls(outputs: list[RequestOutput]) -> list[float]:
    """Every inter-token gap across all requests, pooled into ONE sample —
    the true token-level ITL distribution (each token's wait counts once),
    unlike the per-request-summary aggregation which weights a 2-token
    request's single gap as heavily as a 500-token request's tail."""
    return [g for o in outputs for g in o.itls]


def _latency_lines(outputs: list[RequestOutput]) -> list[str]:
    """Human-readable TTFT/ITL/latency summary; every stage a sequence
    never reached is None and skipped, never zero-filled.  The pooled ITL
    line is the true per-token distribution; the per-request line (mean of
    request means, p99 of request p99s) is kept beside it for continuity
    with earlier runs."""
    lines = []
    lat = [o.latency for o in outputs if o.latency is not None]
    ttft = [o.time_to_first_token for o in outputs
            if o.time_to_first_token is not None]
    itl_m = [o.itl_mean for o in outputs if o.itl_mean is not None]
    itl_p = [o.itl_p99 for o in outputs if o.itl_p99 is not None]
    pooled = pooled_itls(outputs)
    if lat:
        lines.append(f"latency s: mean {float(np.mean(lat)):.3f} "
                     f"p50 {float(np.median(lat)):.3f} "
                     f"max {float(np.max(lat)):.3f}")
    if ttft:
        lines.append(f"ttft s: mean {float(np.mean(ttft)):.4f} "
                     f"p50 {percentile(ttft, 50):.4f} "
                     f"p99 {percentile(ttft, 99):.4f}")
    if pooled:
        lines.append(f"itl s (pooled, {len(pooled)} gaps): "
                     f"mean {float(np.mean(pooled)):.4f} "
                     f"p50 {percentile(pooled, 50):.4f} "
                     f"p99 {percentile(pooled, 99):.4f}")
    if itl_m:
        lines.append(f"itl s (per-request): mean {float(np.mean(itl_m)):.4f} "
                     f"p99 {percentile(itl_p, 99):.4f}")
    if not lines:
        lines.append(f"latency: 0/{len(outputs)} sequences finished "
                     "with timestamps")
    return lines


# ------------------------------------------------------------- HTTP front --
class ServerState:
    """Mutable bits shared by connection handlers: request ids + completed
    outputs for /stats (bounded so a long-lived server cannot grow it)."""

    MAX_COMPLETED = 4096

    def __init__(self):
        self.ids = itertools.count()
        self.completed: list[RequestOutput] = []

    def record(self, out: RequestOutput) -> None:
        self.completed.append(out)
        if len(self.completed) > self.MAX_COMPLETED:
            del self.completed[: len(self.completed) - self.MAX_COMPLETED]


def request_from_json(payload: dict, request_id: str) -> Request:
    """Wire JSON -> Request; raises ValueError on a malformed body (the
    handler maps that to 400)."""
    if not isinstance(payload, dict):
        raise ValueError("body must be a JSON object")
    unknown = set(payload) - {"prompt", "max_new", "temperature", "top_k",
                              "seed", "stop_tokens"}
    if unknown:
        raise ValueError(f"unknown fields: {sorted(unknown)}")
    prompt = payload.get("prompt")
    if not isinstance(prompt, list) or not prompt or \
            not all(isinstance(t, int) for t in prompt):
        raise ValueError("'prompt' must be a non-empty list of token ids")
    sampling = SamplingParams(
        temperature=float(payload.get("temperature", 0.0)),
        top_k=int(payload.get("top_k", 0)),
        seed=int(payload.get("seed", 0)),
        stop_tokens=tuple(payload.get("stop_tokens", ())))
    return Request(request_id=request_id, prompt=tuple(prompt),
                   max_new=int(payload.get("max_new", 16)),
                   sampling=sampling)


def _spec_section(engine: Engine) -> dict | None:
    """The /stats + /healthz speculative block (None when --speculative is
    off): acceptance bookkeeping, per-round yield, dispatch counts, and
    the draft/verify wall-time split."""
    if not engine.speculative:
        return None
    st = engine.stats
    dst = engine.draft_stats
    return {
        "spec_k": engine.spec_k,
        "rounds": st.spec_rounds,
        "proposed": st.spec_proposed,
        "accepted": st.spec_accepted,
        "committed": st.spec_committed,
        "acceptance_rate": (st.spec_accepted / st.spec_proposed
                            if st.spec_proposed else None),
        # mean tokens per per-sequence commit: 1.0 = plain-decode yield,
        # spec_k + 1 = every proposal accepted every round
        "mean_run_length": (st.spec_committed / st.spec_commits
                            if st.spec_commits else None),
        "verify_dispatches": st.verify_dispatches,
        "draft_decode_dispatches": dst.decode_steps,
        "verify_time_s": st.verify_time,
        "draft_time_s": dst.device_time,
        "verify_compile_count": engine.verify_compile_count(),
        "draft_decode_compile_count": engine.draft_decode_compile_count(),
    }


def stats_payload(engine: Engine, state: ServerState) -> dict:
    st = engine.stats
    done = state.completed
    ttft = [o.time_to_first_token for o in done
            if o.time_to_first_token is not None]
    itl_m = [o.itl_mean for o in done if o.itl_mean is not None]
    itl_p = [o.itl_p99 for o in done if o.itl_p99 is not None]
    pooled = pooled_itls(done)
    return {
        "engine": {
            "prefill_tokens": st.prefill_tokens,
            "prefill_dispatches": st.prefill_dispatches,
            "prefill_tps": st.prefill_tps,
            "decode_tokens": st.decode_tokens,
            "decode_steps": st.decode_steps,
            "decode_tps": st.decode_tps,
            # decode steps whose attention ran the paged kernel, and the
            # K/V pages it read over them
            "attn_kernel_steps": st.attn_kernel_steps,
            "kv_pages_walked": st.kv_pages_walked,
            # chunked-prefill composition (--chunk-size): chunk groups run
            # beside decode rows; max_decode_stall_s is the longest gap
            # between decode dispatches while a slot sat decode-ready —
            # the tentpole's before/after number
            "chunk_size": engine.chunk_size,
            "chunk_dispatches": st.chunk_dispatches,
            "max_decode_stall_s": st.max_decode_stall,
            # admission waves that held decode-ready slots back, and
            # their summed wall time
            "prefill_stall_waves": st.prefill_stall_waves,
            "prefill_stall_time_s": st.prefill_stall_time,
            # one compile counter per dispatch kind: decode must stay at 1
            # forever; prefill/prefix grow one per pow2 shape bucket, so a
            # drift here means the bucketing regressed
            "decode_compile_count": engine.decode_compile_count(),
            "prefill_compile_count": engine.prefill_compile_count(),
            "prefix_compile_count": engine.prefix_compile_count(),
            # host-vs-device wall time: device_time_s is spent inside
            # compiled dispatches, host_time_s is step() overhead around
            # them (scheduling, staging, cache bookkeeping)
            "device_time_s": st.device_time,
            "host_time_s": st.host_time,
        },
        "scheduler": {
            "num_slots": engine.num_slots,
            "active": len(engine.scheduler.active),
            "waiting": len(engine.scheduler.waiting),
            "free_slots": engine.scheduler.free_slots,
        },
        # overcommit/preemption counters (all zero at overcommit 1.0)
        "preemption": {
            "overcommit": engine.overcommit,
            "preemptions": st.preemptions,
            "recomputed": st.recomputed,
            "swapped_out": st.swapped_out,
            "swapped_in": st.swapped_in,
        },
        "completed": len(done),
        # speculative decoding (--speculative); None when off.  acceptance
        # _rate is proposals the target agreed with; mean_run_length is
        # tokens committed per verify round (1.0 = never better than plain
        # decode, spec_k + 1 = every proposal accepted); the wall-time
        # split shows where a round's device time goes (draft dispatches
        # accumulate in the DRAFT runner's own stats block)
        "speculative": _spec_section(engine),
        # trie hit-rate counters; None when --prefix-cache is off
        "prefix_cache": (engine.prefix.stats()
                         if engine.prefix is not None else None),
        "ttft_s": {"mean": sum(ttft) / len(ttft) if ttft else None,
                   "p50": percentile(ttft, 50) if ttft else None,
                   "p99": percentile(ttft, 99) if ttft else None},
        # per-request-summary aggregate (kept for continuity): itl_s.p99
        # is the p99 of PER-REQUEST itl_p99 values — a conservative tail
        # proxy that weights every request equally regardless of length
        "itl_s": {"mean": sum(itl_m) / len(itl_m) if itl_m else None,
                  "p99": percentile(itl_p, 99) if itl_p else None},
        # TRUE token-level distribution: every inter-token gap of every
        # retired request pooled into one sample (each token's wait counts
        # once) — this is the number the chunked-prefill bar gates on
        "itl_pooled_s": {
            "count": len(pooled),
            "mean": sum(pooled) / len(pooled) if pooled else None,
            "p50": percentile(pooled, 50) if pooled else None,
            "p99": percentile(pooled, 99) if pooled else None},
    }


def healthz_payload(engine: Engine) -> dict:
    """Liveness snapshot: cheap enough for a router to poll per dispatch.
    ``free_pages`` is None in the fixed-slot regime (no page pool)."""
    alloc = getattr(engine.cache, "allocator", None)
    return {
        "status": "ok",
        "free_slots": engine.scheduler.free_slots,
        "active": len(engine.scheduler.active),
        "waiting": len(engine.scheduler.waiting),
        "free_pages": alloc.num_free if alloc is not None else None,
        # a router can weigh preemption churn when picking a replica
        "preemptions": engine.stats.preemptions,
        # a router can weigh speculative yield too: a replica whose
        # acceptance collapsed is barely faster than plain decode
        "speculative": _spec_section(engine),
    }


def _write_head(writer: asyncio.StreamWriter, status: str,
                ctype: str) -> None:
    writer.write((f"HTTP/1.1 {status}\r\nContent-Type: {ctype}\r\n"
                  "Connection: close\r\n\r\n").encode())


def _write_json(writer: asyncio.StreamWriter, status: str,
                payload: dict) -> None:
    _write_head(writer, status, "application/json")
    writer.write((json.dumps(payload) + "\n").encode())


async def _handle_generate(aeng: AsyncEngine, state: ServerState,
                           body: bytes, writer: asyncio.StreamWriter) -> None:
    rid = f"http-{next(state.ids)}"
    try:
        req = request_from_json(json.loads(body.decode() or "null"), rid)
        stream = await aeng.submit(req)
    except (ValueError, TypeError, json.JSONDecodeError) as e:
        # TypeError covers wrong-typed fields hitting the float()/int()/
        # tuple() coercions (e.g. "temperature": [0.5], "max_new": null)
        _write_json(writer, "400 Bad Request", {"error": str(e)})
        return
    seq = aeng.sequence(rid)
    _write_head(writer, "200 OK", "application/x-ndjson")
    try:
        async for delta in stream:
            writer.write((json.dumps(delta.to_dict()) + "\n").encode())
            await writer.drain()  # raises when the client is gone
    finally:
        # normal end OR client disconnect; closing an unfinished stream
        # aborts the request, freeing its slot and pages immediately
        await stream.aclose()
        if seq is not None and seq.done:
            state.record(seq.to_output())


MAX_BODY_BYTES = 1 << 20  # a /generate body is a token list: 1 MiB is ample


async def _handle_conn(aeng: AsyncEngine, state: ServerState,
                       reader: asyncio.StreamReader,
                       writer: asyncio.StreamWriter) -> None:
    try:
        request_line = (await reader.readline()).decode("latin1")
        parts = request_line.split()
        if len(parts) < 2:
            return
        method, path = parts[0], parts[1]
        headers = {}
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            key, _, val = line.decode("latin1").partition(":")
            headers[key.strip().lower()] = val.strip()
        if method == "POST" and path == "/generate":
            try:
                length = int(headers.get("content-length", 0))
                if length < 0:
                    raise ValueError
            except ValueError:
                _write_json(writer, "400 Bad Request",
                            {"error": "malformed Content-Length"})
                return
            if length > MAX_BODY_BYTES:
                # refuse before buffering: readexactly would otherwise
                # accumulate a client-controlled body without bound
                _write_json(writer, "413 Payload Too Large",
                            {"error": f"body over {MAX_BODY_BYTES} bytes"})
                return
            body = await reader.readexactly(length)
            await _handle_generate(aeng, state, body, writer)
        elif method == "GET" and path == "/stats":
            # read under the engine lock (off-loop): a mid-step snapshot
            # would see half-updated counters / slot accounting
            payload = await aeng.with_engine(
                lambda eng: stats_payload(eng, state))
            _write_json(writer, "200 OK", payload)
        elif method == "GET" and path == "/healthz":
            payload = await aeng.with_engine(healthz_payload)
            _write_json(writer, "200 OK", payload)
        else:
            _write_json(writer, "404 Not Found",
                        {"error": f"no route {method} {path}"})
    except (ConnectionError, asyncio.IncompleteReadError):
        pass  # client went away; any in-flight generate already aborted
    except ValueError:
        # e.g. a request/header line over the StreamReader's 64 KiB limit:
        # best-effort 400 instead of a dead connection + logged traceback
        try:
            _write_json(writer, "400 Bad Request",
                        {"error": "unparseable request"})
        except (ConnectionError, OSError):
            pass
    finally:
        try:
            await writer.drain()
            writer.close()
            await writer.wait_closed()
        except (ConnectionError, OSError):
            pass


async def http_serve(engine: Engine, host: str, port: int,
                     ready=None) -> None:
    """Serve ``engine`` over HTTP until cancelled.  ``ready(port)`` fires
    once the socket is bound (port 0 -> the ephemeral port chosen); tests
    and the smoke client use it instead of polling."""
    state = ServerState()
    async with AsyncEngine(engine) as aeng:
        server = await asyncio.start_server(
            lambda r, w: _handle_conn(aeng, state, r, w), host, port)
        bound = server.sockets[0].getsockname()[1]
        log.info("HTTP serving on http://%s:%d (POST /generate, GET /stats)",
                 host, bound)
        if ready is not None:
            ready(bound)
        async with server:
            await server.serve_forever()


# ------------------------------------------------------------ batch demo --
def run_batch(args, engine: Engine, cfg) -> None:
    rng = np.random.default_rng(args.seed)
    if args.ragged:
        lens = rng.integers(max(1, args.prompt_len // 2), args.prompt_len + 1,
                            size=args.batch)
    else:
        lens = np.full(args.batch, args.prompt_len)
    sampling = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                              seed=args.seed)
    requests = make_requests(
        [rng.integers(0, cfg.vocab_size, size=int(n)) for n in lens],
        max_new=args.max_new, sampling=sampling)

    outputs = engine.run(requests)
    st = engine.stats
    total = sum(len(o.tokens) for o in outputs)
    log.info("generated %d tokens over %d requests", total, len(outputs))
    log.info("prefill: %d tokens in %d dispatches, %.1f tok/s",
             st.prefill_tokens, st.prefill_dispatches, st.prefill_tps)
    log.info("decode: %d tokens in %d steps, %.1f tok/s",
             st.decode_tokens, st.decode_steps, st.decode_tps)
    if engine.chunk_size is not None:
        log.info("chunked prefill: chunk_size %d, %d chunk dispatches",
                 engine.chunk_size, st.chunk_dispatches)
    if engine.speculative:
        spec = _spec_section(engine)
        log.info("speculative: k=%d, %d rounds, %d/%d proposals accepted "
                 "(%.0f%%), run length %.2f; verify %.3fs in %d "
                 "dispatches, draft %.3fs in %d",
                 spec["spec_k"], spec["rounds"], spec["accepted"],
                 spec["proposed"],
                 100 * (spec["acceptance_rate"] or 0.0),
                 spec["mean_run_length"] or 0.0,
                 spec["verify_time_s"], spec["verify_dispatches"],
                 spec["draft_time_s"], spec["draft_decode_dispatches"])
    log.info("max decode stall: %.4f s; %d admission waves held decode "
             "for %.4f s", st.max_decode_stall, st.prefill_stall_waves,
             st.prefill_stall_time)
    for line in _latency_lines(outputs):
        log.info("%s", line)
    log.info("sample %s: %s", outputs[0].request_id,
             list(outputs[0].tokens)[:12])


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--reduce", action="store_true",
                    help="serve the reduced smoke config (CPU-sized) "
                         "instead of the published widths")
    ap.add_argument("--batch", type=int, default=4,
                    help="number of requests to serve (batch mode)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--ragged", action="store_true",
                    help="vary prompt lengths in [prompt_len/2, prompt_len]")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=0,
                    help="engine sequence capacity (0 = prompt_len + "
                         "max_new; the HTTP mode bound on prompt+max_new)")
    ap.add_argument("--slots", type=int, default=0,
                    help="decode slots (0 = min(batch, 8), or derived from "
                         "--memory-budget-mb when given)")
    ap.add_argument("--token-budget", type=int, default=0,
                    help="KV token budget (0 = slot-bound only); with "
                         "paging this converts to a page budget")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV block size in tokens for the paged cache "
                         "(attention archs; recurrent state is O(1) and "
                         "stays slot-indexed)")
    ap.add_argument("--fixed-slots", action="store_true",
                    help="fall back to the fixed max_len-stripe SlotCache "
                         "instead of the paged KV cache")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix-tree prefix cache over the paged pool: "
                         "repeated prompt heads skip prefill (needs "
                         "--page-size, conflicts with --fixed-slots)")
    ap.add_argument("--overcommit", type=float, default=1.0,
                    help="page overcommit factor >= 1.0: admission charges "
                         "current footprints instead of worst cases; pool "
                         "exhaustion preempts the youngest sequence "
                         "(drop-and-recompute, or --swap)")
    ap.add_argument("--swap", action="store_true",
                    help="undo preemptions by restoring the victim's KV "
                         "blocks from a host copy instead of recomputing "
                         "them (pinned host memory)")
    ap.add_argument("--chunk-size", type=int, default=0,
                    help="chunked prefill: per-step prefill token budget "
                         "composed WITH decode into one mixed dispatch, so "
                         "a long prompt no longer stalls running slots "
                         "(needs --page-size; 0 = off, the legacy "
                         "admit-or-decode step)")
    ap.add_argument("--speculative", action="store_true",
                    help="speculative decoding: a small dense draft (the "
                         "target's first --draft-layers layers, shared "
                         "embedding/head) proposes --spec-k tokens per "
                         "slot and one batched target dispatch verifies "
                         "them; bit-identical output, conflicts with "
                         "--chunk-size/--swap")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="draft tokens proposed per slot per verify round "
                         "(0 = the engine default, 3)")
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="layers in the truncated-target draft model (0 = "
                         "one pattern period; must be a multiple of the "
                         "period, below the target's layer count)")
    ap.add_argument("--memory-budget-mb", type=float, default=0.0,
                    help="derive slots + token budget from a device memory "
                         "budget (params priced under the active policy; "
                         "PER-DEVICE when --dp/--tp give a mesh)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel mesh axis (decode slots shard here)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel mesh axis (heads/features shard)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=0, help="0 = full vocab")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--http", type=int, default=None, metavar="PORT",
                    help="serve over HTTP on this port (0 = ephemeral) "
                         "instead of running the closed-batch demo")
    ap.add_argument("--host", default="127.0.0.1",
                    help="bind address for --http")
    ap.add_argument("--fact", default="",
                    help="serve with a uniform factorization kind at the "
                         "classic sites (butterfly|pixelfly|...)")
    ap.add_argument("--fact-block", type=int, default=32)
    ap.add_argument("--policy-json", default="",
                    help="path to a FactorizationPolicy JSON (wins over --fact)")
    return ap


def setup(args):
    """(engine, cfg) for parsed CLI ``args``: the config at its published
    widths (or the smoke preset under ``--reduce``) with the chosen
    policy, the --dp/--tp mesh, weights created in place, and the
    engine over them."""
    cfg = get_config(args.arch)
    if args.reduce:
        cfg = reduced(cfg)
    policy = resolve_policy(args)
    if policy is not None:
        cfg = cfg.with_fact(policy)
    if cfg.input_mode != "tokens":
        raise SystemExit(f"{cfg.name} takes frontend embeddings; use "
                         "examples/serve_decode.py for the stub flow")

    max_len = args.max_len or (args.prompt_len + args.max_new)
    mesh = None
    if args.dp * args.tp > 1:
        try:
            mesh = make_serving_mesh(args.dp, args.tp)
        except ValueError as e:
            raise SystemExit(str(e))
        log.info("mesh: dp=%d x tp=%d over %d devices",
                 args.dp, args.tp, args.dp * args.tp)
    params = init_serving_params(cfg, jax.random.PRNGKey(0), mesh)
    engine = build_engine(args, cfg, params, max_len, mesh)
    log.info("engine: %d slots, %s, cache %.2f MB%s",
             engine.num_slots,
             (f"{engine.num_pages} pages x {engine.page_size} tokens"
              if engine.page_size is not None
              else f"token budget {engine.scheduler.token_budget}"),
             engine.cache.nbytes() / 1e6,
             " (sharded over the mesh)" if mesh is not None else "")
    return engine, cfg


def main():
    args = build_parser().parse_args()
    use_compile_cache()
    engine, cfg = setup(args)
    if args.http is not None:
        try:
            asyncio.run(http_serve(engine, args.host, args.http))
        except KeyboardInterrupt:
            log.info("shutting down")
        return
    run_batch(args, engine, cfg)


if __name__ == "__main__":
    main()
