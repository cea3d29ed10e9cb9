"""ModelRunner: the device-execution layer of the serving stack.

Everything that touches a jax device lives here — the three compiled
dispatches (``step_fn``/``prefill_fn``/``prefix_fn``), sampler
construction, pow2 shape bucketing, ``device_put``/sharding specs, the
KV cache and its insert/evict/COW/swap execution, the per-slot staging
arrays the decode step reads, and the per-dispatch compile + wall-time
counters.  The runner speaks ARRAYS AND SLOT/PAGE INDICES ONLY: it is
forbidden from importing ``scheduler``/``request``/``prefix_cache``/
``events`` (enforced by ``tools/layering_lint.py``), never sees a
``Sequence``, and makes no policy decisions — admission, preemption,
reclaim and retirement belong to :class:`repro.serving.core.EngineCore`,
which drives the runner through the :class:`ExecuteInput` /
:class:`ExecuteOutput` contract (DESIGN.md section 14).

The decode step is compiled once for ``(num_slots, 1)`` and never
recompiled as requests come and go — idle slots ride along and their rows
are fully overwritten at the next insert; the page table is a replicated
VALUE input, so table growth never retraces.  Prefill dispatch shapes are
bucketed to powers of two so a long-lived runner compiles
O(log slots x log max_len) prefill variants, not one per admission shape.

Every dispatch runs under three spans (``serve.stage.<kind>``,
``serve.launch.<kind>``, ``serve.wait.<kind>``) and every cache operation
under ``serve.cache.<op>`` (:mod:`repro.serving.tracing`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import (decode_step, init_params, prefill,
                          prefill_with_past, prefill_with_prefix)
from repro.models.model import cast_params
import repro.serving.tracing as tracing
from repro.parallel import context as pctx
from repro.serving.cache import PagedSlotCache, SlotCache
from repro.serving.utils import EngineStats, pow2_bucket

MAX_TOP_K = 64  # static top-k width compiled into the sampler (overridable)


def _make_sampler(cfg: ModelConfig, max_top_k: int = MAX_TOP_K):
    """(logits (N, padded_vocab), temps, top_k, seeds, positions) -> (N,) int32.

    Vocab-pad logits are sliced away exactly once, here.  temperature 0 is
    greedy argmax; otherwise softmax sampling at that temperature, optionally
    truncated to the top-k logits.  The k candidates come from
    ``jax.lax.top_k`` (O(V log k) on the decode hot path, not a full-vocab
    sort) with its tie rule made explicit: equal logits are ranked by lower
    index, and EXACTLY k candidates survive — so ``top_k=1`` always equals
    greedy argmax, even at temperature > 0 and with tied maxima.  The PRNG
    key for a token at sequence index i is fold_in(PRNGKey(seed), i) —
    independent of batching/slots.
    """
    v = cfg.vocab_size
    kmax = min(max_top_k, v)

    def sample(logits, temps, top_k, seeds, positions):
        with jax.named_scope("sample"):
            return draw(logits, temps, top_k, seeds, positions)

    def draw(logits, temps, top_k, seeds, positions):
        lg = logits[..., :v].astype(jnp.float32)
        n = lg.shape[0]
        greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        # rank-based truncation: keep positions 0..k-1 of the top_k ordering
        # (ties broken toward lower index by lax.top_k), mask the rest
        _, idxs = jax.lax.top_k(lg, kmax)  # (N, kmax)
        keep = jnp.arange(kmax)[None, :] < jnp.minimum(top_k, kmax)[:, None]
        sel = jnp.zeros(lg.shape, bool).at[
            jnp.arange(n)[:, None], idxs].set(keep)
        # top_k >= vocab means no truncation (same as top_k == 0)
        cut = ((top_k > 0) & (top_k < v))[:, None] & ~sel
        scaled = jnp.where(cut, -jnp.inf, lg) / jnp.maximum(temps, 1e-6)[:, None]
        keys = jax.vmap(
            lambda s, p: jax.random.fold_in(jax.random.PRNGKey(s), p)
        )(seeds, positions)
        drawn = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
        return jnp.where(temps > 0, drawn, greedy)

    return sample


@dataclasses.dataclass(frozen=True)
class ExecuteInput:
    """What the EngineCore hands the runner for one dispatch — plain host
    data only (ints/floats/tuples), never a Sequence or any other policy
    object, so a remote or multi-process runner can take the same payload
    over a wire.

    ``kind`` selects the dispatch:
      "decode"   one step over ALL slots; ``slots`` names the rows whose
                 staging state should advance (idle rows ride along).
      "prefill"  batched full prefill; ``tokens[j]`` is row j's complete
                 prefill token stream (prompt, or prompt + generated tail
                 for a resumed recompute).
      "prefix"   tail-only prefill against resident prefix pages;
                 ``tokens[j]`` holds ONLY the unshared tail and
                 ``prefix_lens[j]`` the matched (already-resident) length.
      "mixed"    one token-budget step (chunked prefill): the decode rows
                 in ``slots`` advance one token, AND one chunk group runs —
                 ``chunk_slots[j]`` takes the ``tokens[j]`` chunk as a tail
                 against its ``prefix_lens[j]`` already-written positions
                 (earlier chunks / trie pages).  Chunk 0 is the
                 ``prefix_lens == 0`` degenerate case of the same path.
                 Either half may be empty (pure-decode / pure-chunk step).
      "verify"   speculative verify: ``tokens[j]`` is slot ``slots[j]``'s
                 pending token plus its draft proposals (a tail of at most
                 ``spec_k + 1``), ``prefix_lens[j]`` its committed K/V
                 length.  ONE dispatch at a FIXED shape (all num_slots
                 rows, width spec_k + 1) scores every row's tail against
                 its committed past and samples the target's token after
                 each tail position — so it compiles exactly once,
                 regardless of how many slots are live or how few
                 proposals a near-finished row has left.

    Sampling params travel per ROW for prefill/prefix, and per CHUNK row
    (aligned with ``chunk_slots``) for mixed; decode rows read the staging
    arrays set at admission.
    """

    kind: str  # "decode" | "prefill" | "prefix" | "mixed" | "verify"
    slots: tuple[int, ...] = ()
    tokens: tuple[tuple[int, ...], ...] = ()
    prefix_lens: tuple[int, ...] = ()
    temperatures: tuple[float, ...] = ()
    top_ks: tuple[int, ...] = ()
    seeds: tuple[int, ...] = ()
    # mixed only: the chunk group's slot per row (``tokens``/``prefix_lens``
    # /sampling columns align with THIS tuple, not ``slots``)
    chunk_slots: tuple[int, ...] = ()


@dataclasses.dataclass
class ExecuteOutput:
    """What a dispatch returns to the core.

    ``tokens``: sampled next tokens as a host numpy array — indexed by SLOT
    for decode (all rows present, idle rows garbage), by ROW for
    prefill/prefix (bucketed length; rows past the real group are dummies).
    For mixed it is the decode half's slot-indexed array (None when the
    step had no decode rows).  For verify it is SLOT-indexed and
    two-dimensional, (num_slots, spec_k + 1): ``tokens[slot, j]`` is the
    target's sample after consuming the slot's tail through index j
    (garbage for idle slots and past a row's real tail).
    ``caches``: the dispatch's K/V output when the core must place it —
    full prefill caches to ``insert`` (fixed and paged alike), tail caches
    to ``write_tails`` for prefix hits and mixed-step chunks; None for
    decode (the runner updated its pool in place).  Opaque to the core: it
    round-trips the pytree into the runner's cache calls without looking
    inside.
    ``chunk_tokens``: mixed only — the chunk group's sampled tokens by ROW
    (aligned with ``chunk_slots``).  Only a sequence's FINAL chunk's sample
    is meaningful (it sits at the full prefill position); the core discards
    the rest.
    """

    tokens: np.ndarray | None
    caches: object | None = None
    chunk_tokens: np.ndarray | None = None


def init_serving_params(cfg: ModelConfig, key: jax.Array, mesh=None):
    """Random serving weights, made on the device(s) in the compute dtype.

    One jitted init casts as it creates, so no float32 copy of the model
    is ever resident: the values equal ``init_params`` cast to
    ``cfg.dtype``.  On a mesh the init's ``out_shardings`` are the
    serving partition rules, so each device creates only its own shard
    and no device ever holds the whole model."""
    shardings = None
    if mesh is not None:
        from repro.parallel.sharding import partition_params, to_named
        shardings = to_named(mesh, partition_params(cfg, mesh))
    init = jax.jit(lambda k: cast_params(init_params(cfg, k), cfg.dtype),
                   out_shardings=shardings)
    return init(key)


def _compiled_count(fn) -> int | None:
    """Compile count of one jitted dispatch (None when the running jax
    can't report it, or the fn was monkeypatched by a test spy)."""
    size = getattr(fn, "_cache_size", None)
    return int(size()) if size is not None else None


class ModelRunner:
    """Owns one device (or mesh) worth of serving execution state.

    Sizes arrive RESOLVED (see :func:`repro.serving.executor.
    resolve_engine_spec`): ``num_slots`` is already rounded to a dp
    multiple on a mesh, ``num_pages`` already includes the mesh rounding,
    and ``page_size=None`` selects the fixed-stripe :class:`SlotCache`.
    ``stats`` is the shared :class:`EngineStats` block — the runner
    accumulates the device-side fields (dispatch wall time + token/dispatch
    counters) and the core the policy fields.  ``role`` prefixes the
    runner's span kinds (``"draft."`` for a speculative draft runner, whose
    programs are then not published to trace reductions: they share the
    target's program names).
    """

    def __init__(self, params, cfg: ModelConfig, *, max_len: int,
                 num_slots: int, page_size: int | None = None,
                 num_pages: int | None = None,
                 mesh=None, dp: tuple[str, ...] = ("data",),
                 tp: str | None = "model",
                 max_top_k: int = MAX_TOP_K,
                 spec_k: int = 0,
                 stats: EngineStats | None = None,
                 role: str = ""):
        self.cfg = cfg
        self.max_len = max_len
        self.num_slots = num_slots
        self.page_size = page_size
        self.num_pages = num_pages
        self.spec_k = spec_k
        self.mesh = mesh
        self.dp = tuple(dp)
        self.tp = tp
        self.max_top_k = min(max_top_k, cfg.vocab_size)
        self.stats = stats if stats is not None else EngineStats()
        self.attn_only = all(m == "attn" for m, _ in cfg.pattern)
        # span names, built once: (stage, launch, wait) per dispatch kind
        self._spans = {k: tuple(f"serve.{phase}.{role}{k}"
                                for phase in ("stage", "launch", "wait"))
                       for k in ("decode", "prefill", "prefix", "mixed",
                                 "verify")}
        self._cache_spans = {op: f"serve.cache.{role}{op}" for op in (
            "insert", "write_tails", "map_prefix", "cow", "alloc_tail",
            "evict", "swap_out", "swap_in")}
        self._decode_ran = False
        if not role:
            tracing.publish("jit_step_fn", self.decode_program_text)
        self._sample = _make_sampler(cfg, self.max_top_k)
        # serving holds its weights in the compute dtype: cast once here
        # (a no-op for weights from init_serving_params), so no dispatch
        # carries a per-call cast or a temporary copy of the weights
        params = cast_params(params, cfg.dtype)

        if mesh is not None:
            from repro.parallel.sharding import (guard_spec, partition_caches,
                                                 partition_params, to_named)
            self._param_sh = to_named(mesh, partition_params(cfg, mesh))
            self.params = jax.device_put(params, self._param_sh)
            pages = (num_pages + 1, page_size) if page_size is not None \
                else None
            cache_sh = to_named(mesh, partition_caches(
                cfg, mesh, self.dp, num_slots, max_len, pages=pages))
            if page_size is not None:
                self.cache = PagedSlotCache(cfg, num_slots, max_len,
                                            num_pages, page_size,
                                            shardings=cache_sh)
            else:
                self.cache = SlotCache(cfg, num_slots, max_len,
                                       shardings=cache_sh)
            dpa = self.dp if len(self.dp) > 1 else self.dp[0]
            self._slot_sh = NamedSharding(
                mesh, guard_spec(P(dpa), (num_slots,), mesh))
            self._tok_sh = NamedSharding(
                mesh, guard_spec(P(dpa, None), (num_slots, 1), mesh))
            self._rep_sh = NamedSharding(mesh, P())
        else:
            self.params = params
            if page_size is not None:
                self.cache = PagedSlotCache(cfg, num_slots, max_len,
                                            num_pages, page_size)
            else:
                self.cache = SlotCache(cfg, num_slots, max_len)

        # per-slot host state fed to the jitted step each iteration; the
        # staging arrays live on the host, replicated from the mesh's point
        # of view — every device sees the same admissions
        ns = num_slots
        self._tok = np.zeros((ns, 1), np.int32)
        self._pos = np.zeros((ns,), np.int32)
        self._temps = np.zeros((ns,), np.float32)
        self._topk = np.zeros((ns,), np.int32)
        self._seeds = np.zeros((ns,), np.uint32)

        ps = page_size

        def step_fn(params, data, table, tok, pos, temps, topk, seeds):
            logits, data = decode_step(params, cfg, tok, data, pos,
                                       page_table=table, page_size=ps,
                                       kv_len=max_len if ps else None)
            nxt = self._sample(logits[:, 0], temps, topk, seeds, pos + 1)
            return nxt, data

        def prefill_fn(params, prompts, lengths, temps, topk, seeds,
                       ragged: bool):
            logits, caches = prefill(params, cfg, prompts, max_len,
                                     lengths if ragged else None)
            last = jnp.take_along_axis(
                logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
            first = self._sample(last, temps, topk, seeds, lengths)
            return first, caches

        def prefix_fn(params, data, tables, tails, plens, tlens,
                      temps, topk, seeds):
            # tail-only prefill against the resident prefix pages; the
            # first token samples at the FULL prompt position, so the
            # stream is bit-identical to the uncached fold_in sequence
            logits, tail_caches = prefill_with_prefix(
                params, cfg, tails, data, tables, plens)
            last = jnp.take_along_axis(
                logits, (tlens - 1)[:, None, None], axis=1)[:, 0]
            first = self._sample(last, temps, topk, seeds, plens + tlens)
            return first, tail_caches

        def verify_paged_fn(params, data, tables, tails, plens,
                            temps, topk, seeds):
            # score each row's speculative tail against its committed
            # prefix pages; the sample after tail index j is the token at
            # absolute position plens + 1 + j, so every draw lands on the
            # same fold_in position non-speculative decode would use
            logits, tail_caches = prefill_with_prefix(
                params, cfg, tails, data, tables, plens)
            return self._verify_sample(logits, plens, temps, topk,
                                       seeds), tail_caches

        def verify_fixed_fn(params, data, tails, plens, temps, topk, seeds):
            logits, tail_caches = prefill_with_past(
                params, cfg, tails, data, plens)
            return self._verify_sample(logits, plens, temps, topk,
                                       seeds), tail_caches

        if mesh is not None:
            row = self._slot_sh
            # the page table is replicated host state (None when unpaged)
            self._step = jax.jit(
                step_fn,
                in_shardings=(self._param_sh, self.cache.shardings,
                              self._rep_sh if ps else None, self._tok_sh,
                              row, row, row, row),
                out_shardings=(self._rep_sh, self.cache.shardings))
        else:
            self._step = jax.jit(step_fn)
        # prefill shapes vary by (rows, width) bucket, so inputs are placed
        # per call (_put) and jit infers shardings from the committed args
        self._prefill = jax.jit(prefill_fn, static_argnames=("ragged",))
        self._prefix_prefill = jax.jit(prefix_fn)
        self._verify = jax.jit(
            verify_paged_fn if ps is not None else verify_fixed_fn)

    def _verify_sample(self, logits, plens, temps, topk, seeds):
        """Sample the target's token after EVERY tail position of every
        row: logits (N, W, padded_vocab) -> (N, W) int32, where column j
        draws at fold position ``plens + 1 + j`` — the position the token
        will occupy, identical to the one-at-a-time decode sequence."""
        n, w = logits.shape[:2]
        pos = (plens[:, None] + 1 + jnp.arange(w)[None, :]).reshape(-1)
        out = self._sample(logits.reshape(n * w, -1),
                           jnp.repeat(temps, w), jnp.repeat(topk, w),
                           jnp.repeat(seeds, w), pos)
        return out.reshape(n, w)

    # ------------------------------------------------------------- mesh ---
    def _trace_ctx(self):
        """Install the runner's mesh for pctx.constrain during tracing."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return pctx.mesh_context(self.mesh, self.dp, self.tp)

    def _put(self, x, spec: P | None = None):
        """Host array -> device, sharded per ``spec`` (guarded) on a mesh."""
        x = jnp.asarray(x)
        if self.mesh is None or spec is None:
            return x
        from repro.parallel.sharding import guard_spec
        return jax.device_put(x, NamedSharding(
            self.mesh, guard_spec(spec, x.shape, self.mesh)))

    def _dpa(self):
        if self.mesh is None:
            return None
        return self.dp if len(self.dp) > 1 else self.dp[0]

    # ------------------------------------------------------------ execute --
    def execute(self, inp: ExecuteInput) -> ExecuteOutput:
        """Run ONE compiled dispatch described by ``inp``.  Pure execution:
        allocation-policy operations (cache insert with reclaim-on-
        exhaustion, page-table growth) are separate calls so the core can
        wrap THEM in its retry loop without ever re-dispatching."""
        if inp.kind == "decode":
            return ExecuteOutput(tokens=self._decode_dispatch(inp.slots))
        if inp.kind == "prefill":
            return self._execute_prefill(inp)
        if inp.kind == "prefix":
            return self._execute_prefix(inp)
        if inp.kind == "mixed":
            return self._execute_mixed(inp)
        if inp.kind == "verify":
            return self._execute_verify(inp)
        raise ValueError(f"unknown ExecuteInput kind {inp.kind!r}")

    def _decode_dispatch(self, advance, live_rows=None,
                         kind: str = "decode") -> np.ndarray:
        """One decode dispatch over ALL slots; rows named in ``advance``
        feed their sampled token back and move their position +1.

        ``live_rows`` (mixed steps only) restricts the page-table VALUE the
        step sees to those rows — every other row's table is zeroed so its
        ride-along K/V write lands in the scratch block, exactly like an
        idle slot.  This protects mid-prefill slots: their staging position
        is 0 but their table row maps REAL chunk pages, so an unmasked
        ride-along write would clobber their position-0 K/V.  A masked
        table is the same shape/dtype as the full one — a value change,
        never a recompile."""
        with tracing.span(self._spans[kind][0]):
            if self.page_size is None or live_rows is None:
                staged = self._decode_staging()
            else:
                masked = np.zeros_like(self.cache.table)
                rows = list(live_rows)
                if rows:
                    masked[rows] = self.cache.table[rows]
                staged = self._decode_staging(jnp.asarray(masked))
        nxt, self.cache.data, seconds = self._dispatch(
            kind, self._step, self.params, self.cache.data, *staged)
        self._decode_ran = True
        self.stats.decode_time += seconds
        self.stats.decode_steps += 1
        self.stats.decode_tokens += len(advance)
        for slot in advance:
            self._tok[slot, 0] = nxt[slot]
            self._pos[slot] += 1
        return nxt

    def _execute_mixed(self, inp: ExecuteInput) -> ExecuteOutput:
        """One token-budget step: the decode rows advance one token, then
        the chunk group prefills against its already-resident positions via
        the same bucketed prefix path trie hits use (chunk 0 simply has
        ``prefix_lens == 0``).  The two halves touch DISJOINT pool blocks —
        decode writes land in the decode rows' (or scratch) pages, chunk
        tails return as caches for the core to scatter — so their order
        cannot change either result."""
        nxt = self._decode_dispatch(inp.slots, live_rows=inp.slots,
                                    kind="mixed") if inp.slots else None
        chunk_tokens = caches = None
        if inp.chunk_slots:
            chunk_tokens, caches = self._prefix_dispatch(
                inp.chunk_slots, inp.tokens, inp.prefix_lens,
                inp.temperatures, inp.top_ks, inp.seeds, kind="mixed")
            self.stats.chunk_dispatches += 1
        return ExecuteOutput(tokens=nxt, caches=caches,
                             chunk_tokens=chunk_tokens)

    def _execute_verify(self, inp: ExecuteInput) -> ExecuteOutput:
        """One speculative-verify dispatch at a FIXED shape: all
        ``num_slots`` rows, tail width ``spec_k + 1``.  Live rows land at
        their own SLOT index (the output is slot-indexed, like decode);
        idle rows are zero dummies with ``prefix_lens == 0``.  Deliberately
        NOT pow2-bucketed: bucketing by live-row count or remaining-token
        width would retrace as sequences finish — a fixed shape with
        zero-padded tails compiles exactly once and pads only host-side
        zeros.  Returns tail K/V as ``caches`` for the core to scatter
        (only the ACCEPTED positions — commit is the core's call)."""
        if self.spec_k < 1:
            raise ValueError("runner built without spec_k; no verify fn")
        with tracing.span(self._spans["verify"][0]):
            args = self._stage_verify(inp)
        out, tail_caches, seconds = self._dispatch(
            "verify", self._verify, *args)
        self.stats.verify_time += seconds
        self.stats.verify_dispatches += 1
        # committed tokens count as decode_tokens at the core (they ARE the
        # output stream); the dispatch itself is accounted as verify_*
        return ExecuteOutput(tokens=out, caches=tail_caches)

    def _stage_verify(self, inp: ExecuteInput) -> list:
        ns, w = self.num_slots, self.spec_k + 1
        tails = np.zeros((ns, w), np.int32)
        plens = np.zeros((ns,), np.int32)
        temps = np.zeros((ns,), np.float32)
        topk = np.zeros((ns,), np.int32)
        seeds = np.zeros((ns,), np.uint32)
        for j, slot in enumerate(inp.slots):
            toks = inp.tokens[j]
            if len(toks) > w:
                raise ValueError(
                    f"slot {slot}: verify tail {len(toks)} > spec_k+1 {w}")
            tails[slot, :len(toks)] = toks
            plens[slot] = inp.prefix_lens[j]
            temps[slot] = inp.temperatures[j]
            topk[slot] = inp.top_ks[j]
            seeds[slot] = inp.seeds[j]

        dpa = self._dpa()
        args = [self.params, self.cache.data]
        if self.page_size is not None:
            # the page table at FULL width — a value input, like decode's
            args.append(self.cache.table_device())
        args += [self._put(tails, P(dpa, None)), self._put(plens, P(dpa)),
                 self._put(temps, P(dpa)), self._put(topk, P(dpa)),
                 self._put(seeds, P(dpa))]
        return args

    def _execute_prefill(self, inp: ExecuteInput) -> ExecuteOutput:
        """Batched full prefill.  (rows, width) bucket to powers of two so
        a long-lived runner compiles O(log slots * log max_len) prefill
        variants, not one per admission shape; dummy rows/columns are
        masked out by the ragged lengths and never inserted into the
        cache.  Both caps round through pow2_bucket — clamping width at
        max_len itself (or rows at num_slots) would reintroduce a non-pow2
        bucket whenever the cap isn't a power of two; prefill slices the
        decode-ready K/V back to max_len when width rounds past it."""
        with tracing.span(self._spans["prefill"][0]):
            args, ragged = self._stage_prefill(inp)
        first, caches, seconds = self._dispatch(
            "prefill", self._prefill, *args, ragged=ragged)
        self.stats.prefill_time += seconds
        self.stats.prefill_tokens += sum(len(t) for t in inp.tokens)
        self.stats.prefill_dispatches += 1
        return ExecuteOutput(tokens=first, caches=caches)

    def _stage_prefill(self, inp: ExecuteInput):
        width = max(len(t) for t in inp.tokens)
        rows = len(inp.tokens)
        if self.attn_only:
            width = pow2_bucket(width, self.max_len)
            rows = pow2_bucket(rows, self.num_slots)
        prompts = np.zeros((rows, width), np.int32)
        lens = np.ones((rows,), np.int32)  # dummy rows: length-1 stub
        temps = np.zeros((rows,), np.float32)
        topk = np.zeros((rows,), np.int32)
        seeds = np.zeros((rows,), np.uint32)
        for j, toks in enumerate(inp.tokens):
            prompts[j, : len(toks)] = toks
            lens[j] = len(toks)
            temps[j] = inp.temperatures[j]
            topk[j] = inp.top_ks[j]
            seeds[j] = inp.seeds[j]
        ragged = bool((lens != width).any())
        dpa = self._dpa()
        return (self.params, self._put(prompts, P(dpa, None)),
                self._put(lens, P(dpa)), self._put(temps, P(dpa)),
                self._put(topk, P(dpa)), self._put(seeds, P(dpa))), ragged

    def _execute_prefix(self, inp: ExecuteInput) -> ExecuteOutput:
        first, tail_caches = self._prefix_dispatch(
            inp.slots, inp.tokens, inp.prefix_lens,
            inp.temperatures, inp.top_ks, inp.seeds)
        return ExecuteOutput(tokens=first, caches=tail_caches)

    def _prefix_dispatch(self, slots, tokens, prefix_lens,
                         temperatures, top_ks, seeds_in, kind="prefix"):
        """Tail-only prefill for prefix hits AND mixed-step chunks: the
        already-resident pages are mapped into each slot's table (the core
        did map_prefix/cow_block/alloc_tail first), so ONE bucketed
        ``prefill_with_prefix`` dispatch computes just the tails.  A chunk
        is simply a tail whose "prefix" is the sequence's earlier chunks
        (``prefix_lens == 0`` for chunk 0: the zeroed table gathers the
        scratch block and the mask drops every prefix column).  Rows /
        tail width / prefix pages bucket to powers of two so the compile
        cache stays O(log^3) for a long-lived runner; dummy rows carry a
        zero prefix + length-1 tail and are never scattered."""
        with tracing.span(self._spans[kind][0]):
            args = self._stage_prefix(slots, tokens, prefix_lens,
                                      temperatures, top_ks, seeds_in)
        first, tail_caches, seconds = self._dispatch(
            kind, self._prefix_prefill, *args)
        self.stats.prefill_time += seconds
        self.stats.prefill_tokens += sum(len(t) for t in tokens)
        self.stats.prefill_dispatches += 1
        return first, tail_caches

    def _stage_prefix(self, slots, tokens, prefix_lens, temperatures,
                      top_ks, seeds_in) -> tuple:
        ps = self.page_size
        group = len(slots)
        tail_lens = [len(t) for t in tokens]
        rows = pow2_bucket(group, self.num_slots)
        tailw = pow2_bucket(max(tail_lens), self.max_len)
        npref = pow2_bucket(
            max(math.ceil(p / ps) for p in prefix_lens),
            self.cache.max_pages)
        tails = np.zeros((rows, tailw), np.int32)
        tables = np.zeros((rows, npref), np.int32)
        plens = np.zeros((rows,), np.int32)
        tlens = np.ones((rows,), np.int32)
        temps = np.zeros((rows,), np.float32)
        topk = np.zeros((rows,), np.int32)
        seeds = np.zeros((rows,), np.uint32)
        for j in range(group):
            pages = math.ceil(prefix_lens[j] / ps)
            tables[j, :pages] = self.cache.table[slots[j], :pages]
            tails[j, : tail_lens[j]] = tokens[j]
            plens[j] = prefix_lens[j]
            tlens[j] = tail_lens[j]
            temps[j] = temperatures[j]
            topk[j] = top_ks[j]
            seeds[j] = seeds_in[j]

        dpa = self._dpa()
        return (self.params, self.cache.data,
                self._put(tables, P(dpa, None)),
                self._put(tails, P(dpa, None)), self._put(plens, P(dpa)),
                self._put(tlens, P(dpa)), self._put(temps, P(dpa)),
                self._put(topk, P(dpa)), self._put(seeds, P(dpa)))

    def _dispatch(self, kind: str, fn, *args, **kwargs):
        """Launch one compiled dispatch and wait for its results, under the
        kind's launch and wait spans.  Returns (sampled tokens on the host,
        K/V output, seconds of finished work: from the call to the tokens
        in hand — the ``EngineStats`` dispatch timers)."""
        _, launch_name, wait_name = self._spans[kind]
        with tracing.span(launch_name) as launch, self._trace_ctx():
            tokens, caches = fn(*args, **kwargs)
        with tracing.span(wait_name) as wait:
            jax.block_until_ready(caches)
            tokens = np.asarray(tokens)
        return tokens, caches, launch.seconds + wait.seconds

    def _decode_staging(self, table=None) -> tuple:
        """The decode step's per-call inputs but the weights and the pool:
        the page table (``table``, else the full one; None unpaged) and the
        staging rows."""
        if table is None and self.page_size is not None:
            table = self.cache.table_device()
        return (table, jnp.asarray(self._tok), jnp.asarray(self._pos),
                jnp.asarray(self._temps), jnp.asarray(self._topk),
                jnp.asarray(self._seeds))

    def decode_program_text(self) -> str | None:
        """The compiled decode step's HLO text, op metadata (named scopes)
        included, or None before its first dispatch.  The executable is
        the one the dispatches ran (JAX's in-memory cache)."""
        if not self._decode_ran:
            return None
        with self._trace_ctx():
            lowered = self._step.lower(self.params, self.cache.data,
                                       *self._decode_staging())
        return lowered.compile().as_text()

    # ----------------------------------------------- cache execution ops --
    # The core decides WHEN to allocate/evict/swap (and how to reclaim on
    # PoolExhausted); the runner executes the device-side movement.  All of
    # these speak slot/page indices and cache pytrees only.
    def insert(self, slots, caches, lengths=None) -> None:
        """Scatter a prefill dispatch's K/V into the cache rows.  Paged
        callers pass ``lengths`` (real token counts) so only the mapped
        blocks are written; may raise PoolExhausted for the core to
        reclaim-and-retry WITHOUT re-dispatching."""
        with tracing.span(self._cache_spans["insert"]):
            if lengths is None:
                self.cache.insert(slots, caches)
            else:
                self.cache.insert(slots, caches, lengths=lengths)

    def write_tails(self, slots, tail_caches, *, starts, lengths, rows):
        with tracing.span(self._cache_spans["write_tails"]):
            self.cache.write_tails(slots, tail_caches, starts=starts,
                                   lengths=lengths, rows=rows)

    def map_prefix(self, slot: int, blocks) -> None:
        with tracing.span(self._cache_spans["map_prefix"]):
            self.cache.map_prefix(slot, blocks)

    def cow_block(self, slot: int, page_index: int, src_block: int) -> None:
        with tracing.span(self._cache_spans["cow"]):
            self.cache.cow_block(slot, page_index, src_block)

    def alloc_tail(self, slot: int, matched_len: int, prefill_len: int):
        with tracing.span(self._cache_spans["alloc_tail"]):
            return self.cache.alloc_tail(slot, matched_len, prefill_len)

    def ensure_mapped(self, slot: int, pos: int) -> None:
        # no span per slot: the core spans a step's whole page-table growth
        self.cache.ensure_mapped(slot, pos)

    def evict(self, slots) -> None:
        with tracing.span(self._cache_spans["evict"]):
            self.cache.evict(slots)

    def swap_out(self, slot: int):
        with tracing.span(self._cache_spans["swap_out"]):
            return self.cache.swap_out(slot)

    def swap_in(self, slot: int, state) -> None:
        with tracing.span(self._cache_spans["swap_in"]):
            self.cache.swap_in(slot, state)

    # ---------------------------------------------------------- staging ---
    def set_slot(self, slot: int, *, token: int, pos: int,
                 temperature: float, top_k: int, seed: int) -> None:
        """(Re)arm one slot's decode staging row: the token to feed the
        next step, its position, and the row's sampling params."""
        self._tok[slot, 0] = token
        self._pos[slot] = pos
        self._temps[slot] = temperature
        self._topk[slot] = top_k
        self._seeds[slot] = seed

    def clear_slot(self, slot: int) -> None:
        """Reset one slot's staging row after its sequence left (retired
        or aborted); the cache row was already evicted."""
        self._tok[slot, 0] = 0
        self._pos[slot] = 0
        self._temps[slot] = 0.0
        self._topk[slot] = 0
        self._seeds[slot] = 0

    def position(self, slot: int) -> int:
        """The slot's current write position (next token index)."""
        return int(self._pos[slot])

    # -------------------------------------------------------------- views --
    def decode_compile_count(self) -> int | None:
        """Number of decode-step compilations so far.  Stays at 1 across
        admissions/evictions — the mesh throughput benchmark asserts this."""
        return _compiled_count(self._step)

    def prefill_compile_count(self) -> int | None:
        """Number of prefill-bucket compilations (one per (rows, width,
        ragged) bucket a long-lived runner has seen)."""
        return _compiled_count(self._prefill)

    def prefix_compile_count(self) -> int | None:
        """Number of prefix-prefill bucket compilations."""
        return _compiled_count(self._prefix_prefill)

    def verify_compile_count(self) -> int | None:
        """Number of speculative-verify compilations.  The verify shape is
        fully static (num_slots rows x spec_k+1 width), so this stays at 1
        across admission waves — the speculative benchmark asserts it."""
        return _compiled_count(self._verify)
