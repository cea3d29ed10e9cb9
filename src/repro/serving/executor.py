"""Executor: the placement seam between the EngineCore and its runner(s).

An :class:`Executor` constructs and fronts one or more
:class:`repro.serving.runner.ModelRunner` instances behind the exact
method surface the core drives (execute + cache execution ops + staging +
compile-count views).  Today there is one implementation —
:class:`LocalExecutor`, a single in-process runner on the local device or
mesh — but the core never assumes that: a multi-process-mesh executor
(per-process runners over ``jax.distributed``) or a prefill-only executor
(disaggregated serving) drops in behind the same surface without the core
changing (DESIGN.md section 14; the ROADMAP cross-host item lands here).

:func:`resolve_engine_spec` is the ONE home for engine sizing and
validation — every construction path (``Engine(...)``, ``serve.py
build_engine``, ``examples/serve_decode.py``, benchmarks) normalizes its
arguments through it into a frozen :class:`EngineSpec`, so the paged/mesh
rounding rules and their error messages cannot drift between entry points.
"""
from __future__ import annotations

import dataclasses
import math

from repro.configs.base import ModelConfig
from repro.parallel import context as pctx
from repro.serving.budget import plan_engine_report
from repro.serving.runner import (MAX_TOP_K, ExecuteInput, ExecuteOutput,
                                  ModelRunner)
from repro.serving.utils import EngineStats


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """Fully resolved engine sizing: what a runner is built from.

    All defaulting, budget planning, and mesh rounding has already
    happened — ``num_slots`` is a dp multiple on a mesh, ``num_pages`` is
    set iff ``page_size`` is, ``token_budget`` survives only in the
    fixed-slot regime, and ``max_top_k`` is clamped to the vocabulary."""

    max_len: int
    num_slots: int
    token_budget: int | None = None
    page_size: int | None = None
    num_pages: int | None = None
    overcommit: float = 1.0
    swap: bool = False
    prefix_cache: bool = False
    max_top_k: int = MAX_TOP_K
    # chunked prefill: per-step prefill token budget composed with decode
    # into one mixed dispatch.  None (the default) keeps the legacy
    # admit-or-decode step byte-identical; set iff ``page_size`` is.
    chunk_size: int | None = None
    # speculative decoding: a small dense draft model proposes ``spec_k``
    # tokens per slot per round and ONE batched target dispatch verifies
    # them (DESIGN.md section 16).  Mutually exclusive with chunk_size and
    # swap; works in both the fixed and paged regimes.
    speculative: bool = False
    spec_k: int = 0


def resolve_engine_spec(cfg: ModelConfig, max_len: int, *,
                        num_slots: int | None = None,
                        token_budget: int | None = None,
                        memory_budget_bytes: int | None = None,
                        mesh=None, dp: tuple[str, ...] = ("data",),
                        tp: str | None = "model",
                        max_top_k: int = MAX_TOP_K,
                        page_size: int | None = None,
                        num_pages: int | None = None,
                        prefix_cache: bool = False,
                        overcommit: float = 1.0,
                        swap: bool = False,
                        chunk_size: int | None = None,
                        speculative: bool = False,
                        spec_k: int | None = None,
                        draft_cfg: ModelConfig | None = None) -> EngineSpec:
    """Validate + normalize engine sizing into an :class:`EngineSpec`.

    num_slots/token_budget can be given directly, or derived from a device
    ``memory_budget_bytes`` via :func:`repro.serving.budget.plan_engine`
    (params priced under the active FactorizationPolicy; leftover memory
    becomes KV).  ``page_size`` selects the paged regime — the page budget
    defaults to worst-case capacity or converts from ``token_budget`` —
    and is silently dropped for pure-recurrent stacks (O(1) state, nothing
    to page).  On a mesh, ``memory_budget_bytes`` is PER-DEVICE, the slot
    count rounds up to a data-axis multiple, and the block pool (scratch
    included) likewise.  Raises ValueError with the same messages the
    monolithic ``Engine.__init__`` raised — callers and tests match on
    them.
    """
    if cfg.input_mode != "tokens":
        raise ValueError(
            f"{cfg.name} takes frontend embeddings; the engine serves "
            "token models (see examples/serve_decode.py for the stub flow)")
    if num_pages is not None and page_size is None:
        raise ValueError("num_pages only makes sense with page_size")
    if overcommit < 1.0:
        raise ValueError(f"overcommit must be >= 1.0, got {overcommit}")
    requested_paging = page_size is not None
    if num_pages is not None and token_budget is not None:
        raise ValueError(
            "pass either token_budget (converted to pages) or an "
            "explicit num_pages, not both — one would silently lose")
    if page_size is not None and not any(
            m == "attn" for m, _ in cfg.pattern):
        page_size = num_pages = None  # nothing to page: O(1) state only
    dp = tuple(dp)
    if mesh is not None:
        missing = [a for a in (*dp, tp)
                   if a is not None and a not in mesh.axis_names]
        if missing:
            raise ValueError(
                f"mesh axes {missing} not in mesh {tuple(mesh.axis_names)}")
    dp_size = pctx.axes_product(mesh, dp) if mesh is not None else 1
    if memory_budget_bytes is not None:
        if num_slots is not None or token_budget is not None or \
                num_pages is not None:
            raise ValueError(
                "pass either memory_budget_bytes (slots/budget derived) "
                "or explicit num_slots/token_budget/num_pages, not both")
        plan = plan_engine_report(cfg, memory_budget_bytes, max_len,
                                  mesh=mesh, dp=dp, page_size=page_size,
                                  overcommit=overcommit,
                                  draft_cfg=draft_cfg if speculative
                                  else None)
        num_slots, token_budget = plan.num_slots, plan.token_budget
        num_pages, page_size = plan.num_pages, plan.page_size
    num_slots = num_slots or 4
    if mesh is not None:
        # the slot axis shards over "data": round up to a multiple
        num_slots = math.ceil(num_slots / dp_size) * dp_size
    if page_size is not None:
        if num_pages is None:
            if token_budget is not None:
                # ceil: flooring would shrink the stated budget and
                # reject a max-size request the token regime admits
                num_pages = math.ceil(token_budget / page_size)
                token_budget = None
            else:  # worst case: every slot filled to max_len
                num_pages = num_slots * math.ceil(max_len / page_size)
        if mesh is not None:
            # pool blocks (incl. scratch) shard over "data": round the
            # total block count up to a dp multiple
            num_pages = dp_size * math.ceil((num_pages + 1) / dp_size) - 1
    if page_size is None and (overcommit > 1.0 or swap):
        if requested_paging:
            # pure-recurrent stack: paging was silently dropped (O(1)
            # state, nothing to page) — overcommit/swap are no-ops too
            overcommit, swap = 1.0, False
        else:
            raise ValueError(
                "overcommit > 1 / swap need the paged KV cache; pass "
                "page_size")
    if prefix_cache:
        if page_size is None:
            raise ValueError(
                "prefix_cache needs the paged KV layout; pass page_size "
                "(pure-recurrent stacks have nothing to share)")
        if not all(m == "attn" for m, _ in cfg.pattern):
            raise ValueError(
                f"{cfg.name}: prefix_cache needs a pure-attention "
                "pattern; recurrent prefix state cannot be recovered "
                "from the block pool")
    if chunk_size is not None:
        if chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
        if page_size is None:
            if requested_paging:
                # pure-recurrent stack: paging was silently dropped, and
                # with it chunking (there are no KV pages for chunk N>0 to
                # attend back into) — same convention as overcommit/swap
                chunk_size = None
            else:
                raise ValueError(
                    "chunked prefill (--chunk-size) needs the paged KV "
                    "cache; pass page_size")
        elif not all(m == "attn" for m, _ in cfg.pattern):
            raise ValueError(
                f"{cfg.name}: chunked prefill needs a pure-attention "
                "pattern; recurrent mid-prompt state cannot be rebuilt "
                "from the block pool between chunks")
    if speculative:
        if not all(m == "attn" for m, _ in cfg.pattern):
            raise ValueError(
                f"{cfg.name}: speculative decoding needs a pure-attention "
                "pattern; the batched verify scores tails against cached "
                "history, which recurrent state cannot replay")
        if chunk_size is not None:
            raise ValueError(
                "speculative decoding and chunked prefill are mutually "
                "exclusive: a verify round IS the step's whole token "
                "budget — pass one of --speculative / --chunk-size")
        if swap:
            raise ValueError(
                "speculative decoding composes with drop-and-recompute "
                "preemption only; --swap is not supported (the draft "
                "cache cannot be swapped alongside the target's pages)")
        spec_k = 3 if spec_k is None else spec_k
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
    else:
        if spec_k is not None:
            raise ValueError("spec_k only makes sense with speculative")
        spec_k = 0
    return EngineSpec(max_len=max_len, num_slots=num_slots,
                      token_budget=token_budget, page_size=page_size,
                      num_pages=num_pages, overcommit=float(overcommit),
                      swap=bool(swap), prefix_cache=bool(prefix_cache),
                      max_top_k=min(max_top_k, cfg.vocab_size),
                      chunk_size=chunk_size,
                      speculative=bool(speculative), spec_k=spec_k)


class Executor:
    """Abstract placement seam: the method surface the EngineCore drives.

    Implementations construct their runner(s) and forward the calls; the
    base class exists so the contract is written down in ONE place and a
    non-local implementation cannot silently miss a method.  Everything
    here speaks ExecuteInput/ExecuteOutput, slot/page indices, and opaque
    cache pytrees — no Sequence, no Scheduler."""

    cfg: ModelConfig
    spec: EngineSpec
    stats: EngineStats
    mesh = None

    def execute(self, inp: ExecuteInput) -> ExecuteOutput:
        raise NotImplementedError

    # cache execution (may raise PoolExhausted for the core to reclaim)
    def insert(self, slots, caches, lengths=None) -> None:
        raise NotImplementedError

    def write_tails(self, slots, tail_caches, *, starts, lengths, rows):
        raise NotImplementedError

    def map_prefix(self, slot: int, blocks) -> None:
        raise NotImplementedError

    def cow_block(self, slot: int, page_index: int, src_block: int) -> None:
        raise NotImplementedError

    def alloc_tail(self, slot: int, matched_len: int, prefill_len: int):
        raise NotImplementedError

    def ensure_mapped(self, slot: int, pos: int) -> None:
        raise NotImplementedError

    def evict(self, slots) -> None:
        raise NotImplementedError

    def swap_out(self, slot: int):
        raise NotImplementedError

    def swap_in(self, slot: int, state) -> None:
        raise NotImplementedError

    # per-slot decode staging
    def set_slot(self, slot: int, *, token: int, pos: int,
                 temperature: float, top_k: int, seed: int) -> None:
        raise NotImplementedError

    def clear_slot(self, slot: int) -> None:
        raise NotImplementedError

    def position(self, slot: int) -> int:
        raise NotImplementedError

    # draft model (speculative decoding; only valid when spec.speculative).
    # The draft runner shares slot indices with the target — the core's
    # DraftProposer drives it through the same ExecuteInput contract.
    def draft_execute(self, inp: ExecuteInput) -> ExecuteOutput:
        raise NotImplementedError

    def draft_insert(self, slots, caches) -> None:
        raise NotImplementedError

    def draft_set_slot(self, slot: int, *, token: int, pos: int,
                       temperature: float, top_k: int, seed: int) -> None:
        raise NotImplementedError

    # observability
    def decode_compile_count(self) -> int | None:
        raise NotImplementedError

    def prefill_compile_count(self) -> int | None:
        raise NotImplementedError

    def prefix_compile_count(self) -> int | None:
        raise NotImplementedError

    def verify_compile_count(self) -> int | None:
        raise NotImplementedError

    def draft_decode_compile_count(self) -> int | None:
        raise NotImplementedError


class LocalExecutor(Executor):
    """One in-process ModelRunner on the local device or mesh.

    The degenerate-but-real placement: every call is a plain method call
    into the runner.  ``cache`` is exposed because host policy reads it
    (the prefix trie wraps it, adoption reads page tables, /stats sizes
    it) — remote executors will need an explicit view protocol for those
    reads, which is exactly the seam this class marks."""

    def __init__(self, params, cfg: ModelConfig, spec: EngineSpec, *,
                 mesh=None, dp: tuple[str, ...] = ("data",),
                 tp: str | None = "model",
                 draft_params=None, draft_cfg: ModelConfig | None = None,
                 stats: EngineStats | None = None):
        self.cfg = cfg
        self.spec = spec
        self.mesh = mesh
        self.stats = stats if stats is not None else EngineStats()
        self.runner = ModelRunner(
            params, cfg, max_len=spec.max_len, num_slots=spec.num_slots,
            page_size=spec.page_size, num_pages=spec.num_pages,
            mesh=mesh, dp=dp, tp=tp, max_top_k=spec.max_top_k,
            spec_k=spec.spec_k, stats=self.stats)
        # speculative decoding: a SECOND runner for the draft model, same
        # slot geometry as the target so slot indices are shared, always
        # on the fixed stripe cache (the draft is small — that's the
        # point; paging it would buy nothing and cost a second pool).
        # Its dispatch counters accumulate in a separate EngineStats so
        # /stats can report the draft/verify wall-time split.
        self.draft: ModelRunner | None = None
        self.draft_stats: EngineStats | None = None
        if spec.speculative:
            if draft_params is None or draft_cfg is None:
                raise ValueError(
                    "speculative decoding needs draft_params + draft_cfg")
            self.draft_stats = EngineStats()
            self.draft = ModelRunner(
                draft_params, draft_cfg, max_len=spec.max_len,
                num_slots=spec.num_slots, page_size=None,
                mesh=mesh, dp=dp, tp=tp, max_top_k=spec.max_top_k,
                stats=self.draft_stats, role="draft.")

    @property
    def cache(self):
        return self.runner.cache

    @property
    def attn_only(self) -> bool:
        return self.runner.attn_only

    def execute(self, inp: ExecuteInput) -> ExecuteOutput:
        return self.runner.execute(inp)

    def insert(self, slots, caches, lengths=None) -> None:
        self.runner.insert(slots, caches, lengths=lengths)

    def write_tails(self, slots, tail_caches, *, starts, lengths, rows):
        self.runner.write_tails(slots, tail_caches, starts=starts,
                                lengths=lengths, rows=rows)

    def map_prefix(self, slot: int, blocks) -> None:
        self.runner.map_prefix(slot, blocks)

    def cow_block(self, slot: int, page_index: int, src_block: int) -> None:
        self.runner.cow_block(slot, page_index, src_block)

    def alloc_tail(self, slot: int, matched_len: int, prefill_len: int):
        return self.runner.alloc_tail(slot, matched_len, prefill_len)

    def ensure_mapped(self, slot: int, pos: int) -> None:
        self.runner.ensure_mapped(slot, pos)

    def evict(self, slots) -> None:
        self.runner.evict(slots)
        if self.draft is not None:
            # the draft row dies with the target's — re-admission
            # re-prefills both
            self.draft.evict(slots)

    def swap_out(self, slot: int):
        return self.runner.swap_out(slot)

    def swap_in(self, slot: int, state) -> None:
        self.runner.swap_in(slot, state)

    def set_slot(self, slot: int, *, token: int, pos: int,
                 temperature: float, top_k: int, seed: int) -> None:
        self.runner.set_slot(slot, token=token, pos=pos,
                             temperature=temperature, top_k=top_k, seed=seed)

    def clear_slot(self, slot: int) -> None:
        self.runner.clear_slot(slot)
        if self.draft is not None:
            self.draft.clear_slot(slot)

    def position(self, slot: int) -> int:
        return self.runner.position(slot)

    def draft_execute(self, inp: ExecuteInput) -> ExecuteOutput:
        return self.draft.execute(inp)

    def draft_insert(self, slots, caches) -> None:
        self.draft.insert(slots, caches)

    def draft_set_slot(self, slot: int, *, token: int, pos: int,
                       temperature: float, top_k: int, seed: int) -> None:
        self.draft.set_slot(slot, token=token, pos=pos,
                            temperature=temperature, top_k=top_k, seed=seed)

    def decode_compile_count(self) -> int | None:
        return self.runner.decode_compile_count()

    def prefill_compile_count(self) -> int | None:
        return self.runner.prefill_compile_count()

    def prefix_compile_count(self) -> int | None:
        return self.runner.prefix_compile_count()

    def verify_compile_count(self) -> int | None:
        return self.runner.verify_compile_count()

    def draft_decode_compile_count(self) -> int | None:
        return None if self.draft is None \
            else self.draft.decode_compile_count()
