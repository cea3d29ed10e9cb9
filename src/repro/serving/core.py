"""EngineCore: the host-policy layer of the serving stack.

Everything that decides WHAT runs lives here — the Scheduler, the prefix
trie, admission/preemption/reclaim/resume policy, sequence lifecycle and
retirement, and StepEvent emission.  The core never touches a device: it
drives its :class:`repro.serving.executor.Executor` through the typed
:class:`ExecuteInput`/:class:`ExecuteOutput` contract plus the executor's
slot-indexed cache/staging operations, so the same policy code runs
unchanged whether the executor fronts one local runner, a multi-process
mesh, or (next PR) a disaggregated prefill/decode pair.  The import
direction is one-way — core imports the runner's contract types, the
runner imports nothing from here — and ``tools/layering_lint.py`` keeps
it that way (no ``jax.jit`` outside the runner either).

The public surface (``submit``/``step``/``abort``/``run``) is the same
re-entrant step loop the monolithic Engine exposed; ``Engine`` in
:mod:`repro.serving.engine` is now a thin facade over this class.  Every
wall-clock second a ``step()`` spends OUTSIDE the runner's compiled
dispatches accumulates into ``stats.host_time`` — the host-vs-device
split ``/stats`` reports.  Each step, scheduling pass, reclaim and event
emission runs under a span (``serve.step``, ``serve.schedule``,
``serve.reclaim``, ``serve.emit``; :mod:`repro.serving.tracing`).
"""
from __future__ import annotations

import time

from repro.serving import tracing
from repro.serving.cache import PoolExhausted
from repro.serving.events import StepEvent
from repro.serving.executor import Executor
from repro.serving.prefix_cache import PrefixCache
from repro.serving.request import (Request, RequestOutput, Sequence,
                                   SequenceState)
from repro.serving.runner import ExecuteInput
from repro.serving.scheduler import Scheduler
from repro.serving.speculative import DraftProposer, SpecVerifier


def _sampling_columns(group: list[Sequence]):
    """Per-row sampling params for an ExecuteInput, aligned with tokens."""
    return (tuple(float(s.request.sampling.temperature) for s in group),
            tuple(int(s.request.sampling.top_k) for s in group),
            tuple(int(s.request.sampling.seed) for s in group))


class EngineCore:
    """Host state + policy over one Executor.

    Construction is cheap: the executor already owns the compiled
    dispatches and the cache; the core builds the Scheduler from the
    executor's resolved :class:`EngineSpec` (paged admission when
    ``page_size`` is set, token-budget otherwise), wraps the paged pool in
    a :class:`PrefixCache` when the spec asks for one, and shares the
    executor's :class:`EngineStats` block.
    """

    def __init__(self, executor: Executor, *, eos_id: int | None = None):
        self.executor = executor
        self.cfg = executor.cfg
        spec = executor.spec
        self.spec = spec
        self.max_len = spec.max_len
        self.num_slots = spec.num_slots
        self.page_size = spec.page_size
        self.num_pages = spec.num_pages
        self.overcommit = spec.overcommit
        self.swap_enabled = spec.swap
        self.max_top_k = spec.max_top_k
        self.eos_id = eos_id
        self.chunk_size = spec.chunk_size
        self.speculative = spec.speculative
        self.spec_k = spec.spec_k
        if spec.page_size is not None:
            self.scheduler = Scheduler(spec.num_slots, max_len=spec.max_len,
                                       page_size=spec.page_size,
                                       num_pages=spec.num_pages,
                                       overcommit=spec.overcommit,
                                       chunk_size=spec.chunk_size)
        else:
            self.scheduler = Scheduler(spec.num_slots, spec.token_budget,
                                       max_len=spec.max_len)
        self.stats = executor.stats
        # radix-tree prefix cache over the paged pool (DESIGN.md section
        # 12): admission consults the trie, fully shared prompt pages are
        # mapped read-only into the slot, and only the unshared tail is
        # prefilled — bit-identical to the uncached stream
        self.prefix: PrefixCache | None = None
        if spec.prefix_cache:
            self.prefix = PrefixCache(executor.cache)
            self.scheduler.prefix_hook = self.prefix
        # request_id -> Sequence for everything submitted and not yet
        # retired/aborted: what ``abort`` looks up between steps
        self._live: dict[str, Sequence] = {}
        # request_ids preempted during the CURRENT step (reported as
        # informational tokenless events, then cleared)
        self._preempted_now: list[str] = []
        # decode-stall watermark: wall clock of the last decode dispatch's
        # completion, None while no admitted sequence is decode-ready (the
        # gap only counts as a stall if someone was waiting to decode)
        self._last_decode_done: float | None = None
        # the current step's index, kind (tracing.KIND_*) and row count:
        # meta of its serve.step span; the kind also decides whether the
        # step counts as a prefill stall
        self._step_index = 0
        self._kind = tracing.KIND_DECODE
        self._rows = 0
        # speculative decoding (DESIGN.md section 16): the drafter runs the
        # executor's small dense draft model k tokens ahead per slot, the
        # verifier scores every slot's proposals in ONE batched target
        # dispatch and commits the accepted run — both are host policy
        # driving the SAME executor contract as everything above
        self.drafter: DraftProposer | None = None
        self.verifier: SpecVerifier | None = None
        if spec.speculative:
            self.drafter = DraftProposer(executor, k=spec.spec_k)
            self.verifier = SpecVerifier(
                executor, self.drafter, eos_id=eos_id, stats=self.stats,
                page_size=spec.page_size, reclaim=self._reclaim)

    # ---------------------------------------------------------- lifecycle --
    def validate(self, seq: Sequence) -> None:
        """Raise if ``seq`` can never be served: scheduler feasibility
        (max_len capacity + token/page budget — the scheduler owns those
        bounds) plus the runner's compiled sampler limits (top_k width,
        stop-token ids inside the vocabulary)."""
        self.scheduler.validate(seq)
        tk = seq.request.sampling.top_k
        if self.max_top_k < tk < self.cfg.vocab_size:
            raise ValueError(
                f"{seq.request_id}: top_k = {tk} exceeds the engine's "
                f"max_top_k = {self.max_top_k}; construct the Engine "
                "with a larger max_top_k")
        # id validation has ONE home, here: out-of-range prompt ids would
        # otherwise be silently clamped by the jitted embedding gather and
        # serve garbage instead of erroring (untrusted HTTP clients included)
        v = self.cfg.vocab_size
        bad = [t for t in seq.request.prompt if not 0 <= t < v]
        if bad:
            raise ValueError(
                f"{seq.request_id}: prompt ids {bad[:8]} outside the "
                f"vocabulary [0, {v})")
        bad = [t for t in seq.request.sampling.stop_tokens
               if not 0 <= t < v]
        if bad:
            raise ValueError(
                f"{seq.request_id}: stop_tokens {bad} outside the "
                f"vocabulary [0, {v})")

    def submit(self, request: Request) -> Sequence:
        """Enqueue one request for the step loop (legal at any time, before
        or between ``step()`` calls).  Validates up front — an infeasible
        request raises here and nothing is enqueued.  Returns the live
        :class:`Sequence` (its ``to_output()`` is the final result once a
        step retires it)."""
        if request.request_id in self._live:
            raise ValueError(f"{request.request_id}: already submitted")
        seq = Sequence(request)
        self.validate(seq)
        self.scheduler.add(seq)
        self._live[request.request_id] = seq
        return seq

    def abort(self, request_id: str) -> StepEvent:
        """Cancel a live request between steps.  A WAITING sequence is
        dequeued; a RUNNING one releases its slot and (paged) frees its
        pages immediately — no other slot's state is touched, and the next
        ``step()`` can admit into the freed capacity.  Returns the terminal
        (tokenless) event; ``to_output()`` keeps the partial tokens."""
        seq = self._live.pop(request_id, None)
        if seq is None:
            raise KeyError(f"{request_id}: not a live request")
        if seq.slot is None:  # WAITING: nothing reserved yet
            self.scheduler.remove_waiting(seq)
            seq.mark_aborted()
            seq.state = SequenceState.FINISHED
            seq.t_finished = seq.now()
        else:  # RUNNING: release the slot, free pages, clear host state
            seq.mark_aborted()
            self.executor.evict([seq.slot])
            slot = seq.slot
            self.scheduler.retire(seq)
            self.executor.clear_slot(slot)
            if self.drafter is not None:
                self.drafter.drop(seq.request_id)
        return StepEvent(request_id, token=None, index=None,
                         finish_reason=seq.finish_reason)

    def step(self) -> list[StepEvent]:
        """ONE engine iteration; re-entrant — call until the scheduler
        drains (or forever, interleaving ``submit``/``abort`` between
        calls).  Legacy mode (``chunk_size`` unset): admit-OR-decode — if
        the queue head can be admitted this step is a prefill (first token
        per admitted sequence); otherwise all active slots take one decode
        step.  Chunked mode: ONE token-budget batch per step — every
        caught-up slot decodes AND up to ``chunk_size`` prefill tokens run
        beside them (:meth:`_step_chunked`).  Finished sequences are
        retired before returning, so a freed slot is admissible by the
        NEXT call.  Returns one event per sequence that progressed (empty
        when idle)."""
        if not self.scheduler.has_work:
            return []
        dev0 = self.stats.device_time
        # stall accounting arms only while someone could decode: a gap
        # with no decode-ready sequence (pure prefill warmup, idle) is not
        # a stall
        ready = any(s.tokens and s.swap_state is None
                    for s in self.scheduler.active.values())
        if not ready:
            self._last_decode_done = None
        self._step_index += 1
        self._kind, self._rows = tracing.KIND_DECODE, 0
        step = tracing.span("serve.step", step=self._step_index,
                            decode_ready=int(ready))
        try:
            with step:
                events = self._step_events()
                step.note(kind=self._kind, rows=self._rows)
            if ready and self._kind in (tracing.KIND_PREFILL,
                                        tracing.KIND_PREFIX):
                # an admission wave that held every decode-ready sequence
                # back for its whole step
                self.stats.prefill_stall_time += step.seconds
                self.stats.prefill_stall_waves += 1
            return events
        finally:
            # whatever this step spent outside the runner's dispatch
            # windows is host overhead: scheduling, array staging, cache
            # bookkeeping, event emission
            dev = self.stats.device_time - dev0
            self.stats.host_time += max(0.0, step.seconds - dev)

    def _step_events(self) -> list[StepEvent]:
        """The step body and its events, one per new token."""
        self._preempted_now = []
        # token counts BEFORE the step body: a speculative verify commits
        # several tokens per sequence per step (and commits BEFORE any
        # page-pressure preemption, so even a preempted sequence may have
        # grown) — every path's events come from this one before/after
        # delta, one event per new token
        before = {rid: len(s.tokens) for rid, s in self._live.items()}
        if self.speculative:
            progressed = self._step_speculative()
        elif self.chunk_size is not None:
            progressed = self._step_chunked()
        else:
            progressed = self._step_legacy()
        with tracing.span("serve.emit"):
            events = []
            for s in progressed:
                n = len(s.tokens)
                for i in range(before.get(s.request_id, n - 1), n):
                    events.append(StepEvent(
                        s.request_id, s.tokens[i], i,
                        s.finish_reason if i == n - 1 else None))
            # commit-then-preempt: token events first, the informational
            # preemption notice after — matching the order it happened
            events += [StepEvent(rid, token=None, index=None, preempted=True)
                       for rid in self._preempted_now]
            self._retire_finished()
        return events

    def _admit(self) -> list[Sequence]:
        """``Scheduler.admit`` under the ``serve.schedule`` span, which
        carries the wave's admit_seqno range."""
        with tracing.span("serve.schedule") as sp:
            admitted = self.scheduler.admit()
            if admitted:
                sp.note(first=admitted[0].admit_seqno,
                        last=admitted[-1].admit_seqno)
        return admitted

    def _step_legacy(self) -> list:
        """The admit-OR-decode step body (``chunk_size`` unset): byte-for-
        byte the pre-chunking behavior — one admission wave or one decode
        dispatch per call, never both."""
        admitted = self._admit()
        if admitted:
            before = {s.request_id: len(s.tokens) for s in admitted}
            self._prefill_admitted(admitted)
            # resumed sequences (recompute/swap restore) append no token
            # on their re-admission step — their next token comes from
            # decode — so only sequences whose token count grew produce
            # a delta
            return [s for s in admitted
                    if len(s.tokens) > before[s.request_id]]
        active = list(self.scheduler.active.values())
        if not active:
            raise RuntimeError(
                "scheduler stalled: waiting requests but nothing "
                "active")
        return self._decode_once(active)

    def _step_speculative(self) -> list:
        """The admit-or-verify step body (``--speculative``): same shape as
        legacy admit-OR-decode, but the decode half is one speculative
        round — the draft model proposes up to ``spec_k`` tokens per slot,
        ONE batched verify dispatch on the target scores every slot's
        proposals, and the accepted run (plus the target's own next token)
        commits.  Admission waves additionally prefill the DRAFT cache for
        the admitted sequences (fresh and resumed alike — recompute rebuilds
        both models' state), so a round never mixes prefill and verify."""
        admitted = self._admit()
        if admitted:
            before = {s.request_id: len(s.tokens) for s in admitted}
            self._prefill_admitted(admitted)
            self.drafter.on_prefilled(
                [s for s in admitted
                 if s.state is SequenceState.RUNNING])
            return [s for s in admitted
                    if len(s.tokens) > before[s.request_id]]
        active = list(self.scheduler.active.values())
        if not active:
            raise RuntimeError(
                "scheduler stalled: waiting requests but nothing "
                "active")
        self._kind, self._rows = tracing.KIND_VERIFY, len(active)
        proposals = self.drafter.propose(active)
        progressed = self.verifier.verify_and_commit(active, proposals)
        # a verify round IS the step's decode dispatch for stall purposes:
        # every running slot took at least one token from it
        self._note_decode_dispatch()
        return progressed

    def _step_chunked(self) -> list:
        """One token-budget batch (Sarathi/vLLM-v1 chunked prefill): the
        scheduler's :meth:`~repro.serving.scheduler.Scheduler.plan_step`
        picks the step's decode rows and chunk group; this method executes
        the plan as ONE mixed dispatch.

        Every chunk — the first included — rides the prefix machinery: its
        earlier chunks (and any trie-matched pages) are already pool pages,
        so the chunk prefills as a tail via ``prefill_with_prefix`` with
        absolute positions (chunk 0 is the ``prefix_len == 0`` case).  The
        final chunk's sample lands at the same fold-in position as an
        unchunked prefill, so the output stream is bit-exact against the
        legacy path by construction; intermediate chunk samples are
        discarded (same rule as resumed-recompute prefills).

        Preemption composes: a mid-prefill victim's chunk pages are
        released like any other pages (its cursor resets to 0 for
        drop-and-recompute, survives for swap restore) and the plan rows
        are re-filtered by state after every reclaim."""
        with tracing.span("serve.schedule") as sp:
            plan = self.scheduler.plan_step()
            if plan.admitted:
                sp.note(first=plan.admitted[0].admit_seqno,
                        last=plan.admitted[-1].admit_seqno)
        self._kind = tracing.KIND_MIXED
        self._rows = len(plan.decode) + len(plan.chunks)
        if not plan.admitted and not self.scheduler.active:
            raise RuntimeError(
                "scheduler stalled: waiting requests but nothing active")
        protect = frozenset(s.request_id for s in plan.admitted) | \
            frozenset(s.request_id for s, _ in plan.chunks)
        # admission processing mirrors _prefill_admitted up to (not
        # including) the prefill dispatch: swap restores happen now, trie
        # hits map their resident pages + COW the partial page now (the
        # pins taken at admission are consumed exactly once, here)
        for s in plan.admitted:
            if s.swap_state is not None:
                self._swap_in(s, protect)
                continue
            if s.tokens:
                self.stats.recomputed += 1
            m = s.prefix_match
            if m is not None and m.matched_len > 0:
                self.executor.map_prefix(s.slot, m.full_blocks)
                if m.partial_len > 0:
                    self._with_reclaim(
                        lambda s=s, m=m: self.executor.cow_block(
                            s.slot, m.full_pages, m.partial_block), protect)
            s.prefix_match = None
        # chunk page allocation: extend each chunk row's mapped tail to
        # cover this chunk's positions.  A sequence's TOTAL chunk pages
        # never exceed its current-footprint pages, which its admission
        # charge always covers — so protecting the plan's rows preserves
        # the PR 7 no-deadlock argument.
        for s, n in plan.chunks:
            if s.state is not SequenceState.RUNNING:
                continue  # preempted by an earlier alloc this step
            self._with_reclaim(
                lambda s=s, n=n, p=s.prefill_progress:
                    self.executor.alloc_tail(s.slot, p, p + n), protect)
        # decode page growth keeps legacy semantics: it may preempt ANY
        # active row — including a mid-prefill one, whose already-written
        # chunk pages are simply released (recompute-from-progress later)
        decode = self._grow_tables(list(plan.decode))
        chunks = [(s, n) for s, n in plan.chunks
                  if s.state is SequenceState.RUNNING]
        if not decode and not chunks:
            return []
        chunk_group = [s for s, _ in chunks]
        starts = [s.prefill_progress for s in chunk_group]
        temps, topks, seeds = _sampling_columns(chunk_group)
        out = self.executor.execute(ExecuteInput(
            kind="mixed",
            slots=tuple(s.slot for s in decode),
            chunk_slots=tuple(s.slot for s in chunk_group),
            tokens=tuple(tuple(s.prefill_tokens[p:p + n])
                         for (s, n), p in zip(chunks, starts)),
            prefix_lens=tuple(starts),
            temperatures=temps, top_ks=topks, seeds=seeds))
        progressed = []
        if decode:
            self._note_decode_dispatch()
            progressed += self._append_decoded(decode, out.tokens)
        # advance cursors; a sequence whose cursor reaches prefill_len is
        # done prefilling — its final chunk's sample IS its first token
        # (recorded before the tail scatter, like the prefix path: this is
        # the TTFT stamp), and its staging row arms for decode
        completed = []
        for j, (s, n) in enumerate(chunks):
            s.prefill_progress += n
            if s.prefill_progress < s.prefill_len:
                continue
            if not s.tokens:
                s.append_token(int(out.chunk_tokens[j]), self.eos_id)
                progressed.append(s)
            # resumed recompute: the chunk sample is DISCARDED (wrong fold
            # position for the NEXT token — see _prefill_group); the
            # pending last token goes back into the step buffer
            self.executor.set_slot(
                s.slot, token=s.tokens[-1], pos=s.prefill_len,
                temperature=temps[j], top_k=topks[j], seed=seeds[j])
            completed.append(s)
        if chunks:
            self.executor.write_tails(
                [s.slot for s, _ in chunks], out.caches,
                starts=starts,
                lengths=[p + n for (s, n), p in zip(chunks, starts)],
                rows=list(range(len(chunks))))
        self._adopt_group(completed)
        return progressed

    def _note_decode_dispatch(self) -> None:
        """Record the gap since the previous decode dispatch while at
        least one sequence was decode-ready — the max is the stall metric
        chunked prefill exists to bound."""
        now = time.perf_counter()
        if self._last_decode_done is not None:
            self.stats.max_decode_stall = max(
                self.stats.max_decode_stall, now - self._last_decode_done)
        self._last_decode_done = now

    def run(self, requests: list[Request]) -> list[RequestOutput]:
        """Closed-batch compatibility wrapper: submit all, step until
        drained; returns outputs in request order.  The whole batch is
        validated BEFORE anything is enqueued — a mid-batch rejection must
        not leave ghost sequences in the queue that eat slots on the next
        run and whose outputs nobody collects (``submit`` validates per
        request, which is the same guarantee for a single enqueue)."""
        seqs = [Sequence(r) for r in requests]
        ids = [s.request_id for s in seqs]
        if len(set(ids)) != len(ids) or any(i in self._live for i in ids):
            raise ValueError("duplicate request_id in batch or already live")
        for s in seqs:
            self.validate(s)
        for s in seqs:
            self.scheduler.add(s)
            self._live[s.request_id] = s
        try:
            while self.scheduler.has_work:
                self.step()
        except BaseException:
            # a failed STEP must give the same no-ghost guarantee as a
            # failed validation: retire anything that finished, then abort
            # this run's still-live sequences so nothing lingers in _live /
            # the queue / the slots to poison the next run.  Best-effort —
            # the original error propagates.
            try:
                self._retire_finished()
            except Exception:
                pass
            for s in seqs:
                if self._live.get(s.request_id) is s:
                    try:
                        self.abort(s.request_id)
                    except Exception:
                        pass
            raise
        return [s.to_output() for s in seqs]

    # ------------------------------------------------------------ prefill --
    def _prefill_admitted(self, admitted: list[Sequence]) -> None:
        """Batched prefill: pure-attention stacks take mixed lengths in one
        right-padded dispatch; recurrent stacks are grouped by exact length
        (pad tokens would pollute O(1) state) — still one dispatch per group,
        never per token.  With the prefix cache on, trie hits split off into
        their own tail-only dispatch (the matched pages are already
        resident) and misses take the full path; both adopt their prompt
        pages into the trie afterwards.

        Resumed sequences ride the same dispatches: a preempted sequence's
        ``prefill_tokens`` (prompt + generated-so-far minus the pending
        last token) replace its prompt, rebuilding the exact KV state it
        lost.  Swap-mode sequences skip prefill entirely and restore their
        saved blocks.  The whole admitted wave is protected from being
        preempted by its own prefill allocations — admission reserved the
        wave's charges, so after reclaiming everyone else the wave always
        fits (the no-deadlock argument in DESIGN.md section 13)."""
        protect = frozenset(s.request_id for s in admitted)
        self._rows = len(admitted)
        hits, misses = [], []
        for s in admitted:
            if s.swap_state is not None:
                self._swap_in(s, protect)
            elif s.prefix_match is not None and s.prefix_match.matched_len > 0:
                hits.append(s)
            else:
                misses.append(s)
        self._kind = tracing.KIND_PREFIX if hits and not misses \
            else tracing.KIND_PREFILL
        if misses:
            lengths = {s.prefill_len for s in misses}
            if self.executor.attn_only or len(lengths) == 1:
                groups = [misses]
            else:
                by_len: dict[int, list[Sequence]] = {}
                for s in misses:
                    by_len.setdefault(s.prefill_len, []).append(s)
                groups = list(by_len.values())
            for group in groups:
                self._prefill_group(group, protect)
        if hits:
            self._prefill_prefix_group(hits, protect)

    def _with_reclaim(self, fn, protect: frozenset):
        """Run a pool-allocating operation, reclaiming pages (trie
        eviction first, then preemption of the youngest unprotected
        running sequence) and retrying until it succeeds or nothing more
        can be reclaimed."""
        while True:
            try:
                return fn()
            except PoolExhausted as e:
                if not self._reclaim(e.shortfall, protect):
                    raise

    def _prefill_group(self, group: list[Sequence],
                       protect: frozenset = frozenset()) -> None:
        """Full prefill for one group: ONE runner dispatch, then the cache
        insert (retried under reclaim WITHOUT re-dispatching — the dispatch
        output is already in hand, so a preemption-and-retry costs pages,
        never a second forward), then first tokens and staging state."""
        for s in group:
            if s.tokens:
                self.stats.recomputed += 1
        temps, topks, seeds = _sampling_columns(group)
        out = self.executor.execute(ExecuteInput(
            kind="prefill",
            slots=tuple(s.slot for s in group),
            tokens=tuple(s.prefill_tokens for s in group),
            temperatures=temps, top_ks=topks, seeds=seeds))
        slots = [s.slot for s in group]
        if self.page_size is not None:
            self._with_reclaim(
                lambda: self.executor.insert(
                    slots, out.caches,
                    lengths=[s.prefill_len for s in group]),
                protect)
        else:
            self.executor.insert(slots, out.caches)

        for j, s in enumerate(group):
            if not s.tokens:
                s.append_token(int(out.tokens[j]), self.eos_id)
            # resumed recompute: the prefill's sample is DISCARDED — it was
            # drawn at fold position prefill_len, but the sequence's next
            # token belongs to fold position prefill_len + 1, which the
            # next decode step samples.  The pending last token goes back
            # into the step buffer; either way the staging row holds
            # tokens[-1].
            self.executor.set_slot(
                s.slot, token=s.tokens[-1], pos=s.prefill_len,
                temperature=temps[j], top_k=topks[j], seed=seeds[j])
            s.prefill_progress = s.prefill_len
        self._adopt_group(group)

    def _prefill_prefix_group(self, group: list[Sequence],
                              protect: frozenset = frozenset()) -> None:
        """Tail-only prefill for trie hits: map the matched full pages
        read-only, copy-on-write the partially matched page, allocate the
        private tail pages, then ONE bucketed runner dispatch and the tail
        K/V scatter into the mapped blocks.  The matched tokens are never
        recomputed — that is the TTFT win.  Resumed sequences prefill
        prompt + generated tail against the same matched prefix (the match
        is on the PROMPT, whose length bounds ``matched_len``, so the tail
        always covers the generated part)."""
        for s in group:
            m = s.prefix_match
            self.executor.map_prefix(s.slot, m.full_blocks)
            if m.partial_len > 0:
                # the COW copy consumes the pin reference on the shared
                # partial block; its content is identical, so the gather
                # below may read either copy
                self._with_reclaim(
                    lambda s=s, m=m: self.executor.cow_block(
                        s.slot, m.full_pages, m.partial_block), protect)
            self._with_reclaim(
                lambda s=s, m=m: self.executor.alloc_tail(
                    s.slot, m.matched_len, s.prefill_len), protect)
            if s.tokens:
                self.stats.recomputed += 1

        temps, topks, seeds = _sampling_columns(group)
        out = self.executor.execute(ExecuteInput(
            kind="prefix",
            slots=tuple(s.slot for s in group),
            tokens=tuple(s.prefill_tokens[s.prefix_match.matched_len:]
                         for s in group),
            prefix_lens=tuple(s.prefix_match.matched_len for s in group),
            temperatures=temps, top_ks=topks, seeds=seeds))
        # the first tokens exist the moment the dispatch returns — record
        # them (this is each request's TTFT stamp) BEFORE the tail-KV
        # scatter and trie adoption, which are cache maintenance the next
        # decode step needs, not the client
        for j, s in enumerate(group):
            if not s.tokens:
                s.append_token(int(out.tokens[j]), self.eos_id)
            # resumed recompute: discard the prefill sample (wrong fold
            # position for the NEXT token — see _prefill_group)
            self.executor.set_slot(
                s.slot, token=s.tokens[-1], pos=s.prefill_len,
                temperature=temps[j], top_k=topks[j], seed=seeds[j])
            s.prefill_progress = s.prefill_len
        self.executor.write_tails(
            [s.slot for s in group], out.caches,
            starts=[s.prefix_match.matched_len for s in group],
            lengths=[s.prefill_len for s in group],
            rows=list(range(len(group))))
        self._adopt_group(group)

    def _adopt_group(self, group: list[Sequence]) -> None:
        """Adopt each sequence's full prompt pages into the trie right
        after its prefill and transfer the adopted units from the
        sequence's admission charge to the trie's residency — the
        ``reserved + resident`` sum the admission check bounds is exactly
        conserved."""
        if self.prefix is None:
            return
        for s in group:
            adopted = self.prefix.adopt(s.request.prompt,
                                        self.executor.cache.table[s.slot])
            if adopted:
                self.scheduler.transfer_to_shared(s, adopted)

    # ------------------------------------------------------------- decode --
    def _decode_once(self, active: list[Sequence]) -> list[Sequence]:
        """One decode dispatch over all slots.  Returns the sequences that
        actually progressed — under overcommit, growing a page table can
        exhaust the pool, in which case the core reclaims (trie eviction,
        then preempting the youngest running sequence, possibly one from
        ``active``) and retries; preempted sequences drop out of the
        dispatch (their slots ride along idle) and resume later."""
        if self.page_size is not None:
            active = self._grow_tables(active)
            if not active:
                return []
        self._rows = len(active)
        out = self.executor.execute(ExecuteInput(
            kind="decode", slots=tuple(s.slot for s in active)))
        self._note_decode_dispatch()
        return self._append_decoded(active, out.tokens)

    def _grow_tables(self, rows: list[Sequence]) -> list[Sequence]:
        """Grow page tables before a decode dispatch: each row whose write
        position crosses into an unmapped block gets one from the free
        list.  At overcommit 1.0 admission reserved the worst case and
        this cannot fail; above it PoolExhausted triggers reclaim (which
        may preempt ANY active row).  Values-only change — never a
        recompile.  Returns the rows still running."""
        with tracing.span("serve.cache.ensure_mapped"):
            for s in rows:
                while s.state is SequenceState.RUNNING:
                    try:
                        self.executor.ensure_mapped(
                            s.slot, self.executor.position(s.slot))
                        break
                    except PoolExhausted as e:
                        if not self._reclaim(e.shortfall, frozenset()):
                            raise
        return [s for s in rows if s.state is SequenceState.RUNNING]

    def _append_decoded(self, rows: list[Sequence], tokens) -> list[Sequence]:
        """Append each decode row's sampled token (slot-indexed)."""
        with tracing.span("serve.emit"):
            for s in rows:
                s.append_token(int(tokens[s.slot]), self.eos_id)
                # each appended token extends prefill_len by one cached
                # position (the previous pending token); the cursor
                # tracks it
                s.prefill_progress = s.prefill_len
        return rows

    # --------------------------------------------------------- preemption --
    def _reclaim(self, shortfall: int, protect: frozenset) -> bool:
        """Free pool pages for an allocation that just failed: evict
        unreferenced prefix-trie pages first (cheapest — nothing loses
        state), then preempt the YOUNGEST running sequence outside
        ``protect`` (it has the least KV to rebuild and its victimization
        cannot starve older work).  Returns False when nothing could be
        reclaimed — the caller's retry would loop forever, so it re-raises."""
        with tracing.span("serve.reclaim"):
            freed = 0
            if self.prefix is not None:
                freed = self.prefix.evict(shortfall)
                if freed >= shortfall:
                    return True
            victims = [s for s in self.scheduler.active.values()
                       if s.request_id not in protect]
            if not victims:
                return freed > 0
            self._preempt(self._pick_victim(victims))
            return True

    def _pick_victim(self, victims: list[Sequence]) -> Sequence:
        """Choose which running sequence to preempt.  Among the candidates,
        PREFER one whose full prompt pages the prefix trie still holds: its
        drop-and-recompute resume rides the trie's tail-only prefill path,
        so the recompute bill shrinks from the whole prompt to the
        generated tail.  Within the preferred set (or among all victims
        when the trie holds nothing) pick the YOUNGEST admission — least KV
        beyond the prompt to rebuild, and FIFO fairness is unaffected
        because the scheduler re-enqueues any victim at its arrival-order
        position.  ``PrefixCache.match`` takes no references and touches no
        LRU state, so probing here has no side effects."""
        if self.prefix is not None and self.page_size is not None:
            preferred = []
            for s in victims:
                m = self.prefix.match(s.request.prompt)
                if m.full_pages >= 1 and \
                        m.full_pages >= s.prompt_len // self.page_size:
                    preferred.append(s)
            if preferred:
                return max(preferred, key=lambda s: s.admit_seqno)
        return max(victims, key=lambda s: s.admit_seqno)

    def _preempt(self, victim: Sequence) -> None:
        """Take ``victim``'s pages and slot back: swap-mode saves its
        mapped blocks to host first; eviction releases one reference per
        mapped page (shared prefix pages stay live for the trie and any
        other reader); the scheduler returns its reservation and requeues
        it at the head of the waiting queue."""
        slot = victim.slot
        if self.swap_enabled:
            victim.swap_state = self.executor.swap_out(slot)
            self.stats.swapped_out += 1
        else:
            # drop-and-recompute: the pages are gone, chunked progress with
            # them — re-admission re-prefills from scratch (a mid-prefill
            # victim's partial chunk pages are exactly as releasable as a
            # decoder's, recoverable by recompute-from-progress-0)
            victim.prefill_progress = 0
        self.executor.evict([slot])
        self.scheduler.preempt(victim)
        self.executor.clear_slot(slot)
        if self.drafter is not None:
            # the draft cache rebuilds with the target's at re-admission
            self.drafter.drop(victim.request_id)
        self.stats.preemptions += 1
        self._preempted_now.append(victim.request_id)

    def _swap_in(self, s: Sequence, protect: frozenset) -> None:
        """Restore a swapped-out sequence: allocate fresh blocks (reclaim
        + retry on exhaustion), scatter the host copies back, and rebuild
        the slot's staging state.  No prefill runs and no token is
        appended — the pending last token goes back into the step buffer
        and the next decode step continues the stream exactly where it
        stopped."""
        self._with_reclaim(
            lambda: self.executor.swap_in(s.slot, s.swap_state), protect)
        s.swap_state = None
        # a mid-chunked-prefill victim restores tokenless with its cursor
        # short of prefill_len: it has no pending token to stage and no
        # full prompt to adopt yet — its remaining chunks arm the slot when
        # the cursor catches up
        if s.tokens and s.prefill_progress >= s.prefill_len:
            self.executor.set_slot(
                s.slot, token=s.tokens[-1], pos=s.prefill_len,
                temperature=s.request.sampling.temperature,
                top_k=s.request.sampling.top_k,
                seed=s.request.sampling.seed)
        self.stats.swapped_in += 1
        if s.prefill_progress >= s.prefill_len:
            self._adopt_group([s])

    # ------------------------------------------------------------- retire --
    def _retire_finished(self) -> None:
        done = [s for s in self.scheduler.active.values() if s.done]
        if not done:
            return
        self.executor.evict([s.slot for s in done])
        for s in done:
            slot = s.slot
            self.scheduler.retire(s)
            self.executor.clear_slot(slot)
            if self.drafter is not None:
                self.drafter.drop(s.request_id)
            self._live.pop(s.request_id, None)
