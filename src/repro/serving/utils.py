"""Host-side helpers shared across the serving layers.

This module sits at the BOTTOM of the serving import graph: it may import
nothing from ``repro.serving`` (and nothing device-side), so every layer —
:mod:`repro.serving.runner` included, which is forbidden from importing the
scheduler/request/prefix_cache/events modules — can use it freely.

``next_pow2``/``pow2_bucket`` are the compile-cache bucketing helpers the
runner rounds dispatch shapes through; ``percentile`` is the tiny
linear-interpolated percentile used by request latency summaries, the
serving CLI and the benchmarks; :class:`EngineStats` is the one cumulative
counter block shared by the runner (device dispatch counters/timers) and
the EngineCore (host policy counters + host/device wall-time split).
"""
from __future__ import annotations

import dataclasses
from typing import Sequence as TypingSequence


def next_pow2(x: int) -> int:
    """Smallest power of two >= ``x`` (1 for x <= 1)."""
    return 1 << max(0, x - 1).bit_length()


def pow2_bucket(x: int, cap: int) -> int:
    """Smallest power of two >= x, clamped to the pow2 ceiling of ``cap``.

    Clamping to ``cap`` itself would reintroduce a non-pow2 dispatch shape
    whenever the cap (num_slots, max_len) is not a power of two — the
    compile-cache bound the bucketing exists for requires BOTH rows and
    width to round through this one helper."""
    return min(next_pow2(x), next_pow2(cap))


# Private-name aliases: these helpers lived as engine.py privates before the
# EngineCore/ModelRunner/Executor split and old call sites import them so.
_next_pow2 = next_pow2
_pow2_bucket = pow2_bucket


def percentile(values: TypingSequence[float], q: float) -> float:
    """Linear-interpolated percentile over a small host-side sample (the
    per-request ITL lists are tiny; pulling in numpy here would make the
    request module device-adjacent for no reason)."""
    if not values:
        raise ValueError("percentile of an empty sample")
    xs = sorted(values)
    if len(xs) == 1:
        return xs[0]
    pos = (q / 100.0) * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclasses.dataclass
class EngineStats:
    """Cumulative throughput counters (wall clock, block_until_ready'd).

    The ModelRunner owns the device-side fields (prefill_*/decode_* —
    each dispatch's launch and wait spans: from the jitted call to its
    sampled tokens on the host, i.e. finished work), the EngineCore owns the
    policy fields (preemptions/recomputed/swap counters) and ``host_time``:
    each ``step()`` adds its wall time MINUS whatever the runner spent
    inside dispatches, so scheduling/bookkeeping overhead is visible
    separately from device time (``/stats`` reports both)."""

    prefill_tokens: int = 0
    prefill_time: float = 0.0
    prefill_dispatches: int = 0
    # chunked-prefill dispatches (a subset of prefill_dispatches: each
    # mixed-step chunk group counts in both)
    chunk_dispatches: int = 0
    decode_tokens: int = 0
    decode_time: float = 0.0
    decode_steps: int = 0
    # decode dispatches whose attention took the paged kernel, and the
    # pages it read over them (ceil((pos + 1) / page_size) per live row);
    # over decode_steps x slots x max_pages: the share of a dense gather
    attn_kernel_steps: int = 0
    kv_pages_walked: int = 0
    # longest wall-clock gap between consecutive decode dispatches while at
    # least one admitted sequence was decode-ready — the stall a monolithic
    # prefill inflicts on running slots, and the number chunked prefill
    # exists to bound (before/after evidence for --chunk-size)
    max_decode_stall: float = 0.0
    # non-chunked admission steps (prefill / prefix waves) that ran while
    # at least one admitted sequence was decode-ready, and their summed
    # wall time: what each wave costs the running slots
    prefill_stall_time: float = 0.0
    prefill_stall_waves: int = 0
    # host-vs-device split: step() wall time not spent inside a compiled
    # dispatch (scheduling, cache bookkeeping, event emission)
    host_time: float = 0.0
    # overcommit accounting: how often pool pressure preempted a running
    # sequence, and how each preemption was undone (recompute vs swap)
    preemptions: int = 0
    recomputed: int = 0
    swapped_out: int = 0
    swapped_in: int = 0
    # speculative decoding: verify dispatches run on the TARGET runner
    # (the draft's own decode dispatches accumulate in the draft runner's
    # separate EngineStats); proposal/acceptance bookkeeping lives here so
    # /stats can report acceptance rate and mean accepted-run length.
    verify_time: float = 0.0
    verify_dispatches: int = 0
    spec_rounds: int = 0        # verify rounds executed (all slots batched)
    spec_commits: int = 0       # per-sequence commits (rounds x live rows)
    spec_proposed: int = 0      # draft tokens actually put to the verifier
    spec_accepted: int = 0      # of those, how many the target agreed with
    spec_committed: int = 0     # tokens committed (accepted + 1 corrected)

    @property
    def prefill_tps(self) -> float:
        return self.prefill_tokens / self.prefill_time if self.prefill_time else 0.0

    @property
    def decode_tps(self) -> float:
        return self.decode_tokens / self.decode_time if self.decode_time else 0.0

    @property
    def device_time(self) -> float:
        """Total wall time spent inside compiled dispatches."""
        return self.prefill_time + self.decode_time + self.verify_time
