"""Spans: named stretches of the serving program's host time.

``span(name, **meta)`` is a context manager that enters the profiler's
TraceMe (``jax.profiler.TraceAnnotation``), so under a ``jax.profiler``
trace each span is an event on the host plane, on the same clock as the
device planes' programs and ops.  It also times itself (``seconds``)
whether or not a trace is running: the runner's dispatch timers in
:class:`~repro.serving.utils.EngineStats` read that.  ``meta`` takes ints
already at hand (step index, row count); the profiler keeps them as the
event's stats, and with no trace running they cost nothing more.

TraceMe is looked up at the first span, not at import, so the host-policy
modules that open spans (``core.py``) stay free of jax at import
(``tools/layering_lint.py``).

The vocabulary (DESIGN.md section 14):

``serve.step``                  one ``EngineCore.step``; meta ``step``,
                                ``decode_ready``, then ``kind`` (see
                                ``KIND_*``) and ``rows``
``serve.schedule``              ``Scheduler.admit`` / ``plan_step``; meta
                                ``first``/``last`` admit_seqno of the wave
``serve.reclaim``               ``EngineCore._reclaim``
``serve.stage.<kind>``          host arrays, page table and uploads of one
                                runner dispatch
``serve.launch.<kind>``         the jitted call until it returns; a
                                decode (or mixed) launch whose attention
                                takes the paged kernel has meta
                                ``attn_kernel_steps`` (1) and
                                ``kv_pages_walked`` (pages it reads)
``serve.wait.<kind>``           waiting for its results on the host
``serve.cache.<op>``            insert, write_tails, evict, ensure_mapped
                                (a step's whole page-table growth),
                                map_prefix, cow, alloc_tail, swap_out,
                                swap_in
``serve.emit``                  token append, step events, retirement

``<kind>`` is decode, prefill, prefix, mixed or verify; a draft runner's
spans read ``draft.<kind>`` and ``draft.<op>``.

A trace's op events name only the compiled instruction, so which named
scope (``fact.*``, ``attn.*`` ...) an op ran under is read from the
compiled program's text: :func:`publish` lets a runner offer the text of
each program it has run, and :func:`program_texts` hands it to whoever
reduces the trace in the same process.  Like the profiler it serves, that
table is one per process.
"""
from __future__ import annotations

import time
import weakref

KIND_DECODE, KIND_PREFILL, KIND_PREFIX, KIND_MIXED, KIND_VERIFY = range(5)

_trace_me = None
# module name -> weak references to callables returning that compiled
# program's text (None until the program has run)
_programs: dict[str, list] = {}


def _resolve():
    global _trace_me
    from jax.profiler import TraceAnnotation
    _trace_me = TraceAnnotation
    return TraceAnnotation


class span:
    """One named stretch of host time; see the module docstring."""

    __slots__ = ("_me", "_t0", "seconds")

    def __init__(self, name: str, **meta):
        self._me = (_trace_me or _resolve())(name, **meta)
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._me.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self._t0
        self._me.__exit__(*exc)
        return False

    def note(self, **meta) -> None:
        """Add ints learned inside the span to its event."""
        self._me.set_metadata(**meta)


def publish(module: str, text) -> None:
    """Offer the bound method ``text()`` — the compiled text of the program
    named ``module``, or None while it has not run — to trace reductions.
    Held by weak reference, so it goes with its runner."""
    refs = [r for r in _programs.get(module, []) if r() is not None]
    _programs[module] = refs + [weakref.WeakMethod(text)]


def program_texts(module: str) -> list[str]:
    """Compiled text, op metadata included, of every live program named
    ``module`` that has run in this process."""
    out = []
    for ref in _programs.get(module, []):
        fn = ref()
        text = fn() if fn is not None else None
        if text is not None:
            out.append(text)
    return out
