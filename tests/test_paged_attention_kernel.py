"""The paged decode attention kernel (interpret mode on the CPU) against
its oracle, the gather path (``kernels/paged_attention/ref.py``), and the
decode step's dispatch to it.

Tolerance.  In float32 the kernel and the oracle differ only in summation
order (online softmax over blocks of pages against one softmax over the
gathered view): 1e-5.  In bfloat16 the kernel feeds its softmax weights to
the MXU in bf16, as the chip rounds the oracle's f32 product, and both
outputs round to bf16: two roundings of at most 2^-9 relative each and one
bf16 ulp (2^-7) of outputs of magnitude up to about 2 give 2e-2.  A wrong
page, mask or group moves an output by its own scale, O(1).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.kernels.paged_attention import ops
from repro.kernels.paged_attention.kernel import paged_decode_attention
from repro.kernels.paged_attention.ref import paged_decode_attention_ref
from repro.models import attention, decode_step, init_params
from repro.models.model import init_paged_caches
from repro.serving import runner as runner_mod
from repro.serving.runner import ExecuteInput, ModelRunner

HQ, HKV, HD, PAGE, MAX_PAGES, BLOCKS = 8, 2, 128, 4, 6, 24  # GQA group 4
TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _table(lengths, rng, shared=False):
    """Row r maps ceil(len / PAGE) pages, out of order; zero past them.
    ``shared``: rows 1 and 3 share their first two pages."""
    table = np.zeros((len(lengths), MAX_PAGES), np.int32)
    ids = rng.permutation(np.arange(1, BLOCKS))
    used = 0
    for r, n in enumerate(lengths):
        pages = -(-n // PAGE)
        table[r, :pages] = ids[used:used + pages]
        used += pages
    if shared:
        table[3, :2] = table[1, :2]
    return table


CASES = {
    # 1 position; page boundaries (4, 8); a partial last page; a full
    # table; two pages per block
    "ragged": dict(lengths=[1, 4, 8, 13, 24], ppb=2, dtype=jnp.bfloat16),
    # idle rows first, between and last; pages out of order and shared;
    # blocks of three pages, the last one partly read
    "idle_shared": dict(lengths=[0, 9, 0, 16, 5, 0], ppb=3, shared=True,
                        dtype=jnp.bfloat16),
    # every page of a row in one block; float32: summation order alone
    "one_block": dict(lengths=[6, 23, 0, 1], ppb=8, dtype=jnp.float32),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_gather(case):
    spec = CASES[case]
    dtype = spec["dtype"]
    rng = np.random.default_rng(sorted(CASES).index(case))
    lengths = jnp.asarray(spec["lengths"], jnp.int32)
    table = jnp.asarray(_table(spec["lengths"], rng, spec.get("shared")))
    b = len(spec["lengths"])
    k = jnp.asarray(rng.standard_normal((BLOCKS, PAGE, HKV, HD)), dtype)
    v = jnp.asarray(rng.standard_normal((BLOCKS, PAGE, HKV, HD)), dtype)
    q = jnp.asarray(rng.standard_normal((b, HQ, HD)) * HD ** -0.5, dtype)
    got = paged_decode_attention(q, k, v, lengths, table,
                                 ops.next_live_rows(lengths),
                                 pages_per_block=spec["ppb"], interpret=True)
    want = paged_decode_attention_ref(q, k, v, lengths, table)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert not got[np.asarray(lengths) == 0].any(), "idle rows read nothing"
    np.testing.assert_allclose(got, want, **TOL[dtype], err_msg=case)


def test_next_live_rows():
    lengths = jnp.asarray([0, 3, 0, 0, 11, 0], jnp.int32)
    assert ops.next_live_rows(lengths).tolist() == [1, 1, 4, 4, 4, 6, 6]


def test_supports_the_cells_pool_only():
    """bf16, hd a multiple of 128, kv heads a multiple of the pool's
    8-row tile: what reads in place on the chip."""
    assert ops.supports(128, 8, jnp.bfloat16)
    assert not ops.supports(128, 8, jnp.float32)
    assert not ops.supports(64, 8, jnp.bfloat16)
    assert not ops.supports(128, 4, jnp.bfloat16)


def _engage(monkeypatch):
    """Take the kernel path off the chip (interpret mode), as the chip
    takes it: the predicate is what a test may steer."""
    monkeypatch.setattr(attention, "paged_kernel_engages", lambda *a: True)
    monkeypatch.setattr(runner_mod, "paged_kernel_engages", lambda *a: True)


def _tiny():
    # float32, so the two paths differ by summation order alone
    return dataclasses.replace(reduced(get_config("qwen3-4b")),
                               dtype=jnp.float32)


def test_decode_step_kernel_path_matches_gather(monkeypatch):
    """``attn_decode_paged`` on the kernel path: the same logits and the
    same pool as the gather path, for ragged rows and an idle one (its
    first table entry the scratch block; its logits are discarded)."""
    cfg = _tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    caches = jax.tree.map(
        lambda x: jnp.asarray(rng.standard_normal(x.shape), x.dtype),
        init_paged_caches(cfg, 4, BLOCKS, PAGE))
    pos = jnp.asarray([5, 0, 12, 3], jnp.int32)
    table = jnp.asarray(_table([6, 0, 13, 4], rng))
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 1)), jnp.int32)

    def step(caches):
        return decode_step(params, cfg, tok, caches, pos, page_table=table,
                           page_size=PAGE, kv_len=MAX_PAGES * PAGE)

    want, want_caches = jax.jit(step)(caches)
    assert not attention.paged_kernel_engages(cfg, cfg.dtype)
    _engage(monkeypatch)
    got, got_caches = jax.jit(step)(caches)
    live = np.asarray(table[:, 0] > 0)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=1e-4, atol=1e-4)
    for g, w in zip(jax.tree.leaves(got_caches), jax.tree.leaves(want_caches)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_runner_counts_kernel_steps_and_pages(monkeypatch):
    """The runner counts each decode dispatch whose attention takes the
    kernel and the pages it reads: ceil((pos + 1) / page_size) over rows
    with a mapped first page; a mixed step counts its live rows only."""
    cfg = _tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    _engage(monkeypatch)
    runner = ModelRunner(params, cfg, max_len=MAX_PAGES * PAGE, num_slots=4,
                         page_size=PAGE, num_pages=BLOCKS)
    # the compiled step is not what is counted: a stand-in returns the pool
    runner._step = lambda params, data, *rest: (
        jnp.zeros((4,), jnp.int32), data)
    for slot, pos in ((0, 4), (2, 13)):  # 2 and 4 pages
        runner.set_slot(slot, token=1, pos=pos, temperature=0.0, top_k=0,
                        seed=0)
        for p in range(pos + 1):
            runner.ensure_mapped(slot, p)
    runner.execute(ExecuteInput("decode", slots=(0, 2)))
    assert (runner.stats.attn_kernel_steps,
            runner.stats.kv_pages_walked) == (1, 2 + 4)
    # positions advanced to 5 and 14: 2 and 4 pages; mixed: slot 2 only
    runner.ensure_mapped(2, 14)
    runner.execute(ExecuteInput("mixed", slots=(2,)))
    assert (runner.stats.attn_kernel_steps,
            runner.stats.kv_pages_walked) == (2, 6 + 4)
