"""GQA attention: fused (factorizable) QKV, RoPE/M-RoPE, qk-norm, chunked
flash-style training attention, and KV-cache decode.

Compiled programs carry named scopes for the parts of attention a device
trace should tell apart: ``attn.rope``, ``attn.kv_write`` (new K/V into the
cache or pool), ``attn.kv_gather`` (a paged view gathered from the pool)
and ``attn.core`` (scores, mask, softmax, values).  The projections carry
their own (``Linear``: ``fact.<kind>.<site>`` / ``dense.<site>``)."""
from __future__ import annotations

from typing import Any

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.factorized import Linear
from repro.models.layers import apply_mrope, apply_rope, init_rms_norm, rms_norm
from repro.parallel import context as pctx

NEG_INF = -1e30


def _linears(cfg: ModelConfig):
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    qkv_out = (hq + 2 * hkv) * hd
    qkv = Linear(cfg.fact, cfg.d_model, qkv_out, site="attn_qkv",
                 bias=cfg.qkv_bias, dtype=cfg.param_dtype)
    out = Linear(cfg.fact, hq * hd, cfg.d_model, site="attn_out",
                 bias=False, dtype=cfg.param_dtype)
    return qkv, out


def init_attn(key: jax.Array, cfg: ModelConfig) -> dict:
    qkv, out = _linears(cfg)
    k1, k2 = jax.random.split(key)
    params = {"qkv": qkv.init(k1), "out": out.init(k2)}
    if cfg.qk_norm:
        params["q_norm"] = init_rms_norm(cfg.hd, cfg.param_dtype)
        params["k_norm"] = init_rms_norm(cfg.hd, cfg.param_dtype)
    return params


def _project_qkv(params, cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    """x: (B, S, d) -> q (B,S,Hq,hd), k,v (B,S,Hkv,hd), roped + normed."""
    b, s, _ = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    qkv_lin, _ = _linears(cfg)
    qkv = qkv_lin(params["qkv"], x)  # (B, S, (hq+2hkv)*hd)
    q, k, v = jnp.split(qkv, [hq * hd, (hq + hkv) * hd], axis=-1)
    # head-parallel over "tp" (Megatron); kv heads fall back to replicated
    # when fewer than the tp degree (constrain() guards divisibility)
    q = pctx.constrain(q.reshape(b, s, hq, hd), "dp", None, "tp", None)
    k = pctx.constrain(k.reshape(b, s, hkv, hd), "dp", None, "tp", None)
    v = pctx.constrain(v.reshape(b, s, hkv, hd), "dp", None, "tp", None)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    with jax.named_scope("attn.rope"):
        if cfg.mrope:
            q = apply_mrope(q, positions, cfg.rope_theta)
            k = apply_mrope(k, positions, cfg.rope_theta)
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _direct_attention(q, k, v):
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qh = (q * hd ** -0.5).reshape(b, s, hkv, g, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qh.astype(jnp.float32),
                        k.astype(jnp.float32))
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return o.reshape(b, s, hq, hd).astype(q.dtype)


def _flash_forward(q, k, v, chunk: int):
    """Returns (o, lse).  Never materializes (S, S); memory per step is the
    (B,kv,g,S,chunk) score tile."""
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qh = (q * hd ** -0.5).reshape(b, s, hkv, g, hd)
    nch = s // chunk
    kc = k.reshape(b, nch, chunk, hkv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nch, chunk, hkv, hd).transpose(1, 0, 2, 3, 4)
    q_pos = jnp.arange(s)

    def body(carry, inp):
        m, l, acc = carry  # (B,kv,g,S), (B,kv,g,S), (B,S,kv,g,hd)
        i, (kb, vb) = inp
        scores = jnp.einsum("bqkgh,bckh->bkgqc", qh.astype(jnp.float32),
                            kb.astype(jnp.float32))  # (B,kv,g,S,chunk)
        kv_pos = i * chunk + jnp.arange(chunk)
        mask = q_pos[:, None] >= kv_pos[None, :]
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha.transpose(0, 3, 1, 2)[..., None] + jnp.einsum(
            "bkgqc,bckh->bqkgh", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, s), jnp.float32)
    acc0 = jnp.zeros((b, s, hkv, g, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (jnp.arange(nch), (kc, vc)))
    o = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    lse = m + jnp.log(jnp.maximum(l, 1e-30))  # (B,kv,g,S)
    return o.reshape(b, s, hq, hd).astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_attention(q, k, v, chunk: int):
    return _flash_forward(q, k, v, chunk)[0]


def _flash_fwd_rule(q, k, v, chunk: int):
    o, lse = _flash_forward(q, k, v, chunk)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(chunk: int, res, do):
    """FlashAttention backward: recompute score tiles per kv chunk from the
    saved lse — residuals are O(S) (q, k, v, o, lse), never per-chunk
    accumulators (which an autodiff'd scan would stash: ~nch x acc bytes)."""
    q, k, v, o, lse = res
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    scale = hd ** -0.5
    qh = (q * scale).reshape(b, s, hkv, g, hd).astype(jnp.float32)
    doh = do.reshape(b, s, hkv, g, hd).astype(jnp.float32)
    # D_i = rowsum(do * o)
    dsum = jnp.einsum("bqkgh,bqkgh->bkgq", doh,
                      o.reshape(b, s, hkv, g, hd).astype(jnp.float32))
    nch = s // chunk
    kc = k.reshape(b, nch, chunk, hkv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nch, chunk, hkv, hd).transpose(1, 0, 2, 3, 4)
    q_pos = jnp.arange(s)

    def body(dq_acc, inp):
        i, (kb, vb) = inp
        scores = jnp.einsum("bqkgh,bckh->bkgqc", qh, kb.astype(jnp.float32))
        kv_pos = i * chunk + jnp.arange(chunk)
        mask = q_pos[:, None] >= kv_pos[None, :]
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        p = jnp.exp(scores - lse[..., None])  # (B,kv,g,S,chunk)
        dv = jnp.einsum("bkgqc,bqkgh->bckh", p, doh)
        dp = jnp.einsum("bqkgh,bckh->bkgqc", doh, vb.astype(jnp.float32))
        ds = p * (dp - dsum[..., None])
        dk = jnp.einsum("bkgqc,bqkgh->bckh", ds, qh) * 1.0
        dq_acc = dq_acc + jnp.einsum("bkgqc,bckh->bqkgh", ds,
                                     kb.astype(jnp.float32))
        return dq_acc, (dk, dv)

    dq0 = jnp.zeros((b, s, hkv, g, hd), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(body, dq0, (jnp.arange(nch), (kc, vc)))
    dq = (dq * scale).reshape(b, s, hq, hd).astype(q.dtype)
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(b, s, hkv, hd).astype(k.dtype)
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(b, s, hkv, hd).astype(v.dtype)
    return dq, dk, dv


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def chunked_causal_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, chunk: int
) -> jax.Array:
    """Flash-style causal attention: scan over KV chunks with running
    (max, sum, acc) so the (S, S) score matrix is never materialized;
    custom VJP keeps backward residuals at O(S) (FlashAttention-2 style).

    q: (B, S, Hq, hd); k, v: (B, S, Hkv, hd) with Hq % Hkv == 0.
    """
    s = q.shape[1]
    with jax.named_scope("attn.core"):
        if s <= 2 * chunk:  # small enough: direct masked attention
            return _direct_attention(q, k, v)
        assert s % chunk == 0, (s, chunk)
        return _flash_attention(q, k, v, chunk)


def attn_forward(
    params: dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array
) -> tuple[jax.Array, dict]:
    """Training/prefill forward.  Returns (out (B,S,d), cache {k, v})."""
    q, k, v = _project_qkv(params, cfg, x, positions)
    o = chunked_causal_attention(q, k, v, cfg.attn_chunk)
    o = pctx.constrain(o, "dp", None, "tp", None)
    _, out_lin = _linears(cfg)
    y = out_lin(params["out"], o.reshape(*x.shape[:2], -1))
    cache = {"k": k.astype(cfg.dtype), "v": v.astype(cfg.dtype)}
    return y, cache


def _decode_attention(params: dict, cfg: ModelConfig, q: jax.Array,
                      k: jax.Array, v: jax.Array, pos: jax.Array,
                      out_dtype) -> jax.Array:
    """Shared single-token attention core: q (B, 1, Hq, hd) against a dense
    K/V view (B, T, Hkv, hd) with causal validity ``t <= pos``, followed by
    the output projection.  Both the fixed-stripe and paged decode paths
    end here — bit-exact parity between them depends on this being the ONE
    place the decode attention math lives."""
    b = q.shape[0]
    t = k.shape[1]
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    g = hq // hkv
    with jax.named_scope("attn.core"):
        qh = (q * hd ** -0.5).reshape(b, 1, hkv, g, hd)
        scores = jnp.einsum("bqkgh,btkh->bkgqt", qh.astype(jnp.float32),
                            k.astype(jnp.float32))  # (B,kv,g,1,T)
        valid = jnp.arange(t)[None, :] <= pos[:, None]  # (B, T)
        scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bkgqt,btkh->bqkgh", p, v.astype(jnp.float32))
        o = o.reshape(b, 1, hq * hd).astype(out_dtype)
    _, out_lin = _linears(cfg)
    return out_lin(params["out"], o)


def attn_decode(
    params: dict, cfg: ModelConfig, x: jax.Array, cache: dict, pos: jax.Array
) -> tuple[jax.Array, dict]:
    """Single-token decode.  x: (B, 1, d); cache k/v: (B, T, Hkv, hd);
    pos: (B,) current position (tokens written at cache[pos]).

    The write is a scatter-set, not an add: it overwrites whatever the
    cache holds at ``pos``, so stale K/V past a row's live length (e.g. a
    rejected speculative tail) is harmless — the causal mask already hides
    it from reads, and the next write at that position replaces it."""
    b = x.shape[0]
    positions = pos[:, None]  # (B, 1)
    if cfg.mrope:
        positions = jnp.broadcast_to(positions[..., None], (b, 1, 3))
    q, k_new, v_new = _project_qkv(params, cfg, x, positions)

    rows = jnp.arange(b)
    with jax.named_scope("attn.kv_write"):
        k = cache["k"].at[rows, pos].set(
            k_new[:, 0].astype(cache["k"].dtype), mode="drop")
        v = cache["v"].at[rows, pos].set(
            v_new[:, 0].astype(cache["v"].dtype), mode="drop")

    y = _decode_attention(params, cfg, q, k, v, pos, x.dtype)
    return y, {"k": k, "v": v}


def attn_decode_paged(
    params: dict, cfg: ModelConfig, x: jax.Array, cache: dict,
    page_table: jax.Array, pos: jax.Array, page_size: int, kv_len: int
) -> tuple[jax.Array, dict]:
    """Single-token decode against a paged KV pool (vLLM-style block table).

    x: (B, 1, d); cache k/v: (num_blocks, page_size, Hkv, hd) — the global
    block pool, where block 0 is the reserved scratch block that unmapped
    page-table entries point at; page_table: (B, max_pages) int32 physical
    block ids; pos: (B,) position the new token is written at.

    The write scatters one (page_size-row) entry: block
    ``page_table[b, pos // page_size]``, row ``pos % page_size``.  Idle
    decode rows (pos 0, all-zero table row) write the scratch block, which
    no mapped gather ever reads.  The gather pulls each row's pages into a
    dense view sliced to exactly ``kv_len`` positions, so the attention
    math downstream is shape- and bit-identical to :func:`attn_decode` on
    a fixed (B, kv_len) cache: positions beyond ``pos`` may hold stale page
    contents, but the causal validity mask sends them to NEG_INF exactly
    as the fixed path does for its zero-initialized rows.
    """
    b = x.shape[0]
    positions = pos[:, None]  # (B, 1)
    if cfg.mrope:
        positions = jnp.broadcast_to(positions[..., None], (b, 1, 3))
    q, k_new, v_new = _project_qkv(params, cfg, x, positions)

    with jax.named_scope("attn.kv_write"):
        bid = jnp.take_along_axis(
            page_table, (pos // page_size)[:, None], axis=1)[:, 0]  # (B,)
        off = pos % page_size
        k_pool = cache["k"].at[bid, off].set(
            k_new[:, 0].astype(cache["k"].dtype))
        v_pool = cache["v"].at[bid, off].set(
            v_new[:, 0].astype(cache["v"].dtype))

    hkv, hd = cfg.num_kv_heads, cfg.hd
    with jax.named_scope("attn.kv_gather"):
        k = k_pool[page_table].reshape(b, -1, hkv, hd)[:, :kv_len]
        v = v_pool[page_table].reshape(b, -1, hkv, hd)[:, :kv_len]
        k = pctx.constrain(k, "dp", None, None, None)
        v = pctx.constrain(v, "dp", None, None, None)

    y = _decode_attention(params, cfg, q, k, v, pos, x.dtype)
    return y, {"k": k_pool, "v": v_pool}


def attn_prefill_paged_past(
    params: dict, cfg: ModelConfig, x: jax.Array, cache: dict,
    page_table: jax.Array, prefix_lens: jax.Array, positions: jax.Array,
) -> tuple[jax.Array, dict]:
    """Tail prefill attending to a paged prefix plus itself (causal).

    x: (B, S, d) tail hidden states; cache k/v: (num_blocks, page_size,
    Hkv, hd) — the global block pool; page_table: (B, max_prefix_pages)
    int32 block ids covering each row's matched prefix (scratch-0 padded);
    prefix_lens: (B,) valid prefix token counts; positions: (B, S[, 3])
    absolute positions ``prefix_lens[b] + t`` of each tail token.

    The gathered prefix view is masked at ``t < prefix_lens`` and the tail
    block causally at ``t' <= q`` — the same validity set a full prefill
    over the whole prompt sees, with masked scores at NEG_INF contributing
    exactly zero to the softmax, so the tail activations are bit-identical
    to the uncached forward.  ``prefix_lens[b] == 0`` is valid (chunked
    prefill's first chunk): every prefix column masks away and the row
    reduces to plain causal attention over the tail.  A partially-filled
    page at the prefix/tail boundary is also fine — the stale region past
    ``prefix_lens`` is masked, and the fresh tail K/V arrives via the
    concatenation, never double-counted.  Returns (out (B, S, d),
    {"k", "v"} tail K/V (B, S, Hkv, hd)) for the page-table scatter.
    """
    b, s, _ = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q, k, v = _project_qkv(params, cfg, x, positions)

    with jax.named_scope("attn.kv_gather"):
        kp = cache["k"][page_table].reshape(b, -1, hkv, hd)
        vp = cache["v"][page_table].reshape(b, -1, hkv, hd)
        kp = pctx.constrain(kp, "dp", None, None, None)
        vp = pctx.constrain(vp, "dp", None, None, None)
    y = _prefill_past_attention(params, cfg, q, k, v, kp, vp,
                                prefix_lens, x.dtype)
    return y, {"k": k.astype(cfg.dtype), "v": v.astype(cfg.dtype)}


def attn_prefill_dense_past(
    params: dict, cfg: ModelConfig, x: jax.Array, cache: dict,
    prefix_lens: jax.Array, positions: jax.Array,
) -> tuple[jax.Array, dict]:
    """Tail prefill attending to a fixed-stripe prefix plus itself.

    The fixed-slot analogue of :func:`attn_prefill_paged_past`: cache k/v
    are per-slot dense stripes (B, T, Hkv, hd) and the whole stripe plays
    the role of the gathered page view — ``prefix_lens`` masks everything
    at and beyond each row's live length, so stale positions (zeros, or a
    previously rejected speculative tail) contribute exactly nothing.  The
    attention math itself is the shared :func:`_prefill_past_attention`
    core, which is what makes fixed/paged speculative verify bit-identical.
    Returns (out (B, S, d), {"k", "v"} tail K/V (B, S, Hkv, hd)).
    """
    b, s, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions)
    kp = pctx.constrain(cache["k"], "dp", None, None, None)
    vp = pctx.constrain(cache["v"], "dp", None, None, None)
    y = _prefill_past_attention(params, cfg, q, k, v, kp, vp,
                                prefix_lens, x.dtype)
    return y, {"k": k.astype(cfg.dtype), "v": v.astype(cfg.dtype)}


def _prefill_past_attention(params: dict, cfg: ModelConfig, q: jax.Array,
                            k: jax.Array, v: jax.Array, kp: jax.Array,
                            vp: jax.Array, prefix_lens: jax.Array,
                            out_dtype) -> jax.Array:
    """Shared tail-vs-past attention core: tail q/k/v (B, S, ...) against a
    dense past view kp/vp (B, n_pref, Hkv, hd) masked at ``t <
    prefix_lens`` plus the tail itself masked causally.  Both the paged and
    fixed-stripe past-prefill paths end here — bit-exact parity between
    them depends on this being the ONE place the math lives."""
    b, s = q.shape[0], q.shape[1]
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    n_pref = kp.shape[1]
    with jax.named_scope("attn.core"):
        kf = jnp.concatenate([kp, k.astype(kp.dtype)], axis=1)  # (B,T,Hkv,hd)
        vf = jnp.concatenate([vp, v.astype(vp.dtype)], axis=1)

        t = n_pref + s
        g = hq // hkv
        qh = (q * hd ** -0.5).reshape(b, s, hkv, g, hd)
        scores = jnp.einsum("bqkgh,btkh->bkgqt", qh.astype(jnp.float32),
                            kf.astype(jnp.float32))  # (B,kv,g,S,T)
        tpos = jnp.arange(t)
        causal = (tpos[None, :] - n_pref) <= jnp.arange(s)[:, None]  # (S,T)
        valid = jnp.where((tpos < n_pref)[None, None, :],
                          tpos[None, None, :] < prefix_lens[:, None, None],
                          causal[None])  # (B, S, T)
        scores = jnp.where(valid[:, None, None], scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bkgqt,btkh->bqkgh", p, vf.astype(jnp.float32))
        o = o.reshape(b, s, hq * hd).astype(out_dtype)
    _, out_lin = _linears(cfg)
    return out_lin(params["out"], o)


def init_attn_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    hkv, hd = cfg.num_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((batch, max_len, hkv, hd), cfg.dtype),
        "v": jnp.zeros((batch, max_len, hkv, hd), cfg.dtype),
    }


def init_paged_attn_cache(cfg: ModelConfig, num_blocks: int,
                          page_size: int) -> dict:
    """Global K/V block pool shared by all slots (block 0 = scratch)."""
    hkv, hd = cfg.num_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((num_blocks, page_size, hkv, hd), cfg.dtype),
        "v": jnp.zeros((num_blocks, page_size, hkv, hd), cfg.dtype),
    }
