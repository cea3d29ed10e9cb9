"""LM assembly: embedding -> scan over layer *periods* -> norm -> head.

``cfg.pattern`` is a period of (mixer, ffn) slots; the layer stack is
``num_periods`` repetitions, scanned with stacked parameters so the HLO holds
ONE period body regardless of depth (essential for 80-layer dry-run compiles).
Every linear goes through the factorization registry with a per-site policy
(``cfg.fact``) — the paper's butterfly/pixelfly compression, mixed per
call-site, is a config flag away for any architecture.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.factorized import Linear
from repro.models import attention, moe as moe_lib, ssm, xlstm
from repro.models.layers import init_embedding, init_rms_norm, rms_norm
from repro.models.mlp import init_mlp, mlp_forward
from repro.parallel import context as pctx

NEG_INF = -1e30


# ------------------------------------------------------------- init ------


def _head_linear(cfg: ModelConfig) -> Linear:
    return Linear(cfg.fact, cfg.d_model, cfg.padded_vocab, site="head",
                  dtype=cfg.param_dtype)


def _init_slot(key: jax.Array, cfg: ModelConfig, mixer: str, ffn: str) -> dict:
    k1, k2 = jax.random.split(key)
    p: dict[str, Any] = {"norm1": init_rms_norm(cfg.d_model, cfg.param_dtype)}
    if mixer == "attn":
        p["mixer"] = attention.init_attn(k1, cfg)
    elif mixer == "mamba":
        p["mixer"] = ssm.init_mamba(k1, cfg)
    elif mixer == "mlstm":
        p["mixer"] = xlstm.init_mlstm(k1, cfg)
    elif mixer == "slstm":
        p["mixer"] = xlstm.init_slstm(k1, cfg)
    else:
        raise ValueError(mixer)
    if ffn != "none":
        p["norm2"] = init_rms_norm(cfg.d_model, cfg.param_dtype)
        p["ffn"] = (moe_lib.init_moe(k2, cfg) if ffn == "moe"
                    else init_mlp(k2, cfg))
    return p


def _init_period(key: jax.Array, cfg: ModelConfig) -> dict:
    keys = jax.random.split(key, len(cfg.pattern))
    return {
        f"slot{i}": _init_slot(keys[i], cfg, m, f)
        for i, (m, f) in enumerate(cfg.pattern)
    }


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    ke, kp, kh = jax.random.split(key, 3)
    pkeys = jax.random.split(kp, cfg.num_periods)
    params: dict[str, Any] = {
        "periods": jax.vmap(lambda k: _init_period(k, cfg))(pkeys),
        "final_norm": init_rms_norm(cfg.d_model, cfg.param_dtype),
        "head": _head_linear(cfg).init(kh),
    }
    if cfg.input_mode == "tokens":
        params["embed"] = init_embedding(ke, cfg.padded_vocab, cfg.d_model,
                                         cfg.param_dtype)
    return params


def param_count(cfg: ModelConfig) -> int:
    shapes = jax.eval_shape(functools.partial(init_params, cfg),
                            jax.random.PRNGKey(0))
    return sum(int(jnp.prod(jnp.asarray(x.shape))) if x.shape else 1
               for x in jax.tree.leaves(shapes))


# ------------------------------------------------------------ forward ----


def _slot_forward(p: dict, cfg: ModelConfig, mixer: str, ffn: str,
                  x: jax.Array, positions: jax.Array):
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if mixer == "attn":
        h, cache = attention.attn_forward(p["mixer"], cfg, h, positions)
    elif mixer == "mamba":
        h, cache = ssm.mamba_forward(p["mixer"], cfg, h)
    elif mixer == "mlstm":
        h, cache = xlstm.mlstm_forward(p["mixer"], cfg, h)
    elif mixer == "slstm":
        h, cache = xlstm.slstm_forward(p["mixer"], cfg, h)
    x = x + h
    if ffn != "none":
        g = rms_norm(x, p["norm2"], cfg.norm_eps)
        g = (moe_lib.moe_forward(p["ffn"], cfg, g) if ffn == "moe"
             else mlp_forward(p["ffn"], cfg, g))
        x = x + g
    return x, cache


def _slot_decode(p: dict, cfg: ModelConfig, mixer: str, ffn: str,
                 x: jax.Array, cache: dict, pos: jax.Array,
                 page_table: jax.Array | None = None,
                 page_size: int | None = None,
                 kv_len: int | None = None):
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if mixer == "attn":
        if page_table is not None:
            h, nc = attention.attn_decode_paged(
                p["mixer"], cfg, h, cache, page_table, pos, page_size, kv_len)
        else:
            h, nc = attention.attn_decode(p["mixer"], cfg, h, cache, pos)
    elif mixer == "mamba":
        h, nc = ssm.mamba_decode(p["mixer"], cfg, h, cache, pos)
    elif mixer == "mlstm":
        h, nc = xlstm.mlstm_decode(p["mixer"], cfg, h, cache, pos)
    elif mixer == "slstm":
        h, nc = xlstm.slstm_decode(p["mixer"], cfg, h, cache, pos)
    x = x + h
    if ffn != "none":
        g = rms_norm(x, p["norm2"], cfg.norm_eps)
        g = (moe_lib.moe_forward(p["ffn"], cfg, g) if ffn == "moe"
             else mlp_forward(p["ffn"], cfg, g))
        x = x + g
    return x, nc


def cast_params(params, dtype):
    """Cast floating-point params to the compute dtype (bf16 matmuls on TPU);
    norm scales stay f32 inside rms_norm, which upcasts internally.  Leaves
    already in ``dtype`` pass through untouched, so the call is free for
    serving weights, which are held in the compute dtype from load."""
    def cast(x):
        return x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x
    return jax.tree.map(cast, params)


def _embed_inputs(params: dict, cfg: ModelConfig, inputs: jax.Array) -> jax.Array:
    if cfg.input_mode == "tokens":
        tok = jnp.clip(inputs, 0, cfg.vocab_size - 1)
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tok, axis=0)
    else:
        x = inputs  # precomputed frontend embeddings (B, S, d)
    return x.astype(cfg.dtype)


def forward(params: dict, cfg: ModelConfig, inputs: jax.Array,
            positions: jax.Array | None = None,
            return_caches: bool = False):
    """Full-sequence forward.  inputs: (B, S) tokens or (B, S, d) embeddings.

    Returns logits (B, S, padded_vocab) [+ caches stacked (P, ...)].
    """
    b, s = inputs.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        if cfg.mrope:
            positions = jnp.broadcast_to(positions[..., None], (b, s, 3))
    params = cast_params(params, cfg.dtype)
    x = _embed_inputs(params, cfg, inputs)
    # sequence-parallel residual stream: (B, S, d) sharded (dp, tp, -) between
    # blocks; GSPMD all-gathers S at attention and reduce-scatters after.
    x = pctx.constrain(x, "dp", "tp", None)

    def period_body(x, pp):
        def inner(x):
            caches = []
            for i, (m, f) in enumerate(cfg.pattern):
                x, cache = _slot_forward(pp[f"slot{i}"], cfg, m, f, x, positions)
                x = pctx.constrain(x, "dp", "tp", None)
                caches.append(cache)
            return x, tuple(caches)
        if cfg.remat:
            inner = jax.checkpoint(inner)
        x, caches = inner(x)
        return x, caches

    with jax.named_scope("layers"):
        x, caches = jax.lax.scan(period_body, x, params["periods"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _head_linear(cfg)(params["head"], x)
    if return_caches:
        return logits, caches
    return logits


def decode_step(params: dict, cfg: ModelConfig, inputs: jax.Array,
                caches, pos: jax.Array,
                page_table: jax.Array | None = None,
                page_size: int | None = None,
                kv_len: int | None = None):
    """One decode step.  inputs: (B, 1) tokens or (B, 1, d) embeddings;
    caches: pytree stacked over periods; pos: (B,) int32.
    Returns (logits (B, 1, padded_vocab), new caches).

    With ``page_table`` (B, max_pages) the attention caches are read as a
    paged block pool (``init_paged_caches`` layout, (P, num_blocks,
    page_size, Hkv, hd) leaves) instead of dense (P, B, T, ...) stripes;
    ``page_size``/``kv_len`` are the static block width and gather width
    (the engine's max_len).  Recurrent/conv state is O(1) per sequence and
    stays slot-indexed in both layouts.
    """
    params = cast_params(params, cfg.dtype)
    x = _embed_inputs(params, cfg, inputs)
    # decode is batch(=slot)-parallel over "dp"; S = 1, so no sequence
    # sharding — heads split over "tp" inside the mixers (attention.py)
    x = pctx.constrain(x, "dp", None, None)

    def period_body(x, inp):
        pp, pcaches = inp
        new = []
        for i, (m, f) in enumerate(cfg.pattern):
            x, nc = _slot_decode(pp[f"slot{i}"], cfg, m, f, x, pcaches[i], pos,
                                 page_table, page_size, kv_len)
            x = pctx.constrain(x, "dp", None, None)
            new.append(nc)
        return x, tuple(new)

    # the "layers" scope holds the scan's own work — per-layer slices of the
    # stacked weights and caches, the stacked caches it writes out — and
    # whatever in a layer no inner scope (fact.*, dense.*, attn.*, norm)
    # claims, such as the residual adds
    with jax.named_scope("layers"):
        x, new_caches = jax.lax.scan(period_body, x,
                                     (params["periods"], caches))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _head_linear(cfg)(params["head"], x)
    return logits, new_caches


def prefill(params: dict, cfg: ModelConfig, inputs: jax.Array, max_len: int,
            lengths: jax.Array | None = None):
    """Single-dispatch batched prefill: ONE full-sequence forward that returns
    logits plus decode-ready caches shaped exactly like
    ``init_caches(cfg, B, max_len)``.

    inputs: (B, S) tokens or (B, S, d) embeddings with S <= max_len.
    lengths: (B,) true prompt lengths for right-padded ragged batches
    (default: every row is full length S).  Attention K/V are zero-padded to
    ``max_len`` and zeroed beyond each row's true length — positions past a
    row's live length are never read (the causal/prefix masks hide them),
    and decode overwrites them in place when the row grows.

    Ragged lengths (any row shorter than S) are only exact for pure-attention
    patterns: recurrent mixers (mamba/xlstm) fold right-pad tokens into their
    O(1) state, so callers must batch those by equal length instead.

    S may exceed ``max_len`` only for ragged batches (the engine's pow2
    width buckets can round past a non-pow2 max_len): every true length
    must still be <= max_len, and the decode-ready K/V are sliced back to
    ``max_len`` — the extra columns are all dummy padding.
    """
    b, s = inputs.shape[:2]
    if s > max_len:
        if lengths is None:
            raise ValueError(f"prompt length {s} exceeds max_len {max_len}")
        # beyond-max_len columns are sliced away as dummy padding, so a
        # TRUE length past max_len would be silently truncated — reject it
        # whenever lengths are concrete (the engine always satisfies this;
        # under jit the caller owns the contract)
        if not isinstance(lengths, jax.core.Tracer) and \
                int(jnp.max(lengths)) > max_len:
            raise ValueError(
                f"ragged length {int(jnp.max(lengths))} exceeds max_len "
                f"{max_len}; only dummy pad columns may extend past it")
    ragged = lengths is not None
    if ragged and any(m != "attn" for m, _ in cfg.pattern):
        raise ValueError(
            f"{cfg.name}: ragged prefill needs a pure-attention pattern; "
            "recurrent state would absorb pad tokens — group by length instead")
    logits, caches = forward(params, cfg, inputs, return_caches=True)
    valid = None
    if ragged:
        valid = (jnp.arange(s)[None, :] < lengths[:, None])  # (B, S)

    fixed = []
    for i, (m, _) in enumerate(cfg.pattern):
        c = caches[i]
        if m == "attn":
            k, v = c["k"], c["v"]  # (P, B, S, hkv, hd)
            if valid is not None:
                mask = valid[None, :, :, None, None].astype(k.dtype)
                k, v = k * mask, v * mask
            if s >= max_len:  # bucketed width past max_len: drop dummy cols
                k, v = k[:, :, :max_len], v[:, :, :max_len]
            pad = [(0, 0), (0, 0), (0, max(0, max_len - s)), (0, 0), (0, 0)]
            # decode-ready layout: batch(=slot) over "dp", sequence over
            # "tp" — matches partition_caches, so a mesh engine's cache
            # insert needs no reshard
            c = {"k": pctx.constrain(jnp.pad(k, pad), None, "dp", "tp",
                                     None, None),
                 "v": pctx.constrain(jnp.pad(v, pad), None, "dp", "tp",
                                     None, None)}
        elif m == "mamba" and c["conv"].shape[2] < cfg.mamba_dconv - 1:
            # prompts shorter than the conv window leave a short tail;
            # left-pad with zeros = the init (nothing-seen) window state
            short = cfg.mamba_dconv - 1 - c["conv"].shape[2]
            c = {**c, "conv": jnp.pad(
                c["conv"], [(0, 0), (0, 0), (short, 0), (0, 0)])}
        fixed.append(c)
    return logits, tuple(fixed)


def prefill_with_prefix(params: dict, cfg: ModelConfig, inputs: jax.Array,
                        paged_caches, page_tables: jax.Array,
                        prefix_lens: jax.Array):
    """Tail prefill: forward ONLY the unmatched tail of each prompt,
    attending to the matched prefix K/V already resident in the paged
    block pool — the prefix-cache fast path that turns a long shared
    system prompt into a near-decode-latency dispatch.  Chunked prefill
    rides the same contract: a chunk's "prefix" is the sequence's earlier
    chunks (pool pages written by prior steps), and ``prefix_lens == 0``
    — chunk 0, nothing resident yet — is a supported degenerate case (the
    gathered scratch view is fully masked, see the validity note below).

    inputs: (B, S_tail) right-padded tail tokens; paged_caches: the pool
    pytree (``init_paged_caches`` layout, attention leaves (P, num_blocks,
    page_size, Hkv, hd)); page_tables: (B, NP) int32 block ids covering
    each row's matched prefix (scratch-0 padded past it); prefix_lens:
    (B,) matched token counts — tail token t of row b sits at absolute
    position ``prefix_lens[b] + t``.

    Returns (logits (B, S_tail, padded_vocab), per-period ``{"k", "v"}``
    tail caches (P, B, S_tail, Hkv, hd)) for
    ``PagedSlotCache.write_tails``.  Validity masking matches the full
    prefill exactly (NEG_INF scores contribute exact zeros), so tail
    logits — and therefore every sampled token — are bit-identical to an
    uncached forward over the whole prompt.

    Pure-attention patterns only: recurrent mixers would need their O(1)
    state replayed through the prefix, which the pool does not hold.
    """
    if any(m != "attn" for m, _ in cfg.pattern):
        raise ValueError(
            f"{cfg.name}: prefix-cached prefill needs a pure-attention "
            "pattern; recurrent state cannot be recovered from the pool")
    b, s = inputs.shape[:2]
    positions = prefix_lens[:, None] + jnp.arange(s)[None]  # (B, S)
    if cfg.mrope:
        positions = jnp.broadcast_to(positions[..., None], (b, s, 3))
    params = cast_params(params, cfg.dtype)
    x = _embed_inputs(params, cfg, inputs)
    x = pctx.constrain(x, "dp", None, None)

    def period_body(x, inp):
        pp, pcaches = inp
        tails = []
        for i, (m, f) in enumerate(cfg.pattern):
            p = pp[f"slot{i}"]
            h = rms_norm(x, p["norm1"], cfg.norm_eps)
            h, kv = attention.attn_prefill_paged_past(
                p["mixer"], cfg, h, pcaches[i], page_tables, prefix_lens,
                positions)
            x = x + h
            if f != "none":
                g = rms_norm(x, p["norm2"], cfg.norm_eps)
                g = (moe_lib.moe_forward(p["ffn"], cfg, g) if f == "moe"
                     else mlp_forward(p["ffn"], cfg, g))
                x = x + g
            x = pctx.constrain(x, "dp", None, None)
            tails.append(kv)
        return x, tuple(tails)

    with jax.named_scope("layers"):
        x, tails = jax.lax.scan(period_body, x,
                                (params["periods"], paged_caches))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _head_linear(cfg)(params["head"], x)
    return logits, tails


def prefill_with_past(params: dict, cfg: ModelConfig, inputs: jax.Array,
                      caches, prefix_lens: jax.Array):
    """Tail prefill against fixed-stripe decode caches: the fixed-slot
    analogue of :func:`prefill_with_prefix`, used by speculative verify on
    engines without a paged pool.

    inputs: (B, S_tail) right-padded tail tokens; caches: decode caches in
    the ``init_caches(cfg, B, max_len)`` layout (attention leaves (P, B,
    max_len, Hkv, hd)); prefix_lens: (B,) committed token counts — each
    row's stripe is valid through ``prefix_lens[b]`` and masked beyond it,
    so stale positions (zeros or a rejected speculative tail) contribute
    exactly nothing.  Tail token t of row b sits at absolute position
    ``prefix_lens[b] + t``.

    Returns (logits (B, S_tail, padded_vocab), per-period ``{"k", "v"}``
    tail caches (P, B, S_tail, Hkv, hd)) for ``SlotCache.write_tails``.
    The attention core is shared with the paged path, so tail logits are
    bit-identical to it — and to an uncached forward over the full history.

    Pure-attention patterns only, for the same reason as the paged path.
    """
    if any(m != "attn" for m, _ in cfg.pattern):
        raise ValueError(
            f"{cfg.name}: past-prefill needs a pure-attention pattern; "
            "recurrent state cannot be recovered from the cache stripes")
    b, s = inputs.shape[:2]
    positions = prefix_lens[:, None] + jnp.arange(s)[None]  # (B, S)
    if cfg.mrope:
        positions = jnp.broadcast_to(positions[..., None], (b, s, 3))
    params = cast_params(params, cfg.dtype)
    x = _embed_inputs(params, cfg, inputs)
    x = pctx.constrain(x, "dp", None, None)

    def period_body(x, inp):
        pp, pcaches = inp
        tails = []
        for i, (m, f) in enumerate(cfg.pattern):
            p = pp[f"slot{i}"]
            h = rms_norm(x, p["norm1"], cfg.norm_eps)
            h, kv = attention.attn_prefill_dense_past(
                p["mixer"], cfg, h, pcaches[i], prefix_lens, positions)
            x = x + h
            if f != "none":
                g = rms_norm(x, p["norm2"], cfg.norm_eps)
                g = (moe_lib.moe_forward(p["ffn"], cfg, g) if f == "moe"
                     else mlp_forward(p["ffn"], cfg, g))
                x = x + g
            x = pctx.constrain(x, "dp", None, None)
            tails.append(kv)
        return x, tuple(tails)

    with jax.named_scope("layers"):
        x, tails = jax.lax.scan(period_body, x, (params["periods"], caches))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _head_linear(cfg)(params["head"], x)
    return logits, tails


def init_caches(cfg: ModelConfig, batch: int, max_len: int):
    """Decode caches for the whole stack, stacked over periods."""
    def one_period():
        caches = []
        for m, _ in cfg.pattern:
            if m == "attn":
                caches.append(attention.init_attn_cache(cfg, batch, max_len))
            elif m == "mamba":
                caches.append(ssm.init_mamba_cache(cfg, batch))
            elif m == "mlstm":
                caches.append(xlstm.init_mlstm_cache(cfg, batch))
            elif m == "slstm":
                caches.append(xlstm.init_slstm_cache(cfg, batch))
        return tuple(caches)

    one = one_period()
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.num_periods,) + x.shape), one)


def init_paged_caches(cfg: ModelConfig, num_slots: int, num_blocks: int,
                      page_size: int):
    """Decode caches with paged attention K/V: attn leaves are a global
    block pool (num_blocks, page_size, Hkv, hd) shared by all slots and
    indexed by a per-slot page table (block 0 is the reserved scratch
    block); recurrent/conv leaves stay slot-indexed exactly as in
    :func:`init_caches` — their state is O(1) per sequence, so paging them
    would buy nothing.  Stacked over periods like ``init_caches``."""

    def one_period():
        caches = []
        for m, _ in cfg.pattern:
            if m == "attn":
                caches.append(attention.init_paged_attn_cache(
                    cfg, num_blocks, page_size))
            elif m == "mamba":
                caches.append(ssm.init_mamba_cache(cfg, num_slots))
            elif m == "mlstm":
                caches.append(xlstm.init_mlstm_cache(cfg, num_slots))
            elif m == "slstm":
                caches.append(xlstm.init_slstm_cache(cfg, num_slots))
        return tuple(caches)

    one = one_period()
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.num_periods,) + x.shape), one)


# ------------------------------------------------------------- loss ------


def lm_loss(params: dict, cfg: ModelConfig, inputs: jax.Array,
            labels: jax.Array, positions: jax.Array | None = None) -> jax.Array:
    """Mean next-token cross-entropy (+ z-loss), pad-vocab masked."""
    logits = forward(params, cfg, inputs, positions)
    logits = logits.astype(jnp.float32)
    vp = cfg.padded_vocab
    if vp != cfg.vocab_size:
        pad_mask = jnp.arange(vp) >= cfg.vocab_size
        logits = jnp.where(pad_mask[None, None, :], NEG_INF, logits)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = (lse - gold).mean()
    if cfg.z_loss:
        ce = ce + cfg.z_loss * (lse ** 2).mean()
    return ce
