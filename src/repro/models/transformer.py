"""Decoder-only transformer stack (dense / MoE / VLM / hybrid), scan-over-
layers, GQA KV cache, LoRA hooks on W_q/W_k/W_v (paper sec 7.1).

QKV projections are stored 3-D — (d_model, heads, head_dim) — so head
sharding is decided by head-count divisibility, never splitting a head
across the model axis (DESIGN.md sec 5).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.lora import lora_apply
from repro.models import rglru
from repro.models.layers import (attn_decode, attn_direct, attn_prefill,
                                 cache_init,
                                 cache_kv_for_attn, cache_write_prefill,
                                 cache_write_token, cache_write_token_paged,
                                 emb_w, mlp_apply, mlp_init,
                                 paged_attn_decode, rope)
from repro.models.moe import moe_apply, moe_init
from repro.models.param import (Box, dense_init, norm_apply, norm_init,
                                split, stack_boxes)


# ------------------------------------------------------------ attention ----

def attn_init(cfg, key, cross=False):
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    ew = emb_w(cfg)
    dt = cfg.jdtype

    def proj(k, nh):
        p = {"w": Box(jax.random.normal(k, (d, nh, hd), dt) * d ** -0.5,
                      (ew, "kv_heads" if nh == KV and nh != H else "heads",
                       None))}
        if cfg.qkv_bias:
            p["b"] = Box(jnp.zeros((nh, hd), dt), ("heads", None))
        return p

    return {
        "wq": proj(ks[0], H),
        "wk": proj(ks[1], KV),
        "wv": proj(ks[2], KV),
        "wo": {"w": Box(jax.random.normal(ks[3], (H, hd, d), dt)
                        * (H * hd) ** -0.5, ("heads", None, ew))},
    }


def _proj(p, x):
    y = jnp.einsum("bld,dnh->blnh", x, p["w"])
    if "b" in p:
        y = y + p["b"]
    return y


def _lora_heads(xn, lora_layer, tgt, idx, ranks, mode, rank_block, nh, hd):
    delta = lora_apply(xn, lora_layer, tgt, idx, ranks, mode, rank_block)
    if isinstance(delta, float):
        return 0.0
    return delta.reshape(*delta.shape[:-1], nh, hd)


def attn_apply(cfg, p, x, positions, *, lora_layer=None, lora_idx=None,
               lora_ranks=None, lora_mode="bgmv", window=None, causal=True,
               cache=None, decode=False, kv_override=None, write_mask=None,
               block_table=None):
    """Returns (out, new_cache). positions: (B,L) prefill / (B,) decode.
    kv_override: (k, v) precomputed (whisper cross-attention).
    write_mask: (B,) bool — decode rows excluded from the KV write (their
    cache row stays bitwise-untouched; the serving pipeline's frozen/dead
    rows). block_table: (B, W) — decode against the paged cache layout
    (cache leaves are page pools; see layers.cache_write_token_paged)."""
    B = x.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    rb = cfg.lora.rank_block
    q = _proj(p["wq"], x) + _lora_heads(x, lora_layer, "q", lora_idx,
                                        lora_ranks, lora_mode, rb, H, hd)
    if kv_override is None:
        k = _proj(p["wk"], x) + _lora_heads(x, lora_layer, "k", lora_idx,
                                            lora_ranks, lora_mode, rb, KV, hd)
        v = _proj(p["wv"], x) + _lora_heads(x, lora_layer, "v", lora_idx,
                                            lora_ranks, lora_mode, rb, KV, hd)
    else:
        k, v = kv_override
    if cfg.pos == "rope" and kv_override is None:
        pos2d = positions if positions.ndim == 2 else positions[:, None]
        q = rope(q, pos2d, cfg.rope_theta)
        k = rope(k, pos2d, cfg.rope_theta)
    elif cfg.pos == "rope":
        pos2d = positions if positions.ndim == 2 else positions[:, None]
        q = rope(q, pos2d, cfg.rope_theta)

    new_cache = cache
    if decode:
        if kv_override is None and block_table is not None:
            new_cache = cache_write_token_paged(cache, k, v, positions,
                                                block_table,
                                                write_mask=write_mask)
            out = paged_attn_decode(q, new_cache, block_table, positions,
                                    window=window)
        elif kv_override is None:
            new_cache = cache_write_token(cache, k, v, positions,
                                          write_mask=write_mask)
            ck, cv = cache_kv_for_attn(new_cache, cfg.jdtype)
            out = attn_decode(q, ck, cv, new_cache["pos"], positions,
                              window=window)
        else:
            ck, cv = cache_kv_for_attn(cache, cfg.jdtype)
            out = attn_decode(q, ck, cv, cache["pos"],
                              jnp.full((B,), 2 ** 30, jnp.int32))
    else:
        out = attn_prefill(q, k, v, causal=causal, window=window)
        if cache is not None:
            new_cache = cache_write_prefill(cache, k, v, positions)
    y = jnp.einsum("blnh,nhd->bld", out, p["wo"]["w"])
    return y, new_cache


# ---------------------------------------------------------------- blocks ----

def block_init(cfg, key):
    ks = jax.random.split(key, 2)
    p = {
        "norm1": norm_init(cfg.d_model, cfg.jdtype, cfg.norm),
        "attn": attn_init(cfg, ks[0]),
        "norm2": norm_init(cfg.d_model, cfg.jdtype, cfg.norm),
    }
    p["moe" if cfg.moe else "mlp"] = (
        moe_init(cfg, ks[1]) if cfg.moe else mlp_init(cfg, ks[1]))
    return p


def block_apply(cfg, p, x, positions, *, lora_layer, lora_idx, lora_ranks,
                lora_mode, window, cache, decode, group_by_sequence=True,
                write_mask=None, block_table=None):
    """Returns (y, new_cache, aux)."""
    xn = norm_apply(p["norm1"], x, cfg.norm)
    a, new_cache = attn_apply(
        cfg, p["attn"], xn, positions, lora_layer=lora_layer,
        lora_idx=lora_idx, lora_ranks=lora_ranks, lora_mode=lora_mode,
        window=window, cache=cache, decode=decode, write_mask=write_mask,
        block_table=block_table)
    h = x + a
    hn = norm_apply(p["norm2"], h, cfg.norm)
    if cfg.moe:
        amesh = jax.sharding.get_abstract_mesh()
        if cfg.moe_ep and "data" in amesh.axis_names:
            from repro.models.moe_ep import moe_apply_ep
            data_axes = tuple(a for a in ("pod", "data")
                              if a in amesh.axis_names)
            m, aux = moe_apply_ep(cfg, p["moe"], hn, amesh,
                                  data_axes=data_axes)
        else:
            m, aux = moe_apply(cfg, p["moe"], hn,
                               group_by_sequence=group_by_sequence)
    else:
        m, aux = mlp_apply(cfg, p["mlp"], hn), 0.0
    return h + m, new_cache, aux


# ------------------------------------------------------------- top level ----

def init_params(cfg, rng):
    """Box tree for dense/moe/vlm/hybrid decoder-only models."""
    k_emb, k_blocks, k_head = jax.random.split(rng, 3)
    dt = cfg.jdtype
    params = {
        "embed": Box(jax.random.normal(k_emb, (cfg.vocab, cfg.d_model), dt)
                     * 0.02, ("vocab", "embed")),
        "final_norm": norm_init(cfg.d_model, dt, cfg.norm),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, cfg.d_model, cfg.vocab,
                                       (emb_w(cfg), "vocab"), dt)
    if cfg.hybrid:
        pat = cfg.hybrid.pattern
        keys = jax.random.split(k_blocks, cfg.n_layers)
        params["blocks"] = [
            rglru.rglru_block_init(cfg, keys[i])
            if pat[i % len(pat)] == "rglru" else block_init(cfg, keys[i])
            for i in range(cfg.n_layers)
        ]
    else:
        keys = jax.random.split(k_blocks, cfg.n_layers)
        params["blocks"] = stack_boxes(
            functools.partial(block_init, cfg), keys)
    return params


def hybrid_layer_kinds(cfg):
    pat = cfg.hybrid.pattern
    return [pat[i % len(pat)] for i in range(cfg.n_layers)]


def embed_tokens(cfg, params, tokens, prefix_embeds=None):
    x = params["embed"][tokens].astype(cfg.jdtype)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    return x


def unembed(cfg, params, x):
    xn = norm_apply(params["final_norm"], x, cfg.norm)
    if cfg.tie_embeddings:
        return jnp.einsum("bld,vd->blv", xn, params["embed"])
    return xn @ params["lm_head"]["w"]


def _lora_slice(lora, i=None):
    """Per-layer slice of the lora pool; None-safe. i=None keeps the stacked
    pool (used as scan xs)."""
    if lora is None:
        return None, None, None, "none"
    pool, idx, mode = lora["pool"], lora["idx"], lora.get("mode", "bgmv")
    ranks = pool["ranks"]
    per_layer = {t: ({"a": pool[t]["a"][i], "b": pool[t]["b"][i]}
                     if i is not None else pool[t]) for t in pool
                 if t != "ranks"}
    return per_layer, idx, ranks, mode


def prefill(cfg, params, tokens, *, prefix_embeds=None, lora=None,
            cache_slots=None, window=None, positions=None, last_only=False,
            last_pos=None):
    """Returns (logits, cache). cache_slots=None -> no cache (training).
    last_pos: optional (B,) int32 of per-row positions — the residual
    stream is gathered to those positions *before* the unembed, so a
    padded serving prefill pays the vocab projection for one position per
    row and the (B, L, vocab) logits tensor is never materialized."""
    x = embed_tokens(cfg, params, tokens, prefix_embeds)
    B, L = x.shape[0], x.shape[1]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    make_cache = cache_slots is not None
    slots = cache_slots or 0
    lora_stk, lora_idx, lora_ranks, lora_mode = _lora_slice(lora)

    if cfg.hybrid:
        kinds = hybrid_layer_kinds(cfg)
        caches, aux = [], 0.0
        for i, (kind, p_l) in enumerate(zip(kinds, params["blocks"])):
            if kind == "rglru":
                x, c = rglru.rglru_block_apply(cfg, p_l, x)
                caches.append(c)
            else:
                ll = ({t: {"a": lora_stk[t]["a"][i], "b": lora_stk[t]["b"][i]}
                       for t in lora_stk} if lora_stk else None)
                c0 = cache_init(B, cfg.n_kv_heads,
                                min(slots, cfg.hybrid.window) or cfg.hybrid.window,
                                cfg.hd, cfg.jdtype) if make_cache else None
                x, c, a = block_apply(
                    cfg, p_l, x, positions, lora_layer=ll, lora_idx=lora_idx,
                    lora_ranks=lora_ranks, lora_mode=lora_mode,
                    window=cfg.hybrid.window, cache=c0, decode=False)
                caches.append(c)
                aux += a
        if last_pos is not None:
            x = x[jnp.arange(B), last_pos][:, None]
        elif last_only:
            x = x[:, -1:]
        return unembed(cfg, params, x), (caches if make_cache else None)

    def body(carry, xs):
        x, aux = carry
        if cfg.seq_parallel and \
                "model" in jax.sharding.get_abstract_mesh().axis_names:
            # sequence parallelism: the residual stream lives L-sharded over
            # the model axis; GSPMD turns the TP all-reduces into
            # reduce-scatter + all-gather pairs (half the bytes) and the
            # norms run on 1/16th of the tokens (EXPERIMENTS.md sec Perf)
            U = jax.sharding.PartitionSpec.UNCONSTRAINED
            x = jax.lax.with_sharding_constraint(
                x, jax.sharding.PartitionSpec(U, "model", U))
        p_l, lora_l = xs
        ll = ({t: lora_l[t] for t in lora_l} if lora_l else None)
        c0 = cache_init(B, cfg.n_kv_heads, slots, cfg.hd, cfg.jdtype,
                        quantized=cfg.kv_cache_dtype == "int8") \
            if make_cache else None
        y, c, a = block_apply(
            cfg, p_l, x, positions, lora_layer=ll, lora_idx=lora_idx,
            lora_ranks=lora_ranks, lora_mode=lora_mode, window=window,
            cache=c0, decode=False)
        return (y, aux + a), c

    body_fn = jax.checkpoint(body) if cfg.remat else body
    if cfg.unroll_layers:
        carry = (x, jnp.zeros((), jnp.float32))
        caches = []
        for i in range(cfg.n_layers):
            xs_i = jax.tree.map(lambda v: v[i], (params["blocks"], lora_stk))
            carry, c = body_fn(carry, xs_i)
            caches.append(c)
        (x, aux) = carry
        caches = jax.tree.map(lambda *cs: jnp.stack(cs), *caches) \
            if make_cache else None
    else:
        (x, aux), caches = jax.lax.scan(
            body_fn, (x, jnp.zeros((), jnp.float32)),
            (params["blocks"], lora_stk))
    if last_pos is not None:
        x = x[jnp.arange(B), last_pos][:, None]
    elif last_only:
        x = x[:, -1:]
    logits = unembed(cfg, params, x)
    prefill.last_aux = aux  # inspected by the loss; scan-safe scalar
    return logits, (caches if make_cache else None)


def prefill_with_aux(cfg, params, tokens, **kw):
    logits, _ = prefill(cfg, params, tokens, **kw)
    return logits, prefill.last_aux


def prefill_chunk(cfg, params, tokens_c, start, clen, view, *, lora=None,
                  last=False):
    """One chunk of an incremental prefill against a gathered dense cache
    view (serving's chunked-prefill plane; see backend.prefill_chunk).

    tokens_c: (B, C) token slice padded to C; start: traced scalar — the
    absolute position of the chunk's first token; clen: traced scalar —
    real tokens in the chunk (pad writes are dropped via an OOB scatter,
    so pad slots keep pos -1). view: {"k","v": (L, B, KV, S, hd), "pos":
    (L, B, S)} — the row's claimed pages gathered dense, with unclaimed
    slots at pos -1. Returns (logits | None, new_view): logits (B, 1, V)
    for the chunk's last real token when `last`, via the same pre-unembed
    gather as prefill(last_pos=...).

    Every per-position op (projection + LoRA, RoPE, norms, MLP, residuals)
    is the exact sequence of attn_apply/block_apply, and attention masks
    by cached absolute positions, so valid entries occupy the same
    contiguous softmax prefix as a monolithic prefill — the chunked KV and
    sampled token are bitwise identical to prefill() (asserted in
    test_decode_consistency.py). MoE capacity routing is batch-shape-
    dependent, hence the model.supports_chunked_prefill gate.
    """
    x = embed_tokens(cfg, params, tokens_c)
    B, C = x.shape[0], x.shape[1]
    S = view["pos"].shape[-1]
    offs = jnp.arange(C, dtype=jnp.int32)
    positions = jnp.broadcast_to(start + offs, (B, C))
    sl = jnp.where(offs < clen, start + offs, S)       # pads -> OOB, dropped
    lora_stk, lora_idx, lora_ranks, lora_mode = _lora_slice(lora)
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    rb = cfg.lora.rank_block

    def body(x, xs):
        p_l, lora_l, view_l = xs
        ll = ({t: lora_l[t] for t in lora_l} if lora_l else None)
        pa = p_l["attn"]
        xn = norm_apply(p_l["norm1"], x, cfg.norm)
        q = _proj(pa["wq"], xn) + _lora_heads(xn, ll, "q", lora_idx,
                                              lora_ranks, lora_mode, rb, H, hd)
        k = _proj(pa["wk"], xn) + _lora_heads(xn, ll, "k", lora_idx,
                                              lora_ranks, lora_mode, rb, KV,
                                              hd)
        v = _proj(pa["wv"], xn) + _lora_heads(xn, ll, "v", lora_idx,
                                              lora_ranks, lora_mode, rb, KV,
                                              hd)
        if cfg.pos == "rope":
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        vk = view_l["k"].at[:, :, sl, :].set(k.transpose(0, 2, 1, 3),
                                             mode="drop")
        vv = view_l["v"].at[:, :, sl, :].set(v.transpose(0, 2, 1, 3),
                                             mode="drop")
        vpos = view_l["pos"].at[:, sl].set(positions, mode="drop")
        valid = (vpos[:, None, :] >= 0) \
            & (vpos[:, None, :] <= positions[..., None])
        out = attn_direct(q, vk.transpose(0, 2, 1, 3),
                          vv.transpose(0, 2, 1, 3), valid[:, None, None])
        a = jnp.einsum("blnh,nhd->bld", out, pa["wo"]["w"])
        h = x + a
        hn = norm_apply(p_l["norm2"], h, cfg.norm)
        return h + mlp_apply(cfg, p_l["mlp"], hn), \
            {"k": vk, "v": vv, "pos": vpos}

    if cfg.unroll_layers:
        views = []
        for i in range(cfg.n_layers):
            xs_i = jax.tree.map(lambda t: t[i],
                                (params["blocks"], lora_stk, view))
            x, v_l = body(x, xs_i)
            views.append(v_l)
        new_view = jax.tree.map(lambda *vs: jnp.stack(vs), *views)
    else:
        x, new_view = jax.lax.scan(body, x,
                                   (params["blocks"], lora_stk, view))
    if not last:
        return None, new_view
    x = x[jnp.arange(B), jnp.maximum(clen - 1, 0)][:, None]
    return unembed(cfg, params, x), new_view


def decode_step(cfg, params, cache, tokens_t, pos, *, lora=None, window=None,
                write_mask=None, block_table=None):
    """tokens_t: (B,1); pos: (B,) current absolute position.
    Returns (logits, new_cache). write_mask: (B,) bool — rows with False
    skip the KV write (cache row bitwise-untouched; serving's frozen
    rows). block_table: (B, W) — the cache is the paged page-pool layout
    (uniform layered stacks only; see model.supports_paged)."""
    x = embed_tokens(cfg, params, tokens_t)
    B = x.shape[0]
    lora_stk, lora_idx, lora_ranks, lora_mode = _lora_slice(lora)

    if cfg.hybrid:
        if block_table is not None:
            raise ValueError("paged cache unsupported for hybrid")
        kinds = hybrid_layer_kinds(cfg)
        new_caches = []
        for i, (kind, p_l, c_l) in enumerate(
                zip(kinds, params["blocks"], cache)):
            if kind == "rglru":
                x, c = rglru.rglru_block_step(cfg, p_l, x, c_l)
                if write_mask is not None:
                    # recurrent state has no slot to drop a write into:
                    # per-row select keeps frozen rows' state untouched
                    c = jax.tree.map(
                        lambda new, old: jnp.where(
                            write_mask.reshape((B,) + (1,) * (new.ndim - 1)),
                            new, old), c, c_l)
            else:
                ll = ({t: {"a": lora_stk[t]["a"][i], "b": lora_stk[t]["b"][i]}
                       for t in lora_stk} if lora_stk else None)
                x, c, _ = block_apply(
                    cfg, p_l, x, pos, lora_layer=ll, lora_idx=lora_idx,
                    lora_ranks=lora_ranks, lora_mode=lora_mode,
                    window=cfg.hybrid.window, cache=c_l, decode=True,
                    write_mask=write_mask)
            new_caches.append(c)
        return unembed(cfg, params, x), new_caches

    def body(x, xs):
        p_l, c_l, lora_l = xs
        y, c, _ = block_apply(
            cfg, p_l, x, pos, lora_layer=lora_l, lora_idx=lora_idx,
            lora_ranks=lora_ranks, lora_mode=lora_mode, window=window,
            cache=c_l, decode=True, write_mask=write_mask,
            block_table=block_table)
        return y, c

    if cfg.unroll_layers:
        new_caches = []
        for i in range(cfg.n_layers):
            xs_i = jax.tree.map(lambda v: v[i],
                                (params["blocks"], cache, lora_stk))
            x, c = body(x, xs_i)
            new_caches.append(c)
        new_cache = jax.tree.map(lambda *cs: jnp.stack(cs), *new_caches)
    else:
        x, new_cache = jax.lax.scan(body, x,
                                    (params["blocks"], cache, lora_stk))
    return unembed(cfg, params, x), new_cache
