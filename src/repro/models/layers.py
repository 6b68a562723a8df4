"""Shared neural building blocks: RoPE, GQA attention (direct / chunked
online-softmax / decode-with-cache / sliding window), MLPs.

Conventions:
  activations x: (B, L, D)
  q: (B, L, H, hd); k/v: (B, L, KV, hd)
  KV cache: k/v (B, KV, S, hd) + pos (B, S) absolute positions (-1 = empty).
  RoPE is applied at write time, so cached k never needs re-rotation.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.param import Box, dense_apply, dense_init

NEG_INF = -1e30


# ---------------------------------------------------------------- RoPE ----

def rope(x, positions, theta=10000.0):
    """x: (B, L, H, hd), positions: (B, L) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq          # (B,L,half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------- attention ----

def _gqa_scores(q, k):
    """q: (B,Lq,H,hd), k: (B,Lk,KV,hd) -> (B,KV,G,Lq,Lk) with G=H//KV."""
    b, lq, h, hd = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, lq, kv, h // kv, hd)
    return jnp.einsum("bqkgh,bskh->bkgqs", qg, k) / (hd ** 0.5)


def _gqa_out(probs, v):
    """probs: (B,KV,G,Lq,Lk), v: (B,Lk,KV,hd) -> (B,Lq,H,hd)."""
    b, kv, g, lq, _ = probs.shape
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(b, lq, kv * g, v.shape[-1])


def attn_direct(q, k, v, mask):
    """Materialized-logits attention. mask: broadcastable to (B,KV,G,Lq,Lk)."""
    s = _gqa_scores(q, k).astype(jnp.float32)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return _gqa_out(p.astype(v.dtype), v)


def causal_mask(lq, lk, q_offset=0, window=None):
    """(1,1,1,Lq,Lk) boolean mask; q position i attends k position j iff
    j <= i+q_offset and (window is None or i+q_offset - j < window)."""
    qpos = jnp.arange(lq)[:, None] + q_offset
    kpos = jnp.arange(lk)[None, :]
    m = kpos <= qpos
    if window is not None:
        m &= (qpos - kpos) < window
    return m[None, None, None]


def attn_chunked(q, k, v, *, causal=True, window=None, block=512):
    """Online-softmax attention over KV blocks (flash-style, pure jnp +
    lax.scan): never materializes the (Lq, Lk) logits. This is the pure-JAX
    reference path; the Pallas flash kernel (kernels/flash.py) is the TPU
    target and is validated against attn_direct.
    """
    b, lq, h, hd = q.shape
    lk = k.shape[1]
    kv = k.shape[2]
    g = h // kv
    nblk = -(-lk // block)
    pad = nblk * block - lk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nblk, block, kv, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblk, block, kv, hd).transpose(1, 0, 2, 3, 4)
    qg = q.reshape(b, lq, kv, g, hd)
    qpos = jnp.arange(lq)

    def step(carry, xs):
        m, l, acc = carry
        kblk, vblk, blk_i = xs
        s = jnp.einsum("bqkgh,bskh->bkgqs", qg, kblk) / (hd ** 0.5)
        s = s.astype(jnp.float32)
        kpos = blk_i * block + jnp.arange(block)
        valid = kpos[None, :] < lk
        if causal:
            valid &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            valid &= (qpos[:, None] - kpos[None, :]) < window
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskh->bkgqh", p.astype(vblk.dtype), vblk).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kv, g, lq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kv, g, lq), jnp.float32)
    a0 = jnp.zeros((b, kv, g, lq, hd), jnp.float32)
    # checkpoint per KV block: backward recomputes the (Lq, BK) probs instead
    # of storing them — the flash-attention memory property under autodiff
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(step), (m0, l0, a0), (kb, vb, jnp.arange(nblk)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, lq, h, hd)
    return out.astype(q.dtype)


def attn_prefill(q, k, v, *, causal=True, window=None, block=512,
                 direct_threshold=2048):
    """Pick direct vs chunked by sequence length (static)."""
    if k.shape[1] <= direct_threshold:
        if causal:
            mask = causal_mask(q.shape[1], k.shape[1], window=window)
        else:
            mask = jnp.ones((1, 1, 1, q.shape[1], k.shape[1]), bool)
        return attn_direct(q, k, v, mask)
    return attn_chunked(q, k, v, causal=causal, window=window, block=block)


def attn_decode(q, cache_k, cache_v, cache_pos, pos, window=None):
    """One-token attention over cache. q: (B,1,H,hd); cache_k/v: (B,KV,S,hd);
    cache_pos: (B,S) abs positions (-1 empty); pos: (B,) current position."""
    b, _, h, hd = q.shape
    kv = cache_k.shape[1]
    qg = q.reshape(b, kv, h // kv, hd)
    s = jnp.einsum("bkgh,bksh->bkgs", qg, cache_k) / (hd ** 0.5)
    s = s.astype(jnp.float32)
    valid = (cache_pos >= 0) & (cache_pos <= pos[:, None])           # (B,S)
    if window is not None:
        valid &= (pos[:, None] - cache_pos) < window
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(cache_v.dtype)
    out = jnp.einsum("bkgs,bksh->bkgh", p, cache_v)
    return out.reshape(b, 1, h, hd)


# -------------------------------------------------------------- KV cache ----
#
# Optional int8 quantization (symmetric, per (head, position) scale): halves
# the decode HBM traffic — the dominant roofline term of long-context decode
# (EXPERIMENTS.md sec Perf). Scales live alongside the int8 payload.

def cache_init(batch, kv_heads, slots, hd, dtype, quantized=False):
    c = {
        "k": jnp.zeros((batch, kv_heads, slots, hd),
                       jnp.int8 if quantized else dtype),
        "v": jnp.zeros((batch, kv_heads, slots, hd),
                       jnp.int8 if quantized else dtype),
        "pos": jnp.full((batch, slots), -1, jnp.int32),
    }
    if quantized:
        c["k_scale"] = jnp.zeros((batch, kv_heads, slots), jnp.float32)
        c["v_scale"] = jnp.zeros((batch, kv_heads, slots), jnp.float32)
    return c


def _quantize(x):
    """x: (..., hd) -> (int8, scale(...,))."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    q = jnp.round(x.astype(jnp.float32)
                  / jnp.maximum(scale, 1e-9)[..., None]).astype(jnp.int8)
    return q, scale


def _dequantize(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def cache_write_prefill(cache, k, v, positions):
    """Write a full prefill (B,L,KV,hd) into the cache (ring if L>slots)."""
    quant = cache["k"].dtype == jnp.int8
    slots = cache["k"].shape[2]
    L = k.shape[1]
    kT = k.transpose(0, 2, 1, 3)
    vT = v.transpose(0, 2, 1, 3)
    ks = vs = None
    if quant:
        kT, ks = _quantize(kT)
        vT, vs = _quantize(vT)
    if L <= slots:
        out = {
            "k": jax.lax.dynamic_update_slice(cache["k"], kT, (0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(cache["v"], vT, (0, 0, 0, 0)),
            "pos": jax.lax.dynamic_update_slice(cache["pos"], positions,
                                                (0, 0)),
        }
        if quant:
            out["k_scale"] = jax.lax.dynamic_update_slice(
                cache["k_scale"], ks, (0, 0, 0))
            out["v_scale"] = jax.lax.dynamic_update_slice(
                cache["v_scale"], vs, (0, 0, 0))
        return out
    # keep last `slots` tokens, laid out by the ring invariant
    # slot(p) = p % slots so subsequent decode writes evict correctly
    shift = L % slots
    out = {"k": jnp.roll(kT[:, :, -slots:], shift, axis=2),
           "v": jnp.roll(vT[:, :, -slots:], shift, axis=2),
           "pos": jnp.roll(positions[:, -slots:], shift, axis=1)}
    if quant:
        out["k_scale"] = jnp.roll(ks[:, :, -slots:], shift, axis=2)
        out["v_scale"] = jnp.roll(vs[:, :, -slots:], shift, axis=2)
    return out


def cache_write_token(cache, k_t, v_t, pos, write_mask=None):
    """Write one token at ring slot pos % slots. k_t: (B,1,KV,hd), pos: (B,).

    write_mask: optional (B,) bool — rows with False are excluded from the
    write entirely (their slot index is pushed out of bounds and the
    scatter drops it), leaving every cache leaf bitwise-untouched for that
    row. The batched decode pipeline uses this to freeze finished/inactive
    rows without paying a full-cache select."""
    quant = cache["k"].dtype == jnp.int8
    slots = cache["k"].shape[2]
    slot = pos % slots
    if write_mask is not None:
        slot = jnp.where(write_mask, slot, slots)   # OOB -> scatter drops
    b = k_t.shape[0]
    bidx = jnp.arange(b)
    kt, vt = k_t[:, 0], v_t[:, 0]                      # (B,KV,hd)
    out = dict(cache)
    if quant:
        kt, ks = _quantize(kt)
        vt, vs = _quantize(vt)
        out["k_scale"] = cache["k_scale"].at[bidx, :, slot].set(
            ks, mode="drop")
        out["v_scale"] = cache["v_scale"].at[bidx, :, slot].set(
            vs, mode="drop")
    out["k"] = cache["k"].at[bidx, :, slot].set(kt, mode="drop")
    out["v"] = cache["v"].at[bidx, :, slot].set(vt, mode="drop")
    out["pos"] = cache["pos"].at[bidx, slot].set(pos, mode="drop")
    return out


def cache_kv_for_attn(cache, dtype):
    """Dequantized views for attention."""
    if cache["k"].dtype == jnp.int8:
        return (_dequantize(cache["k"], cache["k_scale"], dtype),
                _dequantize(cache["v"], cache["v_scale"], dtype))
    return cache["k"], cache["v"]


# ------------------------------------------------------- paged KV cache ----
#
# Per-layer paged layout (serving's block-table memory plane): the cache is
# a pool of pages shared by every row — k/v (P, KV, page_size, hd) + pos
# (P, page_size) — and each row owns the pages its block table (B, W) points
# at (-1 = unclaimed logical page). Rows never share a physical page, so a
# frozen row's write can be dropped without a select and the pool update
# stays one scatter.

def cache_write_token_paged(cache, k_t, v_t, pos, block_table,
                            write_mask=None):
    """Write one token at ring slot pos % (W * page_size) through the block
    table. k_t/v_t: (B, 1, KV, hd); pos: (B,). Rows masked out by
    `write_mask` (and rows whose logical page is unclaimed) have their
    physical page index pushed out of bounds so the scatter drops the
    write — every pool leaf stays bitwise-untouched for them, exactly like
    the dense path's OOB slot trick."""
    n_pages, _, ps, _ = cache["k"].shape
    w = block_table.shape[1]
    slot = pos % (w * ps)
    page, off = slot // ps, slot % ps
    bidx = jnp.arange(block_table.shape[0])
    phys = block_table[bidx, page]
    ok = phys >= 0
    if write_mask is not None:
        ok = ok & write_mask
    phys = jnp.where(ok, phys, n_pages)          # OOB -> scatter drops
    kt, vt = k_t[:, 0], v_t[:, 0]                # (B, KV, hd)
    return {
        "k": cache["k"].at[phys, :, off].set(kt, mode="drop"),
        "v": cache["v"].at[phys, :, off].set(vt, mode="drop"),
        "pos": cache["pos"].at[phys, off].set(pos, mode="drop"),
    }


# Decode-attention implementation over the paged layout. "auto" picks the
# Pallas paged-attention kernel (kernels/paged.py) on TPU backends — grid
# (B, W / n): each step's DMA pulls n whole pages, every KV head of each,
# through the scalar-prefetched block table, with n set by the page's bytes,
# so the dense gathered view below never materializes — and the pure-jnp
# gather path elsewhere (it is also the bitwise reference the kernel is
# validated against). Tests/benches override the module global to force
# one side of the equivalence.
PAGED_ATTN_IMPL = "auto"          # auto | pallas | gather


def paged_attn_impl() -> str:
    if PAGED_ATTN_IMPL != "auto":
        return PAGED_ATTN_IMPL
    return "pallas" if jax.default_backend() == "tpu" else "gather"


def paged_attn_decode(q, cache, block_table, pos, window=None):
    """One-token decode attention straight off the paged cache.
    q: (B, 1, H, hd); cache leaves are the page pools; block_table (B, W);
    pos: (B,). Routes per `paged_attn_impl()`; windowed attention always
    takes the gather path (the kernel has no sliding-window mask)."""
    with jax.named_scope("paged_attn_decode"):
        if window is None and paged_attn_impl() == "pallas":
            from repro.kernels.paged import paged_attention
            out = paged_attention(q[:, 0], cache["k"], cache["v"],
                                  cache["pos"], block_table, pos)
            return out[:, None]
        ck, cv, cpos = paged_kv_for_attn(cache, block_table)
        return attn_decode(q, ck, cv, cpos, pos, window=window)


def paged_kv_for_attn(cache, block_table):
    """Gather a per-layer paged cache into dense (B, KV, S, hd) k/v views
    plus their (B, S) absolute positions, S = W * page_size in block-table
    order (logical slot j*ps+o of a row lands at index j*ps+o, matching the
    dense row layout element-for-element). Slots behind unclaimed logical
    pages get pos -1, so attention masks them exactly like empty dense
    slots; whatever page-0 payload the gather pulled for them is weighted
    by an exact softmax zero."""
    safe = jnp.maximum(block_table, 0)
    k = cache["k"][safe]                         # (B, W, KV, ps, hd)
    v = cache["v"][safe]
    b, w, kvh, ps, hd = k.shape
    k = k.transpose(0, 2, 1, 3, 4).reshape(b, kvh, w * ps, hd)
    v = v.transpose(0, 2, 1, 3, 4).reshape(b, kvh, w * ps, hd)
    kpos = jnp.where(block_table[:, :, None] >= 0, cache["pos"][safe], -1)
    return k, v, kpos.reshape(b, w * ps)


# ------------------------------------------------------------------ MLP ----

def emb_w(cfg):
    """Logical axis for the d_model dim of weight matrices."""
    return "embed_fsdp" if cfg.fsdp_weights else "embed"


def mlp_init(cfg, key):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    ew = emb_w(cfg)
    p = {"w1": dense_init(ks[0], d, f, (ew, "mlp"), cfg.jdtype),
         "w2": dense_init(ks[1], f, d, ("mlp", ew), cfg.jdtype)}
    if cfg.mlp_act in ("silu", "geglu"):
        p["w3"] = dense_init(ks[2], d, f, (ew, "mlp"), cfg.jdtype)
    return p


def mlp_apply(cfg, p, x):
    if cfg.mlp_act == "silu":
        h = jax.nn.silu(dense_apply(p["w1"], x)) * dense_apply(p["w3"], x)
    elif cfg.mlp_act == "geglu":
        h = jax.nn.gelu(dense_apply(p["w1"], x)) * dense_apply(p["w3"], x)
    else:
        h = jax.nn.gelu(dense_apply(p["w1"], x))
    return dense_apply(p["w2"], h)
