"""Jit'd public wrappers for the Pallas kernels (each runs in interpret mode
on the CPU backend and lowers to Mosaic on TPU; the pure-jnp oracle is
exported for the dry-run path)."""
from __future__ import annotations

import functools

import jax

from repro.kernels import ref
from repro.kernels.bgmv import bgmv, bgmv_expand, bgmv_shrink
from repro.kernels.flash import flash_attention
from repro.kernels.mbgmv import mbgmv, mbgmv_expand, mbgmv_shrink
from repro.kernels.paged import paged_attention as _paged_attention

lora_delta_bgmv = jax.jit(bgmv, static_argnames=("interpret",))
lora_delta_mbgmv = jax.jit(mbgmv, static_argnames=("rank_block", "interpret"))
lora_delta_ref = jax.jit(ref.bgmv_ref, static_argnums=())

paged_attention = jax.jit(_paged_attention, static_argnames=("interpret",))


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def attention(q, k, v, causal=True, window=None):
    return flash_attention(q, k, v, causal=causal, window=window)


@functools.partial(jax.jit, static_argnames=("mode", "rank_block"))
def lora_delta(x, a_pool, b_pool, idx, ranks=None, mode="bgmv",
               rank_block=16):
    """Dispatch by kernel mode (the scheduler's two performance laws)."""
    if mode == "bgmv":
        return bgmv(x, a_pool, b_pool, idx)
    if mode == "mbgmv":
        return mbgmv(x, a_pool, b_pool, idx, ranks, rank_block=rank_block)
    if mode == "ref":
        return ref.bgmv_ref(x, a_pool, b_pool, idx)
    raise ValueError(mode)
