"""Paged decode attention Pallas TPU kernel — the decode hot-spot of the
paged memory plane (vLLM PagedAttention / S-LoRA unified paging, adapted to
TPU).

One decode token per row attends over that row's block table: grid
(B, KV, W) walks the row's W logical pages for one KV head at a time, and
the KV head's whole query group (H // KV heads, one (group, hd) tile)
attends each page in one step; the physical page id is read
from the scalar-prefetched block table *before* the grid step, so the DMA
engine pulls K/V page tiles HBM->VMEM directly (the same
index_map-as-gather idiom as bgmv.py) — the gathered (B, KV, S, hd) dense
view the jnp oracle materializes never exists. Unclaimed logical pages
(block_table < 0) skip their whole grid step via pl.when; empty slots
inside a claimed page are masked by their cached position. Online softmax
with VMEM scratch accumulators, one (group, 1) / (group, hd) set per
grid row.

Validated against kernels.ref.paged_attention_ref in interpret mode on the
CPU, and compiled for a described TPU v5e at published widths by
tests/test_tpu_compile.py (every block's last two dims are whole array
dims, which Mosaic accepts off the (8, 128) tiling). models/layers.py
routes paged decode here by default on TPU backends (`paged_attn_decode`,
impl switch `layers.PAGED_ATTN_IMPL`); the pure-jnp gather path is the
CPU path and the bitwise-parity reference.

Statically verified by `analysis.kernel_verify` (lint rules `kernel-*`,
CLI `tools/kverify.py`): the block-table gather's clamp
(`jnp.maximum(bt[b, j], 0)`) is proved paired with the
`pl.when(bt_ref[b, j] >= 0)` guard — the tenant-isolation invariant
(clamp without guard silently attends a foreign row's page) — plus
online-softmax scratch init/flush/carry over the W revisit dim, bounds
with `-1` sentinel tables, and the VMEM budget at every `configs/`
shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, pp_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale):
    b, j = pl.program_id(0), pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(bt_ref[b, j] >= 0)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)                       # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)                       # (ps, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale           # (G, ps)
        kpos = pp_ref[0]                                          # (1, ps)
        ok = jnp.logical_and(kpos >= 0, kpos <= pos_ref[b])
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]                                       # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # mask-aware p: when every slot of the page is masked, s == m_new ==
        # NEG_INF and exp(s - m_new) would be 1, silently attending garbage;
        # zeroing by the mask keeps fully-empty pages (lazily grown but not
        # yet written) and fully-masked rows contributing exactly nothing
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)                # (G, ps)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                       # (ps, hd)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)             # (G, hd)
        m_ref[...] = m_new

    @pl.when(j == nj - 1)
    def _():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, pos_pages, block_table, pos, *,
                    interpret=None):
    """q: (B, H, hd); k_pages/v_pages: (P, KV, ps, hd); pos_pages: (P, ps);
    block_table: (B, W) int32 (-1 = unclaimed); pos: (B,) -> (B, H, hd)."""
    B, H, hd = q.shape
    P, KV, ps = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    W = block_table.shape[1]
    if H % KV:
        raise ValueError(f"paged_attention: H ({H}) not divisible by KV "
                         f"({KV}) — q {q.shape} vs k_pages {k_pages.shape}")
    if k_pages.shape != v_pages.shape:
        raise ValueError(f"paged_attention: k_pages {k_pages.shape} != "
                         f"v_pages {v_pages.shape}")
    if pos_pages.shape != (P, ps):
        raise ValueError(f"paged_attention: pos_pages {pos_pages.shape} "
                         f"must be ({P}, {ps}) to match k_pages "
                         f"{k_pages.shape}")
    if block_table.shape[0] != B or pos.shape != (B,):
        raise ValueError(f"paged_attention: block_table "
                         f"{block_table.shape} / pos {pos.shape} must lead "
                         f"with batch {B} (q {q.shape})")
    group = H // KV
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    kern = functools.partial(_paged_kernel, scale=hd ** -0.5)
    page = lambda b, g, j, bt, p: jnp.maximum(bt[b, j], 0)
    # every block's last two dims are whole array dims — (group, hd) query
    # and output tiles, (ps, hd) K/V pages, (1, ps) position pages — which
    # is what Mosaic accepts for dims that are not (8, 128)-aligned
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, KV, W),
            in_specs=[
                pl.BlockSpec((1, 1, group, hd),
                             lambda b, g, j, bt, p: (b, g, 0, 0)),
                pl.BlockSpec((1, 1, ps, hd),
                             lambda b, g, j, bt, p:
                             (page(b, g, j, bt, p), g, 0, 0)),
                pl.BlockSpec((1, 1, ps, hd),
                             lambda b, g, j, bt, p:
                             (page(b, g, j, bt, p), g, 0, 0)),
                pl.BlockSpec((1, 1, ps),
                             lambda b, g, j, bt, p:
                             (page(b, g, j, bt, p), 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, group, hd),
                                   lambda b, g, j, bt, p: (b, g, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, group, hd), q.dtype),
        interpret=interpret,
    )(jnp.asarray(block_table, jnp.int32), jnp.asarray(pos, jnp.int32),
      q.reshape(B, KV, group, hd), k_pages, v_pages,
      pos_pages.reshape(P, 1, ps))
    return out.reshape(B, H, hd)
