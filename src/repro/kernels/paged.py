"""Paged decode attention Pallas TPU kernel — the decode hot-spot of the
paged memory plane (vLLM PagedAttention / S-LoRA unified paging, adapted to
TPU).

One decode token per row attends over that row's block table. Grid
(B, W / n) walks the row's W logical pages n at a time. Each page is one
contiguous (KV, ps, hd) block of the per-layer pool, every KV head at
once, and each of the step's n pages is its own K and V operand whose
index map reads the physical page id from the scalar-prefetched block
table *before* the step, so the DMA engine pulls pages HBM->VMEM directly
(the index_map-as-gather idiom of bgmv.py) and the gathered
(B, KV, S, hd) view the jnp oracle materializes never exists. The step
scores the row's (KV, group, hd) query tile against the (KV, n*ps, hd)
keys in one batched ``dot_general`` over KV, makes one online-softmax
update, and takes one batched product with the values.

Why n pages a step: on a TPU v5e a step costs a serial chain (matmul,
softmax, matmul) of about 0.35 us whatever its size, so a step has to move
enough bytes to outlast it. `pages_per_step` takes the largest power of
two n <= W whose n pages of K fit STEP_KV_BYTES: Phi-3-mini's 192 KiB
pages (32 KV heads, hd 96) go two a step, Yi-9B's 32 KiB pages (4 KV
heads, hd 128) eight. The arithmetic has one form at every GQA group: on
the v5e, a per-head vector form and a block-diagonal single matmul were no
faster at group 1.

VMEM per step: double-buffered query and output tiles (KV, group, hd),
2n pages (KV, ps, hd) and n position rows (1, ps); f32 scratch
(KV, group, 1) twice and (KV, group, hd); the body's f32 copies of the
(KV, n*ps, hd) keys and values. About 4 MiB at Phi-3-mini's widths.

Masking: a step whose n table entries are all unclaimed (block_table < 0)
is skipped via pl.when. In a claimed step, an unclaimed entry's operand is
the clamped page 0, another row's page, so its positions become -1; empty
slots of a claimed page are masked by their cached position. The softmax
weight p is mask-aware, so a masked slot contributes exactly zero and an
all-unclaimed row returns zeros.

Validated against kernels.ref.paged_attention_ref in interpret mode on the
CPU, and compiled for a described TPU v5e at published widths by
tests/test_tpu_compile.py (every block's last two dims are whole array
dims, which Mosaic accepts off the (8, 128) tiling). models/layers.py
routes paged decode here by default on TPU backends (`paged_attn_decode`,
impl switch `layers.PAGED_ATTN_IMPL`); the pure-jnp gather path is the
CPU path and the bitwise-parity reference.

Statically verified by `analysis.kernel_verify` (lint rules `kernel-*`,
CLI `tools/kverify.py`): the page operands' block-table clamp
(`jnp.maximum(bt[b, c], 0)`) is proved paired with a `pl.when` guard on
`bt_ref` (clamp without guard silently attends a foreign row's page),
plus online-softmax scratch init/flush/carry over the page revisit dim,
bounds with `-1` sentinel tables, and the VMEM budget at every `configs/`
shape. The -1 positions of an unclaimed entry inside a claimed step are
checked at run time (`tests/test_kernels.py`, page zero behind unclaimed
entries).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# K bytes one grid step should move (as many again of V)
STEP_KV_BYTES = 384 * 1024


def pages_per_step(page_bytes: int, width: int) -> int:
    """Largest power of two n <= width with n * page_bytes within
    STEP_KV_BYTES (at least 1)."""
    n = 1
    while 2 * n <= width and 2 * n * page_bytes <= STEP_KV_BYTES:
        n *= 2
    return n


def _paged_kernel(bt_ref, pos_ref, q_ref, k_refs, v_refs, pp_refs, o_ref,
                  m_ref, l_ref, acc_ref, *, scale):
    b, j = pl.program_id(0), pl.program_id(1)
    n = len(k_refs)
    claimed = [bt_ref[b, j * n + i] >= 0 for i in range(n)]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(functools.reduce(jnp.logical_or, claimed))
    def _():
        q = q_ref[0].astype(jnp.float32)                    # (KV, G, hd)
        k = jnp.concatenate([r[0] for r in k_refs],
                            axis=1).astype(jnp.float32)     # (KV, n*ps, hd)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale     # (KV, G, n*ps)
        # an unclaimed entry's operand is the clamped page 0: mask it whole
        kpos = jnp.concatenate([jnp.where(c, r[0], -1)
                                for c, r in zip(claimed, pp_refs)],
                               axis=1)                      # (1, n*ps)
        ok = jnp.logical_and(kpos >= 0, kpos <= pos_ref[b])[None]
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]                                 # (KV, G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        # mask-aware p: when every slot of the step is masked, s == m_new ==
        # NEG_INF and exp(s - m_new) would be 1, silently attending garbage;
        # zeroing by the mask keeps fully-empty pages (lazily grown but not
        # yet written) and fully-masked rows contributing exactly nothing
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)          # (KV, G, n*ps)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=2, keepdims=True)
        v = jnp.concatenate([r[0] for r in v_refs],
                            axis=1).astype(jnp.float32)     # (KV, n*ps, hd)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)             # (KV, G, hd)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


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
    n = pages_per_step(KV * ps * hd * k_pages.dtype.itemsize, W)
    steps = pl.cdiv(W, n)
    # -1 columns pad the table to whole steps: masked like unclaimed pages
    bt = jnp.pad(jnp.asarray(block_table, jnp.int32),
                 ((0, 0), (0, steps * n - W)), constant_values=-1)
    kern = functools.partial(_paged_kernel, scale=hd ** -0.5)
    page = lambda bt, b, c: jnp.maximum(bt[b, c], 0)

    def paged_block(shape, i):
        """Page operand i of a step: the block table's entry j * n + i."""
        rest = (0,) * (len(shape) - 1)
        return pl.BlockSpec(
            shape, lambda b, j, bt, p: (page(bt, b, j * n + i),) + rest)

    # every block's last two dims are whole array dims — (group, hd) query
    # and output tiles, (ps, hd) K/V pages, (1, ps) position pages — which
    # is what Mosaic accepts for dims that are not (8, 128)-aligned
    kv_pages = tuple(paged_block((1, KV, ps, hd), i) for i in range(n))
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, steps),
            in_specs=[
                pl.BlockSpec((1, KV, group, hd),
                             lambda b, j, bt, p: (b, 0, 0, 0)),
                kv_pages,
                kv_pages,
                tuple(paged_block((1, 1, ps), i) for i in range(n)),
            ],
            out_specs=pl.BlockSpec((1, KV, group, hd),
                                   lambda b, j, bt, p: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((KV, group, 1), jnp.float32),
                pltpu.VMEM((KV, group, 1), jnp.float32),
                pltpu.VMEM((KV, group, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, group, hd), q.dtype),
        interpret=interpret,
    )(bt, jnp.asarray(pos, jnp.int32), q.reshape(B, KV, group, hd),
      (k_pages,) * n, (v_pages,) * n, (pos_pages.reshape(P, 1, ps),) * n)
    return out.reshape(B, H, hd)
