"""Operations and bytes a served call needs, from its shapes.

Counts the work the algorithm needs and nothing an implementation adds:
the context each row actually holds (never the padded pages of its block
table), the rank each adapter actually has (never the pool's padded
maximum), and one unembed row per sampled token. So a roofline or a
utilisation reads the same work whatever computes it. A multiply-add is
two operations.

What a block's shapes make of these counts is its architecture's to say
(`bench/arch/<name>.py`, named by the configuration; the contract is in
`bench/arch/__init__.py`): each count below routes there.
"""
from __future__ import annotations

from typing import Iterable, Tuple

from bench import arch


def layer_matmul_params(conf) -> int:
    """Weights one token multiplies through in one layer."""
    return arch.load(conf).layer_matmul_params(conf)


def lora_flops(conf, rank: int) -> int:
    """One token through one layer's adapter."""
    return arch.load(conf).lora_flops(conf, rank)


def attn_flops(conf, ctx: int) -> int:
    """One query token against `ctx` cached tokens in one layer."""
    return arch.load(conf).attn_flops(conf, ctx)


def token_flops(conf, ctx: int, rank: int) -> int:
    """One decoded token whose attention sees `ctx` tokens (itself
    included), through every layer and the unembed."""
    return arch.load(conf).token_flops(conf, ctx, rank)


def prefill_flops(conf, length: int, rank: int) -> int:
    """A prompt of `length` tokens under causal attention, unembedding its
    last position only (the token it samples)."""
    return arch.load(conf).prefill_flops(conf, length, rank)


def decode_flops(conf, rows: Iterable[Tuple[int, int]]) -> int:
    """One decode iteration: rows of (ctx, rank)."""
    return sum(token_flops(conf, ctx, rank) for ctx, rank in rows)


def paged_attn_flops(conf, ctxs: Iterable[int]) -> int:
    """The paged decode kernel over every layer it runs, one query per
    row."""
    return arch.load(conf).paged_attn_flops(conf, ctxs)


def paged_attn_bytes(conf, ctxs: Iterable[int]) -> int:
    """Bytes the paged decode kernel must move over every layer it runs:
    each row's cached keys and values (its context, not its pages), its
    query and its output."""
    return arch.load(conf).paged_attn_bytes(conf, ctxs)
