"""Operations and bytes a served call needs, from its shapes.

Counts the work the algorithm needs and nothing an implementation adds:
the context each row actually holds (never the padded pages of its block
table), the rank each adapter actually has (never the pool's padded
maximum), and one unembed row per sampled token. So a roofline or a
utilisation reads the same work whatever computes it. A multiply-add is
two operations.
"""
from __future__ import annotations

from typing import Iterable, Tuple

BF16 = 2


def _n(conf):
    return (conf["hidden_size"], conf["intermediate_size"],
            conf["num_hidden_layers"], conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["head_dim"],
            conf["vocab_size"])


def layer_matmul_params(conf) -> int:
    """Weights one token multiplies through in one layer: q, k, v, o and
    the gated MLP."""
    d, f, _, H, KV, hd, _ = _n(conf)
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f


def lora_flops(conf, rank: int) -> int:
    """One token through one layer's adapter: x A then (x A) B, per
    target."""
    d, _, _, H, KV, hd, _ = _n(conf)
    outs = {"q": H * hd, "k": KV * hd, "v": KV * hd}
    return sum(2 * rank * (d + outs[t]) for t in conf["lora"]["targets"])


def attn_flops(conf, ctx: int) -> int:
    """One query token against `ctx` cached tokens in one layer: scores
    and the weighted sum of values."""
    _, _, _, H, _, hd, _ = _n(conf)
    return 4 * H * hd * ctx


def token_flops(conf, ctx: int, rank: int) -> int:
    """One decoded token whose attention sees `ctx` tokens (itself
    included), through every layer and the unembed."""
    d, _, L, _, _, _, V = _n(conf)
    per_layer = 2 * layer_matmul_params(conf) + lora_flops(conf, rank) \
        + attn_flops(conf, ctx)
    return L * per_layer + 2 * d * V


def prefill_flops(conf, length: int, rank: int) -> int:
    """A prompt of `length` tokens under causal attention, unembedding its
    last position only (the token it samples)."""
    d, _, L, _, _, _, V = _n(conf)
    dense = 2 * layer_matmul_params(conf) + lora_flops(conf, rank)
    attn = attn_flops(conf, length * (length + 1) // 2)   # linear in ctx
    return L * (dense * length + attn) + 2 * d * V


def decode_flops(conf, rows: Iterable[Tuple[int, int]]) -> int:
    """One decode iteration: rows of (ctx, rank)."""
    return sum(token_flops(conf, ctx, rank) for ctx, rank in rows)


def paged_attn_flops(conf, ctxs: Iterable[int]) -> int:
    """The paged decode kernel over every layer, one query per row."""
    return conf["num_hidden_layers"] * sum(attn_flops(conf, c)
                                           for c in ctxs)


def paged_attn_bytes(conf, ctxs: Iterable[int]) -> int:
    """Bytes the paged decode kernel must move over every layer: each
    row's cached keys and values (its context, not its pages), its query
    and its output."""
    _, _, L, H, KV, hd, _ = _n(conf)
    return L * sum(2 * c * KV * hd * BF16 + 2 * H * hd * BF16 for c in ctxs)
