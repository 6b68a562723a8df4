"""The plain dense decoder: Yi-9B, Phi-3-mini and their Llama-style kin.

Every layer alike: RMSNorm, rotary positions, grouped-query attention
over the whole context, a gated MLP, LoRA on q, k and v. The contract of
each function is in `bench/arch/__init__.py`.
"""
from __future__ import annotations

import copy
import dataclasses
from typing import Iterable, Tuple

import jax
import jax.numpy as jnp

BF16 = 2

# published config.json key -> the program's ModelConfig field
FIELDS = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "vocab_size": "vocab", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings", "attention_bias": "qkv_bias",
}


def program_config(conf: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import get_config
    base = get_config(conf["program"]["base"])
    kw = {field: conf[key] for key, field in FIELDS.items()}
    kw.update(conf["program"]["fields"])
    kw["dtype"] = conf["torch_dtype"]
    kw["mlp_act"] = conf["hidden_act"]
    lora = conf["lora"]
    kw["lora"] = dataclasses.replace(base.lora, max_rank=lora["max_rank"],
                                     targets=tuple(lora["targets"]))
    kw["name"] = conf["name"]
    cfg = dataclasses.replace(base, **kw)
    if cfg.hd != conf["head_dim"] or cfg.family != "dense" or cfg.moe \
            or cfg.hybrid or cfg.norm != "rmsnorm" or cfg.pos != "rope":
        raise ValueError(f"{conf['name']}: the program's config does not "
                         "describe a plain dense decoder at these sizes")
    return cfg


def dims(conf: dict) -> Tuple[int, ...]:
    """(d, f, L, H, KV, hd, V): width, MLP width, layers, query and KV
    heads, head size, vocabulary."""
    return (conf["hidden_size"], conf["intermediate_size"],
            conf["num_hidden_layers"], conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["head_dim"],
            conf["vocab_size"])


def target_dims(conf: dict, target: str):
    d, _, _, H, KV, hd, _ = dims(conf)
    return d, (H if target == "q" else KV) * hd


def weights(conf: dict, key):
    d, f, L, H, KV, hd, V = dims(conf)
    dt = jnp.dtype(conf["torch_dtype"])
    ks = iter(jax.random.split(key, 16))

    def normal(shape, scale):
        return jax.random.normal(next(ks), shape, dt) * jnp.asarray(scale,
                                                                   dt)

    def scale_vec(shape):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape,
                                              jnp.float32)).astype(dt)

    # unit-variance embeddings and unit-gain projections: activations stay
    # of order one through the stack, so the norms' eps plays no part and
    # every logit is of order one
    return {
        "embed": normal((V, d), 1.0),
        "final_norm": {"scale": scale_vec((d,))},
        "lm_head": {"w": normal((d, V), d ** -0.5)},
        "blocks": {
            "norm1": {"scale": scale_vec((L, d))},
            "norm2": {"scale": scale_vec((L, d))},
            "attn": {
                "wq": {"w": normal((L, d, H, hd), d ** -0.5)},
                "wk": {"w": normal((L, d, KV, hd), d ** -0.5)},
                "wv": {"w": normal((L, d, KV, hd), d ** -0.5)},
                "wo": {"w": normal((L, H, hd, d), (H * hd) ** -0.5)},
            },
            "mlp": {
                "w1": {"w": normal((L, d, f), d ** -0.5)},
                "w3": {"w": normal((L, d, f), d ** -0.5)},
                "w2": {"w": normal((L, f, d), f ** -0.5)},
            },
        },
    }


def adapter(conf: dict, key, rank):
    L = conf["num_hidden_layers"]
    r_max = conf["lora"]["max_rank"]
    dt = jnp.dtype(conf["torch_dtype"])
    out = {}
    for i, t in enumerate(conf["lora"]["targets"]):
        d_in, d_out = target_dims(conf, t)
        ka, kb = jax.random.split(jax.random.fold_in(key, i))
        live = jnp.arange(r_max) < rank
        a = jax.random.normal(ka, (L, d_in, r_max)) * d_in ** -0.5
        b = jax.random.normal(kb, (L, r_max, d_out)) * \
            jax.lax.rsqrt(rank.astype(jnp.float32))
        out[t] = {"a": jnp.where(live[None, None, :], a, 0).astype(dt),
                  "b": jnp.where(live[None, :, None], b, 0).astype(dt)}
    return out


def tiny(conf: dict) -> dict:
    c = copy.deepcopy(conf)
    c.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, head_dim=16, vocab_size=256,
             num_key_value_heads=min(c["num_key_value_heads"], 4) // 2 or 1)
    return c


def layer_matmul_params(conf) -> int:
    """Weights one token multiplies through in one layer: q, k, v, o and
    the gated MLP."""
    d, f, _, H, KV, hd, _ = dims(conf)
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f


def lora_flops(conf, rank: int) -> int:
    """One token through one layer's adapter: x A then (x A) B, per
    target."""
    d, _, _, H, KV, hd, _ = dims(conf)
    outs = {"q": H * hd, "k": KV * hd, "v": KV * hd}
    return sum(2 * rank * (d + outs[t]) for t in conf["lora"]["targets"])


def attn_flops(conf, ctx: int) -> int:
    """One query token against `ctx` cached tokens in one layer: scores
    and the weighted sum of values."""
    _, _, _, H, _, hd, _ = dims(conf)
    return 4 * H * hd * ctx


def token_flops(conf, ctx: int, rank: int) -> int:
    """One decoded token whose attention sees `ctx` tokens (itself
    included), through every layer and the unembed."""
    d, _, L, _, _, _, V = dims(conf)
    per_layer = 2 * layer_matmul_params(conf) + lora_flops(conf, rank) \
        + attn_flops(conf, ctx)
    return L * per_layer + 2 * d * V


def prefill_flops(conf, length: int, rank: int) -> int:
    """A prompt of `length` tokens under causal attention, unembedding its
    last position only (the token it samples)."""
    d, _, L, _, _, _, V = dims(conf)
    dense = 2 * layer_matmul_params(conf) + lora_flops(conf, rank)
    attn = attn_flops(conf, length * (length + 1) // 2)   # linear in ctx
    return L * (dense * length + attn) + 2 * d * V


def paged_attn_flops(conf, ctxs: Iterable[int]) -> int:
    """The paged decode kernel over every layer, one query per row."""
    return conf["num_hidden_layers"] * sum(attn_flops(conf, c)
                                           for c in ctxs)


def paged_attn_bytes(conf, ctxs: Iterable[int]) -> int:
    """Bytes the paged decode kernel must move over every layer: each
    row's cached keys and values (its context, not its pages), its query
    and its output."""
    _, _, L, H, KV, hd, _ = dims(conf)
    return L * sum(2 * c * KV * hd * BF16 + 2 * H * hd * BF16 for c in ctxs)
