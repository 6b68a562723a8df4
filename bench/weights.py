"""Weights and adapters, made by the benchmark from the seed.

The benchmark makes every weight itself, so the reference that judges the
served tokens takes nothing the program made: the base weights in one
jitted call on the device, in the type they are served in (bf16), laid out
as the program's dense decoder holds them; each adapter on the device and
then copied to host memory, where the server's adapter store keeps it (a
cold start uploads it from there). A configuration file
(`bench/configs/<name>.json`) holds the published sizes; `program_config`
turns it into the program's `ModelConfig` and checks that nothing was lost.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

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


def dims(conf: dict) -> Dict[str, int]:
    return {"d": conf["hidden_size"], "f": conf["intermediate_size"],
            "L": conf["num_hidden_layers"], "H": conf["num_attention_heads"],
            "KV": conf["num_key_value_heads"], "hd": conf["head_dim"],
            "V": conf["vocab_size"]}


def target_dims(conf: dict, target: str):
    n = dims(conf)
    heads = n["H"] if target == "q" else n["KV"]
    return n["d"], heads * n["hd"]


def _weights(conf: dict, key):
    n = dims(conf)
    d, f, L, H, KV, hd, V = (n[k] for k in ("d", "f", "L", "H", "KV", "hd",
                                           "V"))
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


def jax_key(seed: int, stream: int):
    """A JAX key for (seed, stream), for any integer seed."""
    s = seed & (2 ** 64 - 1)
    k = jax.random.fold_in(jax.random.PRNGKey(stream), s & 0x7FFFFFFF)
    return jax.random.fold_in(k, (s >> 31) & 0xFFFFFFFF)


def make_weights(conf: dict, cfg, seed: int):
    """Base weights on the device in one jitted call; checked against the
    tree of shapes the program expects."""
    from repro.models import model as model_lib
    w = jax.jit(lambda k: _weights(conf, k))(jax_key(seed, 0))
    want = model_lib.abstract_params(cfg)[0]
    got_s = jax.tree.map(lambda x: (x.shape, x.dtype), w)
    want_s = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if jax.tree.structure(got_s) != jax.tree.structure(want_s) \
            or jax.tree.leaves(got_s) != jax.tree.leaves(want_s):
        raise ValueError(f"{conf['name']}: the benchmark's weight layout no "
                         "longer matches the program's parameter tree")
    return w


def _adapter(conf: dict, key, rank):
    n = dims(conf)
    r_max = conf["lora"]["max_rank"]
    dt = jnp.dtype(conf["torch_dtype"])
    out = {}
    for i, t in enumerate(conf["lora"]["targets"]):
        d_in, d_out = target_dims(conf, t)
        ka, kb = jax.random.split(jax.random.fold_in(key, i))
        live = jnp.arange(r_max) < rank
        a = jax.random.normal(ka, (n["L"], d_in, r_max)) * d_in ** -0.5
        b = jax.random.normal(kb, (n["L"], r_max, d_out)) * \
            jax.lax.rsqrt(rank.astype(jnp.float32))
        out[t] = {"a": jnp.where(live[None, None, :], a, 0).astype(dt),
                  "b": jnp.where(live[None, :, None], b, 0).astype(dt)}
    return out


def make_adapters(conf: dict, ads, seed: int) -> Dict[str, dict]:
    """{uid: {target: {a: (L, d_in, r_max), b: (L, r_max, d_out)}}} as host
    arrays, zero beyond each adapter's rank; `ads` is [(uid, rank)]."""
    fn = jax.jit(lambda k, r: _adapter(conf, k, r))
    base = jax_key(seed, 1)
    out = {}
    for uid, rank in ads:
        k = jax.random.fold_in(base, int(uid[4:]))
        dev = fn(k, jnp.int32(rank))
        out[uid] = jax.tree.map(np.asarray, jax.device_get(dev))
    return out
