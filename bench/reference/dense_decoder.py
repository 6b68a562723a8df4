"""Plain float32 reference of a dense decoder with LoRA on q, k and v.

The published Llama-style block that Yi-9B and Phi-3-mini share: RMSNorm,
rotary positions (the half-split rotation of the Hugging Face
implementations), grouped-query causal attention, a SwiGLU MLP and an
untied output head. An adapter adds `lora.scale * x A B` to each of the
q, k and v projections, using its first `rank` columns only.

It imports nothing of the program and takes only what the benchmark made:
the weights (read as the program's layout names them) and the adapters.
It runs in float32 at the 'highest' matmul precision, one request at a
time and layer by layer, so it fits beside the weights on one chip.

`quant="fp8"` is the control: every matrix rounded to float8 e4m3 with a
scale per output channel, the step below the bf16 the configurations
state. Its greedy tokens must fail the comparison that sound runs pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _fp8(w, axes):
    """Round to float8 e4m3 with one scale per output channel (the scale
    is the channel's largest magnitude over the e4m3 maximum, 448)."""
    s = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mat(w, axes, quant):
    w = w.astype(F32)
    return _fp8(w, axes) if quant == "fp8" else w


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, theta):
    """x: (S, heads, hd) at positions 0..S-1."""
    S, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(conf, quant, x, blocks, i, lora, rank):
    d, H, KV, hd = (conf["hidden_size"], conf["num_attention_heads"],
                    conf["num_key_value_heads"], conf["head_dim"])
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    r_max, scale = conf["lora"]["max_rank"], conf["lora"]["scale"]
    S = x.shape[0]
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda w: w[i], blocks)
        live = (jnp.arange(r_max) < rank).astype(F32)
        xn = _rms(x, p["norm1"]["scale"], eps)

        def proj(target, w, heads):
            y = jnp.einsum("sd,dnh->snh", xn, _mat(w, (0,), quant))
            a = _mat(lora[target]["a"][i] * live[None, :], (0,), quant)
            b = _mat(lora[target]["b"][i] * live[:, None], (0,), quant)
            delta = (xn @ a) @ b * scale
            return y + delta.reshape(S, heads, hd)

        at = p["attn"]
        q = _rope(proj("q", at["wq"]["w"], H), theta)
        k = _rope(proj("k", at["wk"]["w"], KV), theta)
        v = proj("v", at["wv"]["w"], KV)
        qg = q.reshape(S, KV, H // KV, hd)
        s = jnp.einsum("skgh,tkh->kgst", qg, k) / jnp.sqrt(F32(hd))
        causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        s = jnp.where(causal, s, -jnp.inf)
        probs = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgst,tkh->skgh", probs, v).reshape(S, H * hd)
        wo = _mat(at["wo"]["w"], (0, 1), quant).reshape(H * hd, d)
        h = x + o @ wo
        hn = _rms(h, p["norm2"]["scale"], eps)
        m = p["mlp"]
        up = jax.nn.silu(hn @ _mat(m["w1"]["w"], (0,), quant)) \
            * (hn @ _mat(m["w3"]["w"], (0,), quant))
        return h + up @ _mat(m["w2"]["w"], (0,), quant)


def _embed(weights, tokens):
    return weights["embed"][tokens].astype(F32)


def _head(conf, quant, weights, x, rows):
    with jax.default_matmul_precision("highest"):
        xr = _rms(x[rows], weights["final_norm"]["scale"],
                  conf["rms_norm_eps"])
        return xr @ _mat(weights["lm_head"]["w"], (0,), quant)


class Reference:
    """Teacher-forced logits of one request at a time. `seq` is the padded
    sequence length and `n_rows` the padded number of positions read, so
    every request runs through the same three compiled programs."""

    def __init__(self, conf: dict, weights, seq: int, n_rows: int,
                 quant: str = ""):
        self.conf, self.weights = conf, weights
        self.seq, self.n_rows = seq, n_rows
        self._embed = jax.jit(_embed)
        self._layer = jax.jit(functools.partial(_layer, conf, quant))
        self._head = jax.jit(functools.partial(_head, conf, quant))

    def logits(self, tokens: np.ndarray, first: int, count: int, adapter,
               rank: int) -> np.ndarray:
        """(count, vocab) float32 logits at positions first..first+count-1
        of `tokens` run with `adapter` at `rank`."""
        if len(tokens) > self.seq or count > self.n_rows:
            raise ValueError(f"reference sized for {self.seq} tokens and "
                             f"{self.n_rows} rows, got {len(tokens)} and "
                             f"{count}")
        toks = np.zeros((self.seq,), np.int32)
        toks[:len(tokens)] = tokens
        rows = np.minimum(first + np.arange(self.n_rows), self.seq - 1)
        lora = jax.tree.map(jnp.asarray, adapter)
        x = self._embed(self.weights, jnp.asarray(toks))
        blocks = self.weights["blocks"]
        r = jnp.int32(rank)
        for i in range(self.conf["num_hidden_layers"]):
            x = self._layer(x, blocks, jnp.int32(i), lora, r)
        out = self._head(self.weights, x, jnp.asarray(rows, jnp.int32))
        return np.asarray(out)[:count]
