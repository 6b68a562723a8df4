"""Mixture-of-Experts layer (DBRX 16e/top-4, Grok-1 8e/top-2).

Dispatch is scatter/gather based (MegaBlocks-style adapted to static-shape
JAX): tokens are scattered into per-expert capacity buffers (O(T*k*d) data
movement, no O(T*E*C) one-hot einsum), experts run as one batched einsum over
(E, C, d) buffers, results gathered back. Group size is a knob: prefill
groups = sequences (bounds capacity skew), decode = one global group
(minimizes capacity slack) — see EXPERIMENTS.md sec Perf.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import emb_w
from repro.models.param import Box, dense_init


def moe_init(cfg, key):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.moe.n_experts
    ks = jax.random.split(key, 4)
    if cfg.moe_ep:
        # EP-native layout: (E*s, d, f/s) sharded over data on dim 0 — the
        # all-to-all dispatch path reads weights in place, no resharding
        from repro.models.moe_ep import ep_factors
        s, _ = ep_factors(E, cfg.moe_ep_shards)
        fs = f // s
        p = {"router": dense_init(ks[0], d, E, ("embed", None), cfg.jdtype),
             "w1": {"w": Box(jax.random.normal(ks[1], (E * s, d, fs),
                                               cfg.jdtype) * d ** -0.5,
                             ("experts_ep", None, "mlp"))},
             "w2": {"w": Box(jax.random.normal(ks[2], (E * s, fs, d),
                                               cfg.jdtype) * f ** -0.5,
                             ("experts_ep", "mlp", None))}}
        if cfg.mlp_act in ("silu", "geglu"):
            p["w3"] = {"w": Box(jax.random.normal(ks[3], (E * s, d, fs),
                                                  cfg.jdtype) * d ** -0.5,
                                ("experts_ep", None, "mlp"))}
        return p
    if cfg.moe_2d_ff:
        # both mesh axes on d_ff: the (tokens, d)x(d, f) contraction stays
        # unsharded on d -> no per-layer activation all-reduce from w1/w3;
        # only w2's output (tokens, d) reduces (EXPERIMENTS.md sec Perf)
        ax_w1 = ("experts", None, "mlp_fsdp")
        ax_w2 = ("experts", "mlp_fsdp", None)
    else:
        ew = emb_w(cfg)
        ax_w1 = ("experts", ew, "mlp")
        ax_w2 = ("experts", "mlp", ew)
    p = {
        "router": dense_init(ks[0], d, E, ("embed", None), cfg.jdtype),
        "w1": {"w": Box(jax.random.normal(ks[1], (E, d, f), cfg.jdtype) * d ** -0.5,
                        ax_w1)},
        "w2": {"w": Box(jax.random.normal(ks[2], (E, f, d), cfg.jdtype) * f ** -0.5,
                        ax_w2)},
    }
    if cfg.mlp_act in ("silu", "geglu"):
        p["w3"] = {"w": Box(jax.random.normal(ks[3], (E, d, f), cfg.jdtype)
                            * d ** -0.5, ax_w1)}
    return p


def _dispatch_group(x, eidx, pos, keep, gates, n_experts, capacity):
    """One group. x: (S,d); eidx/pos/keep/gates: (S,k). Returns (y, buf_in)."""
    S, d = x.shape
    k = eidx.shape[-1]
    e_flat = eidx.reshape(-1)
    p_flat = jnp.where(keep, pos, capacity).reshape(-1)     # OOB -> dropped
    x_rep = jnp.repeat(x[:, None], k, axis=1).reshape(-1, d)
    buf = jnp.zeros((n_experts, capacity, d), x.dtype)
    buf = buf.at[e_flat, p_flat].add(x_rep, mode="drop")
    return buf, (e_flat, p_flat)


def moe_apply(cfg, p, x, *, group_by_sequence=True):
    """x: (B, T, d) -> (y, aux_loss). Router in fp32."""
    B, T, d = x.shape
    E, top_k = cfg.moe.n_experts, cfg.moe.top_k
    cf = cfg.moe.capacity_factor
    if group_by_sequence and T > 1:
        G, S = B, T
    else:
        G, S = 1, B * T
    xg = x.reshape(G, S, d)

    logits = (xg @ p["router"]["w"].astype(xg.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # (G,S,E)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)        # (G,S,k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    capacity = max(int(S * top_k * cf / E + 0.999), top_k)
    capacity = -(-capacity // 4) * 4                         # align 4

    # position of each (token, k) assignment within its expert, per group
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)    # (G,S,k,E)
    oh_flat = onehot.reshape(G, S * top_k, E)
    pos_all = jnp.cumsum(oh_flat, axis=1) - oh_flat          # (G,S*k,E)
    pos = (pos_all * oh_flat).sum(-1).reshape(G, S, top_k)
    keep = pos < capacity

    def _act(a, b3=None):
        if cfg.mlp_act == "silu":
            return jax.nn.silu(a) * b3
        if cfg.mlp_act == "geglu":
            return jax.nn.gelu(a) * b3
        return jax.nn.gelu(a)

    if cfg.moe_gather_weights:
        # batched einsum over (G,E,C,d) with output pinned to the dispatch
        # sharding; measured WORSE than the vmapped path on grok train
        # (387s vs 266s collective term) — kept for the sec Perf record
        buf, e_flat, p_flat = jax.vmap(lambda xg_, ei, po, ke: (
            lambda r: (r[0], r[1][0], r[1][1]))(_dispatch_group(
                xg_, ei, po, ke, None, E, capacity)))(
                    xg, gate_idx, pos, keep)
        U = jax.sharding.PartitionSpec.UNCONSTRAINED

        def _c(t):
            if "model" not in jax.sharding.get_abstract_mesh().axis_names:
                return t          # single-device (tests): no-op
            spec = jax.sharding.PartitionSpec(*([U] * (t.ndim - 1)), "model")
            return jax.lax.with_sharding_constraint(t, spec)

        h = _act(_c(jnp.einsum("gecd,edf->gecf", buf, p["w1"]["w"])),
                 _c(jnp.einsum("gecd,edf->gecf", buf, p["w3"]["w"]))
                 if "w3" in p else None)
        out_all = jnp.einsum("gecf,efd->gecd", h, p["w2"]["w"])

        def gather_group(out_g, e_flat_g, p_flat_g, ke, gv):
            g = out_g[e_flat_g, jnp.minimum(p_flat_g, capacity - 1)]
            g = g.reshape(S, top_k, d)
            return (g * (ke * gv).astype(g.dtype)[..., None]).sum(1)

        y = jax.vmap(gather_group)(out_all, e_flat, p_flat, keep, gate_vals)
    else:
        def per_group(xg_, ei, po, ke, gv):
            buf, (e_flat, p_flat) = _dispatch_group(
                xg_, ei, po, ke, gv, E, capacity)
            h = _act(jnp.einsum("ecd,edf->ecf", buf, p["w1"]["w"]),
                     jnp.einsum("ecd,edf->ecf", buf, p["w3"]["w"])
                     if "w3" in p else None)
            out = jnp.einsum("ecf,efd->ecd", h, p["w2"]["w"])    # (E,C,d)
            g = out[e_flat, jnp.minimum(p_flat, capacity - 1)]
            g = g.reshape(S, top_k, d)
            return (g * (ke * gv).astype(g.dtype)[..., None]).sum(1)

        y = jax.vmap(per_group)(xg, gate_idx, pos, keep, gate_vals)
    y = y.reshape(B, T, d)

    # Switch-style load-balance aux loss
    frac = onehot.reshape(G, S, top_k, E).sum((1, 2)) / (S * top_k)  # (G,E)
    mean_prob = probs.mean(1)                                        # (G,E)
    aux = E * (frac.astype(jnp.float32) * mean_prob).sum(-1).mean()
    return y, aux
