"""Expert-parallel MoE via shard_map all-to-all — the beyond-paper fix for
the GSPMD-einsum MoE's pathological collectives (EXPERIMENTS.md sec Perf B).

Experts are owned by shards of the `data` axis; tokens travel to their
experts and back with two all-to-alls (token-proportional bytes), instead of
the einsum formulation's activation-sized all-reduces against FSDP-sharded
expert weights.

Shard layout over n_data = |data axis| (built by `shard_expert_weights`):
  * n_data >= E (production: grok 8 on 16, dbrx 16 on 16): each expert's
    d_ff is split into s = n_data/E slices; shard j owns slice j%s of
    expert j//s. Tokens are duplicated to all s slices of their expert and
    the partial outputs (w2 contracts over the f-slice) sum on return.
  * n_data < E (smoke tests): each shard owns E/n_data whole experts.

Within a shard the f-slice is further TP-sharded over `model` (partial
outputs psum over "model"). Differentiable end-to-end (all_to_all/psum have
transposes); numerics match moe_apply when capacity is not binding
(tests/test_moe_ep.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.param import Box


def ep_factors(E: int, n_data: int):
    """(s_factor, e_per_shard): f-slices per expert, experts per shard."""
    if n_data >= E:
        if n_data % E:
            raise ValueError(f"n_data ({n_data}) not a multiple of E ({E})")
        return n_data // E, 1
    if E % n_data:
        raise ValueError(f"E ({E}) not a multiple of n_data ({n_data})")
    return 1, E // n_data


def shard_expert_weights(cfg, p, n_data: int):
    """Global expert weights (E,d,f)/(E,f,d) -> EP layout with leading dim
    n_shards*e_per (sharded over data) and the f slice dim. No-op when the
    weights are already stored EP-native (cfg.moe_ep at init)."""
    E = cfg.moe.n_experts
    s, e_per = ep_factors(E, n_data)
    f = cfg.d_ff
    fs = f // s
    if p["w1"]["w"].shape[0] == E * s and p["w1"]["w"].shape[2] == fs:
        return p                        # already EP-native

    def win(w):                       # (E, d, f) -> (E*s, d, f/s)
        E_, d_, f_ = w.shape
        return w.reshape(E_, d_, s, fs).transpose(0, 2, 1, 3) \
                .reshape(E_ * s, d_, fs)

    def wout(w):                      # (E, f, d) -> (E*s, f/s, d)
        E_, f_, d_ = w.shape
        return w.reshape(E_, s, fs, d_).reshape(E_ * s, fs, d_)

    out = {"router": p["router"], "w1": {"w": win(p["w1"]["w"])},
           "w2": {"w": wout(p["w2"]["w"])}}
    if "w3" in p:
        out["w3"] = {"w": win(p["w3"]["w"])}
    return out


def moe_apply_ep(cfg, p, x, mesh, *, data_axes=("data",)):
    """x: (B, T, d) -> (y, aux). p: standard moe params (global layout);
    resharded to the EP layout on the fly (a reshape/transpose GSPMD handles
    once per step, amortized across the layer scan by XLA CSE)."""
    B, T, d = x.shape
    E, top_k = cfg.moe.n_experts, cfg.moe.top_k
    cf = cfg.moe.capacity_factor
    axis_sizes = getattr(mesh, "axis_sizes", None)
    if axis_sizes is None:
        axis_sizes = mesh.devices.shape
    sizes = dict(zip(mesh.axis_names, axis_sizes))
    n_data = 1
    for a in data_axes:
        n_data *= sizes.get(a, 1)
    s_factor, e_per = ep_factors(E, n_data)
    n_shards = n_data
    tokens_global = B * T
    if tokens_global % n_data:
        raise ValueError(
            f"B*T ({tokens_global}) must divide over the data axis "
            f"({n_data} shards)")
    t_loc = tokens_global // n_data
    cap = max(-(-t_loc * top_k * int(cf * 4) // (4 * E)), top_k)
    cap = -(-cap // 4) * 4

    pe = shard_expert_weights(cfg, p, n_data)
    P = jax.sharding.PartitionSpec
    da = data_axes if len(data_axes) > 1 else data_axes[0]

    def local(x_loc, wr, w1, w2, w3):
        # x_loc: (t_loc, d); w1: (e_per, d, f_loc); w2: (e_per, f_loc, d)
        logits = (x_loc @ wr).astype(jnp.float32)           # (t, E)
        probs = jax.nn.softmax(logits, -1)
        gate_vals, gate_idx = jax.lax.top_k(probs, top_k)   # (t, k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)
        oh = onehot.reshape(t_loc * top_k, E)
        pos = (jnp.cumsum(oh, 0) - oh)                      # (t*k, E)
        pos = (pos * oh).sum(-1).reshape(t_loc, top_k)
        keep = pos < cap

        # scatter into (n_shards, e_per, cap, d); under s_factor>1 each
        # assignment is duplicated to the s f-slices of its expert
        buf = jnp.zeros((n_shards * e_per * cap, d), x_loc.dtype)
        x_rep = jnp.repeat(x_loc[:, None], top_k, 1).reshape(-1, d)
        e_flat = gate_idx.reshape(-1)
        p_flat = jnp.where(keep, pos, cap).reshape(-1)      # cap -> dropped
        for r in range(s_factor):
            shard = e_flat * s_factor + r if e_per == 1 \
                else e_flat // e_per
            ew = jnp.zeros_like(e_flat) if e_per == 1 else e_flat % e_per
            flat_idx = (shard * e_per + ew) * cap + p_flat
            oob = jnp.where(p_flat >= cap, buf.shape[0], flat_idx)
            buf = buf.at[oob].add(x_rep, mode="drop")
        buf = buf.reshape(n_shards, e_per * cap, d)

        recv = jax.lax.all_to_all(buf, da, 0, 0, tiled=True)
        # recv: (n_shards, e_per*cap, d) — row j: tokens from source j
        xin = recv.reshape(n_shards, e_per, cap, d)
        h1 = jnp.einsum("jecd,edf->jecf", xin, w1)
        if w3 is not None:
            act = jax.nn.silu(h1) if cfg.mlp_act == "silu" \
                else jax.nn.gelu(h1)
            h = act * jnp.einsum("jecd,edf->jecf", xin, w3)
        else:
            h = jax.nn.gelu(h1)
        out = jnp.einsum("jecf,efd->jecd", h, w2)           # f-slice partial
        if "model" in sizes:
            out = jax.lax.psum(out, "model")
        back = jax.lax.all_to_all(
            out.reshape(n_shards, e_per * cap, d), da, 0, 0, tiled=True)
        back = back.reshape(n_shards, e_per, cap, d)

        # combine: sum the s_factor f-slice partials + gate weights
        y = jnp.zeros((t_loc, d), x_loc.dtype)
        safe_p = jnp.minimum(p_flat, cap - 1)
        contrib = jnp.zeros((t_loc * top_k, d), x_loc.dtype)
        for r in range(s_factor):
            shard = e_flat * s_factor + r if e_per == 1 \
                else e_flat // e_per
            ew = jnp.zeros_like(e_flat) if e_per == 1 else e_flat % e_per
            contrib = contrib + back[shard, ew, safe_p]
        contrib = contrib.reshape(t_loc, top_k, d)
        w = (keep * gate_vals).astype(contrib.dtype)[..., None]
        y = (contrib * w).sum(1)

        # load-balance aux (local estimate, averaged over data shards)
        frac = onehot.sum((0, 1)).astype(jnp.float32) / (t_loc * top_k)
        aux = E * (frac * probs.mean(0)).sum()
        aux = jax.lax.pmean(aux, da)
        if "model" in sizes:
            aux = jax.lax.pmean(aux, "model")
        return y, aux

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(da, None), P(None, None), P(da, None, "model"),
                  P(da, "model", None), P(da, None, "model")),
        out_specs=(P(da, None), P()),
    )
    w3 = pe["w3"]["w"] if "w3" in pe else jnp.zeros(
        (pe["w1"]["w"].shape[0], d, pe["w1"]["w"].shape[2]), x.dtype)
    y, aux = fn(x.reshape(tokens_global, d), pe["router"]["w"],
                pe["w1"]["w"], pe["w2"]["w"], w3)
    return y.reshape(B, T, d), aux
