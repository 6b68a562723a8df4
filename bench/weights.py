"""Weights and adapters, made by the benchmark from the seed.

The benchmark makes every weight itself, so the reference that judges the
served tokens takes nothing the program made: the base weights in one
jitted call on the device, in the type they are served in (bf16), laid out
as the program holds them; each adapter on the device and then copied to
host memory, where the server's adapter store keeps it (a cold start
uploads it from there). A configuration file (`bench/configs/<name>.json`)
holds the published sizes and names its architecture's module
(`bench/arch/<name>.py`), which turns it into the program's `ModelConfig`,
checks that nothing was lost, and lays out the weights and adapters.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch


def program_config(conf: dict):
    """The program's ModelConfig for a configuration file, checked by the
    configuration's architecture module."""
    return arch.load(conf).program_config(conf)


def jax_key(seed: int, stream: int):
    """A JAX key for (seed, stream), for any integer seed."""
    s = seed & (2 ** 64 - 1)
    k = jax.random.fold_in(jax.random.PRNGKey(stream), s & 0x7FFFFFFF)
    return jax.random.fold_in(k, (s >> 31) & 0xFFFFFFFF)


def make_weights(conf: dict, cfg, seed: int):
    """Base weights on the device in one jitted call; checked against the
    tree of shapes the program expects."""
    from repro.models import model as model_lib
    build = arch.load(conf).weights
    w = jax.jit(lambda k: build(conf, k))(jax_key(seed, 0))
    want = model_lib.abstract_params(cfg)[0]
    got_s = jax.tree.map(lambda x: (x.shape, x.dtype), w)
    want_s = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if jax.tree.structure(got_s) != jax.tree.structure(want_s) \
            or jax.tree.leaves(got_s) != jax.tree.leaves(want_s):
        raise ValueError(f"{conf['name']}: the benchmark's weight layout no "
                         "longer matches the program's parameter tree")
    return w


def make_adapters(conf: dict, ads, seed: int) -> Dict[str, dict]:
    """{uid: {target: {a: (L, d_in, r_max), b: (L, r_max, d_out)}}} as host
    arrays, zero beyond each adapter's rank; `ads` is [(uid, rank)]."""
    build = arch.load(conf).adapter
    fn = jax.jit(lambda k, r: build(conf, k, r))
    base = jax_key(seed, 1)
    out = {}
    for uid, rank in ads:
        k = jax.random.fold_in(base, int(uid[4:]))
        dev = fn(k, jnp.int32(rank))
        out[uid] = jax.tree.map(np.asarray, jax.device_get(dev))
    return out
