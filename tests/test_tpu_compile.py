"""Compile-only checks against a described TPU v5e (no chip attached).

The chip's compiler (Mosaic, for Pallas kernels) refuses things interpret
mode accepts — block shapes off the (8, 128) tiling, for one — so the
served path's kernels are compiled here at published widths. Each test
asserts `tpu_custom_call` in the compiled HLO: an interpreted lowering
(plain XLA ops, no kernel) cannot pass.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.core import lora as lora_lib
from repro.core.backend import NumericsBackend
from repro.kernels.paged import paged_attention
from repro.models import layers
from repro.models import model as model_lib
from repro.models.param import split


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            jax.config.update("jax_enable_compilation_cache", was)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("H,KV,hd,B,W,P", [
    (32, 4, 128, 8, 64, 520),
    (32, 32, 128, 8, 64, 520),
    (32, 32, 96, 4, 32, 152),
], ids=["yi9b-gqa8", "mha", "phi3mini-hd96"])
def test_paged_attention_compiles_for_v5e(one_chip, H, KV, hd, B, W, P):
    """Decode widths of Yi-9B (GQA group 8), of an MHA model (group 1) and
    of Phi-3-mini (MHA at head_dim 96, off the 128-lane tiling; 4 rows of
    32-page tables): 32-slot pages, bf16."""
    ps = 32
    sd = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    fn = jax.jit(functools.partial(paged_attention, interpret=False))
    compiled = fn.lower(
        sd((B, H, hd), jnp.bfloat16), sd((P, KV, ps, hd), jnp.bfloat16),
        sd((P, KV, ps, hd), jnp.bfloat16), sd((P, ps), jnp.int32),
        sd((B, W), jnp.int32), sd((B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_served_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """The fused decode step the server dispatches (paged cache, LoRA
    delta, on-device sampling) at Yi-9B's published widths, 2 layers,
    with the Pallas paged kernel on the path."""
    monkeypatch.setattr(layers, "PAGED_ATTN_IMPL", "pallas")
    # the described chip is not the default backend: steer the kernel's
    # interpret-mode default the way a TPU process would see it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(get_config("yi-9b"), n_layers=2)
    B, ps, W, P = 8, 32, 32, 300
    params, _ = model_lib.abstract_params(cfg)
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    cache = {"k": jax.ShapeDtypeStruct((L, P, KV, ps, hd), cfg.jdtype),
             "v": jax.ShapeDtypeStruct((L, P, KV, ps, hd), cfg.jdtype),
             "pos": jax.ShapeDtypeStruct((L, P, ps), jnp.int32)}
    pool, _ = split(lora_lib.pool_abstract(cfg))
    i32 = jnp.int32
    vec = jax.ShapeDtypeStruct((B,), i32)
    args = _shapes((params, cache, vec, vec,
                    jax.ShapeDtypeStruct((B,), jnp.bool_), vec,
                    {"pool": pool, "idx": vec},
                    jax.ShapeDtypeStruct((2,), jnp.uint32),
                    jax.ShapeDtypeStruct((B, W), i32)), one_chip)
    step = jax.jit(functools.partial(NumericsBackend._decode_fused_fn, cfg,
                                     "bgmv", 0.0, True))
    compiled = step.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 16e9
