"""LoRA semantics: merged-weights equivalence, pool management, delta paths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import lora as lora_lib
from repro.models import model
from repro.models.param import split


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("llama2-7b").smoke()
    params, _ = split(model.init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, params


def test_merged_weights_equivalence(setup):
    """y = x(W + AB) must equal base y + batched LoRA delta (paper Eq. 1)."""
    cfg, params = setup
    spec = lora_lib.AdapterSpec("ad0", rank=4, base_model=cfg.name)
    w = lora_lib.make_adapter_weights(cfg, spec, dtype=jnp.float32)
    B, L = 2, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, L), 0, cfg.vocab)

    # path 1: lora arg through the model
    pool = lora_lib.pool_init(cfg)
    pool = lora_lib.pool_insert(pool, cfg, w, slot=1, rank=4)
    lora = {"pool": pool, "idx": jnp.ones((B,), jnp.int32), "mode": "bgmv"}
    got, _ = model.prefill(cfg, params, {"tokens": toks}, lora=lora)

    # path 2: merge AB into the q/k/v projections
    merged = jax.tree.map(lambda x: x, params)
    import copy
    blocks = {k: v for k, v in params["blocks"].items()}
    H, KV, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_model
    for tgt, nh in (("q", H), ("k", KV), ("v", KV)):
        delta = jnp.einsum("ldr,lro->ldo", w[tgt]["a"], w[tgt]["b"])
        wkey = {"q": "wq", "k": "wk", "v": "wv"}[tgt]
        old = blocks["attn"][wkey]["w"]          # (Llayers, d, nh, hd)
        blocks["attn"] = dict(blocks["attn"])
        blocks["attn"][wkey] = dict(blocks["attn"][wkey])
        blocks["attn"][wkey]["w"] = old + delta.reshape(old.shape)
    merged = dict(params)
    merged["blocks"] = blocks
    want, _ = model.prefill(cfg, merged, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-4, rtol=5e-4)


def test_no_adapter_is_base_model(setup):
    cfg, params = setup
    B, L = 2, 6
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, L), 0, cfg.vocab)
    pool = lora_lib.pool_init(cfg)
    lora = {"pool": pool, "idx": jnp.full((B,), -1, jnp.int32),
            "mode": "bgmv"}
    got, _ = model.prefill(cfg, params, {"tokens": toks}, lora=lora)
    want, _ = model.prefill(cfg, params, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_heterogeneous_batch_mixes_adapters(setup):
    """Row b must receive exactly adapter idx[b]'s delta."""
    cfg, params = setup
    specs = [lora_lib.AdapterSpec(f"a{i}", rank=2 ** (i + 1),
                                  base_model=cfg.name) for i in range(3)]
    pool = lora_lib.pool_init(cfg)
    for i, s in enumerate(specs):
        pool = lora_lib.pool_insert(
            pool, cfg, lora_lib.make_adapter_weights(cfg, s), i,
            min(s.rank, cfg.lora.max_rank))
    L = 5
    toks = jax.random.randint(jax.random.PRNGKey(3), (3, L), 0, cfg.vocab)
    mixed, _ = model.prefill(cfg, params, {"tokens": toks},
                             lora={"pool": pool,
                                   "idx": jnp.array([0, 1, 2]),
                                   "mode": "bgmv"})
    for b in range(3):
        solo, _ = model.prefill(
            cfg, params, {"tokens": toks[b:b + 1]},
            lora={"pool": pool, "idx": jnp.array([b]), "mode": "bgmv"})
        np.testing.assert_allclose(np.asarray(mixed[b]),
                                   np.asarray(solo[0]), atol=2e-4, rtol=2e-4)


def test_bgmv_mbgmv_model_equivalence(setup):
    cfg, params = setup
    spec = lora_lib.AdapterSpec("ad", rank=3, base_model=cfg.name)
    pool = lora_lib.pool_init(cfg)
    pool = lora_lib.pool_insert(
        pool, cfg, lora_lib.make_adapter_weights(cfg, spec), 0, 3)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 7), 0, cfg.vocab)
    outs = []
    for mode in ("bgmv", "mbgmv"):
        o, _ = model.prefill(cfg, params, {"tokens": toks},
                             lora={"pool": pool,
                                   "idx": jnp.zeros((2,), jnp.int32),
                                   "mode": mode})
        outs.append(np.asarray(o))
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-5)


def test_device_pool_lru_and_pinning():
    cfg = get_config("llama2-7b").smoke()
    pool = lora_lib.DevicePool(cfg, n_slots=2, materialize=False)
    assert pool.insert("a", None, 4) == 0
    assert pool.insert("b", None, 8) == 1
    assert pool.lookup("a") == 0          # refreshes LRU
    assert pool.insert("c", None, 2) == 1  # evicts b (LRU)
    assert pool.lookup("b") is None
    # pinned slots are not evictable
    assert pool.insert("d", None, 2, pinned=(0, 1)) is None


def test_adapter_nbytes_scales_with_rank():
    cfg = get_config("llama2-7b")
    s8 = lora_lib.AdapterSpec("x", 8, cfg.name).nbytes(cfg)
    s64 = lora_lib.AdapterSpec("y", 64, cfg.name).nbytes(cfg)
    assert abs(s64 / s8 - 8.0) < 1e-6
    # rank-64 q/k/v adapter of llama2-7b ~ 100 MiB (paper sec 2.3)
    assert 50e6 < s64 < 250e6


def test_adapter_seed_is_a_stable_digest():
    """Adapter weights are a pure function of (uid, seed): the derived seed
    is a crc32 digest, identical in every process (str hash is salted)."""
    spec = lora_lib.AdapterSpec("a0", rank=8, base_model="yi-9b", seed=3)
    assert lora_lib.adapter_seed(spec) == 483136901
    assert lora_lib.adapter_seed(
        lora_lib.AdapterSpec("a0", rank=8, base_model="yi-9b")) == 2244297791
    cfg = get_config("llama2-7b").smoke()
    w1 = lora_lib.make_adapter_weights(cfg, spec)
    w2 = lora_lib.make_adapter_weights(cfg, spec)
    np.testing.assert_array_equal(np.asarray(w1["q"]["a"]),
                                  np.asarray(w2["q"]["a"]))
