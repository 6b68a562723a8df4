"""The configuration's architecture module (`bench/arch/`): every question
the harness asks of a block's shapes goes through it, the dense decoder's
weights and adapters are those the harness made before the module
existed, and a configuration without a module fails before any weight is
made."""
import hashlib
import json
import pathlib
import sys
import types

import jax
import numpy as np
import pytest

import tiny
from bench import arch, flops, harness
from bench import weights as weights_lib
from bench.arch import dense_decoder

BENCH = pathlib.Path(__file__).resolve().parents[1]
STANDIN = "standin_arch"
COUNTS = ("layer_matmul_params", "lora_flops", "attn_flops", "token_flops",
          "prefill_flops", "paged_attn_flops", "paged_attn_bytes")


def _standin(calls):
    """A module that records each call and answers as the dense decoder,
    except that every count returns its own position in COUNTS plus 1."""
    mod = types.ModuleType(f"bench.arch.{STANDIN}")

    def recorded(name, fn):
        def wrapped(*a, **kw):
            calls.append(name)
            return fn(*a, **kw)
        return wrapped

    for name in ("program_config", "weights", "adapter", "tiny"):
        setattr(mod, name, recorded(name, getattr(dense_decoder, name)))
    for i, name in enumerate(COUNTS):
        setattr(mod, name, recorded(name, lambda *a, _v=i + 1: _v))
    return mod


def test_every_route_goes_through_the_named_module(monkeypatch, tmp_path):
    calls = []
    mod = _standin(calls)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    with open(BENCH / "configs" / "yi-9b-24l.json") as f:
        conf = dict(json.load(f), architecture=STANDIN, name="standin")
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    with open(tmp_path / "bench" / "configs" / "standin.json", "w") as f:
        json.dump(conf, f)
    monkeypatch.setattr(tiny, "ROOT", tmp_path)

    small = tiny.config("standin")
    assert calls == ["tiny"] and small["hidden_size"] == 64
    assert arch.load(small) is mod
    cfg = weights_lib.program_config(small)
    weights_lib.make_weights(small, cfg, 3)
    weights_lib.make_adapters(small, [("lora001", 2)], 3)
    assert calls == ["tiny", "program_config", "weights", "adapter"]

    del calls[:]
    got = [flops.layer_matmul_params(small), flops.lora_flops(small, 8),
           flops.attn_flops(small, 10), flops.token_flops(small, 10, 8),
           flops.prefill_flops(small, 10, 8),
           flops.paged_attn_flops(small, [10]),
           flops.paged_attn_bytes(small, [10])]
    assert got == list(range(1, len(COUNTS) + 1))
    assert calls == list(COUNTS)
    # decode_flops stays generic: a sum of the module's token_flops
    assert flops.decode_flops(small, [(10, 8), (20, 8)]) == 2 * 4

    # the route is data: no file of the benchmark but this one names it
    naming = [p for p in BENCH.rglob("*")
              if p.is_file() and p.suffix in (".py", ".json")
              and STANDIN in p.read_text()]
    assert naming == [pathlib.Path(__file__).resolve()]
    generic = [BENCH / "weights.py", BENCH / "flops.py", BENCH / "harness.py",
               BENCH / "correct.py", BENCH / "tests" / "tiny.py",
               *(BENCH / "metrics").glob("*.py")]
    assert not [p for p in generic if "dense_decoder" in p.read_text()]


def _digest(tree) -> str:
    """sha256 over every leaf's path, dtype, shape and bytes."""
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(jax.device_get(leaf))
        head = f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}"
        h.update(head.encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


# Taken on the CPU from the parent of the commit that added `bench/arch/`
# (its `tiny.config`, `weights.make_weights` and `weights.make_adapters`,
# with this `_digest`): moving the dense block into its module changed no
# bit of what the harness serves.
PARENT = {
    "yi-9b-24l": (2 ** 31 + 5, "9325fbbfab3a1b88", "2b597d365892d822"),
    "phi-3-mini-4k": (11, "d259619b69096c23", "92a6c7440b379add"),
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_weights_and_adapters_match_the_parent(name):
    seed, want_w, want_a = PARENT[name]
    conf = tiny.config(name)
    w = weights_lib.make_weights(conf, weights_lib.program_config(conf),
                                 seed)
    ads = [("lora000", 2), ("lora007", 8), ("lora063", 4)]
    assert _digest(w) == want_w
    assert _digest(weights_lib.make_adapters(conf, ads, seed)) == want_a


@pytest.mark.parametrize("value, where", [
    (None, "bench/arch/<name>.py"),
    ("no_such_arch", "bench/arch/no_such_arch.py"),
])
def test_unnamed_architecture_fails_before_any_weight(monkeypatch, value,
                                                      where):
    def made(*a, **kw):
        raise AssertionError("a weight was made")

    monkeypatch.setattr(weights_lib, "make_weights", made)
    monkeypatch.setattr(weights_lib, "make_adapters", made)
    conf = tiny.config("yi-9b-24l")
    conf.pop("architecture")
    if value is not None:
        conf["architecture"] = value
    with pytest.raises(ValueError) as e:
        harness.build(conf, tiny.mix("zipf64-poisson"), 5)
    assert where in str(e.value)


def test_the_architecture_names_the_reference_too():
    from bench import correct
    from bench.reference import dense_decoder as dense_reference
    conf = tiny.config("phi-3-mini-4k")
    assert arch.load(conf, "reference") is dense_reference
    conf["architecture"] = "no_such_arch"
    with pytest.raises(ValueError) as e:
        correct.reference(conf, None, 8, 1)
    assert "bench/reference/no_such_arch.py" in str(e.value)
