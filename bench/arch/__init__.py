"""What the benchmark knows of one architecture, in one module of its own.

A configuration file (`bench/configs/<name>.json`) names its architecture
under `"architecture"`; `load` returns the module
`bench.arch.<architecture>`, and `load(conf, "reference")` its plain
reference `bench.reference.<architecture>`. The generic harness
(`bench/weights.py`, `bench/flops.py`, `bench/correct.py`,
`bench/tests/tiny.py`) routes every question about the block's shapes to
those modules, so a configuration of a new architecture enters the
benchmark as new files only:

* `bench/arch/<name>.py`: this contract;
* `bench/reference/<name>.py`: its plain float32 reference, which reads
  the weight tree that `weights` lays out;
* `bench/configs/<name>.json`: its sizes, naming the architecture;
* `bench/limits/<name>.json`: the limit of its comparison;
* `bench/traffic/<mix>.json`: a traffic mix, where no existing one fits;
* `bench/metrics/<new>.py`: a reader of a metric that only it has;

and new entries in `BENCHMARK.json`.

A module provides, each taking the configuration file's dict `conf`:

* `program_config(conf)`: the program's `ModelConfig`, checked to describe
  this architecture at these sizes (raises `ValueError` where it does not).
* `weights(conf, key)`: the base weights from one JAX key, traceable (it
  runs inside one `jax.jit`), laid out as the program's parameter tree
  and in the type they are served in. `bench/weights.py` checks the tree
  against the program's.
* `adapter(conf, key, rank)`: one adapter, traceable, `rank` a traced
  int32: `{target: {"a": (L, d_in, r_max), "b": (L, r_max, d_out)}}`,
  zero beyond `rank`.
* `tiny(conf)`: a copy of `conf` shrunk in width and depth for the CPU
  tests (`bench/tests/tiny.py` then shrinks the adapters and the server).
* The work counts, which `bench/flops.py` routes to (a multiply-add is two
  operations; true contexts and ranks, never padded ones):
  `layer_matmul_params(conf)`: weights one token multiplies through in
  one layer; `lora_flops(conf, rank)`: one token through one layer's
  adapter; `attn_flops(conf, ctx)`: one query against `ctx` cached
  tokens in one layer; `token_flops(conf, ctx, rank)`: one decoded token
  through every layer and the unembed; `prefill_flops(conf, length,
  rank)`: a causal prompt that unembeds its last position;
  `paged_attn_flops(conf, ctxs)` and `paged_attn_bytes(conf, ctxs)`: the
  paged decode kernel over every layer, one query per row. A metric
  file that needs a count only its own architecture defines calls it on
  `load(conf)`.

Two rules keep the shares of a roofline and of the chip's peak at or under
100%, where a reading above 105% is refused:

* `paged_attn_flops` and `paged_attn_bytes` count only the layers that the
  paged kernel itself runs, each at the context that layer attends to: a
  windowed layer counts `min(ctx, window)` tokens.
* `token_flops` and `prefill_flops` count the work this chip does. Under
  an expert share that is `top_k * held / published` of the routed
  experts' matmuls per token, plus any shared expert once.
"""
from __future__ import annotations

import importlib
from types import ModuleType


def load(conf: dict, package: str = "arch") -> ModuleType:
    """The module `bench.<package>.<conf["architecture"]>`: `arch` for the
    block's shapes and counts, `reference` for its plain reference."""
    name = conf.get("architecture")
    if not isinstance(name, str) or not name.isidentifier():
        raise ValueError(f"{conf.get('name')}: \"architecture\" is {name!r}; "
                         "it has to name a module, expected "
                         f"bench/{package}/<name>.py")
    module = f"bench.{package}.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise ValueError(f"{conf.get('name')}: architecture {name!r} has no "
                         f"module: expected bench/{package}/{name}.py") \
            from None
