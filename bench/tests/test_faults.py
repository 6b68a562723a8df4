"""A run whose timed path is broken underneath must come out not correct,
and a sound run correct. Tiny cells on the CPU, past the look for a chip,
with the real limits. The faults a served cell can have: a step that
returns its state unchanged, half of the batch left out (its logits the
mean of the rest), and a token altered where it is produced. A one-chip
cell has no exchange between chips to leave out."""
import contextlib

import jax.numpy as jnp
import pytest

import tiny

CELLS = ["yi9b.zipf64-poisson", "phi3mini.longctx-backlog",
         "yi9b.resident-backlog"]


@contextlib.contextmanager
def _patched(obj, name, value):
    old = obj.__dict__[name]
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _unchanged_state():
    from repro.core.backend import NumericsBackend
    step = NumericsBackend._fused_step

    def fused_step(cfg, mode, temperature, mask_ok, params, lora, cache,
                   *rest, **kw):
        _, last_tok, pos, toks, rng = step(cfg, mode, temperature, mask_ok,
                                           params, lora, cache, *rest, **kw)
        return cache, last_tok, pos, toks, rng
    return _patched(NumericsBackend, "_fused_step", staticmethod(fused_step))


def _half_batch():
    from repro.core import backend
    sample = backend.sample

    def half(logits, **kw):
        b = logits.shape[0] // 2
        mean = jnp.mean(logits[:b], axis=0, keepdims=True)
        rest = jnp.broadcast_to(mean, logits[b:].shape)
        return sample(jnp.concatenate([logits[:b], rest]), **kw)
    return _module_patch(backend, "sample", half)


@contextlib.contextmanager
def _module_patch(mod, name, value):
    old = getattr(mod, name)
    setattr(mod, name, value)
    try:
        yield
    finally:
        setattr(mod, name, old)


def _altered_token():
    from repro.core.backend import DecodePipeline
    drain = DecodePipeline._drain_one

    def altered(self):
        _, entries = self._pending[0]
        before = [len(st.generated) for st, _, _ in entries]
        drain(self)
        for (st, _, n), b in zip(entries, before):
            if n and st.req.rid % 3 == 0:
                st.generated[b] = (st.generated[b] + 1) % 256
    return _patched(DecodePipeline, "_drain_one", altered)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = tiny.run(cell, seed=2 ** 31 + 3)
    assert res["correct"], res["checks"]
    assert res["window_compiles"] == 0


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _altered_token])
def test_broken_path_is_not_correct(fault, cell):
    with fault():
        res = tiny.run(cell, seed=11)
    assert not res["correct"], res["checks"]
