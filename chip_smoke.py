"""Serve Yi-9B at its published widths on one TPU and check the tokens.

    python chip_smoke.py [--seed 0]

Drives the served path once, in one process, through the entry points a
user calls: `InferenceServer(mode="caraserve")` -> `AdmissionPlane` ->
`NumericsBackend` — batched paged prefill with the staged adapter
(CPU-assisted while the adapter is cold), paged decode through the Pallas
kernel with the LoRA delta, megasteps, async readback. The model is
Yi-9B (arXiv:2403.04652: d_model 4096, 32 query / 4 KV heads, head_dim
128, d_ff 11008, vocab 64000, bf16, LoRA on q/k/v up to rank 64) cut to
24 of its 48 layers — one stage of a two-stage pipeline, since the whole
model (17.6 GB in bf16) does not fit one 16 GB chip. Weights and adapters
are random, made from `--seed`.

Checks, each fatal:
  * the run cold-starts adapters, serves them CPU-assisted, flips them to
    the device pool, and fuses decode iterations into megasteps;
  * the decode step's HLO holds the Pallas kernel (`tpu_custom_call`);
  * the Pallas paged kernel agrees with `ref.paged_attention_ref` on the
    live KV cache;
  * every generated token is (near-)top-1 under an independent reference:
    `model_lib.prefill` over prompt + output, same params and adapter, no
    paged cache, no Pallas kernel, no pipeline.

The last line of stdout is `{"ok": true, "device": {...}}`. Without a TPU
the script exits non-zero before anything runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

LAYERS = 24
MAX_BATCH = 8
# a row's block table must hold prompt + output (512 + 64) without the
# ring wrapping, or the server would attend less context than the reference
CACHE_SLOTS = 640
RANKS = (8, 16, 32, 64)
# (arrival ms on the server's virtual clock, adapter rank, prompt tokens,
# output tokens). Both t=0 arrivals share one batched cold prefill; every
# first use of a rank is a cold start, CPU-assisted while its upload runs;
# later uses hit the device pool. After the last arrival nothing is queued
# and no upload is in flight, so decode fuses into megasteps.
TRACE = ((0.0, 8, 400, 48), (0.0, 16, 200, 40), (60.0, 8, 300, 64),
         (120.0, 32, 500, 32), (180.0, 64, 100, 64), (240.0, 16, 450, 48),
         (300.0, 64, 64, 56), (360.0, 32, 350, 40))

# Reference check: the logit the reference gives the server's token may
# trail the reference's top logit by at most this much. Both paths run in
# bf16 (8 significant bits): the logits themselves are bf16, whose spacing
# at the top-logit magnitude (about 4 here: the max of 64000 unit-variance
# logits) is 2**-5, and the two paths round differently through 24 layers
# (the Pallas kernel accumulates attention in f32, the reference's
# attention rounds scores and probabilities to bf16; decode runs one token
# per matmul, the reference all of them). A few such ulps give 0.25. A
# wrong adapter, page or position picks a token unrelated to the
# reference's ranking, whose margin is about 4.
LOGIT_MARGIN_TOL = 0.25
# Kernel check: the kernel reads bf16 K/V and accumulates in f32; the
# oracle gets the same values upcast to f32 and runs at full f32 matmul
# precision. The output is a softmax-weighted mean of V rows, so two
# roundings bound the gap, each by bf16's unit roundoff (2**-8) times the
# largest |V|: the MXU may round the f32 probabilities to bf16 for the
# P·V product, and the kernel rounds its output to bf16. A wrong page,
# mask or head mixes other rows' values in: an error the size of |V|.
KERNEL_TOL_OF_VMAX = 2.0 ** -7


def device_info():
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


class CompileClock:
    """Compilation as JAX's own monitoring events report it: `events`
    counts every trace, lowering or compile (a call that saw one was not
    steady), `secs` and `programs` sum the XLA backend compiles, and
    `cache_hits` counts programs loaded from the persistent cache instead.
    Traces nest (a jit traced inside another reports its own), so only
    the backend compiles are summed as time."""

    def __init__(self):
        self.events = 0
        self.secs = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def _on_event(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.events += 1
            if event.endswith("backend_compile_duration"):
                self.secs += duration
                self.programs += 1

    def _on_count(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class PhaseTimer:
    """Host wall time of each backend call, measured after
    `block_until_ready` on what the call produced. Calls that compiled are
    counted apart, so the steady numbers hold no compilation."""

    def __init__(self, backend, clock: CompileClock):
        self.clock = clock
        self.stats = {}
        for name in ("prefill_admitted", "decode", "megastep"):
            self._wrap(backend, name)

    def _wrap(self, backend, name):
        fn = getattr(backend, name)
        st = self.stats.setdefault(name, {"calls": 0, "secs": 0.0,
                                          "compiling_calls": 0,
                                          "compiling_secs": 0.0})

        def timed(*a, **kw):
            c0, t0 = self.clock.events, time.perf_counter()
            out = fn(*a, **kw)
            jax.block_until_ready((backend.cache, backend.pipe.last_tok))
            dt = time.perf_counter() - t0
            if self.clock.events > c0:
                st["compiling_calls"] += 1
                st["compiling_secs"] += dt
            else:
                st["calls"] += 1
                st["secs"] += dt
            return out

        setattr(backend, name, timed)


def build_server(cfg, seed):
    from repro.core.engine import InferenceServer
    from repro.core.lora import AdapterSpec
    srv = InferenceServer(cfg, mode="caraserve", kernel="bgmv",
                          max_batch=MAX_BATCH, cache_slots=CACHE_SLOTS,
                          seed=seed)
    for r in RANKS:
        srv.register_adapter(AdapterSpec(f"r{r}", rank=r,
                                         base_model=cfg.name, seed=seed))
    return srv


def build_trace(cfg, seed):
    from repro.serving.request import Request
    rng = np.random.default_rng(seed)
    return [Request(rid=i, adapter_uid=f"r{rank}",
                    prompt=rng.integers(0, cfg.vocab, plen).astype(np.int32),
                    max_new_tokens=nout, arrival_ms=t)
            for i, (t, rank, plen, nout) in enumerate(TRACE)]


def check_run(srv, summary):
    ms = srv.backend.transfer_stats["megasteps"]
    counts = {k: summary.get(k, 0) for k in ("cold_starts", "assisted",
                                             "flipped")}
    print(f"served {summary['n']}/{len(TRACE)} requests: "
          + ", ".join(f"{k} {v}" for k, v in counts.items())
          + f", megasteps {ms}, decode iterations "
          f"{srv.backend.transfer_stats['decode_steps']}")
    if summary["n"] != len(TRACE):
        raise SystemExit(f"only {summary['n']} of {len(TRACE)} finished")
    for k, v in counts.items():
        if v == 0:
            raise SystemExit(f"run had no {k}: the cold-start path was not "
                             "exercised")
    if ms == 0:
        raise SystemExit("run had no megastep")
    for st in srv.states:
        if len(st.generated) != st.req.max_new_tokens:
            raise SystemExit(f"request {st.req.rid}: {len(st.generated)} "
                             f"tokens, wanted {st.req.max_new_tokens}")


def check_decode_hlo(srv):
    """The served decode step must run the Pallas kernel, not a fallback."""
    be, pipe = srv.backend, srv.backend.pipe
    text = be._decode_jit.lower(
        be.params, be.cache, pipe.last_tok, pipe.pos, pipe.active,
        pipe.target, {"pool": be.pool.pool, "idx": pipe.idx}, pipe.rng,
        pipe.block_table).as_text()
    if "tpu_custom_call" not in text:
        raise SystemExit("decode step holds no tpu_custom_call: the Pallas "
                         "paged kernel is not on the served path")
    print("decode step HLO: tpu_custom_call present (Pallas paged kernel)")


def check_kernel(srv, seed):
    """Pallas kernel vs `ref.paged_attention_ref` on the live page pool:
    the last decode batch's block tables, and tables drawn over every
    written page. Returns the worst error over its tolerance."""
    from repro.kernels import ref
    from repro.kernels.paged import paged_attention
    cfg, cache, pipe = srv.cfg, srv.backend.cache, srv.backend.pipe
    rng = np.random.default_rng(seed + 7)
    B, W = pipe.block_table.shape
    worst = {"err": 0.0, "ratio": 0.0}
    for layer in sorted({0, cfg.n_layers // 2, cfg.n_layers - 1}):
        kp, vp, pp = (cache[n][layer] for n in ("k", "v", "pos"))
        written = np.flatnonzero(np.asarray((pp >= 0).any(axis=1)))
        drawn = np.stack([rng.choice(written, W, replace=len(written) < W)
                          for _ in range(B)]).astype(np.int32)
        tables = ((pipe.block_table, pipe.pos),
                  (jnp.asarray(drawn), jnp.full((B,), 2 ** 30, jnp.int32)))
        f32 = lambda x: x.astype(jnp.float32)
        tol = KERNEL_TOL_OF_VMAX * float(jnp.max(jnp.abs(f32(vp))))
        for bt, pos in tables:
            q = jnp.asarray(rng.normal(size=(B, cfg.n_heads, cfg.hd)),
                            cfg.jdtype)
            got = paged_attention(q, kp, vp, pp, bt, pos)
            with jax.default_matmul_precision("float32"):
                want = ref.paged_attention_ref(f32(q), f32(kp), f32(vp), pp,
                                               bt, pos)
            err = float(jnp.max(jnp.abs(f32(got) - want)))
            worst["err"] = max(worst["err"], err)
            worst["ratio"] = max(worst["ratio"], err / tol)
    print(f"paged kernel vs oracle on the live cache (B {B}, W {W}, "
          f"layers 0/{cfg.n_layers // 2}/{cfg.n_layers - 1}): max abs err "
          f"{worst['err']!r}, worst err/tol {worst['ratio']!r}")
    if worst["ratio"] > 1.0:
        raise SystemExit("Pallas paged kernel disagrees with its oracle")
    return worst


def check_reference(srv):
    """Teacher-forced reference over each request's prompt + output: at
    every generated position, the server's token must be within
    LOGIT_MARGIN_TOL of the reference's top logit."""
    from repro.models import model as model_lib
    cfg, store = srv.cfg, srv.store
    n_pad = CACHE_SLOTS      # one padded length: one reference compile

    @jax.jit
    def ref_logits(params, toks, lora):
        logits, _ = model_lib.prefill(cfg, params, {"tokens": toks},
                                      lora=dict(lora, mode="bgmv"))
        return logits[0].astype(jnp.float32)

    worst, agree, total = 0.0, 0, 0
    seen = {"cold": 0, "warm": 0}
    for st in srv.states:
        req = st.req
        seq = np.concatenate([req.prompt, np.asarray(st.generated[:-1],
                                                     np.int32)])
        toks = np.zeros((1, n_pad), np.int32)
        toks[0, :len(seq)] = seq
        w = store.weights(req.adapter_uid)
        pool = {t: {"a": jnp.asarray(w[t]["a"])[:, None],
                    "b": jnp.asarray(w[t]["b"])[:, None]} for t in w}
        pool["ranks"] = jnp.asarray([store.specs[req.adapter_uid].rank],
                                    jnp.int32)
        lora = {"pool": pool, "idx": jnp.zeros((1,), jnp.int32)}
        logits = ref_logits(srv.params, jnp.asarray(toks), lora)
        # position p's logits predict token p+1: the first generated token
        # comes from the prompt's last position
        rows = logits[req.prompt_len - 1:req.prompt_len - 1
                      + len(st.generated)]
        chosen = jnp.asarray(st.generated, jnp.int32)
        top = rows.max(axis=1)
        mine = jnp.take_along_axis(rows, chosen[:, None], axis=1)[:, 0]
        margin = float((top - mine).max())
        agree += int((rows.argmax(axis=1) == chosen).sum())
        total += len(st.generated)
        worst = max(worst, margin)
        seen["cold" if st.cold_start else "warm"] += 1
        print(f"  request {req.rid} ({'cold' if st.cold_start else 'warm'}"
              f" r{store.specs[req.adapter_uid].rank}, prompt "
              f"{req.prompt_len}, {len(st.generated)} tokens): worst logit "
              f"margin {margin!r}")
    print(f"reference check: worst logit margin {worst!r} (tol "
          f"{LOGIT_MARGIN_TOL}), {agree}/{total} tokens are the reference "
          f"argmax, {seen['cold']} cold and {seen['warm']} warm requests")
    if not (seen["cold"] and seen["warm"]):
        raise SystemExit("reference check needs a cold and a warm request")
    if worst > LOGIT_MARGIN_TOL:
        raise SystemExit("served tokens disagree with the reference")
    return worst


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev['platform']} {dev['kind']!r}); this check runs on a "
              "TPU only", file=sys.stderr)
        sys.exit(2)
    print(f"device: {dev['kind']} x{dev['count']} ({dev['platform']})")

    from repro.configs.base import get_config
    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}")
    full = get_config("yi-9b")
    cfg = dataclasses.replace(full, n_layers=LAYERS)
    print(f"model {cfg.name}: d_model {cfg.d_model}, heads {cfg.n_heads}/"
          f"{cfg.n_kv_heads}, head_dim {cfg.hd}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab}, {cfg.dtype}, LoRA q/k/v max rank "
          f"{cfg.lora.max_rank}; depth cut: {cfg.n_layers} of "
          f"{full.n_layers} layers (one stage of a two-stage pipeline), "
          f"{cfg.param_count() / 1e9:.2f} B parameters")

    clock = CompileClock()
    t0 = time.perf_counter()
    srv = build_server(cfg, args.seed)
    jax.block_until_ready(srv.params)
    print(f"server built in {time.perf_counter() - t0!r} s, of which XLA "
          f"compile {clock.secs!r} s (weights made on device, {len(RANKS)} "
          f"adapters of ranks {RANKS})")
    timer = PhaseTimer(srv.backend, clock)
    reqs = build_trace(cfg, args.seed)
    c0, n0, t0 = clock.secs, clock.programs, time.perf_counter()
    summary = srv.run(reqs)
    wall = time.perf_counter() - t0
    print(f"run wall {wall!r} s, of which XLA compile {clock.secs - c0!r} s "
          f"({clock.programs - n0} programs; host clock, every backend call "
          "synced with block_until_ready)")
    for name, st in timer.stats.items():
        print(f"  {name}: {st['calls']} steady calls in {st['secs']!r} s; "
              f"{st['compiling_calls']} compiling calls in "
              f"{st['compiling_secs']!r} s")
    print("simulated (virtual clock, not measured): "
          f"ttft_mean {summary['ttft_mean']!r} ms, tpt_mean "
          f"{summary['tpt_mean']!r} ms")
    check_run(srv, summary)
    check_decode_hlo(srv)
    kerr = check_kernel(srv, args.seed)
    margin = check_reference(srv)
    peak = jax.devices()[0].memory_stats().get("peak_bytes_in_use")
    print(f"peak_bytes_in_use {peak} ({peak / 2 ** 30:.2f} GiB)")
    print(f"worst logit margin {margin!r}; kernel max abs err "
          f"{kerr['err']!r}; XLA compile {clock.secs!r} s in "
          f"{clock.programs} programs, {clock.cache_hits} programs loaded "
          "from the persistent compile cache")
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
