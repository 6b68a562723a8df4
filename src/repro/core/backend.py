"""Numerics plane of the inference server: real JAX computation.

Owns the base-model params, the batched KV-cache pool, the jit caches, and
LoRA argument construction, organized as a **device-resident decode
pipeline** (`DecodePipeline`): sampling is fused into the jitted step
functions, per-row last-token / position / stop-target state lives in
device buffers donated across steps, and the host reads tokens back
asynchronously (the previous step's tokens are fetched while the current
step executes). Three entry points:

  * `prefill_admitted` — **batched multi-request prefill**: every request
    admitted in one iteration is packed into a single padded (N, L) call
    (per-request LoRA weights come from a small device `StagingCache`,
    stacked along the slot dim), instead of one jit call per request. The
    jit gathers each row's last-position hidden state *before* the
    unembed, samples on device, scatters every row cache into the pool
    with ONE vectorized scatter, and seeds the pipeline buffers — the
    (N, L, vocab) logits tensor never exists, on device or host. Causal
    masking makes the packed result bitwise-identical to per-request
    calls; shapes are bucketed (batch and length both power-of-two) to
    bound compilation.
  * `decode` — one decode iteration over the ready rows against the device
    slot pool (BGMV padding / MBGMV rank-block semantics via the kernel
    mode). In the default `fused` pipeline the jit consumes and returns
    the device buffers: **zero host→device transfers in steady state**
    (the active-row mask and LoRA slot map are re-uploaded only when the
    batch composition changes — an admission, flip, or retirement).
  * `megastep` — K decode iterations in one `lax.scan`-based jit call
    (the engine chooses K from its event horizon). Per-row stop targets
    freeze finished rows: their KV writes are dropped via the cache
    scatter's out-of-bounds mode, so the result — tokens and KV cache —
    is bitwise-identical to K single steps under greedy sampling.

`pipeline="perstep"` keeps the pre-pipeline behaviour (host sampling off
full logits, per-step host→device token/position uploads, synchronous
readback) as a baseline. `transfer_stats` is the server's counter dict
(`core/tracing.py`): host-link crossings and their bytes, decode steps,
megasteps and prefills on both paths, and the nanoseconds blocked in the
readback's `jax.device_get` (`readback_ns`); the server adds its own loop
counters to the same dict.

The timeline plane (InferenceServer) never touches arrays; the admission
plane never touches jit. Timing-only simulations simply do not construct a
backend.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import retrace, sanitizers
from repro.configs.base import ModelConfig
from repro.core import tracing
from repro.core.lora import DevicePool, HostLoRAStore, StagingCache
from repro.models import model as model_lib
from repro.models.param import split
from repro.serving import cache as cache_lib
from repro.serving.request import RequestState
from repro.serving.sampling import sample, split_key

PIPELINES = ("fused", "perstep")
MEGASTEP_MAX = 8          # default cap on iterations fused into one scan


def bucket(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def read_back(stats: Dict[str, int], x) -> np.ndarray:
    """Block on the device-to-host copy of `x`, counted as one `d2h` and
    timed into `readback_ns` under a `serve.readback` span."""
    t0 = time.perf_counter_ns()
    with jax.profiler.TraceAnnotation("serve.readback"):
        # lint: allow-host-sync — the readback IS the designed d2h point:
        # the pipeline drains `readback_depth` megasteps behind dispatch,
        # the perstep baseline blocks on every step
        arr = np.asarray(jax.device_get(x))
    stats["readback_ns"] += time.perf_counter_ns() - t0
    stats["d2h"] += 1
    stats["d2h_bytes"] += arr.nbytes
    return arr


def _select_rows(new_tree, old_tree, active):
    """Per-row select between two cache trees (batch axis from the tree
    layout) — the write-mask fallback for families whose state update
    cannot drop a row's write (see model.supports_write_mask)."""
    ax = cache_lib._batch_axis(new_tree)

    def sel(n, o):
        shape = [1] * n.ndim
        shape[ax] = -1
        return jnp.where(active.reshape(shape), n, o)

    return jax.tree.map(sel, new_tree, old_tree)


class DecodePipeline:
    """Device-resident per-row decode state + the async readback queue.

    Buffers (all (max_batch,), device-resident, donated through the jitted
    step functions):

      last_tok — last sampled token per row (next step's input)
      pos      — next decode position per row
      target   — stop position: the row freezes once pos reaches it
                 (seeded at prefill from prompt_len + max_new_tokens - 1)
      active   — host-owned mask of rows in the current decode batch
      idx      — host-owned LoRA pool slot per row (-1: none)
      rng      — threaded sampling key (unused under greedy, advanced
                 identically either way so megastep stays reproducible)

    `active`/`idx` change only on events (admission / retirement / batch
    recomposition); `refresh` re-uploads them only when their host
    signature changes, so a steady-state decode iteration performs zero
    host→device transfers.

    Readback: `stash` queues the step's token array (a device future) with
    its (state, column, n_tokens) entries; the queue is drained one step
    behind — `jax.device_get` on step k-1's tokens runs while step k
    executes. `flush` drains everything (end of run / perstep mode)."""

    def __init__(self, max_batch: int, seed: int, stats: Dict[str, int],
                 bt_width: int = 0):
        self.max_batch = max_batch
        self.stats = stats
        i32 = jnp.int32
        self.last_tok = jnp.zeros((max_batch,), i32)
        self.pos = jnp.zeros((max_batch,), i32)
        self.target = jnp.zeros((max_batch,), i32)
        self.active = jnp.zeros((max_batch,), bool)
        self.idx = jnp.full((max_batch,), -1, i32)
        # paged memory plane: per-row block table (logical page -> physical
        # page, -1 unclaimed). Device-resident like active/idx: re-uploaded
        # only on events — an admission, retirement, or a lazy growth claim
        # appending a page to a row's table (the signature covers the table
        # bytes, so a boundary-claim re-uploads exactly once).
        self.bt_width = bt_width
        self.block_table = jnp.full((max_batch, bt_width), -1, i32) \
            if bt_width else None
        self.rng = jax.random.PRNGKey(seed)
        self._sig: Optional[bytes] = None
        self._pending: List[Tuple[jax.Array,
                                  List[Tuple[RequestState, int, int]]]] = []
        self.readback_depth = 1

    # ------------------------------------------------------- row state ----
    def refresh(self, ready: List[RequestState], row_slot, row_pages=None):
        """Sync the active mask, LoRA slot map, and (paged) block table
        with the engine's ready set; uploads only when the composition
        changed (an event)."""
        active = np.zeros((self.max_batch,), bool)
        for st in ready:
            active[st.row] = True
        # lint: allow-host-sync — row_slot is host-resident batch metadata,
        # not a device array; no transfer happens here
        idx = np.asarray(row_slot, np.int64).copy()
        idx[~active] = -1
        sig = active.tobytes() + idx.tobytes()
        bt = None
        if self.bt_width:
            bt = np.full((self.max_batch, self.bt_width), -1, np.int32)
            for st in ready:
                pg = row_pages[st.row]
                bt[st.row, :len(pg)] = pg
            sig += bt.tobytes()
        if sig != self._sig:
            self.active = jnp.asarray(active)
            self.idx = jnp.asarray(idx, jnp.int32)
            self._sig = sig
            self.stats["h2d"] += 2
            self.stats["h2d_bytes"] += active.nbytes + 4 * self.max_batch
            if bt is not None:
                self.block_table = jnp.asarray(bt)
                self.stats["h2d"] += 1
                self.stats["h2d_bytes"] += bt.nbytes
        return self.active, self.idx

    # -------------------------------------------------------- readback ----
    def stash(self, toks, entries: List[Tuple[RequestState, int, int]]):
        """Queue a step's device token array; each entry (st, col, n)
        drains n tokens for `st` from column `col` (prefill: batch index,
        decode/megastep: engine row)."""
        for st, _, n in entries:
            st.pending_tokens += n
        self._pending.append((toks, entries))
        while len(self._pending) > self.readback_depth:
            self._drain_one()

    def _drain_one(self):
        toks, entries = self._pending.pop(0)
        arr = read_back(self.stats, toks)
        now = time.perf_counter()
        for st, col, n in entries:
            vals = [int(arr[col])] if arr.ndim == 1 \
                else [int(v) for v in arr[:n, col]]
            if st.first_token_s is None and vals:
                st.first_token_s = now
            st.generated.extend(vals)
            st.pending_tokens -= n

    def flush(self):
        while self._pending:
            self._drain_one()


class NumericsBackend:
    def __init__(self, cfg: ModelConfig, *, kernel: str, max_batch: int,
                 cache_slots: int, store: HostLoRAStore, pool: DevicePool,
                 stats: Dict[str, int], params=None, seed: int = 0,
                 pipeline: str = "fused",
                 megastep: int = MEGASTEP_MAX, temperature: float = 0.0,
                 staging_slots: int = 16, memory: str = "dense",
                 page_size: int = 32, allocator=None):
        if pipeline not in PIPELINES:
            raise ValueError(f"unknown pipeline {pipeline!r}")
        if memory not in ("dense", "paged"):
            raise ValueError(f"unknown memory plane {memory!r}")
        if pipeline == "perstep" and temperature > 0.0:
            raise ValueError(
                "pipeline='perstep' is the greedy-only legacy baseline; "
                "temperature sampling needs the fused pipeline (its rng "
                "is threaded through the device-resident step state)")
        self.cfg = cfg
        self.kernel = kernel
        self.max_batch = max_batch
        self.cache_slots = cache_slots
        self.store = store
        self.pool = pool
        self.pipeline = pipeline
        self.megastep_max = megastep if pipeline == "fused" else 0
        self.temperature = temperature
        self.paged = memory == "paged"
        self.page_size = page_size
        if self.paged:
            if pipeline != "fused":
                raise ValueError(
                    "the paged memory plane rides the fused pipeline")
            if not model_lib.supports_paged(cfg):
                raise ValueError(
                    f"{cfg.name}: family does not support the paged cache")
            if not model_lib.supports_write_mask(cfg):
                raise ValueError(
                    f"{cfg.name}: family does not support write masks")
            if cache_slots % page_size:
                raise ValueError(
                    f"cache_slots ({cache_slots}) must be a multiple of "
                    f"page_size ({page_size}) so a row's block table tiles "
                    "its ring exactly (paged decode stays bitwise-equal to "
                    "the dense row layout)")
            if allocator is None:
                raise ValueError("memory='paged' requires a PageAllocator")
        self.allocator = allocator
        self.bt_width = cache_slots // page_size if self.paged else 0
        if params is None:
            # one jitted init: each weight's random draw fuses into its
            # output, so a published-width model never holds a weight's
            # random bits and intermediates beside the weights already made
            params = jax.jit(
                lambda k: split(model_lib.init_params(cfg, k))[0],
                static_argnums=())(jax.random.PRNGKey(seed))
        self.params = params
        row_cache = model_lib.cache_abstract(cfg, 1, cache_slots)
        self.cache = cache_lib.zeros_paged(
            row_cache, allocator.n_pages, page_size) if self.paged \
            else cache_lib.zeros_like_batched(row_cache, max_batch)
        self.transfer_stats = stats       # the owning server's counters
        self.pipe = DecodePipeline(max_batch, seed + 1, self.transfer_stats,
                                   bt_width=self.bt_width)
        self.staging = StagingCache(staging_slots, on_upload=functools.partial(
            tracing.count_upload, self.transfer_stats))
        mask_ok = model_lib.supports_write_mask(cfg)
        self._decode_legacy_jit = jax.jit(
            functools.partial(self._decode_legacy_fn, cfg, self._mode_str()),
            donate_argnums=(1,))
        self._decode_jit = jax.jit(
            functools.partial(self._decode_fused_fn, cfg, self._mode_str(),
                              temperature, mask_ok),
            donate_argnums=(1, 2, 3, 7))
        self._megastep_jits = {}
        self._prefill_jit = {}
        self._chunk_jit = {}
        # RetraceSan (REPRO_SANITIZE=1): per-dispatch trace-cache watch on
        # every hot jit. Tests call mark_steady()/assert_clean(); a retrace
        # after steady state means a shape-unstable decode step.
        self.retrace_san = (retrace.RetraceSan()
                            if sanitizers.enabled() else None)

    def _observe_trace(self, name: str, fn) -> None:
        if self.retrace_san is not None:
            self.retrace_san.observe(name, fn)

    def _san_check(self, ids, prefix: str, op: str) -> None:
        """PageSan access check for host-known page id lists (no device
        sync: every id list here is host-built)."""
        san = getattr(self.allocator, "san", None) \
            if self.allocator is not None else None
        if san is not None:
            san.check_access(ids, prefix, op)

    def _mode_str(self):
        return "bgmv" if self.kernel == "bgmv" else "mbgmv"

    def flush_readback(self):
        """Drain every queued async token readback (end of run, or before
        host code that needs `st.generated` current)."""
        self.pipe.flush()

    # ------------------------------------------- preemption (paged plane) ----
    def swap_out(self, pages: List[int]):
        """Copy a preemption victim's KV pages to host memory. Returns the
        host-side payload `swap_in` restores from; the timeline plane
        charges the re-upload through the link scheduler, the d2h copy is
        counted here."""
        self._san_check(pages, "kv:", "swap-out extract")
        payload = cache_lib.extract_pages(self.cache, pages)
        self.transfer_stats["d2h"] += 1
        self.transfer_stats["d2h_bytes"] += cache_lib.tree_nbytes(payload)
        return payload

    def swap_in(self, states: List[RequestState], row_pages):
        """Restore swap-preempted rows: insert each saved payload into the
        freshly claimed pages and re-seed the pipeline's per-row buffers.
        The page contents (including the pos leaves the attention mask
        trusts) come back exactly as extracted, so the row continues
        decoding bitwise-identically — no prefill, no re-sampling."""
        pipe = self.pipe
        for st in states:
            payload, st.swap_payload = st.swap_payload, None
            self._san_check(st.kv_pages, "kv:", "swap-in insert")
            self.cache = cache_lib.insert_pages(self.cache, payload,
                                                st.kv_pages)
            self.transfer_stats["h2d"] += 1
            self.transfer_stats["h2d_bytes"] += \
                cache_lib.tree_nbytes(payload)
            r = st.row
            pipe.last_tok = pipe.last_tok.at[r].set(int(st.generated[-1]))
            pipe.pos = pipe.pos.at[r].set(int(st.resume_pos))
            pipe.target = pipe.target.at[r].set(
                st.req.prompt_len + st.req.max_new_tokens - 1)

    def clear_pages(self, ids: List[int]):
        """Scrub freshly grown pages (pos = -1): a page claimed mid-decode
        may carry a previous tenant's positions, which would become
        attendable the moment the growing row's clock passes them."""
        self._san_check(ids, "kv:", "page scrub")
        self.cache = cache_lib.clear_pages(self.cache, ids)

    def restore_pages(self, st: RequestState):
        """Swap-in for a half-prefilled (chunk-phase) row: reinsert the
        saved page payload only. Unlike `swap_in` there is no pipeline
        re-seed — the row has no sampled token yet; its next chunk simply
        continues from st.prefill_pos against the restored pages."""
        payload, st.swap_payload = st.swap_payload, None
        self._san_check(st.kv_pages, "kv:", "chunk swap-in insert")
        self.cache = cache_lib.insert_pages(self.cache, payload,
                                            st.kv_pages)
        self.transfer_stats["h2d"] += 1
        self.transfer_stats["h2d_bytes"] += cache_lib.tree_nbytes(payload)

    # ---------------------------------------------------------- prefill ----
    def _lora_arg_stacked(self, uids: List[str]):
        """Batch-N lora arg (CPU-assist path numerics): request i reads
        pseudo-slot i of a pool stacked from the staged device copies —
        repeated prefills of a hot adapter hit the `StagingCache` instead
        of re-crossing the host link."""
        ws = [self.staging.get(u, self.store) for u in uids]
        targets = ws[0].keys()
        pool = {t: {"a": jnp.stack([w[t]["a"] for w in ws], 1),
                    "b": jnp.stack([w[t]["b"] for w in ws], 1)}
                for t in targets}
        ranks = [min(self.store.specs[u].rank, self.cfg.lora.max_rank)
                 for u in uids]
        pool["ranks"] = jnp.asarray(ranks, jnp.int32)
        return {"pool": pool, "idx": jnp.arange(len(uids), dtype=jnp.int32)}

    def prefill_admitted(self, states: List[RequestState]):
        """One padded prefill call for all requests admitted this
        iteration. The jit samples each row's first token on device,
        scatters every row cache into the pool in one vectorized write,
        and seeds the decode pipeline's last-token/position/stop-target
        buffers; tokens reach `st.generated` through the async readback
        queue.

        Recompute resumes (`st.preempted`, drop-and-recompute preemption)
        ride the same packed call: the row prefills prompt + generated[:-1]
        — every KV slot it had written — and under greedy the re-sampled
        "first token" is exactly generated[-1] (the prefix replayed
        predicts what it predicted before), which re-seeds last_tok for
        bitwise continuation. No token is emitted and no timestamp is
        appended for resumed rows: their token already reached the client
        before preemption."""
        if not states:
            return
        # lint: allow-host-sync — built from host ints, no device transfer
        lens = np.array([min(st.resume_pos, self.cache_slots)
                         if st.preempted else st.req.prompt_len
                         for st in states])
        if int(lens.max()) > self.cache_slots:
            bad = [st.req.rid for st in states
                   if st.req.prompt_len > self.cache_slots]
            unit = (f"{self.bt_width}-page block table "
                    f"(page_size {self.page_size})" if self.paged
                    else f"{self.cache_slots} KV-cache slots") + " per row"
            raise ValueError(
                f"requests {bad}: prompt exceeds the {unit} — the engine "
                "must reject these at submit time (raise cache_slots or "
                "truncate the prompt)")
        Lp = min(bucket(int(lens.max())), self.cache_slots)
        Nb = bucket(len(states), lo=1)
        N = len(states)
        toks = np.zeros((Nb, Lp), np.int32)
        lens_b = np.ones((Nb,), np.int32)
        rows = np.full((Nb,), self.max_batch, np.int32)   # pad rows: dropped
        tgts = np.zeros((Nb,), np.int32)
        for i, st in enumerate(states):
            if st.preempted:
                # lint: allow-host-sync — prompt/generated are host lists
                seq = np.asarray(
                    list(st.req.prompt) + list(st.generated[:-1]), np.int32)
                if len(seq) != lens[i]:
                    raise RuntimeError(
                        f"resume length mismatch for {st.req.rid}: "
                        f"{len(seq)} != {lens[i]}")
                toks[i, :lens[i]] = seq
            else:
                toks[i, :lens[i]] = st.req.prompt
            lens_b[i] = lens[i]
            rows[i] = st.row
            # the stop target is the request's original one — a resumed
            # row owes the remaining tokens, not max_new more
            tgts[i] = st.req.prompt_len + st.req.max_new_tokens - 1
        uids = [st.req.adapter_uid for st in states]
        # pad the lora arg to Nb rows (repeat row 0; idx -1 would also work
        # but a valid slot keeps the gather in-bounds without a select)
        uids_p = uids + [uids[0]] * (Nb - N)
        lora = self._lora_arg_stacked(uids_p)
        pipe = self.pipe
        self.transfer_stats["h2d"] += 4          # toks, lens, rows, targets
        self.transfer_stats["h2d_bytes"] += (toks.nbytes + lens_b.nbytes
                                             + rows.nbytes + tgts.nbytes)
        self.transfer_stats["prefills"] += 1
        args = (self.params, jnp.asarray(toks), jnp.asarray(lens_b),
                jnp.asarray(rows), jnp.asarray(tgts), self.cache,
                pipe.last_tok, pipe.pos, pipe.target, pipe.rng, lora)
        if self.paged:
            ps = self.page_size
            Sp = -(-Lp // ps) * ps          # prefill cache depth, page-tiled
            npr = Sp // ps
            page_ids = np.full((Nb, npr), -1, np.int32)
            claimed = []
            for i, st in enumerate(states):
                page_ids[i, :min(len(st.kv_pages), npr)] = \
                    st.kv_pages[:npr]
                claimed.extend(st.kv_pages)
            self._san_check(claimed, "kv:", "prefill scatter")
            # every claimed page gets its pos slots invalidated before the
            # prompt scatter lands: pages reclaimed from a retired row
            # carry stale positions the attention mask would trust
            C = bucket(len(claimed), lo=1)
            clear_ids = np.full((C,), -1, np.int32)
            clear_ids[:len(claimed)] = claimed
            key = (Nb, Lp, C)
            if key not in self._prefill_jit:
                donate = (5, 6, 7, 8, 9)
                self._prefill_jit[key] = jax.jit(functools.partial(
                    self._prefill_paged_fn, self.cfg, self._mode_str(),
                    Sp, self.temperature), donate_argnums=donate)
            self.transfer_stats["h2d"] += 2      # page ids, clear list
            self.transfer_stats["h2d_bytes"] += (page_ids.nbytes
                                                 + clear_ids.nbytes)
            (toks_out, self.cache, pipe.last_tok, pipe.pos, pipe.target,
             pipe.rng) = self._prefill_jit[key](
                *args, jnp.asarray(page_ids), jnp.asarray(clear_ids))
        else:
            key = (Nb, Lp)
            if key not in self._prefill_jit:
                donate = (5, 6, 7, 8, 9)
                self._prefill_jit[key] = jax.jit(functools.partial(
                    self._prefill_fn, self.cfg, self._mode_str(),
                    self.cache_slots, self.temperature,
                    model_lib.supports_last_pos(self.cfg)),
                    donate_argnums=donate)
            (toks_out, self.cache, pipe.last_tok, pipe.pos, pipe.target,
             pipe.rng) = self._prefill_jit[key](*args)
        now = time.perf_counter()
        for st in states:
            if st.prefill_s is None:
                st.prefill_s = now
            if not st.preempted:
                st.token_times_ms.append(st.first_token_ms)
        # resumed rows re-sample a token they already emitted — exclude
        # them from the stash so the readback never appends it again
        pipe.stash(toks_out, [(st, i, 1) for i, st in enumerate(states)
                              if not st.preempted])
        if self.pipeline == "perstep":
            pipe.flush()       # legacy path: synchronous readback

    @staticmethod
    def _prefill_fn(cfg, mode, cache_slots, temperature, use_last_pos,
                    params, toks, lens, rows, tgts, cache, last_tok, pos,
                    target, rng, lora):
        lora = dict(lora, mode=mode)
        gather = lens - 1
        if use_last_pos:
            logits, row_caches = model_lib.prefill(
                cfg, params, {"tokens": toks}, lora=lora,
                cache_slots=cache_slots, last_pos=gather)
            last = logits[:, 0]
        else:   # encdec: full logits stay on device; gather post-unembed
            logits, row_caches = model_lib.prefill(
                cfg, params, {"tokens": toks}, lora=lora,
                cache_slots=cache_slots)
            last = logits[jnp.arange(toks.shape[0]), gather]
        rng, sub = split_key(rng)
        toks_out = sample(last, temperature=temperature, rng=sub)
        row_caches = NumericsBackend._mask_pad_slots(row_caches, lens)
        cache = cache_lib.scatter_rows(cache, row_caches, rows)
        last_tok = last_tok.at[rows].set(toks_out, mode="drop")
        pos = pos.at[rows].set(lens, mode="drop")
        target = target.at[rows].set(tgts, mode="drop")
        return toks_out, cache, last_tok, pos, target, rng

    @staticmethod
    def _prefill_paged_fn(cfg, mode, slots, temperature, params, toks, lens,
                          rows, tgts, cache, last_tok, pos, target, rng,
                          lora, page_ids, clear_ids):
        """Paged prefill: identical compute to `_prefill_fn` (the logits —
        and therefore the first sampled token — never see the cache
        layout), but the row caches land in freshly claimed pages via one
        page scatter instead of one row scatter. `slots` is the padded
        prompt length rounded up to whole pages, so each row cache tiles
        exactly into `slots/page_size` pages."""
        lora = dict(lora, mode=mode)
        gather = lens - 1
        logits, row_caches = model_lib.prefill(
            cfg, params, {"tokens": toks}, lora=lora,
            cache_slots=slots, last_pos=gather)
        last = logits[:, 0]
        rng, sub = split_key(rng)
        toks_out = sample(last, temperature=temperature, rng=sub)
        row_caches = NumericsBackend._mask_pad_slots(row_caches, lens)
        n_pages = cache["pos"].shape[1]
        cids = jnp.where(clear_ids >= 0, clear_ids, n_pages)
        cache = dict(cache)
        cache["pos"] = cache["pos"].at[:, cids].set(-1, mode="drop")
        cache = cache_lib.scatter_pages(cache, row_caches, page_ids)
        last_tok = last_tok.at[rows].set(toks_out, mode="drop")
        pos = pos.at[rows].set(lens, mode="drop")
        target = target.at[rows].set(tgts, mode="drop")
        return toks_out, cache, last_tok, pos, target, rng

    @staticmethod
    def _mask_pad_slots(row_caches, lens_j):
        """Invalidate cache slots beyond each request's true prompt length
        (padding rows of the packed call never become attendable)."""
        def fix(path, x):
            name = path[-1].key if hasattr(path[-1], "key") else ""
            if name == "pos":
                slots = x.shape[-1]
                live = jnp.arange(slots)[None] < lens_j[:, None]
                while live.ndim < x.ndim:      # stacked: (L, B, slots)
                    live = live[None]
                return jnp.where(live, x, -1)
            return x
        return jax.tree_util.tree_map_with_path(fix, row_caches)

    # --------------------------------------------------- chunked prefill ----
    def prefill_chunk(self, st: RequestState, row_pages: List[int],
                      start: int, n_tokens: int, final: bool):
        """One chunk of an incremental prefill for a single row: consume
        prompt[start : start+n_tokens], gather the row's claimed pages into
        a dense view, run the chunk through the stack (attention masked by
        cached absolute positions), and scatter the updated view back via
        `scatter_pages`. Only the final chunk samples — through the same
        last-position gather / sample / pipeline-seed sequence as
        `prefill_admitted`, so the first token is bitwise identical to a
        monolithic prefill. The chunk width is bucketed so a fixed
        chunk_budget compiles at most two variants (mid + final)."""
        if not self.paged:
            raise RuntimeError("chunked prefill rides the paged memory "
                               "plane (memory='paged')")
        if start + n_tokens > self.cache_slots:
            raise ValueError(
                f"request {st.req.rid}: chunk [{start}, {start + n_tokens})"
                f" exceeds the {self.cache_slots}-slot block table")
        W = self.bt_width
        Cb = min(bucket(n_tokens), self.cache_slots)
        toks = np.zeros((1, Cb), np.int32)
        # lint: allow-host-sync — prompt is a host array, no device sync
        toks[0, :n_tokens] = np.asarray(st.req.prompt[start:start + n_tokens])
        ids = np.full((W,), -1, np.int32)
        ids[:len(row_pages)] = row_pages
        self._san_check(list(row_pages), "kv:", "chunk scatter")
        lora = self._lora_arg_stacked([st.req.adapter_uid])
        self.transfer_stats["h2d"] += 2            # tokens, page ids
        self.transfer_stats["h2d_bytes"] += toks.nbytes + ids.nbytes
        self.transfer_stats["prefill_chunks"] += 1
        pipe = self.pipe
        key = (Cb, bool(final))
        if key not in self._chunk_jit:
            if final:
                donate = (7, 8, 9, 10, 11)
                self._chunk_jit[key] = jax.jit(functools.partial(
                    self._prefill_chunk_final_fn, self.cfg,
                    self._mode_str(), self.temperature),
                    donate_argnums=donate)
            else:
                donate = (4,)
                self._chunk_jit[key] = jax.jit(functools.partial(
                    self._prefill_chunk_fn, self.cfg, self._mode_str()),
                    donate_argnums=donate)
        start_j = jnp.asarray(start, jnp.int32)
        clen_j = jnp.asarray(n_tokens, jnp.int32)
        if final:
            row = jnp.asarray([st.row], jnp.int32)
            plen = jnp.asarray([st.req.prompt_len], jnp.int32)
            tgt = jnp.asarray(
                [st.req.prompt_len + st.req.max_new_tokens - 1], jnp.int32)
            (toks_out, self.cache, pipe.last_tok, pipe.pos, pipe.target,
             pipe.rng) = self._chunk_jit[key](
                self.params, jnp.asarray(toks), start_j, clen_j, row, plen,
                tgt, self.cache, pipe.last_tok, pipe.pos, pipe.target,
                pipe.rng, lora, jnp.asarray(ids))
            if st.prefill_s is None:
                st.prefill_s = time.perf_counter()
            self._observe_trace("prefill_chunk_final", self._chunk_jit[key])
            pipe.stash(toks_out, [(st, 0, 1)])
            if self.pipeline == "perstep":
                pipe.flush()
        else:
            self.cache = self._chunk_jit[key](
                self.params, jnp.asarray(toks), start_j, clen_j,
                self.cache, lora, jnp.asarray(ids))
            self._observe_trace("prefill_chunk", self._chunk_jit[key])

    @staticmethod
    def _prefill_chunk_fn(cfg, mode, params, toks, start, clen, cache,
                          lora, page_ids):
        lora = dict(lora, mode=mode)
        view = cache_lib.gather_pages(cache, page_ids)
        _, new_view = model_lib.prefill_chunk(
            cfg, params, toks, start, clen, view, lora=lora, last=False)
        return cache_lib.scatter_pages(cache, new_view, page_ids[None])

    @staticmethod
    def _prefill_chunk_final_fn(cfg, mode, temperature, params, toks, start,
                                clen, row, plen, tgt, cache, last_tok, pos,
                                target, rng, lora, page_ids):
        lora = dict(lora, mode=mode)
        view = cache_lib.gather_pages(cache, page_ids)
        logits, new_view = model_lib.prefill_chunk(
            cfg, params, toks, start, clen, view, lora=lora, last=True)
        cache = cache_lib.scatter_pages(cache, new_view, page_ids[None])
        last = logits[:, 0]
        rng, sub = split_key(rng)
        toks_out = sample(last, temperature=temperature, rng=sub)
        last_tok = last_tok.at[row].set(toks_out, mode="drop")
        pos = pos.at[row].set(plen, mode="drop")
        target = target.at[row].set(tgt, mode="drop")
        return toks_out, cache, last_tok, pos, target, rng

    # ----------------------------------------------------------- decode ----
    def decode(self, ready: List[RequestState], row_slot, row_pos,
               row_pages=None):
        """One decode iteration over the ready rows."""
        self.transfer_stats["decode_steps"] += 1
        if self.pipeline == "perstep":
            return self._decode_perstep(ready, row_slot, row_pos)
        pipe = self.pipe
        if self.paged and row_pages is not None:
            self._san_check([p for st in ready for p in row_pages[st.row]],
                            "kv:", "decode block table")
        active, idx = pipe.refresh(ready, row_slot, row_pages)
        lora = {"pool": self.pool.pool, "idx": idx}
        toks, self.cache, pipe.last_tok, pipe.pos, pipe.rng = \
            self._decode_jit(self.params, self.cache, pipe.last_tok,
                             pipe.pos, active, pipe.target, lora, pipe.rng,
                             pipe.block_table)
        self._observe_trace("decode", self._decode_jit)
        pipe.stash(toks, [(st, st.row, 1) for st in ready])

    @staticmethod
    def _fused_step(cfg, mode, temperature, mask_ok, params, lora, cache,
                    last_tok, pos, act, rng, block_table=None):
        """Shared single-iteration body of the fused and megastep paths —
        one implementation, so K fused iterations are bitwise-identical
        to K single calls. Frozen/inactive rows: KV write dropped (or
        row-selected), token and position frozen. With a block table the
        cache is the shared page pool — frozen rows MUST drop their write
        (pages are per-request, a post-hoc row select cannot undo a write
        into the shared pool), hence paged requires supports_write_mask."""
        rng, sub = split_key(rng)
        wm = act if mask_ok else None
        logits, new_cache = model_lib.decode(
            cfg, params, cache, last_tok[:, None], pos, lora=lora,
            write_mask=wm, block_table=block_table)
        if not mask_ok:
            new_cache = _select_rows(new_cache, cache, act)
        toks = sample(logits[:, -1], temperature=temperature, rng=sub)
        last_tok = jnp.where(act, toks, last_tok)
        pos = jnp.where(act, pos + 1, pos)
        return new_cache, last_tok, pos, toks, rng

    @staticmethod
    def _decode_fused_fn(cfg, mode, temperature, mask_ok, params, cache,
                         last_tok, pos, active, target, lora, rng,
                         block_table):
        lora = dict(lora, mode=mode)
        act = active & (pos < target)
        cache, last_tok, pos, toks, rng = NumericsBackend._fused_step(
            cfg, mode, temperature, mask_ok, params, lora, cache, last_tok,
            pos, act, rng, block_table)
        return toks, cache, last_tok, pos, rng

    # --------------------------------------------------------- megastep ----
    def megastep(self, ready: List[RequestState], nsteps: List[int], K: int,
                 row_slot, row_pages=None):
        """K decode iterations in one jit call (`lax.scan`); per-row stop
        targets freeze rows that reach max_new_tokens mid-window. The
        engine guarantees no admission/arrival/load event lands inside
        the window. `nsteps[i]` = tokens request i actually produces
        (= min(steps left, K)); the (K, B) token block drains through the
        async readback queue like any other step."""
        if self.pipeline != "fused" or K < 2:
            raise RuntimeError(
                "megastep needs the fused pipeline and K >= 2 "
                f"(pipeline={self.pipeline!r}, K={K})")
        self.transfer_stats["decode_steps"] += K
        self.transfer_stats["megasteps"] += 1
        self.transfer_stats["megastep_iters"] += K
        pipe = self.pipe
        if self.paged and row_pages is not None:
            self._san_check([p for st in ready for p in row_pages[st.row]],
                            "kv:", "megastep block table")
        pipe.refresh(ready, row_slot, row_pages)
        if K not in self._megastep_jits:
            donate = (1, 2, 3, 7)
            self._megastep_jits[K] = jax.jit(functools.partial(
                self._megastep_fn, self.cfg, self._mode_str(),
                self.temperature, model_lib.supports_write_mask(self.cfg),
                K), donate_argnums=donate)
        lora = {"pool": self.pool.pool, "idx": pipe.idx}
        ys, self.cache, pipe.last_tok, pipe.pos, pipe.rng = \
            self._megastep_jits[K](
                self.params, self.cache, pipe.last_tok, pipe.pos,
                pipe.active, pipe.target, lora, pipe.rng, pipe.block_table)
        self._observe_trace(f"megastep[K={K}]", self._megastep_jits[K])
        pipe.stash(ys, [(st, st.row, n) for st, n in zip(ready, nsteps)])

    @staticmethod
    def _megastep_fn(cfg, mode, temperature, mask_ok, K, params, cache,
                     last_tok, pos, active, target, lora, rng, block_table):
        lora = dict(lora, mode=mode)

        def body(carry, _):
            cache, last_tok, pos, rng = carry
            act = active & (pos < target)
            cache, last_tok, pos, toks, rng = NumericsBackend._fused_step(
                cfg, mode, temperature, mask_ok, params, lora, cache,
                last_tok, pos, act, rng, block_table)
            return (cache, last_tok, pos, rng), toks

        (cache, last_tok, pos, rng), ys = jax.lax.scan(
            body, (cache, last_tok, pos, rng), None, length=K)
        return ys, cache, last_tok, pos, rng

    # ------------------------------------------------ legacy (perstep) ----
    def _decode_perstep(self, ready, row_slot, row_pos):
        """Pre-pipeline baseline: host-built token/position arrays each
        step, sampling off the full logits tensor, synchronous readback."""
        toks = np.zeros((self.max_batch, 1), np.int32)
        pos = np.zeros((self.max_batch,), np.int32)
        live = np.zeros((self.max_batch,), bool)
        # lint: allow-host-sync — row_slot is host metadata, no transfer
        idx = np.asarray(row_slot).copy()
        for st in ready:
            toks[st.row, 0] = st.generated[-1] if st.generated else 0
            pos[st.row] = row_pos[st.row]
            live[st.row] = True
        idx[~live] = -1
        lora = {"pool": self.pool.pool, "idx": jnp.asarray(idx, jnp.int32)}
        self.transfer_stats["h2d"] += 3
        self.transfer_stats["h2d_bytes"] += (toks.nbytes + pos.nbytes
                                             + idx.nbytes)
        logits, self.cache = self._decode_legacy_jit(
            self.params, self.cache, jnp.asarray(toks), jnp.asarray(pos),
            lora)
        # the synchronous legacy baseline: blocking readback each step is
        # its defining cost
        new = read_back(self.transfer_stats, sample(logits[:, -1]))
        for st in ready:
            st.generated.append(int(new[st.row]))

    @staticmethod
    def _decode_legacy_fn(cfg, mode, params, cache, toks, pos, lora):
        lora = dict(lora, mode=mode)
        return model_lib.decode(cfg, params, cache, toks, pos, lora=lora)
