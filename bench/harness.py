"""One run of one cell: set-up, the measured window, the check.

The harness drives the program's own server, `InferenceServer.submit` and
`InferenceServer.step`, from one thread on the host's wall clock, as an
open-loop client would: each request is submitted when it falls due, and
each token is stamped when it reaches `RequestState.generated` (the moment
the server's asynchronous readback hands it to the host), read after every
`step()`. The server still gates adapter uploads and their flip on its own
simulated clock; the harness times only what a client sees.

Set-up (`setup_s`): weights and adapters from the seed, the server, and a
warm-up that compiles (or loads from the persistent cache) every program
the cell's traffic can reach: each prefill bucket, decode, and megasteps of
K = 2, 4, 8; then, where the mix asks for it, a few seconds of the mix's
own traffic so the adapter pool is in its steady state. Nothing compiles
in the window: `CompileClock` counts what does, and the run says so.

With `trace`, `TRACE_S` seconds in the middle of the window run under
the profiler, the harness's spans (`bench.step`, `bench.submit`,
`bench.readback_wait`, `bench.wait_arrival`, and `bench.window` around the
traced stretch) go into the same trace, and the per-layer metrics
(`bench/metrics/<name>.py`) read the reduced trace and the harness's
records of that stretch.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import json
import pathlib
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from bench import correct, traffic, weights as weights_lib

ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = ROOT / "bench"
OUT = ROOT / ".bench_out"
# the served step's programs, by a piece of their name in the trace: JAX
# names a jitted functools.partial "jit__unknown"
STEP_PROGRAMS = ("jit__unknown", "_prefill_paged_fn", "_decode_fused_fn",
                 "_megastep_fn")
# a traced run profiles this long a stretch in the middle of its window,
# between two syncs, so every program in the trace was dispatched inside it
TRACE_S = 10.0
WARMUP_NEW = 2           # tokens each prefill-bucket warm-up request asks
# a request alone, its prompt one whole page: the first decode claims a
# page, then 1 + 8 + 4 + 2 + 1 tokens take every megastep K once
MEGASTEP_NEW = 17


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


class CompileClock:
    """Compilation as JAX's monitoring events report it: `events` counts
    every trace, lowering or compile; `programs` and `secs` the XLA
    backend compiles; `cache_hits` programs loaded from the persistent
    cache instead."""

    def __init__(self):
        import jax
        self.events = 0
        self.programs = 0
        self.secs = 0.0
        self.cache_hits = 0
        self.names: List[str] = []     # what the backend compiled, in order
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def _on_event(self, event, duration, **kw):
        if event.startswith("/jax/core/compile/"):
            self.events += 1
            if event.endswith("backend_compile_duration"):
                self.secs += duration
                self.programs += 1
                self.names.append(str(kw.get("fun_name", "?")))

    def _on_count(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.events, self.programs, self.secs, self.cache_hits)


@dataclasses.dataclass
class Rec:
    """What the client saw of one request."""
    item: traffic.Item
    due: float                      # wall time it fell due
    st: object = None               # the server's RequestState
    submitted: float = 0.0
    admitted: Optional[float] = None
    stamps: List[float] = dataclasses.field(default_factory=list)
    error: str = ""


class Spans:
    """Host spans into the profiler's trace, only when tracing."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


class Tracer:
    """Profiles `TRACE_S` seconds in the middle of the window. Each end is
    a sync (the device finishes what was dispatched), so the programs in
    the trace are exactly the calls `Work` records between the two marks;
    `bench.window` spans the traced stretch."""

    def __init__(self, client: "Client", work: "Work", seconds: float,
                 directory: pathlib.Path):
        self.client, self.work, self.dir = client, work, directory
        self.start = max(0.0, (seconds - TRACE_S) / 2)
        self.stop = self.start + min(TRACE_S, seconds)
        self.state = "before"
        self.span = None

    def _sync(self):
        import jax
        be = self.client.srv.backend
        jax.block_until_ready((be.cache, be.pipe.last_tok))

    def __call__(self, now: float, t0: float):
        import jax
        if self.state == "before" and now >= t0 + self.start:
            self._sync()
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.dir))
            self.span = jax.profiler.TraceAnnotation("bench.window")
            self.span.__enter__()
            self.mark = self.work.mark(self.client)
            self.state = "on"
        elif self.state == "on" and now >= t0 + self.stop:
            self._sync()
            self.marks = (self.mark, self.work.mark(self.client))
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"


class Work:
    """Traced runs only: what each served call computed (for the FLOP and
    byte counts), and the host time blocked in the readback."""

    def __init__(self, srv, spans: Spans):
        self.calls: List[tuple] = []
        self.readback_s = 0.0
        be, adm, store = srv.backend, srv.admission, srv.store
        rank = lambda st: store.specs[st.req.adapter_uid].rank

        def wrap(name, record):
            fn = getattr(be, name)

            def wrapped(*a, **kw):
                record(*a, **kw)
                return fn(*a, **kw)
            setattr(be, name, wrapped)

        wrap("prefill_admitted", lambda states: self.calls.append(
            ("prefill", [(st.req.prompt_len, rank(st)) for st in states])))
        wrap("decode", lambda ready, *a, **k: self.calls.append(
            ("decode", [(int(adm.row_pos[st.row]) + 1, rank(st))
                        for st in ready])))
        wrap("megastep", lambda ready, nsteps, K, *a, **k: self.calls.append(
            ("megastep", [(int(adm.row_pos[st.row]) + 1 + j, rank(st))
                          for st, n in zip(ready, nsteps)
                          for j in range(n)], K)))
        pipe = be.pipe
        drain = pipe._drain_one

        def timed_drain():
            t = time.perf_counter()
            with spans("bench.readback_wait"):
                drain()
            self.readback_s += time.perf_counter() - t
        pipe._drain_one = timed_drain

    def mark(self, client: "Client") -> dict:
        return {"calls": len(self.calls), "readback_s": self.readback_s,
                "step_s": client.step_s,
                "stats": dict(client.srv.backend.transfer_stats)}


def prefill_groups(lo: int, hi: int, page: int, slots: int, max_n: int
                   ) -> List[List[int]]:
    """Groups of prompt lengths in [lo, hi] that, admitted together, reach
    every padded prefill shape the program can compile for such traffic:
    (rows bucket, length bucket, claimed-pages bucket), each once. The
    key mirrors `NumericsBackend.prefill_admitted`'s, with its own `bucket`;
    a shape this misses compiles in the window, and the run says so."""
    from repro.core.backend import bucket
    pages = lambda m: -(-min(m, slots) // page)
    length_bucket = lambda m: min(bucket(m, 8), slots)
    ranges: Dict[int, List[int]] = {}
    for m in range(lo, hi + 1):
        ranges.setdefault(length_bucket(m), []).append(m)
    groups, seen = [], set()
    for n in range(1, max_n + 1):
        for lb, ms in sorted(ranges.items()):
            m_lo, m_hi = ms[0], ms[-1]
            s_min = pages(m_lo) + (n - 1) * pages(lo)
            s_max = n * pages(m_hi)
            for s in range(s_min, s_max + 1):
                key = (bucket(n, 1), lb, bucket(s, 1))
                if key in seen:
                    continue
                p1 = min(pages(m_hi), s - (n - 1) * pages(lo))
                if p1 < pages(m_lo):
                    continue
                m = m_hi if p1 == pages(m_hi) else max(m_lo, p1 * page)
                rest, group = s - p1, [m]
                for j in range(n - 1):
                    left = n - 1 - j
                    q = max(pages(lo), min(p1, rest - (left - 1) * pages(lo)))
                    group.append(max(lo, min(q * page, m)))
                    rest -= q
                if rest == 0:
                    seen.add(key)
                    groups.append(group)
    return groups


class Client:
    """The open-loop client around one server."""

    def __init__(self, srv, spans: Spans, vocab: int):
        self.srv, self.spans, self.vocab = srv, spans, vocab
        self.live: List[Rec] = []
        self.rid = 0
        self.step_s = 0.0        # wall time inside step()

    def submit(self, item: traffic.Item, due: float) -> Rec:
        from repro.serving.request import Request
        rec = Rec(item, due)
        with self.spans("bench.submit"):
            req = Request(rid=self.rid, adapter_uid=item.adapter,
                          prompt=item.prompt, max_new_tokens=item.max_new,
                          arrival_ms=self.srv.clock)
            self.rid += 1
            try:
                rec.st = self.srv.submit(req)
            except ValueError as e:
                rec.error = str(e)
                return rec
        rec.submitted = time.perf_counter()
        self.live.append(rec)
        return rec

    def step(self):
        t = time.perf_counter()
        with self.spans("bench.step"):
            self.srv.step()
        now = time.perf_counter()
        self.step_s += now - t
        self.stamp(now)

    def stamp(self, now: float):
        keep = []
        for rec in self.live:
            st = rec.st
            if rec.admitted is None and st.row >= 0:
                rec.admitted = now
            n = len(st.generated)
            if n > len(rec.stamps):
                rec.stamps.extend([now] * (n - len(rec.stamps)))
            if n < st.req.max_new_tokens:
                keep.append(rec)
        self.live = keep

    def flush(self):
        self.srv.backend.flush_readback()
        self.stamp(time.perf_counter())

    def until_idle(self, cap_s: float = 600.0):
        end = time.perf_counter() + cap_s
        while self.srv.busy() and time.perf_counter() < end:
            self.step()
        self.flush()

    def serve(self, items: List[traffic.Item], seconds: float,
              drain_cap_s: float, tracer=None) -> tuple:
        """Open loop over `items` for `seconds`; then, where `drain_cap_s`,
        keep stepping until every request due in the window has its first
        token or the cap passes. `tracer(now, t0)` is called between steps.
        Returns (records, t0, t_end, t_drained)."""
        recs: List[Rec] = []
        t0 = time.perf_counter()
        end = t0 + seconds
        i = 0
        while True:
            now = time.perf_counter()
            if tracer is not None:
                tracer(now, t0)
            if now >= end:
                break
            while i < len(items) and t0 + items[i].due_s <= now:
                recs.append(self.submit(items[i], t0 + items[i].due_s))
                i += 1
            if self.srv.busy():
                self.step()
            else:
                nxt = t0 + items[i].due_s if i < len(items) else end
                with self.spans("bench.wait_arrival"):
                    time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))
        t_end = time.perf_counter()
        if tracer is not None:
            tracer(float("inf"), t0)
        cap = t_end + drain_cap_s
        while drain_cap_s and time.perf_counter() < cap and any(
                not r.stamps and not r.error for r in recs) \
                and self.srv.busy():
            self.step()
        self.flush()
        return recs, t0, t_end, time.perf_counter()


def finished(recs: List[Rec], srv) -> List[dict]:
    """The requests served to the end, as the check reads them."""
    return [dict(prompt=r.item.prompt, tokens=list(r.st.generated),
                 adapter=r.item.adapter,
                 rank=srv.store.specs[r.item.adapter].rank,
                 cold=bool(r.st.cold_start))
            for r in recs if r.st is not None
            and len(r.st.generated) == r.item.max_new]


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def build(conf: dict, mix: dict, seed: int, params=None):
    """Server, its adapters, and the bench-made weights."""
    from repro.core.engine import InferenceServer
    from repro.core.lora import AdapterSpec
    cfg = weights_lib.program_config(conf)
    if params is None:
        params = weights_lib.make_weights(conf, cfg, seed)
    ads = traffic.registration_order(mix, seed)
    adapter_w = weights_lib.make_adapters(conf, ads, seed)
    srv_kw = dict(conf["server"])
    srv = InferenceServer(cfg, params=params, seed=seed % (2 ** 31),
                          **srv_kw)
    for uid, rank in ads:
        srv.store.register(AdapterSpec(uid, rank, base_model=cfg.name),
                           materialize=False)
        srv.store._weights[uid] = adapter_w[uid]
    return cfg, params, adapter_w, srv


def warm(client: Client, conf: dict, mix: dict, seed: int):
    """Compile or load every program the cell's traffic reaches, then
    bring the adapter pool to the state the window starts from. Returns
    the number of prefill warm-up groups."""
    ps, slots = conf["server"]["page_size"], conf["server"]["cache_slots"]
    ads = [uid for uid, _ in traffic.adapters(mix)]
    rng = traffic.rng_for(seed, 77)
    tok = lambda n: rng.integers(0, client.vocab, n).astype(np.int32)
    groups = prefill_groups(mix["prompt"]["min"], mix["prompt"]["max"], ps,
                            slots, conf["server"]["max_batch"])
    for g, lens in enumerate(groups):
        for j, n in enumerate(lens):
            client.submit(traffic.Item(0.0, ads[(g + j) % min(4, len(ads))],
                                       tok(n), WARMUP_NEW), 0.0)
        client.until_idle()
    client.submit(traffic.Item(0.0, ads[0], tok(ps), MEGASTEP_NEW), 0.0)
    client.until_idle()
    warm_pool(client, mix, seed)
    return len(groups)


def warm_pool(client: Client, mix: dict, seed: int):
    """The adapter pool as the window finds it: every adapter of a
    resident mix on the device, or a few seconds of the mix's own traffic
    served (another draw than the window's)."""
    rng = traffic.rng_for(seed, 78)
    if mix["adapters"].get("resident"):
        for uid, _ in traffic.adapters(mix):
            client.submit(traffic.Item(0.0, uid, rng.integers(
                0, client.vocab, mix["prompt"]["min"]).astype(np.int32),
                WARMUP_NEW), 0.0)
        client.until_idle()
    if mix.get("warmup_traffic_s", 0.0) > 0:
        items = traffic.generate(mix, seed, mix["warmup_traffic_s"],
                                 client.vocab, stream=1)
        client.serve(items, mix["warmup_traffic_s"], 0.0)
        client.until_idle()


def device_info(jax) -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peaks(kind: str) -> dict:
    with open(HERE / "peaks.json") as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, *, bench: Optional[dict] = None,
        configs: Optional[Dict[str, dict]] = None,
        mixes: Optional[Dict[str, dict]] = None,
        limits: Optional[dict] = None, peak: Optional[dict] = None,
        control: bool = False) -> dict:
    """One run; returns the result line as a dict. With `control`, the
    check judges the control's tokens in place of the program's (the
    readings a limit is set from). The other keyword arguments replace
    files under `bench/` (tests run tiny cells on the CPU)."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if bench is None:
        with open(ROOT / "BENCHMARK.json") as f:
            bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    centry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    if configs is None:
        with open(ROOT / centry["file"]) as f:
            conf = json.load(f)
    else:
        conf = configs[cell["config"]]
    mix = mixes[cell["traffic"]] if mixes else traffic.load(cell["traffic"])
    lim = limits if limits is not None else correct.limits(cell["config"])
    dev = device_info(jax)
    pk = peak if peak is not None else peaks(dev["kind"])
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    layer = [m for m in bench["per_layer"]
             if workload in m.get("workloads", [workload])]

    clock = CompileClock()
    spans = Spans(trace)
    cfg, params, adapter_w, srv = build(conf, mix, seed)
    client = Client(srv, spans, conf["vocab_size"])
    n_groups = warm(client, conf, mix, seed)
    jax.block_until_ready(srv.backend.cache)
    items = traffic.generate(mix, seed, seconds, conf["vocab_size"])
    gc.collect()
    work = Work(srv, spans) if trace else None
    tdir = OUT / "trace" / workload
    tracer = Tracer(client, work, seconds, tdir) if trace else None
    stats0 = dict(srv.backend.transfer_stats)
    c0 = clock.snapshot()
    setup_s = time.perf_counter() - t_start
    recs, t0, t_end, t_drained = client.serve(
        items, seconds, float(mix.get("drain_cap_s", 0.0)), tracer)
    c1 = clock.snapshot()
    stats1 = dict(srv.backend.transfer_stats)
    mem = jax.devices()[0].memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))

    # ------------------------------------------------ end-to-end metrics
    window = t_end - t0
    due = [r for r in recs if r.due < t_end]
    if not mix.get("drain_cap_s"):
        # a backlog: what the window reached is what was attempted
        due = [r for r in due if r.error or r.admitted is not None]
    failed = [r for r in due if r.error or not r.stamps]
    ttft = [((r.stamps[0] if r.stamps else t_drained) - r.due) * 1e3
            for r in due]
    tpot = []
    for r in recs:
        s = [t for t in r.stamps if t < t_end]
        if len(s) >= 2:
            tpot.append((s[-1] - s[0]) / (len(s) - 1) * 1e3)
    out_tokens = sum(sum(1 for t in r.stamps if t < t_end) for r in recs)
    values = {"tpot_p50_ms": percentile(tpot, 50) if tpot else None,
              "tpot_p90_ms": percentile(tpot, 90) if tpot else None,
              "out_tok_s": out_tokens / window,
              "setup_s": setup_s}
    late = [(r.submitted - r.due) * 1e3 for r in recs if r.submitted]
    log(f"window {window!r} s: {len(recs)} submitted, {len(due)} due, "
        f"{len(failed)} without a first token, "
        f"{sum(1 for r in recs if r.st is not None and r.st.done)} finished;"
        f" {out_tokens} tokens in the window; ttft samples {len(ttft)}, "
        f"tpot samples {len(tpot)}; drain {t_drained - t_end!r} s")
    if late:
        log(f"client lateness ms (submit - due): p50 {percentile(late, 50)!r}"
            f" p99 {percentile(late, 99)!r} max {max(late)!r}")
    if ttft:
        log(f"ttft ms: p50 {percentile(ttft, 50)!r} p90 "
            f"{percentile(ttft, 90)!r} max {max(ttft)!r}")
    if tpot:
        log(f"tpot ms: p50 {percentile(tpot, 50)!r} p90 "
            f"{percentile(tpot, 90)!r} max {max(tpot)!r}")
    d_stats = {k: stats1[k] - stats0.get(k, 0) for k in stats1}
    log(f"server counters in the window: {d_stats}")
    log(f"set-up {setup_s!r} s: {n_groups} prefill warm-up groups; compile "
        f"{c0[2]!r} s in {c0[1]} programs, {c0[3]} loaded from the "
        "persistent cache")
    in_window = {"events": c1[0] - c0[0], "programs": c1[1] - c0[1],
                 "secs": c1[2] - c0[2]}
    in_window["names"] = clock.names[c0[1]:c1[1]]
    log(f"compilation inside the window: {in_window}")
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in e2e if values.get(m["name"]) is not None}

    # ------------------------------------------------ per-layer metrics
    breakdown = None
    if trace:
        from bench import trace as trace_lib
        t_load = time.perf_counter()
        tr = trace_lib.load(trace_lib.find(str(tdir)))
        win = trace_lib.span_intervals(tr, "bench.window")
        lo, hi = win[0][0], win[-1][1]
        m0, m1 = tracer.marks
        calls = work.calls[m0["calls"]:m1["calls"]]
        # syncs bracket the traced stretch, so every step program in the
        # trace is one of its calls (the device clock may trail the host's
        # by a millisecond or two: no cut at the span's ends)
        progs = trace_lib.step_programs(tr, 0, 0, 2 ** 62, STEP_PROGRAMS) \
            if tr.programs else []
        if not trace_lib.matched(progs, calls):
            log(f"trace: {len(progs)} step programs on the device do not "
                f"pair with the {len(calls)} calls dispatched")
            progs = None
        ctx = dict(conf=conf, mix=mix, peak=pk, trace=tr, lo=lo, hi=hi,
                   recs=recs, t_end=t_end, t_drained=t_drained,
                   calls=calls, programs=progs,
                   stats={k: m1["stats"][k] - m0["stats"][k]
                          for k in m1["stats"]},
                   step_s=m1["step_s"] - m0["step_s"],
                   readback_s=m1["readback_s"] - m0["readback_s"])
        busy_s = np.mean([trace_lib.total(trace_lib.busy(tr, c, lo, hi))
                          for c in range(len(tr.ops))]) / 1e9 \
            if tr.ops else 0.0
        dev.update(busy_s=float(busy_s), window_s=(hi - lo) / 1e9)
        metrics = {}
        for m in layer:
            mod = importlib.import_module(f"bench.metrics.{m['name']}")
            v = mod.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        missing = [m["name"] for m in layer if m["name"] not in metrics]
        if missing:
            # BENCHMARK.json lists these for this cell: a traced run
            # without them is no result
            raise RuntimeError(f"traced run read no {missing}")
        if tr.ops:
            breakdown = {"device_ops": trace_lib.top_ops(tr, lo, hi),
                         "idle_gaps": trace_lib.idle_gaps(tr, 0, lo, hi)}
        log(f"trace: {(hi - lo) / 1e9!r} s traced, "
            f"{sum(len(o) for o in tr.ops)} device ops, {len(tr.spans)} host"
            f" spans, {len(calls)} calls; read in "
            f"{time.perf_counter() - t_load!r} s")
        shutil.rmtree(tdir, ignore_errors=True)
    dev["memory_peak_bytes"] = peak_bytes

    # ------------------------------------------------ correct
    done = finished(recs, srv)
    seq = conf["server"]["cache_slots"]
    del client, srv, work
    gc.collect()
    sample = correct.choose(done, seed)
    verdict = {"logit_gap": None, "requests": 0, "tokens": 0}
    t_ref = time.perf_counter()
    if sample:
        n_rows = mix["output"]["max"]
        ref = correct.reference(conf, params, seq, n_rows)
        ctrl = correct.reference(conf, params, seq, n_rows,
                                 quant=correct.CONTROL) if control else None
        verdict = correct.judge(ref, sample, adapter_w, ctrl)
    verdict["seconds"] = time.perf_counter() - t_ref
    if control:
        log("the control is in the program's place: logit_gap below is "
            "its widest gap")
    checks = {"logit_gap": {"value": verdict.get(
                  "control_gap" if control else "logit_gap"),
                            "limit": lim["logit_gap"]["limit"]},
              "failed": {"value": len(failed), "limit": 0}}
    ok = bool(sample) and all(c["value"] is not None
                              and c["value"] <= c["limit"]
                              for c in checks.values())
    log(f"reference check: {verdict}")
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    result = {"correct": ok, "attempted": len(due), "failed": len(failed),
              "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window_compiles"] = in_window["programs"]
    result["checks"] = checks
    return result
