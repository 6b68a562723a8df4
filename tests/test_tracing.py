"""Tracing inside the serving loop: wall-clock request stamps, the loop
counters in `transfer_stats`, the `serve.*` profiler spans, the named
scopes of the LoRA delta and the paged decode attention, and the one
process-wide garbage-collection hook."""
import gc
import glob
import time

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import tracing
from repro.core.engine import InferenceServer
from repro.core.lora import AdapterSpec, DevicePool, make_adapter_weights
from repro.serving.request import Request

CFG = get_config("llama2-7b").smoke()


def _server(**kw):
    srv = InferenceServer(CFG, mode="caraserve", max_batch=2, cache_slots=64,
                          numerics=True, seed=0, pool_slots=2, **kw)
    for i in range(3):
        srv.register_adapter(AdapterSpec(f"ad{i}", rank=8,
                                         base_model=CFG.name))
    return srv


def _serve_staggered(srv, n=5, prompt=(6, 20), max_new=5):
    """Submit `n` requests a few steps apart (more than the rows, so some
    wait), stepping until all are done. Returns (states, the wall time
    each step returned, per state the index of the step whose return
    first saw a token in `generated`)."""
    rng = np.random.default_rng(3)
    states, returns, seen = [], [], {}
    i = 0
    while i < n or srv.busy():
        if i < n and len(returns) % 2 == 0:
            req = Request(rid=i, adapter_uid=f"ad{i % 3}",
                          prompt=rng.integers(0, CFG.vocab, int(
                              rng.integers(*prompt))).astype(np.int32),
                          max_new_tokens=max_new, arrival_ms=srv.clock)
            states.append(srv.submit(req))
            i += 1
        srv.step()
        returns.append(time.perf_counter())
        for st in states:
            if st.generated and id(st) not in seen:
                seen[id(st)] = len(returns) - 1
        if len(returns) > 500:
            raise RuntimeError("server did not drain")
    return states, returns, seen


@pytest.mark.parametrize("kw", [
    {}, {"megastep": 0}, {"chunk_budget": 8}, {"pipeline": "perstep"}],
    ids=["megastep", "single", "chunked", "perstep"])
def test_request_stamps_in_order(kw):
    srv = _server(**kw)
    states, returns, seen = _serve_staggered(srv)
    assert all(st.generated for st in states)
    for st in states:
        assert st.submit_s <= st.admit_s <= st.prefill_s \
            <= st.first_token_s, st.req.rid
        assert st.first_token_s <= returns[seen[id(st)]], st.req.rid
    # the virtual-clock fields are untouched by the wall-clock stamps
    assert all(st.first_token_ms is not None for st in states)


def test_loop_counters():
    srv = _server()
    stats = srv.transfer_stats
    assert srv.backend.transfer_stats is stats
    _serve_staggered(srv)
    assert stats["step_ns"] > 0 and stats["decode_steps"] > 0
    assert 0 <= stats["readback_ns"] <= stats["step_ns"]
    assert stats["gc_ns"] >= 0 and stats["gc_runs"] >= 0
    t0 = stats["gc_runs"]
    gc.collect()        # outside any step: not counted
    assert stats["gc_runs"] == t0
    timing = InferenceServer(CFG, numerics=False, max_batch=2)
    assert set(timing.transfer_stats) == set(tracing.STATS)


def test_perstep_readback_is_counted():
    """The perstep baseline blocks on every decode step's tokens: each of
    those readbacks is a `d2h` timed into `readback_ns` too."""
    srv = _server(pipeline="perstep")
    stats = srv.transfer_stats
    _serve_staggered(srv)
    assert stats["d2h"] >= stats["decode_steps"] + stats["prefills"]
    assert 0 < stats["readback_ns"] <= stats["step_ns"]


def test_gc_inside_a_step_is_counted(monkeypatch):
    srv = _server()
    stats = srv.transfer_stats
    run0, ns0 = stats["gc_runs"], stats["gc_ns"]
    inner = srv._step
    monkeypatch.setattr(srv, "_step", lambda h: (gc.collect(), inner(h)))
    srv.step()
    assert stats["gc_runs"] >= run0 + 1 and stats["gc_ns"] > ns0


def test_pool_uploads_are_counted():
    seen = []
    pool = DevicePool(CFG, n_slots=2, on_upload=seen.append)
    spec = AdapterSpec("a", rank=8, base_model=CFG.name)
    w = make_adapter_weights(CFG, spec)
    pool.reserve("a", w, 8)
    assert seen == [sum(ab[k].nbytes for ab in w.values() for k in "ab")]
    srv = _server()
    before = dict(srv.transfer_stats)
    _serve_staggered(srv, n=3)
    # three adapters made cold starts into the device pool: each crossed
    # the link at least once on top of the staging cache's copies
    got = srv.transfer_stats["h2d_bytes"] - before["h2d_bytes"]
    assert got >= 3 * seen[0] + srv.backend.staging.misses * seen[0]


def test_admission_stop_reason():
    srv = InferenceServer(CFG, numerics=False, max_batch=1)
    srv.register_adapter(AdapterSpec("ad0", rank=8, base_model=CFG.name))
    srv.step()
    assert srv.admission.stop_reason == "empty"
    for i in range(2):
        srv.submit(Request(rid=i, adapter_uid="ad0",
                           prompt=np.zeros(4, np.int32), max_new_tokens=3))
    srv.step()
    assert srv.admission.stop_reason == "rows"


def _events(path):
    """Host events named `serve.*`: (name, start, end, stats, line id)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats), (plane.name, k)))
    return out


def test_spans_nest_inside_the_step(tmp_path):
    srv = _server()
    _serve_staggered(srv, n=2)         # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        _serve_staggered(srv, n=4)
    ev = _events(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0])
    steps = [e for e in ev if e[0] == "serve.step"]
    assert steps and all("step_num" in e[3] for e in steps)

    def inside(e):
        return any(s[4] == e[4] and s[1] <= e[1] and e[2] <= s[2]
                   for s in steps)

    names = {e[0] for e in ev}
    for name in ("serve.admit", "serve.prefill", "serve.readback"):
        assert name in names
    assert names & {"serve.decode", "serve.megastep"}
    for e in ev:
        if e[0] in ("serve.admit", "serve.prefill", "serve.decode",
                    "serve.megastep", "serve.plan", "serve.retire"):
            assert inside(e), e
    admits = [e[3] for e in ev if e[0] == "serve.admit"]
    assert all({"queue", "admitted", "stop"} <= set(a) for a in admits)
    assert {a["stop"] for a in admits} <= {
        "empty", "rows", "arrival", "kv_pages", "adapter_slots"}
    assert any(e[3].get("admitted", 0) > 0 for e in ev
               if e[0] == "serve.admit")


def test_named_scopes_in_decode_program():
    srv = _server()
    assert srv.backend.paged
    be, pipe = srv.backend, srv.backend.pipe
    lora = {"pool": srv.pool.pool, "idx": pipe.idx}
    text = be._decode_jit.lower(
        be.params, be.cache, pipe.last_tok, pipe.pos, pipe.active,
        pipe.target, lora, pipe.rng, pipe.block_table).as_text(
            debug_info=True)
    assert "lora_delta" in text
    assert "paged_attn_decode" in text


def test_one_gc_hook_per_process():
    InferenceServer(CFG, numerics=False)
    n = len(gc.callbacks)
    for _ in range(200):
        InferenceServer(CFG, numerics=False, max_batch=2)
    assert len(gc.callbacks) <= n + 1
    assert gc.callbacks.count(tracing._on_gc) == 1
