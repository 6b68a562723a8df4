"""The per-layer metrics read from the program's own stamps and counters:
each on a synthetic context (zero cases read 0.0, a program without the
fields reads None), then on a tiny Poisson cell served on the CPU."""
import copy
import importlib
import time

import numpy as np
import pytest

import tiny
from bench import harness, traffic
from repro.serving.request import Request, RequestState

STAMPED = ("arrival_lag_p90_ms", "queued_p90_ms", "first_token_lag_p90_ms",
           "cold_first_token_lag_p50_ms", "prefill_dispatch_p90_ms",
           "prefill_readback_lag_p90_ms")
COUNTED = ("loop_host_ms_per_iter", "gc_ms_per_s")
NEW = STAMPED + COUNTED
CLIENT = ("client_ttft_p90_ms",)


def read(name, ctx):
    return importlib.import_module(f"bench.metrics.{name}").read(ctx)


class _Bare:
    """A request state as a program without the wall-clock stamps has it."""

    def __init__(self, cold):
        self.cold_start = cold


def rec(due, lags, cold=False, stamped=True):
    """A client record whose request was submitted, admitted and given
    its first token `lags` (arrival, queued, first token; seconds) apart,
    its prefill dispatched a third of the way into the last."""
    if stamped:
        st = RequestState(Request(0, "a", np.zeros(4, np.int32), 4),
                          cold_start=cold, submit_s=due + lags[0])
        st.admit_s = st.submit_s + lags[1]
        st.prefill_s = st.admit_s + lags[2] / 3
        st.first_token_s = st.admit_s + lags[2]
    else:
        st = _Bare(cold)
    r = harness.Rec(traffic.Item(due, "a", np.zeros(4, np.int32), 4), due,
                    st=st)
    r.stamps = [due + sum(lags)]
    return r


def ctx_of(recs, stats=None, lo=0, hi=int(2e9), t_end=100.0,
           t_drained=None):
    return {"recs": recs, "t_end": t_end, "t_drained": t_drained or t_end,
            "stats": stats or {}, "lo": lo, "hi": hi}


def test_client_ttft_p90_on_synthetic_records():
    """Every request due in the window counts, one without a first token
    as waiting until the drain ended; one due after the window does not."""
    recs = [rec(float(i), (0.01, 0.02, 0.01 * i)) for i in range(9)]
    late = rec(9.0, (0.0, 0.0, 0.0))
    late.stamps = []
    after = rec(12.0, (5.0, 5.0, 5.0))
    ctx = ctx_of(recs + [late, after], t_end=11.0, t_drained=19.5)
    want = [30.0 + 10.0 * i for i in range(9)] + [10500.0]
    got = read("client_ttft_p90_ms", ctx)
    assert got == pytest.approx(float(np.percentile(want, 90)))
    assert read("client_ttft_p90_ms", ctx_of([after], t_end=11.0)) is None


def test_request_lags_on_synthetic_records():
    recs = [rec(float(i), (0.001 * i, 0.002 * i, 0.003 * i), cold=i % 2)
            for i in range(11)]
    recs.append(rec(200.0, (1.0, 1.0, 1.0)))     # due after the window
    ctx = ctx_of(recs)
    assert read("arrival_lag_p90_ms", ctx) == pytest.approx(9.0)
    assert read("queued_p90_ms", ctx) == pytest.approx(18.0)
    assert read("first_token_lag_p90_ms", ctx) == pytest.approx(27.0)
    assert read("prefill_dispatch_p90_ms", ctx) == pytest.approx(9.0)
    assert read("prefill_readback_lag_p90_ms", ctx) == pytest.approx(18.0)
    # cold: i = 1, 3, 5, 7, 9 -> median 3 ms per unit
    assert read("cold_first_token_lag_p50_ms", ctx) == pytest.approx(15.0)


def test_zero_lags_read_zero():
    ctx = ctx_of([rec(1.0, (0.0, 0.0, 0.0), cold=True) for _ in range(3)])
    for name in STAMPED:
        v = read(name, ctx)
        assert v == 0.0 and v is not None, name


def test_program_without_stamps_reads_none():
    ctx = ctx_of([rec(1.0, (0.1, 0.1, 0.1), cold=True, stamped=False)])
    for name in STAMPED:
        assert read(name, ctx) is None, name
    # the program's counters absent (a parent without them)
    bare = ctx_of([], stats={"decode_steps": 10, "h2d": 3})
    for name in COUNTED:
        assert read(name, bare) is None, name


def test_loop_counters_on_synthetic_stats():
    stats = {"decode_steps": 40, "step_ns": 500_000_000,
             "readback_ns": 300_000_000, "gc_ns": 30_000_000, "gc_runs": 3}
    ctx = ctx_of([], stats=stats, lo=1_000, hi=1_000 + 2_000_000_000)
    assert read("loop_host_ms_per_iter", ctx) == pytest.approx(5.0)
    assert read("gc_ms_per_s", ctx) == pytest.approx(15.0)
    zero = dict(stats, step_ns=7, readback_ns=7, gc_ns=0, gc_runs=0)
    ctx = ctx_of([], stats=zero, lo=0, hi=10 ** 9)
    for name in COUNTED:
        v = read(name, ctx)
        assert v == 0.0 and v is not None, name


def _served(seed=11, seconds=2.0):
    conf = tiny.config("yi-9b-24l")
    mix = tiny.mix("zipf64-poisson")
    _, _, _, srv = harness.build(conf, mix, seed)
    client = harness.Client(srv, harness.Spans(False), conf["vocab_size"])
    harness.warm(client, conf, mix, seed)
    items = traffic.generate(mix, seed, seconds, conf["vocab_size"])
    recs, t0, t_end, _ = client.serve(items, seconds,
                                      float(mix["drain_cap_s"]))
    return recs, t_end


def test_split_is_within_the_clients_ttft():
    """Per request: arrival lag + queued + first-token lag <= the TTFT the
    client saw, each part >= 0, and the prefill's dispatch splits the
    first-token lag."""
    recs, t_end = _served()
    due = [r for r in recs if r.due < t_end and r.stamps]
    assert len(due) >= 5
    for r in due:
        st = r.st
        parts = (st.submit_s - r.due, st.admit_s - st.submit_s,
                 st.first_token_s - st.admit_s)
        assert min(parts) >= 0, parts
        assert sum(parts) <= r.stamps[0] - r.due + 1e-9, (parts, r.stamps)
        assert st.admit_s <= st.prefill_s <= st.first_token_s
    ctx = ctx_of(recs, t_end=t_end)
    for name in STAMPED[:3] + STAMPED[4:]:
        assert read(name, ctx) is not None, name


def test_traced_tiny_run_reads_every_new_metric():
    """Through `harness.run`, with a cell that lists the new metrics (and
    none of the device-trace ones, which read nothing on the CPU)."""
    b = copy.deepcopy(tiny.bench())
    cell = "yi9b.zipf64-poisson"
    b["per_layer"] = [
        {"name": n, "unit": "ms", "better": "lower", "source": "host_clock",
         "layer": "event loop", "moves": "tpot_p90_ms", "workloads": [cell]}
        for n in NEW + CLIENT]
    conf = tiny.config("yi-9b-24l")
    mix = tiny.mix("zipf64-poisson")
    out = harness.run(cell, 5, 2.0, True, time.perf_counter(), bench=b,
                      configs={"yi-9b-24l": conf},
                      mixes={"zipf64-poisson": mix},
                      limits={"logit_gap": {"limit": 0.06}}, peak=tiny.PEAK)
    for n in NEW + CLIENT:
        assert out["metrics"][n]["value"] >= 0.0, n
