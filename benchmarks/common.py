import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

use_compile_cache()

ROWS = []


def emit(name: str, us_per_call: float, derived: str = ""):
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.3f},{derived}", flush=True)


def write_bench_json(name: str, payload: dict, out_dir: str = None):
    """Write BENCH_<name>.json so the perf trajectory is machine-readable
    across PRs (tokens/s, TTFT, SLO, h2d counts, ...). `payload` should be
    a plain dict of metrics; the emitted CSV rows so far are attached under
    "rows" for free. Returns the path."""
    path = os.path.join(out_dir or os.environ.get("BENCH_OUT_DIR", "."),
                        f"BENCH_{name}.json")
    doc = dict(payload)
    doc.setdefault("bench", name)
    doc["rows"] = [{"name": n, "value_us": v, "derived": d}
                   for n, v, d in ROWS]
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True, default=float)
        f.write("\n")
    print(f"wrote {path}", flush=True)
    return path


def oversub_stats(srv) -> dict:
    """Preemption / KV over-subscription telemetry of one InferenceServer
    for BENCH_*.json (all-zero on dense layouts and never-preempting runs).
    Keys: preemptions, swap_preemptions, recompute_preemptions,
    swapped_pages, recompute_tokens, grown_pages, peak_oversub."""
    d = {k: int(v) for k, v in srv.preempt_stats.items()}
    d["peak_oversub"] = float(srv.peak_oversub)
    return d


def cluster_oversub_stats(cluster) -> dict:
    """Aggregate oversub_stats over a Cluster: counters sum, peak_oversub
    takes the per-server max (a ratio — summing it is meaningless)."""
    agg = {}
    for srv in cluster.servers:
        for k, v in oversub_stats(srv).items():
            if k == "peak_oversub":
                agg[k] = max(agg.get(k, 0.0), v)
            else:
                agg[k] = agg.get(k, 0) + v
    return agg


def fault_stats(srv) -> dict:
    """Failure-plane telemetry of one InferenceServer for BENCH_*.json:
    crash/restart/drain counters from the engine, upload failure/retry/
    cancel counters from the link tracker, and admission-level shedding.
    All-zero on fault-free runs — the counters exist in every BENCH doc so
    the trajectory is comparable across PRs."""
    d = {k: int(v) for k, v in srv.fault_stats.items()}
    tr = srv.cold.tracker.stats
    for k in ("upload_failures", "retries", "prefetch_dropped",
              "crash_canceled"):
        d[k] = int(tr[k])
    d["admission_shed"] = int(srv.admission.shed_count)
    return d


def cluster_fault_stats(cluster) -> dict:
    """Aggregate fault_stats over a Cluster (counters sum) plus the
    cluster-level failover/shed ledger under a `cluster_` prefix."""
    agg = {}
    for srv in cluster.servers:
        for k, v in fault_stats(srv).items():
            agg[k] = agg.get(k, 0) + v
    for k, v in cluster.fault_stats.items():
        agg[f"cluster_{k}"] = int(v)
    return agg


def itl_stats(srv) -> dict:
    """Inter-token-latency percentiles of one InferenceServer for
    BENCH_*.json: n_gaps, itl_mean_ms, itl_p50_ms, itl_p99_ms."""
    return srv.itl_stats()


def cluster_itl_stats(cluster) -> dict:
    """ITL percentiles pooled across every server of a Cluster (gaps are
    pooled, not averaged — a percentile of percentiles is meaningless)."""
    from repro.serving.request import itl_percentiles
    return itl_percentiles(g for srv in cluster.servers
                           for g in srv.itl_samples())


def time_us(fn, iters=5, warmup=2):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters * 1e6
