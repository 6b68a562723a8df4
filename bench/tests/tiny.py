"""Tiny cells for the CPU tests: the benchmark's own files, shrunk in width
and depth so a whole run takes seconds on the CPU, driven through
`harness.run` past the look for a chip."""
from __future__ import annotations

import json
import pathlib
import time

from bench import arch

ROOT = pathlib.Path(__file__).resolve().parents[2]

PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def bench() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def config(name: str) -> dict:
    with open(ROOT / "bench" / "configs" / f"{name}.json") as f:
        c = json.load(f)
    c = arch.load(c).tiny(c)
    c["lora"]["max_rank"] = 8
    c["server"].update(max_batch=4, cache_slots=96, page_size=16)
    return c


def mix(name: str) -> dict:
    with open(ROOT / "bench" / "traffic" / f"{name}.json") as f:
        m = json.load(f)
    m["adapters"]["ranks"] = [2, 4, 8, 8]
    m["adapters"]["count"] = min(m["adapters"]["count"], 12)
    m["prompt"].update(min=8, max=48, median=20)
    m["output"].update(min=4, max=32, median=8)
    if m["arrival"]["kind"] == "poisson":
        m["arrival"]["rate_per_s"] = 8.0
    else:
        m["arrival"]["count"] = 64
    m["warmup_traffic_s"] = min(m["warmup_traffic_s"], 1.0)
    return m


def run(workload: str, seed: int = 7, seconds: float = 2.0,
        trace: bool = False, limit: float = None,
        control: bool = False) -> dict:
    from bench import correct, harness
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == workload)
    lim = correct.limits(cell["config"])
    if limit is not None:
        lim = {"logit_gap": {"limit": limit}}
    return harness.run(
        workload, seed, seconds, trace, time.perf_counter(), bench=b,
        configs={cell["config"]: config(cell["config"])},
        mixes={cell["traffic"]: mix(cell["traffic"])}, limits=lim,
        peak=PEAK, control=control)
