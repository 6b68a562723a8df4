"""Serving launcher: run a CaraServe inference server (or a scheduler-fronted
cluster) over a generated trace and report the paper's three metrics.

  PYTHONPATH=src python -m repro.launch.serve --arch llama2-7b --smoke \\
      --mode caraserve --kernel bgmv --rps 6 --duration 10
  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --layers 24
  PYTHONPATH=src python -m repro.launch.serve --cluster 8 --policy rank_aware

A single server always computes real tokens (the numerics plane); only
`--cluster` runs timing-only replicas on virtual clocks. `--smoke` shrinks
every width for a CPU run; `--layers N` keeps every published width and
cuts depth only.
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import numpy as np

from repro.configs.base import get_config
from repro.core.cluster import Cluster
from repro.core.engine import InferenceServer
from repro.core.perf_model import ServerPerfModel
from repro.core.scheduler import make_scheduler
from repro.launch.compile_cache import use_compile_cache
from repro.traces import gen


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-7b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced widths and depth (CPU-runnable numerics)")
    ap.add_argument("--layers", type=int, default=0,
                    help="keep the first N layers at published widths "
                         "(0 = all)")
    ap.add_argument("--mode", default="caraserve",
                    choices=["cached", "ondemand", "slora", "caraserve"])
    ap.add_argument("--kernel", default="bgmv", choices=["bgmv", "mbgmv"])
    ap.add_argument("--rps", type=float, default=6.0)
    ap.add_argument("--duration", type=float, default=10.0)
    ap.add_argument("--n-adapters", type=int, default=32)
    ap.add_argument("--rank", type=int, default=None)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--trace", default="maf", choices=["maf", "synthetic"])
    ap.add_argument("--cluster", type=int, default=0,
                    help="run N servers behind the scheduler (timing-only)")
    ap.add_argument("--policy", default="rank_aware",
                    choices=["rank_aware", "most_idle", "first_fit",
                             "random"])
    ap.add_argument("--slo-scale", type=float, default=1.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    serve_cfg = cfg.smoke() if args.smoke else cfg
    if args.layers:
        if not 0 < args.layers <= serve_cfg.n_layers:
            ap.error(f"--layers must be in 1..{serve_cfg.n_layers}")
        serve_cfg = dataclasses.replace(serve_cfg, n_layers=args.layers)
    print(f"model {serve_cfg.name}: d_model {serve_cfg.d_model}, "
          f"heads {serve_cfg.n_heads}/{serve_cfg.n_kv_heads}, d_ff "
          f"{serve_cfg.d_ff}, vocab {serve_cfg.vocab}, {serve_cfg.dtype}, "
          f"layers {serve_cfg.n_layers} of {cfg.n_layers}")
    rng = np.random.default_rng(args.seed)
    adapters = gen.make_adapters(args.n_adapters, cfg.name, rng,
                                 uniform_rank=args.rank)
    perf = ServerPerfModel(cfg, kernel=args.kernel)
    slo = args.slo_scale * perf.dec_perf([64] * args.max_batch)
    mk = gen.maf_trace if args.trace == "maf" else gen.synthetic_trace
    reqs = mk(adapters, rps=args.rps, duration_s=args.duration,
              vocab=serve_cfg.vocab, seed=args.seed, slo_tpt_ms=slo)
    print(f"{len(reqs)} requests, SLO={slo:.1f} ms/token")

    if args.cluster:
        servers = []
        for _ in range(args.cluster):
            srv = InferenceServer(cfg, mode=args.mode, kernel=args.kernel,
                                  max_batch=args.max_batch, numerics=False)
            for ad in adapters:
                srv.register_adapter(ad)
            servers.append(srv)
        sched = make_scheduler(args.policy, perf, slo_ms=slo) \
            if args.policy == "rank_aware" else make_scheduler(args.policy)
        out, _ = Cluster(servers, sched).run(reqs)
    else:
        use_compile_cache()
        srv = InferenceServer(serve_cfg, mode=args.mode, kernel=args.kernel,
                              max_batch=args.max_batch, seed=args.seed)
        for ad in adapters:
            srv.register_adapter(ad)
        out = srv.run(reqs)
        print(f"generated {sum(len(s.generated) for s in srv.states)} "
              f"tokens on {srv.backend.params['embed'].devices()}")

    # every time below is on TimingModel's virtual clock, not measured
    print("simulated (virtual-clock) serving metrics:")
    for k, v in out.items():
        print(f"  {k:16s} {v:.3f}" if isinstance(v, float) else
              f"  {k:16s} {v}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
