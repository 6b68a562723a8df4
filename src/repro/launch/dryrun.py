"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, extract memory/cost/collective analyses, and emit the
roofline table rows (EXPERIMENTS.md sec Dry-run / sec Roofline).

MUST be the process entrypoint: the XLA flag below creates 512 placeholder
host devices and jax locks the device count at first init.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out d/]
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import roofline, sharding as shd                     # noqa: E402
from repro.configs.base import (INPUT_SHAPES, ModelConfig,      # noqa: E402
                                all_arch_ids, combo_is_supported, get_config)
from repro.core import lora as lora_lib                         # noqa: E402
from repro.launch.mesh import make_production_mesh              # noqa: E402
from repro.models import model as model_lib                     # noqa: E402
from repro.models.param import split                            # noqa: E402
from repro.training import optim, train as train_lib            # noqa: E402


def _cost_dict(cost):
    """compiled.cost_analysis() returns a dict (new jax) or a one-element
    list of dicts per device (old jax); normalize to a dict."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost or {}


def _shardings_for(mesh, axes_tree, shapes_tree):
    return shd.tree_shardings(mesh, axes_tree, shapes_tree)


def _replicated(mesh):
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())


def _batch_shardings(mesh, batch_tree):
    axes = model_lib.batch_logical_axes(batch_tree)
    return shd.tree_shardings(mesh, axes, batch_tree)


def build_train(cfg: ModelConfig, shape, mesh):
    p_shapes, p_axes = model_lib.abstract_params(cfg)
    opt_shapes = jax.eval_shape(
        lambda p: optim.init(p, jnp.dtype(cfg.opt_moments_dtype)), p_shapes)
    p_shard = _shardings_for(mesh, p_axes, p_shapes)
    opt_shard = optim.AdamWState(
        step=_replicated(mesh),
        mu=jax.tree.map(lambda _, s: s, opt_shapes.mu, p_shard),
        nu=jax.tree.map(lambda _, s: s, opt_shapes.nu, p_shard))
    specs = model_lib.input_specs(cfg, shape)
    batch = specs["batch"]
    b_shard = _batch_shardings(mesh, batch)
    ocfg = optim.AdamWConfig(moments_dtype=cfg.opt_moments_dtype)
    step = train_lib.make_train_step(cfg, ocfg)
    args = (p_shapes, opt_shapes, batch)
    in_shardings = (p_shard, opt_shard, b_shard)
    return step, args, in_shardings


def build_prefill(cfg: ModelConfig, shape, mesh):
    rules = shd.serve_rules() if cfg.serve_tp else None
    p_shapes, p_axes = model_lib.abstract_params(cfg)
    p_shard = shd.tree_shardings(mesh, p_axes, p_shapes, rules)
    specs = model_lib.input_specs(cfg, shape)
    batch = specs["batch"]
    b_shard = _batch_shardings(mesh, batch)
    pool_box = lora_lib.pool_abstract(cfg)
    pool_shapes, pool_axes = split(pool_box)
    pool_shard = shd.tree_shardings(mesh, pool_axes, pool_shapes, rules)
    B = shape.global_batch
    idx = jax.ShapeDtypeStruct((B,), jnp.int32)
    idx_shard = shd.named_sharding(mesh, ("batch",), (B,))

    def fn(params, batch, pool, idx):
        lora = {"pool": pool, "idx": idx, "mode": "mbgmv"}
        logits, cache = model_lib.prefill(cfg, params, batch, lora=lora,
                                          cache_slots=shape.seq_len,
                                          last_only=True)
        return logits, cache

    return fn, (p_shapes, batch, pool_shapes, idx), \
        (p_shard, b_shard, pool_shard, idx_shard)


def build_decode(cfg: ModelConfig, shape, mesh):
    rules = shd.serve_rules() if cfg.serve_tp else None
    p_shapes, p_axes = model_lib.abstract_params(cfg)
    p_shard = shd.tree_shardings(mesh, p_axes, p_shapes, rules)
    specs = model_lib.input_specs(cfg, shape)
    cache = specs["cache"]
    cache_axes = model_lib.cache_logical_axes(cfg, cache)
    cache_shard = shd.tree_shardings(mesh, cache_axes, cache)
    pool_box = lora_lib.pool_abstract(cfg)
    pool_shapes, pool_axes = split(pool_box)
    pool_shard = shd.tree_shardings(mesh, pool_axes, pool_shapes, rules)
    B = shape.global_batch
    tok_shard = shd.named_sharding(mesh, ("batch", None), (B, 1))
    pos_shard = shd.named_sharding(mesh, ("batch",), (B,))
    idx_shard = shd.named_sharding(mesh, ("batch",), (B,))
    window = model_lib.decode_window(cfg, shape.seq_len)

    def fn(params, cache, toks, pos, pool, idx):
        lora = {"pool": pool, "idx": idx, "mode": "mbgmv"}
        return model_lib.decode(cfg, params, cache, toks, pos, lora=lora,
                                window=window)

    args = (p_shapes, cache, specs["tokens_t"], specs["pos"], pool_shapes,
            jax.ShapeDtypeStruct((B,), jnp.int32))
    in_sh = (p_shard, cache_shard, tok_shard, pos_shard, pool_shard,
             idx_shard)
    return fn, args, in_sh


def _builder(kind):
    return {"train": build_train, "prefill": build_prefill,
            "decode": build_decode}[kind]


def analytic_bytes_per_chip(args, in_shardings) -> float:
    """True per-chip residency of the step's persistent inputs (params, opt
    state, cache, pool, batch) from the actual shardings — the XLA-CPU
    temp accounting is an upper bound without TPU buffer optimizations."""
    total = 0.0
    for leaf, sh in zip(jax.tree.leaves(args), jax.tree.leaves(
            in_shardings, is_leaf=lambda x: isinstance(
                x, jax.sharding.NamedSharding))):
        n = 1
        for d in leaf.shape:
            n *= d
        shards = 1
        sizes = dict(zip(sh.mesh.axis_names, sh.mesh.devices.shape))
        for entry in sh.spec:
            if entry is None:
                continue
            for ax in (entry if isinstance(entry, tuple) else (entry,)):
                shards *= sizes[ax]
        total += n * jnp.dtype(leaf.dtype).itemsize / shards
    return total


def _probe_costs(cfg: ModelConfig, shape, mesh):
    """Lower+compile unrolled 1- and 2-unit probes and linearly extrapolate
    per-device totals (XLA cost analysis counts while-loop bodies once; the
    probes contain no loops, so probe costs are exact for their depth)."""
    out = {}
    for k in (1, 2):
        pcfg = cfg.probe(k)
        fn, args, in_sh = _builder(shape.kind)(pcfg, shape, mesh)
        compiled = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
        cost = _cost_dict(compiled.cost_analysis())
        coll = roofline.collective_bytes(compiled.as_text())
        out[k] = (float(cost.get("flops", 0.0)),
                  float(cost.get("bytes accessed", 0.0)),
                  float(sum(coll.values())))
    step = cfg.probe(2).n_layers - cfg.probe(1).n_layers
    m = cfg.n_layers / step          # layer-units at full depth
    f1, b1, c1 = out[1]
    f2, b2, c2 = out[2]
    corr = lambda v1, v2: v1 + (m - 1) * (v2 - v1)
    return {"flops": corr(f1, f2), "bytes": corr(b1, b2),
            "coll": max(corr(c1, c2), 0.0),
            "per_layer": {"flops": f2 - f1, "bytes": b2 - b1,
                          "coll": c2 - c1}}


OPTS = ("serve_tp", "kv8", "moe2d", "moe_gather", "moe_ep", "seqpar")


def apply_opts(cfg: ModelConfig, opts) -> ModelConfig:
    """Perf-iteration knobs (EXPERIMENTS.md sec Perf)."""
    import dataclasses
    kw = {}
    if "serve_tp" in opts:
        kw["serve_tp"] = True
    if "kv8" in opts:
        kw["kv_cache_dtype"] = "int8"
    if "moe2d" in opts:
        kw["moe_2d_ff"] = True
    if "moe_gather" in opts:
        kw["moe_gather_weights"] = True
    if "moe_ep" in opts:
        kw["moe_ep"] = True
    if "seqpar" in opts:
        kw["seq_parallel"] = True
    return dataclasses.replace(cfg, **kw) if kw else cfg


def run_combo(arch: str, shape_name: str, multi_pod: bool = False,
              out_dir: str = "experiments/dryrun", save_hlo: bool = False,
              probes: bool = True, opts=()):
    cfg = apply_opts(get_config(arch), opts)
    shape = INPUT_SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    tagext = ("+" + "+".join(sorted(opts))) if opts else ""
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name + tagext,
           "status": "ok", "opts": sorted(opts)}
    ok, why = combo_is_supported(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            fn, args, in_sh = build_train(cfg, shape, mesh)
            donate = ()
        elif shape.kind == "prefill":
            fn, args, in_sh = build_prefill(cfg, shape, mesh)
            donate = ()
        else:
            fn, args, in_sh = build_decode(cfg, shape, mesh)
            donate = (1,)                      # cache aliasing
        rec["analytic_input_bytes_per_chip"] = analytic_bytes_per_chip(
            args, in_sh)
        jitted = jax.jit(fn, in_shardings=in_sh, donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = _cost_dict(compiled.cost_analysis())
        hlo = compiled.as_text()
    coll = roofline.collective_bytes(hlo)
    if save_hlo:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(
                out_dir, f"{arch}_{shape_name}_{mesh_name}.hlo"), "w") as f:
            f.write(hlo)
    # cost_analysis()/HLO text describe the per-device SPMD program; raw
    # numbers count scan bodies once, the probe-corrected totals fix that.
    flops = float(cost.get("flops", 0.0))
    bytes_hbm = float(cost.get("bytes accessed", 0.0))
    coll_total = float(sum(coll.values()))
    if probes:
        with jax.set_mesh(mesh):
            pc = _probe_costs(cfg, shape, mesh)
        flops, bytes_hbm, coll_total = pc["flops"], pc["bytes"], pc["coll"]
        rec["probe_per_layer"] = pc["per_layer"]
        rec["scan_corrected"] = True
    terms = roofline.roofline_terms(flops, bytes_hbm, coll_total, chips,
                                    per_device=True)
    mflops = roofline.model_flops(cfg, shape)
    rec.update({
        "chips": chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "hlo_flops_per_dev": flops,
        "hlo_flops_total": flops * chips,
        "hlo_bytes_per_dev": bytes_hbm,
        "collective_bytes": coll,
        "collective_total_per_dev": coll_total,
        "roofline": terms,
        "model_flops": mflops,
        "useful_flops_ratio": (mflops / (flops * chips)) if flops else None,
        "memory_analysis": {
            k: getattr(mem, k)
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes")
            if hasattr(mem, k)},
    })
    ma = rec["memory_analysis"]
    if ma.get("argument_size_in_bytes") is not None:
        # memory_analysis is per-device for SPMD executables
        live = (ma.get("argument_size_in_bytes", 0)
                + ma.get("output_size_in_bytes", 0)
                - ma.get("alias_size_in_bytes", 0)
                + ma.get("temp_size_in_bytes", 0))
        rec["bytes_per_chip"] = live
        rec["fits_16g"] = live < 16 * 2 ** 30
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--opt", default="",
                    help="comma-separated perf knobs: " + ",".join(OPTS))
    args = ap.parse_args()
    opts = tuple(o for o in args.opt.split(",") if o)

    archs = all_arch_ids() if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    os.makedirs(args.out, exist_ok=True)
    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}" \
                    + (f" [{args.opt}]" if opts else "")
                try:
                    rec = run_combo(arch, shape, mp, args.out,
                                    args.save_hlo, opts=opts)
                except Exception as e:          # a failure here is a bug
                    mname = ("pod2x16x16" if mp else "pod16x16") \
                        + (("+" + "+".join(sorted(opts))) if opts else "")
                    rec = {"arch": arch, "shape": shape, "mesh": mname,
                           "status": "FAILED", "error": repr(e),
                           "trace": traceback.format_exc()[-2000:]}
                results.append(rec)
                path = os.path.join(
                    args.out, f"{rec['arch']}_{rec['shape']}_{rec['mesh']}.json")
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1, default=str)
                if rec["status"] == "ok":
                    r = rec["roofline"]
                    print(f"[ok] {tag}: compile={rec['compile_s']}s "
                          f"dominant={r['dominant']} "
                          f"c/m/x={r['compute_s']:.4f}/{r['memory_s']:.4f}/"
                          f"{r['collective_s']:.4f}s", flush=True)
                else:
                    print(f"[{rec['status']}] {tag}: "
                          f"{rec.get('reason', rec.get('error', ''))}",
                          flush=True)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_fail = sum(r["status"] == "FAILED" for r in results)
    print(f"\n{n_ok} ok / {n_skip} skipped / {n_fail} FAILED")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
