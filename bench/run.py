"""Benchmark entry point: one run of one cell on the accelerator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the root of a checkout. The cell (`BENCHMARK.json`) names a
configuration (`bench/configs/<name>.json`) and a traffic mix
(`bench/traffic/<name>.json`). With `--trace 0` the last line of standard
output is the cell's end-to-end metrics; with `--trace 1`, its per-layer
metrics (`bench/metrics/<name>.py`), the device's busy time and a
breakdown. The numbers `correct` was decided by come last, on standard
error and in the line.

The run needs a TPU with as many chips as the cell asks for: without one
it exits with code 3 and prints no result. JAX's persistent compilation
cache lives in `<checkout>/.jax_cache`, or where `JAX_COMPILATION_CACHE_DIR`
names, so only a cell's first run in a checkout compiles. A traced run
that cannot read every per-layer metric the cell lists fails.

`--control 1` puts the control (the plain reference at float8) in the
program's place for the check; such a run must come out not correct, and
its `logit_gap` is the upper reading of the limit in `bench/limits/`.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
NO_DEVICE = 3


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the control (the reference at float8) in "
                         "the program's place; it must come out not correct")
    args = ap.parse_args()

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        sys.exit(f"unknown workload {args.workload!r}; BENCHMARK.json has "
                 f"{sorted(cells)}")
    import jax
    devs = jax.devices()
    want = cells[args.workload]["chips"]
    if devs[0].platform != "tpu" or len(devs) < want:
        print(f"bench: cell {args.workload} needs {want} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s) "
              f"({devs[0].device_kind!r})", file=sys.stderr)
        sys.exit(NO_DEVICE)

    from bench import harness
    result = harness.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), T_START,
                         control=bool(args.control))
    print(json.dumps(result))


if __name__ == "__main__":
    main()
