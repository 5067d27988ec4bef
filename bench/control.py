#!/usr/bin/env python3
"""Readings of the comparison for its limits: the program and the control.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 ...

For each seed, in one process: one run of the cell as ``run.py`` makes it
(set-up, the measured window, the comparison), then the reference in the
program's place at the lower precisions — ``bf16``, the control, and
``high`` (three bf16 passes) — over the same back-end queries and turns,
compared by the same numbers.  Prints a JSON line per seed:
``{"seed", "program": {...}, "control": {...}, "high": {...}}``.  The
benchmark's own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import bench_path  # noqa: E402,F401  (puts the checkout root on sys.path)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench import check, run
    bench, cell, cfg, mix = run.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    import jax

    from repro import compile_cache
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("bench/control.py: no TPU")
    compile_cache.enable()
    run.CompileClock.get()
    k, k_c = int(cfg["k"]), int(cfg["k_c"])

    def ctrl(ref, served, exact):
        return {p: check.numbers(ref, check.control(ref, served, k, k_c, p),
                                 exact, k, k_c) for p in ("bf16", "high")}

    t0 = T_START
    for seed in args.seeds:
        res = run.run_cell(cell, cfg, mix, seed=seed, seconds=args.seconds,
                           trace=False, bench=bench,
                           devices=jax.devices(), t_start=t0,
                           extra=ctrl, say=lambda m: print(m, file=sys.stderr))
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "attempted": res["attempted"], "failed": res["failed"],
            "program": {n: c["value"] for n, c in res["checks"].items()},
            "control": res["extra"]["bf16"], "high": res["extra"]["high"],
            "metrics": {n: m["value"] for n, m in res["metrics"].items()}}),
            flush=True)
        t0 = time.perf_counter()


if __name__ == "__main__":
    main()
