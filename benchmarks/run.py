"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per the harness contract, where
``derived`` carries each table's headline quality/efficiency number.

Every phase runs in this one process, over ``jax.devices()``: a chip
belongs to one process, so a child started after this one touched JAX
could not reach it.  A failing phase fails the run.
"""

from __future__ import annotations

import sys
import time


def _csv(name, us, derived):
    print(f"{name},{us:.3f},{derived}")


def main() -> None:
    from benchmarks import common as C
    from repro import compile_cache
    compile_cache.enable()

    t0 = time.perf_counter()
    world = C.make_world(C.DEFAULT_WORLD)
    index = C.build_index(world)
    _csv("world_build", 1e6 * (time.perf_counter() - t0), f"docs={world.n_docs}")

    # --- Table 1: effectiveness + hit rate -------------------------------
    # every row reports ITS OWN elapsed wall clock (rows used to share one
    # whole-table average, which flattened per-policy timing trajectories)
    from benchmarks import table1_effectiveness
    rows = table1_effectiveness.run(world, index)
    base = rows[0]
    _csv("table1_no_caching", 1e6 * base.elapsed_s,
         f"MAP200={base.map200:.3f};nDCG3={base.ndcg3:.3f}")
    for r in rows[1:]:
        _csv(f"table1_{r.policy}_kc{r.k_c}", 1e6 * r.elapsed_s,
             f"MAP200={r.map200:.3f};nDCG3={r.ndcg3:.3f};cov10={r.cov10:.2f};"
             f"hit={100 * r.hit_rate:.1f}%;p_ndcg={r.p_ndcg:.3f}")

    # --- Table 2 / Fig 4-5: epsilon tuning --------------------------------
    from benchmarks import table2_epsilon
    t0 = time.perf_counter()
    out = table2_epsilon.run(world, index)
    dt = 1e6 * (time.perf_counter() - t0)
    _csv("table2_eps_tuned", dt, f"eps10={out['eps10']:.4f};"
                                 f"eps200={out['eps200']:.4f}")
    for r in out["rows"]:
        _csv(f"table2_dynamic_eps{r.epsilon:.3f}_kc{r.k_c}",
             1e6 * r.elapsed_s,
             f"MAP200={r.map200:.3f};hit={100 * r.hit_rate:.1f}%;"
             f"p_map={r.p_map:.3f}")

    # --- Table 3: latency --------------------------------------------------
    from benchmarks import table3_latency
    rows3 = table3_latency.run(world, index)
    for (name, k_c), t in rows3.items():
        _csv(f"table3_{name}_kc{k_c}", 1e6 * t, f"ms={1e3 * t:.4f}")
    kc_top = C.KC_SWEEP[-1]
    hit = rows3[("cache_hit", kc_top)]
    back = rows3[("backend", kc_top)]
    _csv(f"table3_speedup_kc{kc_top}", 1e6 * hit,
         f"speedup={back / hit:.0f}x")

    # --- kernels ------------------------------------------------------------
    from benchmarks import kernel_bench
    rowsk = kernel_bench.run()
    for name, t in rowsk.items():
        _csv(f"kernel_{name}", 1e6 * t, f"ms={1e3 * t:.3f}")

    # --- distributed retrieval: exact vs chunked vs sharded @ 1M docs -------
    # in this process, sharded over this process's devices
    from benchmarks import retrieval_bench
    rec = retrieval_bench.run()
    for name in ("exact", "chunked", "sharded"):
        _csv(f"retrieval_{name}_1M", rec[f"{name}_us"],
             f"ndev={rec['n_devices']};"
             f"identical={rec['rankings_identical']}")
    _csv("retrieval_sharded_speedup", rec["sharded_us"],
         f"vs_chunked={rec['sharded_speedup_vs_chunked']:.2f}x")

    # --- batched multi-session serving (sequential loop vs waves) -----------
    from benchmarks import serve_bench
    rec_s = serve_bench.run((64,), repeats=1,
                            out_path="BENCH_serve_row.json")
    for row in rec_s["rows"]:
        _csv(f"serve_batched_s{row['sessions']}",
             1e6 * row["batched_s"] / max(row["queries"], 1),
             f"qps={row['batched_qps']:.1f};"
             f"vs_sequential={row['speedup']:.2f}x;"
             f"hit={100 * row['hit_rate_batched']:.1f}%")

    # --- roofline table (from dry-run artifacts, if present) ----------------
    from benchmarks import roofline_table
    rows_r = roofline_table.load()
    for r in rows_r:
        rl = r["roofline"]
        _csv(f"roofline_{r['arch']}@{r['shape']}", 0.0,
             f"dom={rl['dominant']};frac={rl['roofline_fraction']:.4f}")
    print("benchmarks complete", file=sys.stderr)


if __name__ == "__main__":
    main()
