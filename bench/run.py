#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``: the
deployment's sizes, its reduced and assumed keys, and the limits of the
comparison) and a traffic mix (``bench/mixes/<traffic>.json``), both
found through ``BENCHMARK.json``.  One run, in one process:

1. refuses any platform but a TPU, fewer chips than the cell asks for,
   and any kernel tier but ``compiled``;
2. draws the corpus on the device from ``--seed`` and brings it to the
   host array the program's API takes; plans the traffic from the seed;
3. places the corpus (``ShardedRouter.over_devices``), builds the
   engine, the L2 tier and the front door (``SessionManager``);
4. warms every wave bucket the cell can form, and opens its sessions;
5. drives the traffic for ``--seconds`` (profiled when ``--trace 1``),
   then waits for every turn due in the window;
6. reads the device's peak memory, frees the program and compares what
   the timed path produced with the plain reference (``bench/check.py``);
7. prints the result as the last line of standard output:
   ``{"correct", "attempted", "failed", "metrics", "device"[,
   "breakdown"], "checks"}``, with each compared number beside its limit
   also on the last lines of standard error.

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer ones; each is read by
``bench/metrics/<name>.py``.  ``setup_s`` runs from process start to the
first measured turn.  JAX's compilation cache lives in ``.jax_cache`` at
the root of the checkout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Callable, Optional  # noqa: E402

import numpy as np  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
DRAIN_S = 120.0           # wait for turns still in flight at the close


def load_cell(workload: str, root: pathlib.Path = ROOT) -> tuple:
    """(benchmark, cell, configuration, mix) of ``workload``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    mix = json.loads((root / "bench" / "mixes"
                      / f"{cell['traffic']}.json").read_text())
    return bench, cell, cfg, mix


def metrics_of(bench: dict, cell: str, traced: bool) -> list:
    """The metric entries a run of ``cell`` reports."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(name: str, root: pathlib.Path = ROOT) -> Callable:
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileClock:
    """Backend compiles and their seconds, from JAX's monitoring events."""

    _one = None

    def __init__(self):
        import jax
        self.seconds, self.compiles = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    @classmethod
    def get(cls) -> "CompileClock":
        if cls._one is None:
            cls._one = cls()
        return cls._one


def buckets(n: int) -> list:
    """Every power-of-two wave bucket up to ``n`` (the engine's rule)."""
    out, b = [], 1
    while b < n:
        out.append(b)
        b *= 2
    return out + [n]


@dataclass
class RunData:
    """What the metric readers read."""

    cfg: dict
    mix: dict
    seconds: float
    setup_s: float
    turns: list                      # traffic.Turn of the window
    waves: list                      # wave sizes formed in the window
    calls: list                      # [(rows, t0, t1)] back-end searches
    window: tuple = (0.0, 0.0)       # perf_counter open / close
    trace: Optional[object] = None   # xplane.Trace
    device: int = 0
    peak: dict = field(default_factory=dict)

    def answered(self) -> list:
        return [t for t in self.turns
                if t.result is not None
                and not isinstance(t.result, BaseException)]


def _annotate(name: str, fn: Callable) -> Callable:
    import jax

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return wrapped


def run_cell(cell: dict, cfg: dict, mix: dict, *, seed: int, seconds: float,
             trace: bool, bench: dict, devices: list, t_start: float,
             fault: Optional[Callable] = None,
             extra: Optional[Callable] = None, say: Callable = print
             ) -> dict:
    """One run of ``cell``; returns the result line's object.  ``fault``
    (tests) breaks the built system before the window; ``extra`` gets
    (reference, served, exact) after the comparison and its dict is
    returned under ``"extra"``."""
    import jax

    from bench import check, roofline, traffic, world as world_mod, xplane
    from bench.reference import Reference
    from repro.core.shared import SharedTier
    from repro.serve.router import ShardedRouter
    from repro.serve.session import BatchedEngine, SessionManager

    clock = CompileClock.get()
    dev = devices[0]
    marks = [("start", t_start), ("imports", time.perf_counter())]
    geo = world_mod.Geometry.from_config(cfg)
    world = world_mod.make_world(geo, seed, dev)
    plan = traffic.plan(mix, seed, seconds, geo.n_topics, geo.subspace_dim)
    queries = world.queries(plan.topic, plan.draws, plan.jump)
    dim = geo.dim + 1
    k, k_c = int(cfg["k"]), int(cfg["k_c"])
    slots = int(cfg["l1_slots"])

    marks.append(("world", time.perf_counter()))
    router = ShardedRouter.over_devices(
        world.corpus, devices=devices[:int(cfg["chips"])],
        dtype=cfg["corpus_dtype"], deadline_s=float(cfg["deadline_s"]),
        n_docs=geo.n_docs)
    shared = SharedTier(dim=dim, dtype=cfg["cache_dtype"],
                        **cfg.get("l2", {})) if "l2" in cfg else None
    engine = BatchedEngine(router, world.corpus, dim=dim, n_sessions=slots,
                           k=k, k_c=k_c, epsilon=float(cfg["epsilon"]),
                           capacity=int(cfg["l1_capacity"]),
                           dtype=cfg["cache_dtype"], shared=shared)

    jax.block_until_ready([sh.docs for sh in router.shards])
    marks.append(("placement", time.perf_counter()))
    # warm-up: each wave bucket runs one all-miss and one all-hit wave of
    # random directions (far from every conversation; the slots are reset
    # when the sessions open); a miss wave at bucket b scans at b rows
    rng = np.random.default_rng(seed + 1)
    sizes = buckets(slots)
    if shared is not None:
        shared.warm(k=k, k_c=k_c, rows=sizes, backend=engine.backend)
    for b in sizes:
        q = rng.standard_normal((b, dim)).astype(np.float32)
        q /= np.linalg.norm(q, axis=1, keepdims=True)
        engine.answer_batch(list(range(b)), list(q))
        engine.answer_batch(list(range(b)), list(q))

    marks.append(("warm-up", time.perf_counter()))
    calls = []                  # the window's back-end answers, as merged
    spans = []
    search = router.search

    def recording_search(qs, kk):
        t0 = time.perf_counter()
        ans, degraded = search(qs, kk)
        calls.append((qs, ans.ids, ans.scores))
        spans.append((len(qs), t0, time.perf_counter()))
        return ans, degraded

    router.search = recording_search
    if trace:
        router.search = _annotate("bench.router_search", router.search)
        engine.probe_wave = _annotate("bench.probe_wave", engine.probe_wave)
        engine.fill_wave = _annotate("bench.fill_wave", engine.fill_wave)
    if fault is not None:
        fault(engine, router)

    mgr = SessionManager(engine, **cfg.get("front_door", {}))
    loop = traffic.LOOPS[plan.loop](mgr, plan, queries)
    loop.open_all()
    jax.block_until_ready(engine.cache.state)
    if trace:
        mgr.open = _annotate("bench.session_open", mgr.open)
        mgr.close = _annotate("bench.session_close", mgr.close)
    calls.clear()
    spans.clear()
    waves0 = mgr.telemetry.waves
    c0 = (clock.seconds, clock.compiles)
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(tmp)
    setup_s = time.perf_counter() - t_start
    marks.append(("sessions", t_start + setup_s))

    w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        turns = loop.run(seconds)
    w1 = time.perf_counter()
    deadline = time.perf_counter() + DRAIN_S
    while any(t.result is None for t in turns) and \
            time.perf_counter() < deadline:
        time.sleep(0.01)
    if trace:
        jax.profiler.stop_trace()
    compiles = (clock.compiles - c0[1], clock.seconds - c0[0])
    stats = [d.memory_stats() or {} for d in devices[:int(cfg["chips"])]]
    peak_bytes = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    sizes_seen = _wave_sizes(mgr.telemetry, waves0)

    # ---------------------------------------------------------- readings
    run = RunData(cfg=cfg, mix=mix, seconds=seconds, setup_s=setup_s,
                  turns=turns, waves=sizes_seen, calls=spans,
                  window=(w0, w1), device=0,
                  peak=roofline.peaks(dev.device_kind)
                  if dev.platform == "tpu" else {})
    result_dev = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": peak_bytes}
    breakdown = None
    if trace:
        run.trace = xplane.load(tmp)
        shutil.rmtree(tmp, ignore_errors=True)
        used = sorted(run.trace.devices)[:int(cfg["chips"])]
        run.device = used[0] if used else 0
        result_dev["busy_s"] = xplane.busy_s(run.trace, used) if used else 0.0
        result_dev["window_s"] = run.trace.window_s
        breakdown = xplane.breakdown(run.trace, run.device)
    metrics = {}
    for m in metrics_of(bench, cell["name"], trace):
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    answered = run.answered()
    failed = [t for t in turns if t.result is None
              or isinstance(t.result, BaseException)
              or t.result.degraded]
    late = np.asarray(getattr(loop, "lateness_s", [0.0]) or [0.0])
    say(f"bench: {len(turns)} turns due in the window, {len(answered)} "
        f"answered, {len(failed)} failed or degraded; generator lateness "
        f"p50 {np.median(late) * 1e3:.3f} ms max {late.max() * 1e3:.3f} ms;"
        f" {compiles[0]} compiles ({compiles[1]:.3f} s) inside the window;"
        f" peak_bytes_in_use {peak_bytes}; setup_s {setup_s:.3f}")
    say("bench: set-up s " + ", ".join(
        f"{n} {b - a:.3f}" for (_, a), (n, b) in zip(marks, marks[1:])))
    tiers = {}
    for t in answered:
        tiers[t.result.tier] = tiers.get(t.result.tier, 0) + 1
    say(f"bench: tiers {tiers}; waves {len(sizes_seen)} "
        f"(mean size {np.mean(sizes_seen) if sizes_seen else 0:.2f}); "
        f"back-end calls {len(spans)}; router {router.stats.calls} calls "
        f"{router.stats.failures} failures {router.stats.timeouts} timeouts; "
        f"L1 inserts dropped {engine.cache.total_dropped}")

    # ------------------------------------------------------- correctness
    pick = np.random.default_rng(seed + 2).choice(
        slots, size=min(slots, int(cfg.get("check_slots", 8))),
        replace=False)
    st = engine.cache.state
    cached = [(loop.live.get(int(s)), np.asarray(st.doc_ids[int(s)]),
               np.asarray(st.doc_emb[int(s)])) for s in pick]
    served = check.Served(
        calls=list(calls),
        turns=[(t.key, t.turn, queries[t.conv, t.turn], t.result.tier,
                np.asarray(t.result.ids), np.asarray(t.result.scores))
               for t in answered if not t.result.degraded])
    mgr.shutdown()
    router.close()
    del mgr, loop, engine, shared, router, st, search, recording_search
    gc.collect()

    t_check = time.perf_counter()
    ref = Reference(world.corpus, dev)
    exact = check._backend(ref, served, k_c)
    values = check.numbers(ref, served, exact, k, k_c)
    values["fill_row_gap"] = check.fill_row_gap(
        world.corpus, [(ids, rows) for _, ids, rows in cached])
    values["foreign_ids"] = check.foreign_ids(
        served, [(key, ids) for key, ids, _ in cached])
    values["unanswered"] = len(failed)
    short = values.pop("short_turns", [])
    if short:
        say(f"bench: {len(short)} turns answered below the reference's "
            f"top-{k} over their session's own answers by over 1e-5: "
            f"{short[:12]}")
    ok, checks = check.verdict(values, cfg["limits"])
    say(f"bench: the comparison took {time.perf_counter() - t_check:.3f} s")
    out = {"correct": bool(ok), "attempted": len(turns),
           "failed": len(failed), "metrics": metrics, "device": result_dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    if extra is not None:
        out["extra"] = extra(ref, served, exact)
    return out


def _wave_sizes(tel, since: int) -> list:
    """Sizes of the waves the front door formed after its ``since``-th."""
    ring = tel.wave_sizes
    n = ring.count - since
    buf = ring._window()
    if n <= 0:
        return []
    if ring.count <= ring.capacity:
        return [int(x) for x in buf[since:ring.count]]
    end = ring.count % ring.capacity
    order = buf[end:] + buf[:end]
    return [int(x) for x in order[-min(n, ring.capacity):]]


def _fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(2)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, cell, cfg, mix = load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    from repro import compile_cache
    from repro.kernels import dispatch

    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"JAX found platform {devices[0].platform!r} "
              f"({devices[0].device_kind}), not a TPU; the benchmark "
              f"times nothing else")
    if len(devices) < int(cell["chips"]):
        _fail(f"{cell['name']} needs {cell['chips']} chips, JAX found "
              f"{len(devices)}")
    for name, tier in (("serving default", dispatch.default_backend()),
                       ("kernel default", dispatch.kernel_backend())):
        if tier != "compiled":
            _fail(f"the {name} kernel tier is {tier!r}, not 'compiled'")
    compile_cache.enable()
    CompileClock.get()

    res = run_cell(cell, cfg, mix, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), bench=bench, devices=devices,
                   t_start=T_START)
    for name, c in res["checks"].items():
        print(f"bench: check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
