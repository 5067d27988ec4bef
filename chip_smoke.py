#!/usr/bin/env python3
"""Bring-up smoke of the conversational serving path on a TPU.

Drives the path a deployment runs, through its normal entry points —

    SessionManager -> ContinuousScheduler -> BatchedEngine (+ SharedTier)
        -> ShardedRouter.over_devices -> DeviceShard

— on the compiled Pallas tier, at the paper's widths: 768-d raw
embeddings (769 after Eq. 1), an int8 corpus generated from ``--seed`` by
``repro.data.conversations.make_world``, 64 session slots of capacity
16 * k_c, k_c = 1000 and k = 10.  Every session serves several turns
through ``SessionManager.submit``; the cold turns' back-end answers are
checked against the plain reference tier (``knn_search(backend="ref")``
over the same shards, merged as the router merges).

    python chip_smoke.py             # one chip: 2,000,000 docs, 64 sessions
    python chip_smoke.py --chips 4   # four chips: the sharded back end only

With ``--chips 4`` only the device-sharded back end runs: 4 x the
one-chip corpus, one quarter per chip, a few miss waves through
``ShardedRouter.search`` and the same reference comparison.

Earlier lines of output describe the run (sizes, resident bytes, compile
seconds, per-span seconds, hit rate); they are bring-up observations,
not benchmark results.  The last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The script exits non-zero, and prints no such line, when JAX finds no
TPU, when anything pins a kernel tier other than ``compiled``, or when
any check fails.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.core import embedding as emb  # noqa: E402
from repro.core.cache import insert_query_batched, probe_batched  # noqa: E402
from repro.core.shared import SharedTier  # noqa: E402
from repro.data.conversations import WorldConfig, make_world  # noqa: E402
from repro.dist.retrieval import ShardTopK  # noqa: E402
from repro.kernels import dispatch, jaxpr_util  # noqa: E402
from repro.kernels.knn.ops import knn_search  # noqa: E402
from repro.serve.router import ShardedRouter  # noqa: E402
from repro.serve.session import BatchedEngine, SessionManager  # noqa: E402

RAW_DIM = 768            # STAR embedding width; 769 after Eq. 1
K, K_C = 10, 1000        # the paper's answer and cache-fill depths
SESSIONS = 64
TURNS = 4
# CAsT 2019-20 holds 38.6M passages (one chip's quarter: ~9.6M).  The host
# build path keeps the f32 corpus in host memory several times over, which
# caps one chip's corpus here until the corpus is generated on the device.
DOCS_PER_CHIP = 2_000_000
DOCS_PER_TOPIC = 5000
CORPUS_DTYPE = "int8"    # the deployment storage format of the corpus
CACHE_DTYPE = "fp32"     # L1 / L2 cache storage format
DEADLINE_S = 900.0       # a cold miss wave must never read as a timeout
REF_BLOCK = 1 << 18      # corpus rows per reference-scan block
TIE_TOL = 1e-6           # ids may differ only between scores this close
_BLOCK = 1 << 16         # host rows per Eq. 1 transform step
PART_DOCS = 500_000      # docs per make_world part (one host thread each)
BUILD_THREADS = 6        # parts built at once: bounds the host's f64 peak
# per-device bound above the resident quarter (``--chips 4``): the
# reference scan's dequantized block plus the search's scores and buffers
SHARD_WORKING_SET = 4 * 2 ** 30


# ------------------------------------------------------------------ corpus
def build_world(n_docs: int, n_sessions: int, turns: int, seed: int,
                dim: int = RAW_DIM, docs_per_topic: int = DOCS_PER_TOPIC):
    """Transformed corpus (n, dim + 1) f32 on the host, plus the (S, turns,
    dim + 1) transformed queries of S seeded conversations.

    The corpus is the concatenation of ``make_world`` parts of at most
    ``PART_DOCS`` docs, each from its own seed derived from ``seed`` and
    built on one of ``BUILD_THREADS`` host threads (numpy releases the GIL
    in its random fills and array passes); the conversations come from
    part 0.  In each part half the docs sit in topics (``docs_per_topic``
    each), half are background.  Each part lands as f32 rows in the one
    output array, and Eq. 1 then rewrites it in place with the corpus-wide
    max norm: the host never holds a second copy of the corpus.
    """
    starts = list(range(0, n_docs, PART_DOCS))
    docs = np.empty((n_docs, dim + 1), np.float32)

    def part(i):
        lo, n = starts[i], min(PART_DOCS, n_docs - starts[i])
        n_topics = max(1, n // (2 * docs_per_topic))
        world = make_world(WorldConfig(
            n_topics=n_topics, docs_per_topic=min(docs_per_topic, n),
            n_background=n - n_topics * min(docs_per_topic, n), dim=dim,
            turns=turns, n_conversations=n_sessions if i == 0 else 0,
            seed=seed * 65536 + i))
        raw, world.doc_emb = world.doc_emb, None
        docs[lo:lo + n, :dim] = raw
        return world, float(np.sqrt(np.max(np.einsum("ij,ij->i", raw, raw))))

    transform = jax.jit(emb.transform_documents)
    cpu = jax.devices("cpu")[0]            # Eq. 1 on the host CPU backend

    def eq1(lo, m):
        rows = slice(lo, lo + _BLOCK)
        docs[rows] = np.asarray(transform(
            jax.device_put(docs[rows, :dim], cpu), m)[0])

    with ThreadPoolExecutor(BUILD_THREADS) as pool:
        worlds, norms = zip(*pool.map(part, range(len(starts))))
        m = max(norms)
        list(pool.map(lambda lo: eq1(lo, m), range(0, n_docs, _BLOCK)))
    psi = np.stack([np.asarray(emb.transform_queries(jnp.asarray(
        c.queries, jnp.float32))) for c in worlds[0].conversations])
    return docs, psi


def _buckets(n: int) -> list:
    """The engine's pow2 wave buckets up to ``n`` rows (``_bucket``)."""
    out, b = [], 1
    while b < n:
        out.append(b)
        b *= 2
    return out + [n]


# --------------------------------------------------------------- reference
def reference_answer(shards, queries: np.ndarray, k: int):
    """The plain reference: ``knn_search(backend="ref")`` over each shard's
    own arrays (scanned in ``REF_BLOCK``-row blocks to bound the f32
    dequantization), merged exactly as ``ShardedRouter`` merges."""
    parts = []
    for sh in shards:
        q = np.pad(queries, ((0, 0), (0, sh.docs.shape[1] - queries.shape[1])))
        q = jax.device_put(q.astype(np.float32), sh.device)
        for lo in range(0, sh.docs.shape[0], REF_BLOCK):
            rows = slice(lo, lo + REF_BLOCK)
            s, i = knn_search(sh.docs[rows], sh.doc_ids[rows], q, k,
                              backend="ref", int8_dot=sh.int8_dot,
                              scale=None if sh.scale is None
                              else sh.scale[rows])
            parts.append(ShardTopK(np.asarray(s), np.asarray(i)))
    return ShardedRouter._merge(parts, k)


def compare(served_ids, served_scores, ref):
    """(tie swaps, max |score difference|).  Raises when a served id
    differs from the reference anywhere but between scores within
    ``TIE_TOL`` of each other."""
    diff = served_ids != ref.ids
    gap = np.abs(served_scores - ref.scores)
    bad = np.logical_and(diff, gap > TIE_TOL)
    if bad.any():
        r, j = map(int, np.argwhere(bad)[0])
        raise AssertionError(
            f"served answer differs from the reference tier at row {r} "
            f"rank {j}: id {served_ids[r, j]} ({served_scores[r, j]:.9f}) vs "
            f"{ref.ids[r, j]} ({ref.scores[r, j]:.9f}); "
            f"{int(bad.sum())} such positions")
    return int(diff.sum()), float(gap.max())


class _CompileClock:
    """Backend-compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit costs its read, not a compile)."""

    def __init__(self):
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.compiles, self.cache_hits


_CLOCK = None


def _clock() -> _CompileClock:
    global _CLOCK
    if _CLOCK is None:
        _CLOCK = _CompileClock()
    return _CLOCK


def _resident(shards) -> int:
    return sum(x.nbytes for sh in shards
               for x in (sh.docs, sh.doc_ids, sh.scale) if x is not None)


# ------------------------------------------------------------ one chip
def run(*, n_docs: int = DOCS_PER_CHIP, n_sessions: int = SESSIONS,
        turns: int = TURNS, k: int = K, k_c: int = K_C, dim: int = RAW_DIM,
        docs_per_topic: int = DOCS_PER_TOPIC, seed: int = 0) -> dict:
    """Serve ``turns`` turns for each of ``n_sessions`` sessions through
    the full stack on the first device; returns the report ``main``
    checks.  The kernel tier is the dispatch default (compiled on a TPU)."""
    clock = _clock()
    t0 = time.perf_counter()
    docs, psi = build_world(n_docs, n_sessions, turns, seed, dim,
                            docs_per_topic)
    t_world = time.perf_counter() - t0

    t0 = time.perf_counter()
    router = ShardedRouter.over_devices(
        docs, devices=jax.devices()[:1], dtype=CORPUS_DTYPE,
        deadline_s=DEADLINE_S, n_docs=n_docs)
    jax.block_until_ready([sh.docs for sh in router.shards])
    t_place = time.perf_counter() - t0
    shared = SharedTier(dim=dim + 1, dtype=CACHE_DTYPE)
    engine = BatchedEngine(router, docs, dim=dim + 1, n_sessions=n_sessions,
                           k=k, k_c=k_c, capacity=16 * k_c,
                           dtype=CACHE_DTYPE, shared=shared)

    # warm-up: every program the served turns run, before any of them —
    # the L2 answer query and admission flush, each shard at each wave
    # bucket, then per bucket one miss wave and one all-hit wave of random
    # queries (far from every conversation; the slots are reset when the
    # sessions open)
    c0 = clock.snapshot()
    t0 = time.perf_counter()
    buckets = _buckets(n_sessions)
    shared.warm(k=k, k_c=k_c, rows=buckets, backend=engine.backend)
    rng = np.random.default_rng(seed + 1)
    for b in buckets:
        for sh in router.shards:
            sh(np.zeros((b, dim + 1), np.float32), k_c)
        q = rng.standard_normal((b, dim + 1)).astype(np.float32)
        q /= np.linalg.norm(q, axis=1, keepdims=True)
        engine.answer_batch(list(range(b)), list(q))
        engine.answer_batch(list(range(b)), list(q))
    t_warm = time.perf_counter() - t0

    cold = []
    search = router.search

    def recording_search(queries, kk):
        ans, degraded = search(queries, kk)
        cold.append((np.array(queries), ans))
        return ans, degraded

    served = []
    with SessionManager(engine) as mgr:
        for s in range(n_sessions):
            mgr.open(s)
        c1 = clock.snapshot()
        t0 = time.perf_counter()
        for r in range(turns):
            router.search = recording_search if r == 0 else search
            futures = [mgr.submit(s, psi[s, r]) for s in range(n_sessions)]
            served.append([f.result() for f in futures])
    router.search = search
    t_serve = time.perf_counter() - t0
    c2 = clock.snapshot()

    # cold turns: every round-0 back-end answer against the reference
    swaps = gap = 0
    n_cold = 0
    for queries, ans in cold:
        sw, g = compare(ans.ids, ans.scores,
                        reference_answer(router.shards, queries, k_c))
        swaps, gap, n_cold = swaps + sw, max(gap, g), n_cold + len(queries)

    turns_flat = [t for rnd in served[1:] for t in rnd]
    miss_backend = [t.spans.backend_s for rnd in served for t in rnd
                    if t.tier == "backend"]
    report = {
        "n_docs": n_docs, "dim": dim + 1, "corpus_dtype": CORPUS_DTYPE,
        "cache_dtype": CACHE_DTYPE, "sessions": n_sessions, "turns": turns,
        "k": k, "k_c": k_c, "capacity": 16 * k_c,
        "tiers": {"engine": engine.backend, "shared": shared.backend,
                  "shards": sorted({sh.backend for sh in router.shards})},
        "resident_bytes": {"corpus": _resident(router.shards),
                           "l1": engine.cache.memory_bytes(),
                           "l2": sum(x.nbytes for x in jax.tree_util.tree_leaves(
                               shared.state))},
        "seconds": {"world": t_world, "placement": t_place,
                    "warmup": t_warm, "serve": t_serve,
                    "compile_warmup": c1[0] - c0[0],
                    "compile_serving": c2[0] - c1[0]},
        "compiles": {"warmup": c1[1] - c0[1], "serving": c2[1] - c1[1],
                     "cache_hits": c2[2] - c0[2]},
        "degraded": sum(t.degraded for rnd in served for t in rnd),
        "router": {f: getattr(router.stats, f) for f in
                   ("calls", "failures", "timeouts", "retries", "rejected",
                    "hedges", "degraded")},
        "router_last_error": router.stats.last_error,
        "quarantined": engine.quarantined,
        "hit_rate": engine.hit_rate(),
        "tier_counts": engine.tier_counts(),
        "spans_median_s": {
            name: float(np.median([getattr(t.spans, name)
                                   for t in turns_flat]))
            for name in ("probe_s", "backend_s", "insert_s", "total_s")},
        "miss_wave_backend_s": (float(np.median(miss_backend)),
                                float(np.max(miss_backend))),
        "cold_answers": n_cold, "tie_swaps": swaps, "max_score_gap": gap,
        "launches": _wave_launches(engine, router, n_sessions, k_c, dim + 1),
    }
    router.close()
    return report


def _wave_launches(engine, router, s: int, k_c: int, d: int) -> dict:
    """Kernel launches of one tiered full-miss wave at the full bucket,
    counted in the traced programs (the L2 probe is the L1 probe kernel)."""
    st = engine.cache.state
    psi = jax.ShapeDtypeStruct((s, d), jnp.float32)
    probe = jaxpr_util.pallas_call_count(jax.make_jaxpr(
        lambda a, p: probe_batched(a, p, engine.epsilon,
                                   backend=engine.backend))(st, psi))
    fill = jaxpr_util.pallas_call_count(jax.make_jaxpr(
        lambda a, p, r, e, i: insert_query_batched(
            a, engine.cache.cfg, p, r, e, i, engine.k,
            backend=engine.backend))(
        st, psi, jax.ShapeDtypeStruct((s,), jnp.float32),
        jax.ShapeDtypeStruct((s, k_c, d), jnp.float32),
        jax.ShapeDtypeStruct((s, k_c), jnp.int32)))
    sh = router.shards[0]
    scan = jaxpr_util.pallas_call_count(jax.make_jaxpr(
        lambda q: knn_search(sh.docs, sh.doc_ids, q, k_c,
                             backend=sh.backend, scale=sh.scale,
                             int8_dot=sh.int8_dot))(
        jax.ShapeDtypeStruct((s, sh.docs.shape[1]), jnp.float32)))
    n = len(router.shards)
    return {"l1_probe": probe, "l2_probe": probe, "backend": n * scan,
            "insert_query": fill, "wave": 2 * probe + n * scan + fill}


# ----------------------------------------------------------- four chips
def run_sharded(*, n_chips: int = 4, n_docs: int = 4 * DOCS_PER_CHIP,
                n_queries: int = SESSIONS, waves: int = 3, k_c: int = K_C,
                dim: int = RAW_DIM, docs_per_topic: int = DOCS_PER_TOPIC,
                seed: int = 0) -> dict:
    """Only the device-sharded back end: ``ShardedRouter.over_devices``
    over ``n_chips`` ``DeviceShard``s, ``waves`` miss waves of
    ``n_queries`` conversation turns, each answer against the reference."""
    devices = jax.devices()[:n_chips]
    t0 = time.perf_counter()
    docs, psi = build_world(n_docs, n_queries, waves, seed, dim,
                            docs_per_topic)
    t_world = time.perf_counter() - t0
    t0 = time.perf_counter()
    router = ShardedRouter.over_devices(
        docs, devices=devices, dtype=CORPUS_DTYPE, deadline_s=DEADLINE_S,
        n_docs=n_docs)
    jax.block_until_ready([sh.docs for sh in router.shards])
    t_place = time.perf_counter() - t0
    del docs
    placement = [sorted({str(d) for x in (sh.docs, sh.doc_ids, sh.scale)
                         if x is not None for d in x.devices()})
                 for sh in router.shards]
    resident = [_resident([sh]) for sh in router.shards]

    clock = _clock()
    c0 = clock.snapshot()
    for sh in router.shards:                       # warm the wave shape
        sh(np.zeros((n_queries, dim + 1), np.float32), k_c)
    c1 = clock.snapshot()
    swaps = gap = 0
    wave_s = []
    for w in range(waves):
        queries = psi[:, w]
        t0 = time.perf_counter()
        ans, degraded = router.search(queries, k_c)
        wave_s.append(time.perf_counter() - t0)
        if degraded:
            raise AssertionError(f"wave {w}: degraded merge")
        sw, g = compare(ans.ids, ans.scores,
                        reference_answer(router.shards, queries, k_c))
        swaps, gap = swaps + sw, max(gap, g)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    report = {
        "n_docs": n_docs, "dim": dim + 1, "corpus_dtype": CORPUS_DTYPE,
        "chips": n_chips, "k_c": k_c, "waves": waves, "queries": n_queries,
        "tiers": {"shards": sorted({sh.backend for sh in router.shards})},
        "placement": placement, "devices": [str(d) for d in devices],
        "resident_bytes": resident, "peak_bytes_in_use": peaks,
        "seconds": {"world": t_world, "placement": t_place,
                    "compile": c1[0] - c0[0], "waves": wave_s},
        "router": {f: getattr(router.stats, f) for f in
                   ("calls", "failures", "timeouts", "retries", "rejected",
                    "degraded")},
        "router_last_error": router.stats.last_error,
        "tie_swaps": swaps, "max_score_gap": gap,
    }
    router.close()
    return report


# ------------------------------------------------------------------ main
def _fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def _check(cond: bool, msg: str):
    if not cond:
        _fail(msg)


def _say(msg: str):
    print(f"chip_smoke: {msg}", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the whole serving path on one chip (default); "
                         "4: only the sharded back end, one quarter per chip")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated corpus and conversations")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    _check(dev.platform == "tpu",
           f"JAX found platform {dev.platform!r} ({dev.device_kind}), not a "
           f"TPU; this smoke never serves or times on anything else")
    _check(len(devices) >= args.chips,
           f"--chips {args.chips} needs {args.chips} TPU devices, found "
           f"{len(devices)}")
    for name, tier in (("serving default", dispatch.default_backend()),
                       ("kernel default", dispatch.kernel_backend())):
        _check(tier == "compiled",
               f"the {name} kernel tier is {tier!r}, not 'compiled' "
               f"(REPRO_KERNEL_BACKEND="
               f"{os.environ.get('REPRO_KERNEL_BACKEND', '')!r})")
    _say(f"cache dir {compile_cache.enable()}")

    if args.chips == 4:
        _main_sharded(args, devices)
    else:
        _main_one_chip(args, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


def _main_one_chip(args, dev):
    _say(f"corpus {DOCS_PER_CHIP} docs x {RAW_DIM + 1} ({CORPUS_DTYPE}): "
         f"cut from CAsT's 38.6M passages (a chip's quarter ~9.6M) because "
         f"the host build path keeps the f32 corpus in host memory")
    rep = run(seed=args.seed)
    sec, comp = rep["seconds"], rep["compiles"]
    _say(f"sizes: {rep['sessions']} sessions x {rep['turns']} turns, "
         f"k={rep['k']} k_c={rep['k_c']} capacity={rep['capacity']} "
         f"L1/L2 store {rep['cache_dtype']}")
    _say(f"resident bytes: {rep['resident_bytes']}")
    _say(f"setup s: world {sec['world']:.1f} placement {sec['placement']:.1f} "
         f"warm-up {sec['warmup']:.1f} (compile {sec['compile_warmup']:.1f} s "
         f"in {comp['warmup']} compiles; {comp['cache_hits']} persistent "
         f"cache hits)")
    _say(f"serving: {sec['serve']:.1f} s wall, {comp['serving']} compiles "
         f"({sec['compile_serving']:.1f} s) inside the served window")
    _say(f"span medians (s), turns after the first: "
         f"{rep['spans_median_s']}")
    _say(f"miss-wave back-end span (s): median "
         f"{rep['miss_wave_backend_s'][0]:.3f} max "
         f"{rep['miss_wave_backend_s'][1]:.3f}")
    _say(f"hit rate after turn 0: {rep['hit_rate']:.3f} "
         f"tiers {rep['tier_counts']}")
    _say(f"launches per tiered full-miss wave: {rep['launches']}")
    _say(f"router {rep['router']} degraded turns {rep['degraded']} "
         f"quarantined {rep['quarantined']}")
    _say(f"cold turns: {rep['cold_answers']} back-end answers x {rep['k_c']} "
         f"ids equal to the ref tier; {rep['tie_swaps']} swaps within "
         f"{TIE_TOL} score ties, max |score gap| {rep['max_score_gap']:.3g}")
    stats = dev.memory_stats() or {}
    _say(f"device {dev.device_kind}: peak_bytes_in_use "
         f"{stats.get('peak_bytes_in_use')} bytes_limit "
         f"{stats.get('bytes_limit')}")
    _check(set(rep["tiers"]["shards"]) == {"compiled"}
           and rep["tiers"]["engine"] == "compiled"
           and rep["tiers"]["shared"] == "compiled",
           f"tiers {rep['tiers']}")
    _check(rep["degraded"] == 0, f"{rep['degraded']} degraded turns")
    bad = {f: v for f, v in rep["router"].items()
           if f in ("failures", "timeouts", "retries", "rejected") and v}
    _check(not bad, f"router {bad}: {rep['router_last_error']}")
    _check(rep["quarantined"] == 0, f"{rep['quarantined']} quarantined slots")
    _check(rep["hit_rate"] > 0, f"hit rate {rep['hit_rate']} after turn 0")


def _main_sharded(args, devices):
    rep = run_sharded(seed=args.seed)
    _say(f"corpus {rep['n_docs']} docs x {rep['dim']} ({rep['corpus_dtype']})"
         f" over {rep['chips']} chips, one quarter each")
    for dev, where, res, peak in zip(rep["devices"], rep["placement"],
                                     rep["resident_bytes"],
                                     rep["peak_bytes_in_use"]):
        _say(f"shard on {dev}: arrays on {where}, resident {res} B, "
             f"peak_bytes_in_use {peak} B (working set {peak - res} B)")
    _say(f"seconds {rep['seconds']}")
    _say(f"router {rep['router']}; {rep['waves']} waves x {rep['queries']} "
         f"queries equal to the ref tier; {rep['tie_swaps']} swaps within "
         f"{TIE_TOL} score ties, max |score gap| {rep['max_score_gap']:.3g}")
    _check(rep["tiers"]["shards"] == ["compiled"], f"tiers {rep['tiers']}")
    _check(rep["placement"] == [[d] for d in rep["devices"]],
           f"shard placement {rep['placement']}")
    for dev, res, peak in zip(devices, rep["resident_bytes"],
                              rep["peak_bytes_in_use"]):
        _check(peak <= res + SHARD_WORKING_SET,
               f"{dev}: peak {peak} B above its quarter {res} B plus the "
               f"{SHARD_WORKING_SET} B working set")
    bad = {f: v for f, v in rep["router"].items()
           if f in ("failures", "timeouts", "retries", "rejected", "degraded")
           and v}
    _check(not bad, f"router {bad}: {rep['router_last_error']}")


if __name__ == "__main__":
    main()
