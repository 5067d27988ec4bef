"""Multi-session serving benchmark: sequential engine loop vs batched serving.

Replays the same per-session turn streams two ways —

  * **sequential**: one ``ConversationalEngine`` per session, answered one
    turn at a time (the paper's client model: one probe, one router
    round-trip, one cache query per turn), and
  * **batched**: one ``BatchedEngine`` answering each turn wave with one
    batched probe, one ``router.search`` over the whole miss subset, and one
    batched insert/query

— and reports wall-clock queries/sec for each at several concurrency
levels.  Writes ``BENCH_serve.json``.

The closed-loop run also sweeps the topical-locality prefetch path
(``bench_prefetch``): the same conversations replayed at several
``prefetch_width`` settings over a clustered corpus, emitting hit-rate
vs cache-traffic Pareto rows with width 0 as the pre-prefetch tiered
baseline (the gap is gated by ``check_regression.py``).

``--open-loop`` instead drives the asynchronous front door with an
open-loop Poisson arrival process (arrivals do NOT wait for previous
turns — the honest way to measure tail latency) plus session churn, twice:
once through the continuous scheduler and once through the deprecated
fixed-window admission, reporting per-turn p50/p95/p99 (total and queue
wait, per serving tier) and the continuous-vs-windowed p99 improvement
``check_regression.py`` gates.

    PYTHONPATH=src python benchmarks/serve_bench.py [--smoke] [--open-loop]

``--chaos`` replays the COMMITTED fault schedule (``repro.serve.faults.
chaos_plan``: one flapping shard, one latency-spiking shard, one shard
returning corrupt answers) against the full resilient serving stack and
emits the ``chaos`` record ``check_regression.py`` gates: warm-session
availability, zero corrupt answers merged, breaker open/re-close counts,
degraded-answer rank overlap vs a clean fleet, and tail latency under
faults.

``--smoke`` runs a seconds-scale configuration (CI exercises the batched
path on every push); the default sweep covers 64-512 concurrent sessions.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.core.cache import (CacheConfig, init_batched_cache,
                              insert_query_batched, probe_batched)
from repro.core.metric_index import MetricIndex
from repro.core.shared import SharedTier
from repro.kernels import jaxpr_util
from repro.data.conversations import WorldConfig, make_world
from repro.serve.engine import ConversationalEngine
from repro.serve.faults import chaos_plan
from repro.serve.router import ShardAnswer, ShardedRouter
from repro.serve.session import BatchedEngine, SessionManager
from repro.serve.telemetry import ServeTelemetry


def make_shards(index: MetricIndex, n_shards: int):
    docs = np.asarray(index.dequantized()[:index.n_docs])
    ids = np.arange(index.n_docs)
    bounds = np.linspace(0, index.n_docs, n_shards + 1).astype(int)
    shards = []
    for i in range(n_shards):
        d, did = docs[bounds[i]:bounds[i + 1]], ids[bounds[i]:bounds[i + 1]]

        def shard(queries, k, d=d, did=did):
            scores = queries @ d.T
            top = np.argsort(-scores, axis=1)[:, :k]
            return ShardAnswer(np.take_along_axis(scores, top, axis=1),
                               did[top])
        shards.append(shard)
    return shards


def _streams(world, index, n_sessions: int):
    """Per-session transformed query streams (conversations reused round-
    robin when sessions outnumber generated conversations)."""
    convs = world.conversations
    return [np.asarray(index.transform_queries(
        jnp.asarray(convs[s % len(convs)].queries, jnp.float32)))
        for s in range(n_sessions)]


def bench_sequential(index, streams, *, n_shards, k, k_c, capacity,
                     dtype=None):
    with ShardedRouter(make_shards(index, n_shards),
                       deadline_s=30) as router:
        doc = np.asarray(index.dequantized())
        engines = [ConversationalEngine(router, doc, dim=index.dim, k=k,
                                        k_c=k_c, capacity=capacity,
                                        dtype=dtype)
                   for _ in streams]
        for e in engines:
            e.start_session()
        turns = streams[0].shape[0]
        t0 = time.perf_counter()
        for t in range(turns):
            for s, e in enumerate(engines):
                e.answer(streams[s][t])
        elapsed = time.perf_counter() - t0
        hits = float(np.mean([e.hit_rate() for e in engines]))
        return elapsed, len(streams) * turns, hits


def _rank_overlap(ids_a, ids_b, k: int) -> float:
    """Top-k set overlap in [0, 1] for one result pair (the per-query core
    of benchmarks.kernel_bench._rank_overlap, standalone for script use)."""
    return len(set(np.asarray(ids_a)[:k].tolist())
               & set(np.asarray(ids_b)[:k].tolist())) / k


def bench_zipf(index, world, *, n_sessions, n_generations=3, alpha=1.1,
               jitter=0.005, n_shards=4, k=10, k_c=100, capacity=None,
               dtype=None, with_shared=True, seed=11):
    """Popularity-skewed multi-user workload: the global-vs-private gap.

    ``n_generations`` cohorts of ``n_sessions`` sessions each run a full
    conversation; every session draws its conversation from a Zipf(alpha)
    popularity distribution over the world's conversation pool, with
    per-session query jitter (so cross-session repeats are near-duplicate,
    never identical — the semantic-reuse case, not trivial memoization).
    Between generations every session restarts with an empty L1 cache: a
    new user asking a popular question is exactly where a private cache
    pays a compulsory miss and the shared tier does not.

    Returns hit-rate accounting over ALL turns (compulsory first turns
    included — they are the point), per-tier counts, back-end query count,
    and the rank overlap of semantically reused result sets vs fresh
    retrieval (the quality gate for the memo's similarity floor).
    """
    router = ShardedRouter(make_shards(index, n_shards), deadline_s=30)
    try:
        return _bench_zipf_body(router, index, world,
                                n_sessions=n_sessions,
                                n_generations=n_generations, alpha=alpha,
                                jitter=jitter, n_shards=n_shards, k=k,
                                k_c=k_c, capacity=capacity, dtype=dtype,
                                with_shared=with_shared, seed=seed)
    finally:
        router.close()


def _bench_zipf_body(router, index, world, *, n_sessions, n_generations,
                     alpha, jitter, n_shards, k, k_c, capacity, dtype,
                     with_shared, seed):
    shared = SharedTier(dim=index.dim, n_shards=n_shards,
                        capacity=max(8 * k_c, 1024), memo_sim=0.995,
                        dtype=dtype) if with_shared else None
    engine = BatchedEngine(router, np.asarray(index.dequantized()),
                           dim=index.dim, n_sessions=n_sessions, k=k,
                           k_c=k_c, capacity=capacity or 4 * k_c,
                           dtype=dtype, shared=shared)
    rng = np.random.default_rng(seed)
    convs = world.conversations
    pop = np.arange(1, len(convs) + 1, dtype=np.float64) ** -alpha
    pop /= pop.sum()
    sids = list(range(n_sessions))
    counts = {"l1": 0, "l2": 0, "l2_reuse": 0, "backend": 0}
    reuse_samples: list = []
    t0 = time.perf_counter()
    for _g in range(n_generations):
        choice = rng.choice(len(convs), size=n_sessions, p=pop)
        for s in sids:
            engine.start_session(s)
        streams = []
        for s in sids:
            raw = (np.asarray(convs[choice[s]].queries)
                   + jitter * rng.standard_normal(
                       convs[choice[s]].queries.shape))
            streams.append(np.asarray(index.transform_queries(
                jnp.asarray(raw, jnp.float32))))
        for t in range(streams[0].shape[0]):
            qs = [streams[s][t] for s in sids]
            turns = engine.answer_batch(sids, qs)
            for s, turn in zip(sids, turns):
                counts[turn.tier] += 1
                if turn.tier == "l2_reuse" and len(reuse_samples) < 32:
                    reuse_samples.append((qs[s], np.asarray(turn.ids)))
    elapsed = time.perf_counter() - t0
    total = sum(counts.values())
    # quality of reused result sets: top-k overlap vs a fresh retrieval of
    # the SAME query (the gated floor backing the memo_sim calibration)
    overlaps = []
    for psi_q, served_ids in reuse_samples:
        ans, _ = router.search(np.asarray(psi_q)[None], k_c)
        fresh = ans.ids[0][ans.ids[0] >= 0]
        overlaps.append(_rank_overlap(served_ids, fresh, k))
    return {
        "sessions": n_sessions, "generations": n_generations,
        "alpha": alpha, "queries": total, "elapsed_s": elapsed,
        "qps": total / max(elapsed, 1e-12),
        "hit_rate": 1.0 - counts["backend"] / max(total, 1),
        "l1_hit_rate": counts["l1"] / max(total, 1),
        "l2_hit_rate": (counts["l2"] + counts["l2_reuse"]) / max(total, 1),
        "backend_queries": counts["backend"],
        "tier_counts": counts,
        "n_reuse_sampled": len(overlaps),
        "reuse_overlap": float(np.mean(overlaps)) if overlaps else None,
    }


def bench_prefetch(*, widths=(0, 100, 200, 300, 400), n_clusters=8,
                   cluster_iters=10, max_width=400, n_shards=2, k=5, k_c=20,
                   capacity=4096, dtype=None, backend="ref", seed=11) -> dict:
    """Topical-locality prefetch sweep: hit rate vs cache traffic Pareto.

    Builds a dedicated topical world — few dense topics in a low-dim
    subspace, small query noise, misses driven by subtopic jumps, and
    ``norm_jitter=0`` so the Eq. 1 appended coordinate does not inflate
    query-to-centroid distances — clusters it once
    (``repro.core.cluster``), then replays the same conversations at
    several ``prefetch_width`` settings.  Width 0 is the tiered baseline
    (no cluster attached anywhere — exactly the pre-prefetch serving
    stack); each width > 0 attaches the cluster to both the engine
    (miss-time neighbor prefetch folded into the fused insert+query
    launch, claim widened by the triangle inequality) and the shared
    tier (cluster-aware admission).

    Each row reports the combined hit rate alongside the traffic bought:
    docs pushed through the L1 insert launch (and their fp32 wire bytes),
    prefetch issues, and warm hits (cache-served docs that arrived via
    prefetch).  The rows form the Pareto frontier check_regression gates:
    ``hit_gap_best`` (best width > 0 hit rate minus the width-0 baseline)
    must be strictly positive.
    """
    cfg = WorldConfig(n_topics=4, docs_per_topic=300, n_background=600,
                      dim=48, subspace_dim=4, turns=6, n_conversations=6,
                      doc_sigma=0.8, query_sigma=0.05, drift_sigma=0.08,
                      subtopic_prob=0.4, subtopic_sigma=0.45,
                      norm_jitter=0.0, seed=seed)
    world = make_world(cfg)
    index = MetricIndex(jnp.asarray(world.doc_emb, jnp.float32), dtype=dtype)
    cluster = index.cluster(n_clusters, iters=cluster_iters, seed=0,
                            max_width=max_width, backend=backend)
    n_sessions = len(world.conversations)
    streams = _streams(world, index, n_sessions)
    turns = streams[0].shape[0]
    emb_bytes = index.dim * 4            # fp32 wire width per inserted doc
    sids = list(range(n_sessions))
    rows = []
    for width in widths:
        with ShardedRouter(make_shards(index, n_shards),
                           deadline_s=30) as router:
            shared = SharedTier(
                dim=index.dim, n_shards=n_shards,
                capacity=max(8 * k_c, 1024), memo_sim=0.995,
                dtype=dtype, cluster=cluster if width else None)
            engine = BatchedEngine(router, np.asarray(index.dequantized()),
                                   dim=index.dim, n_sessions=n_sessions,
                                   k=k, k_c=k_c, capacity=capacity,
                                   dtype=dtype, backend=backend,
                                   shared=shared,
                                   cluster=cluster if width else None,
                                   prefetch_width=width)
            for s in sids:
                engine.start_session(s)
            counts = {"l1": 0, "l2": 0, "l2_reuse": 0, "backend": 0}
            t0 = time.perf_counter()
            for t in range(turns):
                for turn in engine.answer_batch(
                        sids, [streams[s][t] for s in sids]):
                    counts[turn.tier] += 1
            elapsed = time.perf_counter() - t0
        total = sum(counts.values())
        pf = engine.prefetch_stats()
        rows.append({
            "prefetch_width": width,
            "hit_rate": 1.0 - counts["backend"] / max(total, 1),
            "tier_counts": counts,
            "backend_queries": counts["backend"],
            "prefetch_issued": pf["issued"],
            "prefetch_warm_hits": pf["warm_hits"],
            "insert_traffic_docs": pf["insert_traffic_docs"],
            "insert_traffic_bytes": pf["insert_traffic_docs"] * emb_bytes,
            "queries": total,
            "elapsed_s": elapsed,
        })
        print(f"prefetch w={width:4d}  hit {rows[-1]['hit_rate']:.3f}"
              f"  warm {pf['warm_hits']:4d}  issued {pf['issued']:5d}"
              f"  traffic {pf['insert_traffic_docs']:5d} docs")
    base = rows[0]
    best = max(rows[1:], key=lambda r: r["hit_rate"]) if len(rows) > 1 \
        else base
    return {
        "n_docs": index.n_docs, "dim": index.dim,
        "n_clusters": n_clusters, "max_width": max_width,
        "sessions": n_sessions, "turns": turns, "k": k, "k_c": k_c,
        "capacity": capacity, "rows": rows,
        "baseline_hit_rate": base["hit_rate"],
        "best_width": best["prefetch_width"],
        "hit_gap_best": best["hit_rate"] - base["hit_rate"],
    }


def bench_batched(index, streams, *, n_shards, k, k_c, capacity, dtype=None):
    with ShardedRouter(make_shards(index, n_shards),
                       deadline_s=30) as router:
        engine = BatchedEngine(router, np.asarray(index.dequantized()),
                               dim=index.dim,
                               n_sessions=len(streams), k=k, k_c=k_c,
                               capacity=capacity, dtype=dtype)
        sids = list(range(len(streams)))
        for s in sids:
            engine.start_session(s)
        turns = streams[0].shape[0]
        # warm the jit caches outside the timed region (compile happens once
        # per session-count; a server reuses the compiled wave for its life)
        engine.answer_batch(sids, [streams[s][0] for s in sids])
        for s in sids:
            engine.start_session(s)
        t0 = time.perf_counter()
        wave_best = float("inf")
        for t in range(turns):
            t1 = time.perf_counter()
            engine.answer_batch(sids, [streams[s][t] for s in sids])
            wave_best = min(wave_best, time.perf_counter() - t1)
        elapsed = time.perf_counter() - t0
        hits = engine.hit_rate()   # aggregate across sessions (NaN-safe
        return elapsed, len(streams) * turns, hits, wave_best  # 1-turn)


def wave_traffic(*, n_sessions, dim, capacity, k_c, k, dtype=None):
    """Machine-independent zero-copy metric: trace the kernel-tier cache
    ops of one full miss wave (batched probe + fused insert+query) and sum
    the bytes produced by every NON-Pallas equation — the per-wave overhead
    traffic around the launches.  The pre-padding layout copied the whole
    stacked payload in and out of each launch (>= 2x payload per wave);
    the pre-padded layout moves only wave-sized operands.  Returns
    (wave_moved_bytes, wave_payload_bytes) where the payload is one stacked
    (S, phys_capacity, phys_dim) doc allocation."""
    cfg = CacheConfig(capacity=capacity, dim=dim,
                      store_dtype=quant.resolve_dtype(dtype))
    state = init_batched_cache(cfg, n_sessions)
    psi = jnp.zeros((n_sessions, dim), jnp.float32)
    ids = jnp.zeros((n_sessions, k_c), jnp.int32)
    emb = jnp.zeros((n_sessions, k_c, dim), jnp.float32)
    radius = jnp.zeros((n_sessions,), jnp.float32)
    moved = jaxpr_util.trace_moved_bytes(
        lambda st, p: probe_batched(st, p, cfg.epsilon, backend="interpret",
                                    max_queries=cfg.max_queries),
        state, psi)
    moved += jaxpr_util.trace_moved_bytes(
        lambda st, p, r, e, i: insert_query_batched(
            st, cfg, p, r, e, i, k=k, backend="interpret"),
        state, psi, radius, emb, ids)
    return int(moved), int(state.doc_emb.nbytes)


def _make_engine(index, *, n_sessions, n_shards, k, k_c, capacity, dtype):
    router = ShardedRouter(make_shards(index, n_shards), deadline_s=30)
    return BatchedEngine(router, np.asarray(index.dequantized()),
                         dim=index.dim, n_sessions=n_sessions, k=k, k_c=k_c,
                         capacity=capacity, dtype=dtype)


def _warm_buckets(engine, streams) -> float:
    """Compile every power-of-two wave bucket on both the miss path
    (probe + miss-search + fused insert+query) and the hit path (probe +
    query) so the open-loop measurement never pays an XLA compile, then
    reset all sessions.  Returns the warm full-wave service time (best of
    3 miss waves) — the calibration input for arrival rate and the
    fixed-window baseline."""
    n = engine.n_sessions
    sizes, b = [], 1
    while b < n:
        sizes.append(b)
        b *= 2
    sizes.append(n)
    for size in sizes:
        sids = list(range(size))
        for s in sids:
            engine.start_session(s)
        qs = [streams[s][0] for s in sids]
        engine.answer_batch(sids, qs)   # miss path (insert+query shape)
        engine.answer_batch(sids, qs)   # hit path (query shape)
    svc = float("inf")
    for _ in range(3):
        for s in range(n):
            engine.start_session(s)
        t0 = time.perf_counter()
        engine.answer_batch(list(range(n)), [streams[s][0] for s in range(n)])
        svc = min(svc, time.perf_counter() - t0)
    for s in range(n):
        engine.start_session(s)
    return svc


def _percentiles_ms(xs) -> dict:
    xs = np.asarray(xs, np.float64) * 1e3
    if xs.size == 0:
        return {"count": 0, "p50_ms": None, "p95_ms": None, "p99_ms": None}
    return {"count": int(xs.size),
            "mean_ms": float(xs.mean()),
            "p50_ms": float(np.percentile(xs, 50)),
            "p95_ms": float(np.percentile(xs, 95)),
            "p99_ms": float(np.percentile(xs, 99))}


def _open_loop_once(index, world, *, mode, n_sessions, n_arrivals,
                    arrival_hz, window_s, n_shards, k, k_c, capacity,
                    dtype, seed) -> dict:
    """One open-loop replay: Poisson arrivals at ``arrival_hz`` against a
    ``SessionManager`` in ``mode`` ('continuous' or 'windowed'), with
    session churn (a session whose conversation ends is closed and its key
    reopened on a fresh conversation).  Arrivals follow an absolute
    schedule (sleep-until, no drift), and never wait for earlier turns —
    queue wait lands in the measured latency instead of silently throttling
    the workload."""
    engine = _make_engine(index, n_sessions=n_sessions, n_shards=n_shards,
                          k=k, k_c=k_c, capacity=capacity, dtype=dtype)
    mgr_kwargs = (dict(window_s=0.0, adaptive=True, overlap=True)
                  if mode == "continuous" else
                  dict(window_s=window_s, adaptive=False, overlap=False))
    rng = np.random.default_rng(seed)
    convs = world.conversations
    conv_len = convs[0].queries.shape[0]
    next_conv = n_sessions          # global cursor for churned sessions

    def stream_for(conv_idx):
        return np.asarray(index.transform_queries(jnp.asarray(
            convs[conv_idx % len(convs)].queries, jnp.float32)))

    streams = {key: stream_for(key) for key in range(n_sessions)}
    ptr = {key: 0 for key in range(n_sessions)}
    churns = 0
    futures = []
    try:
        with SessionManager(engine, max_batch=n_sessions,
                            **mgr_kwargs) as mgr:
            for key in range(n_sessions):
                mgr.open(key)
            gaps = rng.exponential(1.0 / arrival_hz, size=n_arrivals)
            sched = np.cumsum(gaps) + time.perf_counter()
            for i in range(n_arrivals):
                now = time.perf_counter()
                if sched[i] > now:
                    time.sleep(sched[i] - now)
                key = int(rng.integers(n_sessions))
                if ptr[key] >= conv_len:
                    # churn: this conversation is over — drain + recycle
                    # the slot, open the key on a fresh conversation
                    mgr.close(key)
                    mgr.open(key)
                    streams[key] = stream_for(next_conv)
                    ptr[key] = 0
                    next_conv += 1
                    churns += 1
                futures.append(mgr.submit(key, streams[key][ptr[key]]))
                ptr[key] += 1
            mgr.flush()
            turns = [f.result(timeout=60) for f in futures]
            summary = mgr.telemetry.summary()
    finally:
        engine.router.close()
    totals = [t.latency_s for t in turns]
    waits = [t.queue_wait_s for t in turns]
    rec = {
        "mode": mode,
        "arrivals": n_arrivals,
        "arrival_hz": arrival_hz,
        "churns": churns,
        "hit_rate": float(np.mean([t.hit for t in turns])),
        "total": _percentiles_ms(totals),
        "queue_wait": _percentiles_ms(waits),
        "tiers": {tier: _percentiles_ms(
            [t.latency_s for t in turns if t.tier == tier])
            for tier in sorted({t.tier for t in turns})},
        "waves": summary["waves"],
        "mean_wave": summary["wave_size"]["mean"],
    }
    if mode == "windowed":
        rec["window_ms"] = window_s * 1e3
    return rec


def bench_open_loop(index, world, *, n_sessions, n_arrivals, load=0.5,
                    n_shards=4, k=10, k_c=100, capacity=None, dtype=None,
                    repeats=2, seed=17) -> dict:
    """Continuous scheduler vs fixed-window admission under identical
    open-loop Poisson traffic.

    Calibration keeps the record machine-independent in shape: the warm
    full-wave service time ``svc`` sets both the arrival rate
    (``load / svc`` — a fixed multiple of the wave rate, not a fixed Hz;
    small enough that neither mode saturates, so the A/B measures
    admission policy rather than queue buildup) and the fixed-window
    baseline's window (``4 x svc``, floored at 4 ms — the historical
    fixed-window default regime).  Each mode runs ``repeats`` times and
    keeps its lowest-p99 run (wall-clock on shared hosts is noisy; the
    minimum is each policy's least-contended estimate).  The gated
    headline is ``p99_improvement``: windowed p99 over continuous p99,
    which the continuous scheduler wins by not holding arrivals hostage
    to the window timer.
    """
    capacity = capacity or 4 * k_c
    warm_engine = _make_engine(index, n_sessions=n_sessions,
                               n_shards=n_shards, k=k, k_c=k_c,
                               capacity=capacity, dtype=dtype)
    warm_streams = _streams(world, index, n_sessions)
    svc = _warm_buckets(warm_engine, warm_streams)
    arrival_hz = load / max(svc, 1e-5)
    window_s = max(4.0 * svc, 0.004)
    kwargs = dict(n_sessions=n_sessions, n_arrivals=n_arrivals,
                  arrival_hz=arrival_hz, window_s=window_s,
                  n_shards=n_shards, k=k, k_c=k_c, capacity=capacity,
                  dtype=dtype)
    def best(mode):
        runs = [_open_loop_once(index, world, mode=mode, seed=seed + r,
                                **kwargs) for r in range(repeats)]
        return min(runs, key=lambda r: r["total"]["p99_ms"])
    continuous = best("continuous")
    windowed = best("windowed")
    improvement = (windowed["total"]["p99_ms"]
                   / max(continuous["total"]["p99_ms"], 1e-9))
    rec = {
        "sessions": n_sessions,
        "load": load,
        "wave_service_ms": svc * 1e3,
        "arrival_hz": arrival_hz,
        "window_ms": window_s * 1e3,
        "continuous": continuous,
        "windowed": windowed,
        "p99_improvement": improvement,
    }
    print(f"open-loop({n_sessions} sessions, {arrival_hz:.0f}/s): "
          f"continuous p99 {continuous['total']['p99_ms']:.1f}ms "
          f"(wait p99 {continuous['queue_wait']['p99_ms']:.1f}ms) | "
          f"windowed p99 {windowed['total']['p99_ms']:.1f}ms "
          f"(window {window_s * 1e3:.1f}ms) | "
          f"p99 improvement {improvement:.2f}x")
    return rec


def run_open_loop(*, smoke=False, dtype=None,
                  out_path="BENCH_serve.json") -> dict:
    """Entry point for ``--open-loop``: builds the world, runs the A/B
    open-loop measurement, and merge-writes it under ``open_loop`` (nested
    in ``smoke`` for smoke runs, the schema check_regression gates)."""
    if smoke:
        cfg = WorldConfig(n_topics=4, docs_per_topic=200, n_background=1000,
                          dim=64, subspace_dim=8, turns=3, n_conversations=8,
                          doc_sigma=0.6, query_sigma=0.12, drift_sigma=0.16,
                          subtopic_prob=0.35, subtopic_sigma=0.75, seed=7)
        n_sessions, n_arrivals, k_c = 8, 240, 50
    else:
        cfg = WorldConfig(n_topics=8, docs_per_topic=800, n_background=4000,
                          dim=128, subspace_dim=8, turns=4,
                          n_conversations=16, doc_sigma=0.6,
                          query_sigma=0.12, drift_sigma=0.16,
                          subtopic_prob=0.35, subtopic_sigma=0.75, seed=7)
        n_sessions, n_arrivals, k_c = 64, 2000, 100
    world = make_world(cfg)
    index = MetricIndex(jnp.asarray(world.doc_emb, jnp.float32), dtype=dtype)
    rec = bench_open_loop(index, world, n_sessions=n_sessions,
                          n_arrivals=n_arrivals, k_c=k_c, dtype=dtype)
    rec["timestamp"] = time.time()
    merge_json(out_path,
               {"smoke": {"open_loop": rec}} if smoke
               else {"open_loop": rec})
    return rec


def _jittered_streams(world, index, n_sessions, rng, jitter):
    """Per-session streams with fresh per-call query jitter, so replaying
    the same conversations across chaos rounds yields near-duplicate (not
    identical) queries — semantic reuse stays possible, trivial
    memoization does not hide the back end from the fault schedule."""
    convs = world.conversations
    out = []
    for s in range(n_sessions):
        raw = np.asarray(convs[s % len(convs)].queries)
        raw = raw + jitter * rng.standard_normal(raw.shape)
        out.append(np.asarray(index.transform_queries(
            jnp.asarray(raw, jnp.float32))))
    return out


def bench_chaos(index, world, *, n_sessions=8, rounds=10, n_shards=4,
                k=10, k_c=50, capacity=None, dtype=None, deadline_s=2.0,
                spike_s=0.02, jitter=0.1, seed=23) -> dict:
    """Replay the committed chaos schedule against the resilient stack.

    ``rounds`` cohorts of ``n_sessions`` sessions each replay their
    conversations (with per-round query jitter) through a ``BatchedEngine``
    whose router fleet is wrapped by ``repro.serve.faults.chaos_plan``:
    shard 0 flaps through two outage windows, shard 1 spikes latency past
    the hedge trigger, shard 2 returns corrupt answers rotating through
    every corruption mode, shards 3+ stay healthy.  Breaker knobs are
    sized so the flapping shard's breaker opens, half-open probes, and
    re-closes *within the run* — the transition counts land in the gated
    record.

    The emitted record is the chaos gate's input: ``warm_availability``
    (answered fraction of turns whose session already had a turn this
    round; >= 0.99), ``corrupt_served`` (answers merged with
    out-of-corpus ids or non-finite scores; must be 0 — the validator's
    whole job), breaker open/close counts (>= 1 each), the rank overlap
    of degraded answers vs a clean fleet's fresh retrieval, and tail
    latency under faults.
    """
    capacity = capacity or 4 * k_c
    rng = np.random.default_rng(seed)
    sids = list(range(n_sessions))
    plan = chaos_plan(n_shards, seed=seed, spike_s=spike_s)
    telemetry = ServeTelemetry()
    total = answered = warm_total = warm_answered = 0
    corrupt = degraded_turns = 0
    turn_times: list = []
    degraded_samples: list = []
    with ShardedRouter(plan.wrap(make_shards(index, n_shards)),
                       deadline_s=deadline_s, hedge_after_s=spike_s / 2,
                       n_docs=index.n_docs, max_retries=1,
                       backoff_base_s=0.002, breaker_window=8,
                       breaker_fail_rate=0.5, breaker_min_calls=2,
                       breaker_cooldown_s=0.25,
                       telemetry=telemetry) as router:
        shared = SharedTier(dim=index.dim, n_shards=n_shards,
                            capacity=max(8 * k_c, 1024), memo_sim=0.995,
                            ttl_waves=3, dtype=dtype)
        engine = BatchedEngine(router, np.asarray(index.dequantized()),
                               dim=index.dim, n_sessions=n_sessions, k=k,
                               k_c=k_c, capacity=capacity, dtype=dtype,
                               shared=shared, telemetry=telemetry,
                               validate_every=4)
        t_run = time.perf_counter()
        for _r in range(rounds):
            streams = _jittered_streams(world, index, n_sessions, rng,
                                        jitter)
            for s in sids:
                engine.start_session(s)
            for t in range(streams[0].shape[0]):
                qs = [streams[s][t] for s in sids]
                t0 = time.perf_counter()
                try:
                    out = engine.answer_batch(sids, qs)
                except TimeoutError:      # whole wave fenced, caches empty
                    out = [None] * len(sids)
                dt = time.perf_counter() - t0
                if _r > 0:     # round 0 pays the XLA wave compiles; the
                    # tail under FAULTS is the record, not compile noise
                    turn_times.extend([dt] * len(sids))
                for s, turn in zip(sids, out):
                    total += 1
                    if t > 0:
                        warm_total += 1
                    if turn is None or isinstance(turn, Exception):
                        continue
                    answered += 1
                    if t > 0:
                        warm_answered += 1
                    row_ids = np.asarray(turn.ids)
                    row_scores = np.asarray(turn.scores)
                    if row_ids.size and (
                            (row_ids < 0).any()
                            or (row_ids >= index.n_docs).any()
                            or not np.isfinite(row_scores).all()):
                        corrupt += 1
                    if turn.degraded:
                        degraded_turns += 1
                        if len(degraded_samples) < 64 and row_ids.size:
                            degraded_samples.append((qs[s], row_ids))
        elapsed = time.perf_counter() - t_run
        stats = router.stats
        health = router.shard_health()
    # quality of degraded answers: top-k overlap vs a CLEAN fleet's fresh
    # retrieval of the same query (missing-shard merges and stale serves
    # should stay mostly right, not confidently wrong)
    overlaps = []
    with ShardedRouter(make_shards(index, n_shards),
                       deadline_s=30) as clean:
        for psi_q, served in degraded_samples:
            ans, _ = clean.search(np.asarray(psi_q)[None], k_c)
            fresh = ans.ids[0][ans.ids[0] >= 0]
            overlaps.append(_rank_overlap(
                served, fresh, min(k, int(served.size))))
    rec = {
        "sessions": n_sessions, "rounds": rounds, "n_shards": n_shards,
        "turns_per_round": int(
            world.conversations[0].queries.shape[0]),
        "k": k, "k_c": k_c, "seed": seed, "elapsed_s": elapsed,
        "total_turns": total, "answered_turns": answered,
        "availability": answered / max(total, 1),
        "warm_availability": warm_answered / max(warm_total, 1),
        "corrupt_served": corrupt,
        "degraded_turns": degraded_turns,
        "n_degraded_sampled": len(overlaps),
        "degraded_overlap": float(np.mean(overlaps)) if overlaps else None,
        "latency": _percentiles_ms(turn_times),
        "breaker_opens": stats.breaker_opens,
        "breaker_closes": stats.breaker_closes,
        "breaker_skips": stats.breaker_skips,
        "rejected_answers": stats.rejected,
        "retries": stats.retries, "hedges": stats.hedges,
        "failures": stats.failures, "timeouts": stats.timeouts,
        "searches": stats.calls, "shed": stats.shed,
        "stale_served": shared.n_stale_served,
        "quarantined": engine.quarantined,
        "faults": telemetry.summary()["faults"],
        "injected_calls": plan.calls(),
        "injected_faults": [w.faults for w in plan.wrapped],
        "shard_health": health,
    }
    print(f"chaos({n_sessions} sessions x {rounds} rounds): "
          f"avail {rec['availability']:.4f} "
          f"(warm {rec['warm_availability']:.4f}) | corrupt served "
          f"{corrupt} | rejected {stats.rejected} | breaker "
          f"open/close {stats.breaker_opens}/{stats.breaker_closes} | "
          f"degraded {degraded_turns} overlap {rec['degraded_overlap']} | "
          f"p99 {rec['latency']['p99_ms']:.1f}ms")
    return rec


def run_chaos(*, smoke=False, dtype=None,
              out_path="BENCH_serve.json") -> dict:
    """Entry point for ``--chaos``: builds the world, replays the committed
    chaos schedule, and merge-writes the record under ``chaos`` (nested in
    ``smoke`` for smoke runs — the schema check_regression gates)."""
    if smoke:
        cfg = WorldConfig(n_topics=4, docs_per_topic=200, n_background=1000,
                          dim=64, subspace_dim=8, turns=3, n_conversations=8,
                          doc_sigma=0.6, query_sigma=0.12, drift_sigma=0.16,
                          subtopic_prob=0.35, subtopic_sigma=0.75, seed=7)
        kwargs = dict(n_sessions=8, rounds=10, k_c=50)
    else:
        cfg = WorldConfig(n_topics=8, docs_per_topic=800, n_background=4000,
                          dim=128, subspace_dim=8, turns=4,
                          n_conversations=16, doc_sigma=0.6,
                          query_sigma=0.12, drift_sigma=0.16,
                          subtopic_prob=0.35, subtopic_sigma=0.75, seed=7)
        kwargs = dict(n_sessions=16, rounds=16, k_c=100)
    world = make_world(cfg)
    index = MetricIndex(jnp.asarray(world.doc_emb, jnp.float32), dtype=dtype)
    rec = bench_chaos(index, world, dtype=dtype, **kwargs)
    rec["timestamp"] = time.time()
    merge_json(out_path,
               {"smoke": {"chaos": rec}} if smoke else {"chaos": rec})
    return rec


def run(session_counts=(64, 128, 256, 512), *, turns=4, n_shards=4,
        k=10, k_c=100, repeats=3, world_cfg=None, dtype=None, smoke=False,
        out_path="BENCH_serve.json") -> dict:
    world = make_world(world_cfg or WorldConfig(
        n_topics=8, docs_per_topic=800, n_background=4000, dim=128,
        subspace_dim=8, turns=turns, n_conversations=16, doc_sigma=0.6,
        query_sigma=0.12, drift_sigma=0.16, subtopic_prob=0.35,
        subtopic_sigma=0.75, seed=7))
    index = MetricIndex(jnp.asarray(world.doc_emb, jnp.float32), dtype=dtype)
    capacity = 4 * k_c
    rows = []
    for n_sessions in session_counts:
        streams = _streams(world, index, n_sessions)
        # best-of-N: wall-clock on a shared host is noisy; the minimum is
        # the least-contended estimate of each path's real cost
        t_seq, t_bat, t_wave = float("inf"), float("inf"), float("inf")
        for _ in range(repeats):
            t, n_q, hit_seq = bench_sequential(
                index, streams, n_shards=n_shards, k=k, k_c=k_c,
                capacity=capacity, dtype=dtype)
            t_seq = min(t_seq, t)
            t, _, hit_bat, wave_best = bench_batched(
                index, streams, n_shards=n_shards, k=k, k_c=k_c,
                capacity=capacity, dtype=dtype)
            t_bat = min(t_bat, t)
            t_wave = min(t_wave, wave_best)
        moved, payload = wave_traffic(
            n_sessions=n_sessions, dim=index.dim, capacity=capacity,
            k_c=k_c, k=k, dtype=dtype)
        row = {
            "sessions": n_sessions, "turns": int(streams[0].shape[0]),
            "queries": n_q,
            "sequential_s": t_seq, "batched_s": t_bat,
            "sequential_qps": n_q / t_seq, "batched_qps": n_q / t_bat,
            "speedup": t_seq / max(t_bat, 1e-12),
            "hit_rate_sequential": hit_seq, "hit_rate_batched": hit_bat,
            # zero-copy columns: best-of-N single-wave latency, and the
            # traced non-launch traffic of one miss wave vs one stacked
            # payload (machine-independent; gated by check_regression)
            "batched_wave_best_s": t_wave,
            "wave_moved_bytes": moved,
            "wave_payload_bytes": payload,
        }
        rows.append(row)
        print(f"sessions={n_sessions:4d}  sequential {row['sequential_qps']:8.1f} q/s"
              f"  batched {row['batched_qps']:8.1f} q/s"
              f"  speedup {row['speedup']:.1f}x"
              f"  wave {1e3 * t_wave:.1f}ms"
              f"  moved/payload {moved / max(payload, 1):.2f}x")
    # Zipfian multi-user workload: the same skewed traffic served with the
    # shared L2 tier attached and private-cache-only; the gap between the
    # two combined hit rates is the tier's raison d'etre (gated by
    # check_regression alongside the reuse-quality overlap floor)
    zipf_sessions = min(max(session_counts), 8 if smoke else 64)
    zipf_kwargs = dict(n_sessions=zipf_sessions, n_generations=3,
                       n_shards=n_shards, k=k, k_c=k_c,
                       capacity=capacity, dtype=dtype)
    tiered = bench_zipf(index, world, with_shared=True, **zipf_kwargs)
    l1only = bench_zipf(index, world, with_shared=False, **zipf_kwargs)
    zipf = dict(tiered)
    zipf["l1_only_hit_rate"] = l1only["hit_rate"]
    zipf["hit_gap"] = tiered["hit_rate"] - l1only["hit_rate"]
    zipf["backend_queries_saved"] = (l1only["backend_queries"]
                                     - tiered["backend_queries"])
    print(f"zipf({zipf_sessions} sessions x {zipf['generations']} gens)"
          f"  l1-only hit {zipf['l1_only_hit_rate']:.3f}"
          f"  tiered hit {zipf['hit_rate']:.3f}"
          f"  (l1 {zipf['l1_hit_rate']:.3f} + l2 {zipf['l2_hit_rate']:.3f})"
          f"  backend saved {zipf['backend_queries_saved']}"
          f"  reuse overlap {zipf['reuse_overlap']}")
    # Topical-locality prefetch sweep: its own world (norm_jitter=0, dense
    # topics) so the triangle-inequality claim widening has a regime to
    # win in; width 0 is the pre-prefetch tiered stack, the gated Pareto
    # headline is hit_gap_best > 0 (strictly)
    prefetch = bench_prefetch(dtype=dtype)
    print(f"prefetch sweep: baseline hit {prefetch['baseline_hit_rate']:.3f}"
          f"  best hit {prefetch['baseline_hit_rate'] + prefetch['hit_gap_best']:.3f}"
          f" @ width {prefetch['best_width']}"
          f"  gap {prefetch['hit_gap_best']:+.3f}")
    record = {"n_docs": index.n_docs, "dim": world.cfg.dim, "k": k,
              "k_c": k_c, "n_shards": n_shards, "dtype": index.dtype,
              "rows": rows, "zipf": zipf, "prefetch": prefetch,
              "timestamp": time.time()}
    # merge-write so full runs and smoke runs co-own one file: the smoke
    # record nests under "smoke" (the committed-baseline schema
    # benchmarks/check_regression.py reads) and neither overwrites the other
    merge_json(out_path, {"smoke": record} if smoke else record)
    return record


def _deep_merge(dst: dict, src: dict) -> dict:
    """Recursively merge ``src`` into ``dst`` (nested dicts merge key-wise,
    anything else overwrites) so e.g. ``--smoke --open-loop`` extends the
    existing ``smoke`` record instead of replacing it."""
    for key, val in src.items():
        if isinstance(val, dict) and isinstance(dst.get(key), dict):
            _deep_merge(dst[key], val)
        else:
            dst[key] = val
    return dst


def merge_json(path: str, updates: dict) -> None:
    """Deep-merge ``updates`` into a JSON object file, preserving other
    keys (standalone sibling of benchmarks.kernel_bench.merge_json: this
    module must run as a plain script, where sibling imports don't
    resolve)."""
    rec = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (json.JSONDecodeError, OSError):
            rec = {}
    if not isinstance(rec, dict):
        rec = {}
    _deep_merge(rec, updates)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale run for CI (8 sessions, tiny world)")
    ap.add_argument("--open-loop", action="store_true",
                    help="open-loop Poisson tail-latency A/B (continuous "
                         "scheduler vs fixed-window admission) instead of "
                         "the closed-loop throughput sweep")
    ap.add_argument("--chaos", action="store_true",
                    help="replay the committed fault schedule "
                         "(repro.serve.faults.chaos_plan) and emit the "
                         "availability / corruption / breaker record the "
                         "chaos gate checks")
    ap.add_argument("--dtype", default=None,
                    help="corpus + cache storage format (fp32/bf16/int8; "
                         "default follows REPRO_CORPUS_DTYPE)")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()
    from repro import compile_cache
    compile_cache.enable()
    if args.chaos:
        run_chaos(smoke=args.smoke, dtype=args.dtype, out_path=args.out)
    elif args.open_loop:
        run_open_loop(smoke=args.smoke, dtype=args.dtype, out_path=args.out)
    elif args.smoke:
        cfg = WorldConfig(n_topics=4, docs_per_topic=200, n_background=1000,
                          dim=64, subspace_dim=8, turns=3, n_conversations=8,
                          doc_sigma=0.6, query_sigma=0.12, drift_sigma=0.16,
                          subtopic_prob=0.35, subtopic_sigma=0.75, seed=7)
        run((8,), turns=3, k_c=50, repeats=1, world_cfg=cfg, dtype=args.dtype,
            smoke=True, out_path=args.out)
    else:
        run(dtype=args.dtype, out_path=args.out)


if __name__ == "__main__":
    main()
