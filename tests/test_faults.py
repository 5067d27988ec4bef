"""Fault-domain resilience tests: the deterministic fault injector, answer
validation, per-shard circuit breakers, router retry/backoff, the
degradation ladder (load-shed waves, stale-while-error memo serves, the
cache-state integrity guard), the RouterStats lock, router lifecycle, and
the 2-launch shed-wave kernel contract."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cache_ops import validate_state
from repro.core.metric_index import MetricIndex
from repro.core.shared import SharedTier
from repro.serve.engine import EngineTurn
from repro.serve.faults import (CORRUPT_MODES, FaultError, FaultPlan,
                                FaultSpec, FaultyShard, _corrupt, chaos_plan)
from repro.serve.router import (AnswerValidationError, CircuitBreaker,
                                ShardAnswer, ShardedRouter, validate_answer)
from repro.serve.scheduler import ContinuousScheduler
from repro.serve.session import BatchedEngine
from repro.serve.telemetry import ServeTelemetry

jax.config.update("jax_platform_name", "cpu")

N_DOCS, DIM = 240, 32


@pytest.fixture(scope="module")
def index():
    rng = np.random.default_rng(3)
    raw = rng.standard_normal((N_DOCS, DIM)).astype(np.float32)
    return MetricIndex(jnp.asarray(raw))


@pytest.fixture(scope="module")
def docs(index):
    return np.asarray(index.dequantized()[:index.n_docs])


def make_shards(index, n_shards):
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


def queries_for(index, n, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((n, DIM)).astype(np.float32)
    return np.asarray(index.transform_queries(jnp.asarray(q)))


# ------------------------------------------------------------ fault injector
def test_fault_spec_schedule_windows_and_flapping():
    solid = FaultSpec("error", start=3, stop=6)
    assert [solid.active(c) for c in range(8)] == \
        [False] * 3 + [True] * 3 + [False] * 2
    flap = FaultSpec("latency", start=2, period=3, width=1, delay_s=0.01)
    assert [flap.active(c) for c in range(2, 8)] == \
        [True, False, False, True, False, False]
    open_ended = FaultSpec("corrupt", start=5)
    assert not open_ended.active(4) and open_ended.active(10 ** 6)
    with pytest.raises(ValueError):
        FaultSpec("meteor")
    with pytest.raises(ValueError):
        FaultSpec("error", period=2, width=3)
    with pytest.raises(ValueError):
        FaultSpec("corrupt", mode="garbled")


def test_faulty_shard_applies_each_kind(index):
    inner = make_shards(index, 1)[0]
    q = queries_for(index, 2)

    lat = FaultyShard(inner, [FaultSpec("latency", stop=1, delay_s=0.05)])
    t0 = time.perf_counter()
    lat(q, 5)
    assert time.perf_counter() - t0 >= 0.05
    t0 = time.perf_counter()
    lat(q, 5)                                     # past the window: no sleep
    assert time.perf_counter() - t0 < 0.04

    err = FaultyShard(inner, [FaultSpec("error", stop=1)])
    with pytest.raises(FaultError):
        err(q, 5)
    err(q, 5)                                     # recovers after the window
    assert err.calls == 2 and err.faults == 1

    bad = FaultyShard(inner, [FaultSpec("corrupt", mode="nan")])
    assert np.isnan(bad(q, 5).scores).any()

    clean = FaultyShard(inner)                    # spec-less: transparent
    ans = clean(q, 5)
    validate_answer(ans, 2, 5, index.n_docs)
    assert clean.calls == 1 and clean.faults == 0


def test_fault_plan_is_deterministic(index):
    q = queries_for(index, 2)

    def run():
        plan = FaultPlan({0: (FaultSpec("corrupt", mode="mix"),)}, seed=5)
        shard = plan.wrap(make_shards(index, 1))[0]
        return [shard(q, 5) for _ in range(len(CORRUPT_MODES))]

    for a, b in zip(run(), run()):
        np.testing.assert_array_equal(
            np.asarray(a.scores), np.asarray(b.scores))
        np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))


def test_chaos_plan_shape(index):
    with pytest.raises(ValueError):
        chaos_plan(2)
    plan = chaos_plan(4)
    wrapped = plan.wrap(make_shards(index, 4))
    assert len(wrapped) == 4
    assert [len(w.specs) for w in wrapped] == [2, 1, 1, 0]
    assert plan.calls() == [0, 0, 0, 0]


# --------------------------------------------------------- answer validation
def test_validate_answer_accepts_sentinels_and_short_rows():
    # a short answer from a tiny shard, with legal (-inf, -1) sentinel pads
    ans = ShardAnswer(np.array([[2.0, -np.inf], [1.0, 0.5]]),
                      np.array([[3, -1], [4, 0]]))
    validate_answer(ans, 2, 5, n_docs=10)


def test_validate_answer_rejects_each_corrupt_mode():
    # non-square on purpose: a transposed ("shape") answer must not alias
    clean = ShardAnswer(
        np.array([[2.0, 1.0, 0.5], [1.5, 0.5, 0.2]], np.float32),
        np.array([[3, 1, 5], [4, 0, 2]]))
    validate_answer(clean, 2, 3, n_docs=10)
    for mode in CORRUPT_MODES:
        with pytest.raises(AnswerValidationError):
            validate_answer(_corrupt(clean, mode, seed=0, call=0),
                            2, 3, n_docs=10)
    with pytest.raises(AnswerValidationError):                 # wrong rows
        validate_answer(clean, 3, 3, n_docs=10)
    with pytest.raises(AnswerValidationError):                 # float ids
        validate_answer(ShardAnswer(clean.scores,
                                    clean.ids.astype(np.float64)),
                        2, 3, n_docs=10)
    with pytest.raises(AnswerValidationError):   # -inf on a real id
        validate_answer(ShardAnswer(np.array([[-np.inf, 1.0]]),
                                    np.array([[3, 1]])), 1, 2, n_docs=10)


# ------------------------------------------------------------ circuit breaker
def test_circuit_breaker_state_machine():
    t = [0.0]
    seen = []
    br = CircuitBreaker(window=8, fail_rate=0.5, min_calls=4, cooldown_s=1.0,
                        clock=lambda: t[0],
                        on_transition=lambda old, new: seen.append((old, new)))
    br.record(False)
    br.record(False)
    assert br.state == "closed"                 # min_calls not met yet
    br.record(True)
    br.record(False)                            # 3/4 failed >= 0.5: trip
    assert br.state == "open" and br.opens == 1
    assert not br.allow() and not br.peek()
    br.record(False)                            # late result: ignored
    assert br.state == "open"
    t[0] = 1.0                                  # cooldown elapsed
    assert br.peek() and br.state == "open"     # peek never transitions
    assert br.allow() and br.state == "half_open"
    assert not br.allow() and not br.peek()     # single probe in flight
    br.record(False)                            # probe failed: re-open
    assert br.state == "open" and br.opens == 2
    t[0] = 2.0
    assert br.allow()
    br.record(True)                             # probe succeeded: close
    assert br.state == "closed" and br.closes == 1
    br.record(False)
    br.record(True)
    br.record(True)
    br.record(True)
    assert br.state == "closed"                 # window restarted clean
    assert ("closed", "open") in seen and ("half_open", "closed") in seen


# --------------------------------------------------------- router integration
def test_router_rejects_corrupt_answers_and_merge_stays_finite(index):
    plan = FaultPlan({1: (FaultSpec("corrupt", mode="nan"),)}, seed=1)
    with ShardedRouter(plan.wrap(make_shards(index, 3)), deadline_s=5.0,
                       n_docs=index.n_docs) as router:
        ans, degraded = router.search(queries_for(index, 4), 5)
        assert degraded
        assert not np.isnan(np.asarray(ans.scores)).any()
        assert (np.asarray(ans.ids) < index.n_docs).all()
        # initial call + its retry both rejected, never merged
        assert router.stats.rejected >= 2
        assert router.shard_health()[1]["rejected"] >= 2
        assert router.stats.failures >= 1


def test_router_retry_recovers_transient_fault(index):
    plan = FaultPlan({0: (FaultSpec("error", stop=1),)})
    with ShardedRouter(plan.wrap(make_shards(index, 2)), deadline_s=5.0,
                       backoff_base_s=0.001, n_docs=index.n_docs) as router:
        ans, degraded = router.search(queries_for(index, 2), 5)
        assert not degraded                     # retry healed inside the call
        assert router.stats.retries >= 1
        assert router.stats.failures == 0       # the search saw no failure
        validate_answer(ans, 2, 5, index.n_docs)


def test_router_breaker_opens_skips_and_recovers(index):
    plan = FaultPlan({0: (FaultSpec("error", stop=6),)})
    q = queries_for(index, 2)
    with ShardedRouter(plan.wrap(make_shards(index, 2)), deadline_s=5.0,
                       max_retries=1, backoff_base_s=0.001,
                       breaker_window=4, breaker_min_calls=2,
                       breaker_cooldown_s=0.05,
                       n_docs=index.n_docs) as router:
        for _ in range(4):                      # outage: breaker 0 trips
            ans, degraded = router.search(q, 5)
            assert degraded
            validate_answer(ans, 2, 5, index.n_docs)
        assert router.stats.breaker_opens >= 1
        assert router.stats.breaker_skips >= 1  # open shard skipped up front
        assert not router.backend_open          # shard 1 still serving
        time.sleep(0.06)                        # cooldown -> half-open probe
        deadline = time.monotonic() + 5.0
        while router.breakers[0].state != "closed":
            router.search(q, 5)
            time.sleep(0.06)
            assert time.monotonic() < deadline, "breaker never re-closed"
        assert router.stats.breaker_closes >= 1
        ans, degraded = router.search(q, 5)     # healthy again: full merge
        assert not degraded


def test_router_all_shards_failed_but_one_pads_sentinels(index):
    # shards 0+1 hard-down; the tiny survivor holds fewer docs than k, so
    # the degraded merge must sentinel-pad, never invent columns
    plan = FaultPlan({0: (FaultSpec("error"),), 1: (FaultSpec("error"),)})
    shards = make_shards(index, 3)
    lo = 2 * index.n_docs // 3                  # survivor's id range
    k = (index.n_docs - lo) + 3                 # k beyond the survivor
    with ShardedRouter(plan.wrap(shards), deadline_s=5.0, max_retries=0,
                       n_docs=index.n_docs) as router:
        ans, degraded = router.search(queries_for(index, 2), k)
        assert degraded
        ids, scores = np.asarray(ans.ids), np.asarray(ans.scores)
        assert ids.shape == (2, k)
        real = ids >= 0
        assert (ids[real] >= lo).all()          # only the survivor's docs
        assert np.isneginf(scores[~real]).all()  # sentinel-padded tail
        assert (~real).any()


def test_router_stats_lock_no_lost_updates(index):
    with ShardedRouter(make_shards(index, 2), deadline_s=10.0) as router:
        # raw counter hammering from many threads: totals must be exact
        def hammer():
            for _ in range(500):
                router.stats.bump("hedges")
                router.stats.shard_bump(0, "retries")
        threads = [threading.Thread(target=hammer) for _ in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert router.stats.hedges == 8 * 500
        assert router.stats.per_shard[0]["retries"] == 8 * 500

        # concurrent searches (the scheduler overlaps backend waves): every
        # search and every per-shard call accounted, none lost
        q = queries_for(index, 2)
        errs = []

        def search_many():
            try:
                for _ in range(5):
                    router.search(q, 5)
            except Exception as e:              # pragma: no cover
                errs.append(e)
        threads = [threading.Thread(target=search_many) for _ in range(6)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errs
        assert router.stats.calls == 6 * 5
        health = router.shard_health()
        assert sum(h["calls"] for h in health) == 2 * 6 * 5


def test_router_close_is_idempotent_and_context_managed(index):
    router = ShardedRouter(make_shards(index, 2), deadline_s=5.0)
    with router:
        ans, degraded = router.search(queries_for(index, 2), 5)
        assert not degraded
    router.close()                              # second close: no-op
    with pytest.raises(RuntimeError):           # pool is shut down
        router.search(queries_for(index, 2), 5)


# ------------------------------------------------------- degradation ladder
def _engine(index, docs, *, n_sessions=2, shared=None, router=None,
            backend="ref", validate_every=0, telemetry=None, epsilon=0.04,
            **router_kw):
    if router is None:
        # breaker cooldown defaults far out so a fenced back end STAYS
        # fenced for the duration of a test (recovery tests inject their
        # own clock); real serving uses sub-second cooldowns
        kw = dict(deadline_s=5.0, n_docs=index.n_docs,
                  breaker_window=4, breaker_min_calls=2,
                  breaker_cooldown_s=3600.0)
        kw.update(router_kw)
        router = ShardedRouter(make_shards(index, 2), **kw)
    return BatchedEngine(router, docs, dim=index.dim, n_sessions=n_sessions,
                         k=5, k_c=16, capacity=64, backend=backend,
                         shared=shared, validate_every=validate_every,
                         telemetry=telemetry, epsilon=epsilon)


def test_engine_shed_wave_serves_cache_without_router(index, docs):
    tel = ServeTelemetry()
    # epsilon far above any claim radius: every probe misses, so the wave
    # under a fenced back end must take the load-shed path
    eng = _engine(index, docs, telemetry=tel, epsilon=1e9)
    router = eng.router
    with router:
        for s in (0, 1):
            eng.start_session(s)
        q = queries_for(index, 2, seed=1)
        t_warm = eng.answer_batch([0, 1], list(q))
        assert all(isinstance(t, EngineTurn) for t in t_warm)
        for b in router.breakers:               # fence the whole back end
            for _ in range(2):
                b.record(False)
        assert router.backend_open

        def boom(*a, **k):                      # shed waves never search
            raise AssertionError("router.search called during shed wave")
        router.search = boom
        q2 = queries_for(index, 2, seed=2)
        before = int(np.asarray(eng.cache.state.n_queries).sum())
        turns = eng.answer_batch([0, 1], list(q2))
        for t in turns:
            assert isinstance(t, EngineTurn) and t.degraded
            assert t.ids.size and (t.ids >= 0).all()
        after = int(np.asarray(eng.cache.state.n_queries).sum())
        assert after == before                  # shed turns claim nothing
        assert tel.faults.get("shed_waves", 0) >= 1
        assert tel.faults.get("shed_turns", 0) >= 2
        assert tel.faults.get("degraded_turns", 0) >= 2


def test_engine_shed_then_breaker_recovery(index, docs):
    eng = _engine(index, docs, epsilon=1e9)
    router = eng.router
    # injected clock so the cooldown elapses exactly when the test says so
    # (wall-clock wave compiles would otherwise race a real cooldown)
    t = [0.0]
    router.breakers = [
        CircuitBreaker(window=4, fail_rate=0.5, min_calls=2,
                       cooldown_s=10.0, clock=lambda: t[0],
                       on_transition=router._on_transition)
        for _ in router.shards]
    with router:
        for s in (0, 1):
            eng.start_session(s)
        q = queries_for(index, 2, seed=1)
        eng.answer_batch([0, 1], list(q))
        for b in router.breakers:
            for _ in range(2):
                b.record(False)
        assert router.backend_open
        turns = eng.answer_batch([0, 1], list(queries_for(index, 2, seed=2)))
        assert all(t.degraded for t in turns)
        t[0] = 11.0                             # cooldown: probes go out
        assert not router.backend_open
        turns = eng.answer_batch([0, 1], list(queries_for(index, 2, seed=3)))
        assert all(isinstance(t, EngineTurn) and not t.degraded
                   for t in turns)
        assert all(b.state == "closed" for b in router.breakers)
        assert router.stats.breaker_closes >= 2


def test_stale_memo_served_under_outage_never_records(index, docs):
    shared = SharedTier(dim=index.dim, n_shards=2, capacity=256,
                        memo_sim=0.9, ttl_waves=1)
    eng = _engine(index, docs, shared=shared)
    router = eng.router
    with router:
        for s in (0, 1):
            eng.start_session(s)
        q = queries_for(index, 2, seed=4)
        eng.answer_batch([0, 1], list(q))       # session 1 memoizes q[1]
        for _ in range(3):                      # TTL-expire the memo
            shared.tick()
        assert shared.memo_lookup(0, q[1]) is None      # fresh path: miss
        assert shared.memo_lookup(0, q[1], allow_stale=True) is not None
        for b in router.breakers:
            for _ in range(2):
                b.record(False)
        eng.start_session(0)                    # cold cache + fenced backend
        before = shared.n_promoted
        turns = eng.answer_batch([0], [q[1]])
        assert isinstance(turns[0], EngineTurn)
        assert turns[0].tier == "l2_reuse" and turns[0].degraded
        assert shared.n_stale_served >= 1
        assert shared.n_promoted == before      # stale serve claims nothing
        assert eng.telemetry.faults.get("stale_served", 0) >= 1


def test_engine_outage_with_cold_cache_still_fails(index, docs):
    eng = _engine(index, docs)
    with eng.router:
        for b in eng.router.breakers:
            for _ in range(2):
                b.record(False)
        eng.start_session(0)                    # no cache, no memo, no shards
        with pytest.raises(TimeoutError):
            eng.answer_batch([0], [queries_for(index, 1, seed=5)[0]])


# ------------------------------------------------------- cache-state guard
def test_validate_state_flags_each_corruption(index, docs):
    eng = _engine(index, docs, n_sessions=3)
    with eng.router:
        for s in range(3):
            eng.start_session(s)
        q = queries_for(index, 3, seed=6)
        eng.answer_batch([0, 1, 2], list(q))
        st = eng.cache.state
        cfg = eng.cache.cfg
        ok, problems = validate_state(st, cfg, n_corpus=index.n_docs)
        assert ok.all() and not problems

        bad = np.asarray(st.q_radius).copy()
        bad[0, 0] = np.nan                      # poisoned claim radius
        ok, problems = validate_state(st._replace(q_radius=jnp.asarray(bad)),
                                      cfg, n_corpus=index.n_docs)
        assert not ok[0] and ok[1] and ok[2]
        assert any("radius" in p for p in problems)

        bad = np.asarray(st.doc_ids).copy()
        bad[1, 0] = index.n_docs + 7            # out-of-corpus doc id
        ok, _ = validate_state(st._replace(doc_ids=jnp.asarray(bad)),
                               cfg, n_corpus=index.n_docs)
        assert not ok[1] and ok[0] and ok[2]

        bad = np.asarray(st.doc_emb).copy()
        bad[2, 0, 0] = np.inf                   # corrupted embedding payload
        ok, _ = validate_state(st._replace(doc_emb=jnp.asarray(bad)),
                               cfg, n_corpus=index.n_docs)
        assert not ok[2] and ok[0] and ok[1]

        bad = np.asarray(st.n_docs).copy()
        bad[0] = cfg.capacity + 1               # counter out of bounds
        ok, _ = validate_state(st._replace(n_docs=jnp.asarray(bad)),
                               cfg, n_corpus=index.n_docs)
        assert not ok[0]


def test_engine_quarantines_corrupt_slot_and_keeps_serving(index, docs):
    eng = _engine(index, docs, n_sessions=3, validate_every=1)
    with eng.router:
        for s in range(3):
            eng.start_session(s)
        q = queries_for(index, 3, seed=7)
        eng.answer_batch([0, 1, 2], list(q))
        st = eng.cache.state
        qr = np.asarray(st.q_radius).copy()
        qr[1, 0] = np.nan                       # bitrot in session 1's slot
        eng.cache.state = st._replace(q_radius=jnp.asarray(qr))
        # the next wave's integrity sweep quarantines + resets the slot and
        # the wave itself still answers every session
        turns = eng.answer_batch([0, 1, 2],
                                 list(queries_for(index, 3, seed=8)))
        assert all(isinstance(t, EngineTurn) for t in turns)
        assert eng.quarantined >= 1
        assert eng.telemetry.faults.get("quarantined_slots", 0) >= 1
        ok, _ = validate_state(eng.cache.state, eng.cache.cfg,
                               n_corpus=index.n_docs)
        assert ok.all()
        # the reset slot restarted from empty: its turn was a compulsory
        # back-end miss, not a hit on quarantined state
        assert turns[1].tier == "backend" and not turns[1].hit


def test_validate_state_scalar_unbatched_state(index):
    from repro.core.cache import CacheConfig, MetricCache
    cache = MetricCache(CacheConfig(capacity=32, dim=index.dim))
    ok, problems = validate_state(cache.state, cache.cfg)
    assert bool(ok) and not problems


# ------------------------------------------------------- launch contracts
def test_shed_wave_is_two_launches(index, docs, monkeypatch):
    """The load-shed wave keeps the outage launch contract: probe ->
    cache-fallback query, exactly 2 Pallas launches (claims never
    recorded, nothing inserted) — counted at trace time on the
    interpret tier, against a device-resident shard so the full-miss
    baseline shows its 3-launch shape first."""
    import jax.experimental.pallas as plmod

    from repro.dist.retrieval import DeviceShard

    dev = DeviceShard(jnp.asarray(docs),
                      jnp.arange(index.n_docs, dtype=jnp.int32),
                      backend="interpret")
    router = ShardedRouter([dev], deadline_s=120.0, n_docs=index.n_docs,
                           breaker_min_calls=2, breaker_cooldown_s=3600.0)
    eng = _engine(index, docs, backend="interpret", epsilon=1e9,
                  router=router)
    calls = {"n": 0}
    orig = plmod.pallas_call

    def counting(*args, **kwargs):
        calls["n"] += 1
        return orig(*args, **kwargs)

    monkeypatch.setattr(plmod, "pallas_call", counting)
    with router:
        for s in (0, 1):
            eng.start_session(s)
        q = queries_for(index, 2, seed=9)
        jax.clear_caches()
        calls["n"] = 0
        eng.answer_batch([0, 1], list(q))       # compulsory full-miss wave
        assert calls["n"] == 3, f"miss wave traced {calls['n']} launches"
        for b in router.breakers:
            for _ in range(2):
                b.record(False)
        assert router.backend_open
        jax.clear_caches()
        calls["n"] = 0
        turns = eng.answer_batch([0, 1], list(queries_for(index, 2,
                                                          seed=10)))
        assert calls["n"] == 2, f"shed wave traced {calls['n']} launches"
        assert all(t.degraded for t in turns)


@pytest.mark.slow
def test_scheduler_breaker_outage_recovery_interpret(index, docs):
    """Satellite: breaker-driven outage -> shed -> half-open recovery,
    driven through the continuous scheduler on the interpret tier — warm
    slots stay answerable (degraded) while the back end is fenced, and
    the first post-recovery wave is first-class again."""
    down = {"on": False}
    inner = make_shards(index, 2)

    def flaky(queries, k, j=0):
        if down["on"]:
            raise RuntimeError("shard down")
        return inner[j](queries, k)

    shards = [lambda q, k, j=j: flaky(q, k, j) for j in range(2)]
    router = ShardedRouter(shards, deadline_s=10.0, max_retries=1,
                           backoff_base_s=0.001, breaker_window=4,
                           breaker_min_calls=2, breaker_cooldown_s=0.2,
                           n_docs=index.n_docs)
    eng = BatchedEngine(router, docs, dim=index.dim, n_sessions=2, k=5,
                        k_c=16, capacity=64, backend="interpret")
    q = queries_for(index, 8, seed=11)
    with router, ContinuousScheduler(eng, window_s=60.0,
                                     adaptive=False) as sched:
        for s in (0, 1):
            eng.start_session(s)
        futs = [sched.submit(q[s], slot=s) for s in (0, 1)]
        assert all(isinstance(f.result(timeout=120), EngineTurn)
                   for f in futs)
        down["on"] = True                       # outage: breakers trip...
        futs = [sched.submit(q[2 + s], slot=s) for s in (0, 1)]
        t1 = [f.result(timeout=120) for f in futs]
        assert all(isinstance(t, EngineTurn) and t.degraded for t in t1)
        assert router.stats.breaker_opens >= 1
        futs = [sched.submit(q[4 + s], slot=s) for s in (0, 1)]
        t2 = [f.result(timeout=120) for f in futs]  # ...then waves shed
        assert all(isinstance(t, EngineTurn) and t.degraded for t in t2)
        down["on"] = False                      # recovery after cooldown
        time.sleep(0.25)
        deadline = time.monotonic() + 30.0
        while any(b.state != "closed" for b in router.breakers):
            futs = [sched.submit(q[6 + s], slot=s) for s in (0, 1)]
            [f.result(timeout=120) for f in futs]
            time.sleep(0.25)
            assert time.monotonic() < deadline, "breakers never re-closed"
        futs = [sched.submit(q[6 + s], slot=s) for s in (0, 1)]
        t3 = [f.result(timeout=120) for f in futs]
        assert all(isinstance(t, EngineTurn) and not t.degraded
                   for t in t3)
        assert router.stats.breaker_closes >= 1
