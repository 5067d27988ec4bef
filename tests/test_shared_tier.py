"""Cross-session shared tier (L2) tests: admission policy, claim TTL,
semantic result reuse, the tiered probe order inside ``BatchedEngine``,
and the tiered wave's kernel-launch / zero-copy contracts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cache_ops import probe_batched
from repro.core.shared import SharedTier
from repro.kernels import jaxpr_util
from repro.serve.router import ShardAnswer, ShardedRouter
from repro.serve.session import BatchedEngine

jax.config.update("jax_platform_name", "cpu")

pytestmark = pytest.mark.kernels  # L2 rides the L1 kernels: gate with them


def _unit(rng, shape):
    x = rng.standard_normal(shape).astype(np.float32)
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


def _counting_router(docs, counter):
    """Single-shard exact router over host docs; counts back-end calls —
    the unit-level twin of serve_bench's backend_queries_saved column."""
    ids = np.arange(len(docs))

    def shard(queries, k):
        counter["calls"] += 1
        counter["queries"] += len(queries)
        scores = queries @ docs.T
        top = np.argsort(-scores, axis=1)[:, :k]
        return ShardAnswer(np.take_along_axis(scores, top, axis=1), ids[top])

    return ShardedRouter([shard], deadline_s=30.0)


# ------------------------------------------------------- admission policy
def test_admission_requires_distinct_sessions():
    """One session's answer never enters the shared tier; the same answer
    retrieved by a SECOND distinct session promotes wholesale."""
    tier = SharedTier(dim=64, n_shards=2, capacity=100, max_queries=5,
                      backend="interpret")
    rng = np.random.default_rng(7)
    psi = _unit(rng, (64,))
    emb = _unit(rng, (6, 64))
    ids = np.arange(10, 16)
    tier.tick()
    assert not tier.offer(("a", 1), psi, 0.5, emb, ids)
    assert tier.flush_admissions() == 0
    assert not tier.contains(ids).any()
    # re-offering from the SAME session does not advance the count
    assert not tier.offer(("a", 1), psi, 0.5, emb, ids)
    # ...a second distinct session does, and the whole answer promotes
    assert tier.offer(("b", 1), psi, 0.5, emb, ids)
    assert tier.flush_admissions() == 1
    assert tier.contains(ids).all()
    assert tier.n_promoted == 1 and tier.n_offered == 3


def test_admission_frac_gates_partial_overlap():
    """An answer whose documents are mostly one-session-only stays out even
    when a few of them are globally popular."""
    tier = SharedTier(dim=32, capacity=100, max_queries=5,
                      admission_frac=0.5, backend="interpret")
    rng = np.random.default_rng(8)
    emb = _unit(rng, (10, 32))
    hot, cold = np.arange(3), np.arange(100, 107)
    tier.tick()
    tier.offer(("a", 1), _unit(rng, (32,)), 0.5, emb[:3], hot)
    tier.offer(("b", 1), _unit(rng, (32,)), 0.5, emb[:3], hot)  # hot: 2 sess
    # 3/10 promotable (< admission_frac) -> the mixed answer is rejected
    mixed = np.concatenate([hot, cold])
    assert not tier.offer(("c", 1), _unit(rng, (32,)), 0.5, emb, mixed)


# ------------------------------------------------------------- claim TTL
def test_ttl_expires_claims_but_not_documents():
    """Past ttl_waves the coverage claim stops producing probe hits (its
    ring slot's -inf sentinel is restored) while the promoted documents
    stay resident — embeddings don't go stale, claims do."""
    tier = SharedTier(dim=64, n_shards=2, capacity=100, max_queries=5,
                      ttl_waves=3, admission_sessions=1, backend="interpret")
    rng = np.random.default_rng(9)
    psi = _unit(rng, (64,))
    ids = np.arange(20, 27)
    tier.tick()
    assert tier.offer(("a", 1), psi, 0.5, _unit(rng, (7, 64)), ids)
    tier.flush_admissions()
    shards = tier.route(psi[None])
    pr = tier.probe_rows(jnp.asarray(psi[None]), shards)
    assert bool(np.asarray(pr.hit)[0])        # claim live: probe hits
    for _ in range(4):
        tier.tick()                            # age past ttl_waves
    pr = tier.probe_rows(jnp.asarray(psi[None]), shards)
    assert not bool(np.asarray(pr.hit)[0])    # claim retired...
    assert tier.contains(ids).all()           # ...documents survive


def test_flush_runs_the_one_program_warm_compiles():
    """Every admission flush, whatever the number of admitted answers,
    launches the whole-state insert program ``warm`` compiles, and the
    answer query runs the programs it compiled per wave size; ``warm``
    and the shards without an admitted answer keep their state."""
    from repro.core.cache_ops import insert_batched, query_batched
    tier = SharedTier(dim=32, n_shards=3, capacity=60, max_queries=5,
                      admission_sessions=1, backend="interpret")
    rng = np.random.default_rng(11)
    leaves = jax.tree_util.tree_leaves
    before = tier.state
    tier.warm(k=4, k_c=6, rows=[1, 2], backend="interpret")
    assert tier.state is before
    programs = (insert_batched._cache_size(), query_batched._cache_size())
    tier.tick()
    psi = _unit(rng, (6, 32))
    assert tier.offer(("a", 1), psi[0], 0.5, _unit(rng, (6, 32)),
                      np.arange(6))
    before = tier.state
    assert tier.flush_admissions() == 1
    others = np.arange(3) != tier.route(psi[:1])[0]
    for a, b in zip(leaves(before), leaves(tier.state)):
        np.testing.assert_array_equal(np.asarray(a)[others],
                                      np.asarray(b)[others])
    assert tier.contains(np.arange(6)).all()
    for j in range(1, 6):
        assert tier.offer(("a", 1), psi[j], 0.5, _unit(rng, (6, 32)),
                          np.arange(10 * j, 10 * j + 6))
    assert tier.flush_admissions() == 5
    assert tier.contains(np.arange(10, 56)[np.arange(46) % 10 < 6]).all()
    for b in (1, 2):
        tier.query_rows(psi[:b], tier.route(psi[:b]), 4)
    assert (insert_batched._cache_size(),
            query_batched._cache_size()) == programs


# ------------------------------------------------------ semantic result memo
def test_memo_serves_other_sessions_only():
    tier = SharedTier(dim=32, backend="interpret")
    rng = np.random.default_rng(3)
    psi = _unit(rng, (32,))
    ids = np.arange(9)
    scores = np.linspace(0.9, 0.5, 9).astype(np.float32)
    tier.tick()
    tier.memo_record(("a", 1), psi, ids, scores, radius=0.4)
    # a same-session near-duplicate is the L1 tier's job
    assert tier.memo_lookup(("a", 1), psi) is None
    got = tier.memo_lookup(("b", 1), psi)
    assert got is not None
    g_ids, g_scores, claim = got
    np.testing.assert_array_equal(g_ids, ids)
    np.testing.assert_array_equal(g_scores, scores)
    # delta(psi, psi) = 0 up to the fp32 dot's rounding (sqrt amplifies
    # a 1e-7 cosine error to ~5e-4 in distance)
    assert abs(claim - 0.4) < 2e-3
    # an unrelated query never clears the cosine floor
    assert tier.memo_lookup(("b", 1), _unit(rng, (32,))) is None


def test_memo_claim_is_triangle_corrected():
    """The claim handed to a reusing session is r_a - delta(psi_a, psi) —
    the paper's Eq. 3 bound — never the recorded radius itself."""
    tier = SharedTier(dim=48, memo_sim=0.9, backend="interpret")
    rng = np.random.default_rng(4)
    psi = _unit(rng, (48,))
    tier.tick()
    tier.memo_record(("a", 1), psi, np.arange(5),
                     np.ones(5, np.float32), radius=0.7)
    near = psi + 0.05 * _unit(rng, (48,))
    near = near / np.linalg.norm(near)
    sim = float(near @ psi)
    _, _, claim = tier.memo_lookup(("b", 1), near)
    assert abs(claim - (0.7 - np.sqrt(2.0 - 2.0 * sim))) < 2e-3
    assert claim < 0.7


def test_memo_entries_expire_after_ttl():
    tier = SharedTier(dim=32, ttl_waves=2, backend="interpret")
    rng = np.random.default_rng(5)
    psi = _unit(rng, (32,))
    tier.tick()
    tier.memo_record(("a", 1), psi, np.arange(4),
                     np.ones(4, np.float32), radius=0.3)
    tier.tick()
    assert tier.memo_lookup(("b", 1), psi) is not None
    tier.tick()
    tier.tick()                                # age = 3 > ttl_waves
    assert tier.memo_lookup(("b", 1), psi) is None


# --------------------------------------------- tiered BatchedEngine waves
def test_engine_memo_reuse_cross_session_saves_backend_and_overlaps():
    """A near-duplicate query from ANOTHER session is served from the
    result memo (tier l2_reuse) with zero new back-end calls, and the
    reused ranking stays rank-faithful to fresh retrieval (>= 0.95)."""
    rng = np.random.default_rng(11)
    n, d, k, kc = 400, 48, 10, 50
    docs = _unit(rng, (n, d))
    counter = {"calls": 0, "queries": 0}
    router = _counting_router(docs, counter)
    tier = SharedTier(dim=d, n_shards=2, capacity=1024, backend="ref")
    eng = BatchedEngine(router, docs, dim=d, n_sessions=2, k=k, k_c=kc,
                        backend="ref", shared=tier)
    q0 = _unit(rng, (d,))
    t0 = eng.answer_batch([0], [jnp.asarray(q0)])[0]
    assert t0.tier == "backend" and not t0.hit
    calls_before = counter["calls"]
    q1 = q0 + 0.01 * _unit(rng, (d,))          # cosine >> memo_sim floor
    q1 = q1 / np.linalg.norm(q1)
    t1 = eng.answer_batch([1], [jnp.asarray(q1)])[0]
    assert t1.tier == "l2_reuse" and t1.hit
    assert counter["calls"] == calls_before    # back-end query saved
    assert tier.n_memo_served == 1
    fresh, _ = router.search(q1[None], k)
    overlap = len(set(t1.ids[:k].tolist())
                  & set(fresh.ids[0][:k].tolist())) / k
    assert overlap >= 0.95


def test_engine_l2_shard_hit_cross_session_and_l1_reset_survival():
    """With the memo disabled, a promoted shard claim serves a third
    session straight from L2 (tier l2, no back-end call) — and resetting a
    contributing session's L1 cache evicts nothing from the shared tier."""
    rng = np.random.default_rng(12)
    n, d, kc = 400, 48, 50
    docs = _unit(rng, (n, d))
    counter = {"calls": 0, "queries": 0}
    router = _counting_router(docs, counter)
    # memo_sim > 1 can never fire: isolates the shard-cache path
    tier = SharedTier(dim=d, n_shards=2, capacity=1024, memo_sim=1.5,
                      backend="ref")
    eng = BatchedEngine(router, docs, dim=d, n_sessions=3, k=10, k_c=kc,
                        backend="ref", shared=tier)
    base = _unit(rng, (d,))

    def jitter(scale):
        q = base + scale * _unit(rng, (d,))
        return jnp.asarray(q / np.linalg.norm(q))

    # two distinct sessions retrieve the same topic -> answer promotes
    t0, t1 = eng.answer_batch([0, 1], [jitter(0.01), jitter(0.01)])
    assert t0.tier == t1.tier == "backend"
    assert tier.n_promoted >= 1
    promoted = t0.ids[:10]
    assert tier.contains(promoted).all()
    calls_before = counter["calls"]
    # a THIRD session's compulsory first turn is covered by the shared claim
    t2 = eng.answer_batch([2], [jitter(0.01)])[0]
    assert t2.tier == "l2" and t2.hit
    assert counter["calls"] == calls_before
    assert (t2.ids >= 0).all() and t2.ids.size > 0
    # satellite: recycling the contributing L1 slots leaves L2 intact
    eng.start_session(0)
    eng.start_session(1)
    assert tier.contains(promoted).all()
    assert (np.asarray(eng.cache.state.n_docs)[:2] == 0).all()


def test_engine_tier_counts_and_aggregate_hit_rate():
    rng = np.random.default_rng(13)
    docs = _unit(rng, (300, 32))
    counter = {"calls": 0, "queries": 0}
    router = _counting_router(docs, counter)
    tier = SharedTier(dim=32, n_shards=2, capacity=1024, backend="ref")
    eng = BatchedEngine(router, docs, dim=32, n_sessions=2, k=5, k_c=40,
                        backend="ref", shared=tier)
    assert np.isnan(eng.hit_rate())            # no eligible turns yet
    q = jnp.asarray(_unit(rng, (32,)))
    eng.answer_batch([0, 1], [q, q])           # compulsory misses
    eng.answer_batch([0, 1], [q, q])           # L1 covers both
    counts = eng.tier_counts()
    assert counts["l1"] == 2 and counts["backend"] == 0
    assert sum(counts.values()) == 2           # first turns excluded
    assert eng.hit_rate() == 1.0
    assert eng.hit_rate(0) == 1.0 and eng.hit_rate(1) == 1.0
    assert sum(eng.tier_counts(skip_first=False).values()) == 4


# ------------------------------------- launch-count / zero-copy contracts
def test_l2_probe_trace_is_zero_copy_single_launch():
    """The L2 shard probe rides the SAME cache_probe_batched contract as
    L1: tracing it over gathered shard rows shows one Pallas launch and no
    pad/slice/copy at the stacked payload size."""
    tier = SharedTier(dim=200, n_shards=3, capacity=100, max_queries=5,
                      backend="interpret")
    rng = np.random.default_rng(14)
    psi = jnp.asarray(_unit(rng, (3, 200)))
    sub = tier._gather(np.arange(3))
    payload = 3 * tier.cfg.phys_capacity * tier.cfg.phys_dim
    jx = jax.make_jaxpr(
        lambda st, p: probe_batched(st, p, tier.cfg.epsilon,
                                    backend="interpret",
                                    max_queries=tier.cfg.max_queries))(
        sub, psi)
    assert jaxpr_util.payload_copy_eqns(jx, payload) == []
    assert jaxpr_util.pallas_call_count(jx) == 1


@pytest.mark.slow
def test_tiered_engine_full_miss_wave_is_four_launches(monkeypatch):
    """Acceptance (ISSUE 7): on the kernel tier a full-miss TIERED wave is
    exactly FOUR Pallas launches — L1 probe -> L2 probe -> miss-search ->
    fused insert+query — one more than the L1-only contract (asserted in
    test_kernel_equivalence).  A follow-up memo-reuse wave adds NO search
    launch: L1 probe -> fused insert+query -> the admission flush its
    second-session vote triggers."""
    import jax.experimental.pallas as plmod

    from repro.dist.retrieval import DeviceShard

    rng = np.random.default_rng(15)
    n, d, s = 300, 48, 4
    docs = _unit(rng, (n, d))
    shard = DeviceShard(jnp.asarray(docs), jnp.arange(n, dtype=jnp.int32),
                        backend="interpret")
    router = ShardedRouter([shard], deadline_s=120.0)
    tier = SharedTier(dim=d, n_shards=2, capacity=128, max_queries=8,
                      backend="interpret")
    eng = BatchedEngine(router, docs, dim=d, n_sessions=s + 1, k=5, k_c=17,
                        capacity=64, backend="interpret", shared=tier)

    calls = {"n": 0}
    orig = plmod.pallas_call

    def counting(*args, **kwargs):
        calls["n"] += 1
        return orig(*args, **kwargs)

    monkeypatch.setattr(plmod, "pallas_call", counting)

    qs = _unit(rng, (s, d))
    # pallas_call counting happens at TRACE time: drop the process-wide jit
    # cache so the count can't depend on shapes earlier tests compiled
    jax.clear_caches()
    calls["n"] = 0
    turns = eng.answer_batch(list(range(s)), [jnp.asarray(q) for q in qs])
    assert all(t.tier == "backend" for t in turns)
    assert calls["n"] == 4, f"tiered miss wave traced {calls['n']} launches"

    # a new session near-duplicating session 0's query: memo reuse skips
    # both probes-beyond-L1 and the back-end search entirely
    q = qs[0] + 0.01 * _unit(rng, (d,))
    q = q / np.linalg.norm(q)
    jax.clear_caches()
    calls["n"] = 0
    turn = eng.answer_batch([s], [jnp.asarray(q)])[0]
    assert turn.tier == "l2_reuse"
    assert calls["n"] == 3, f"reuse wave traced {calls['n']} launches"
