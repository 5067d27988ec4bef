"""The shared L2 tier's spans and counters.

A tiered ``BatchedEngine`` runs a wave that admits an answer into the L2
tier, then a mixed wave with one row per tier: an L1 hit, an L2 memo
reuse, an L2 shard hit and a back-end miss.  Under a profile the
``l2.*`` spans carry their args inside their parents; with none they
record nothing.  The tier counters split ``wave_rows``, the tier's own
transfers land in ``h2d_bytes`` / ``d2h_bytes``, and ``TurnSpans.l2_s``
is the wave's time inside the tier (0 with no tier).  The stacked L1
state is updated in place: a reset keeps every other slot bit-equal, and
a reset beside a write-back on another thread loses neither."""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.core.shared import SharedTier
from repro.serve.router import ShardAnswer, ShardedRouter
from repro.serve.session import BatchedEngine

N, D, K, K_C = 400, 48, 10, 50
TIERS = ("l1_hit_rows", "l2_hit_rows", "l2_reuse_rows", "backend_rows")


def _unit(x):
    x = np.asarray(x, np.float32)
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


@pytest.fixture(scope="module")
def world():
    rng = np.random.default_rng(12)
    docs = _unit(rng.standard_normal((N, D)))
    base = _unit(rng.standard_normal(D))

    def near(scale):
        return _unit(base + scale * _unit(rng.standard_normal(D)))
    first = [near(0.01), near(0.01)]
    mixed = [near(0.01),                 # session 0 again: its own L1
             _unit(first[1] + 0.001 * rng.standard_normal(D)),  # memo
             near(0.2),                  # inside the admitted claim
             _unit(rng.standard_normal(D))]                     # back end
    return docs, first, mixed


def _router(docs):
    ids = np.arange(len(docs))

    def shard(queries, k):
        scores = queries @ docs.T
        top = np.argsort(-scores, axis=1)[:, :k]
        return ShardAnswer(np.take_along_axis(scores, top, axis=1), ids[top])
    return ShardedRouter([shard], deadline_s=30.0)


def _engine(docs, tiered=True):
    shared = SharedTier(dim=D, n_shards=1, capacity=1024,
                        backend="ref") if tiered else None
    return BatchedEngine(_router(docs), docs, dim=D, n_sessions=8, k=K,
                         k_c=K_C, backend="ref", shared=shared)


def _counters(eng):
    tel = eng.telemetry
    return {k: getattr(tel, k) for k in
            ("h2d_bytes", "d2h_bytes", "wave_rows") + TIERS}


def _delta(a, b):
    return {k: b[k] - a[k] for k in a}


def _load(path) -> list:
    """(name without ``repro.``, start, end, host line, args) by start."""
    pd = ProfileData.from_file(glob.glob(
        str(path / "**" / "*.xplane.pb"), recursive=True)[0])
    out, n = [], 0
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            n += 1
            out += [(ev.name[len("repro."):], ev.start_ns, ev.end_ns, n,
                     dict(ev.stats))
                    for ev in line.events if ev.name.startswith("repro.")]
    return sorted(out, key=lambda sp: (sp[1], -sp[2]))


@pytest.fixture(scope="module")
def traced(world, tmp_path_factory):
    """Spans, turns and counter deltas of the admitting wave and the
    mixed wave, run under a profile."""
    docs, first, mixed = world
    eng = _engine(docs)
    path = tmp_path_factory.mktemp("trace")
    c0 = _counters(eng)
    jax.profiler.start_trace(str(path))
    one = eng.answer_batch([0, 1], first)
    c1 = _counters(eng)
    two = eng.answer_batch([0, 2, 3, 4], mixed)
    jax.profiler.stop_trace()
    c2 = _counters(eng)
    return _load(path), one, two, _delta(c0, c1), _delta(c1, c2), eng


def test_the_mixed_wave_takes_one_row_per_tier(traced):
    _spans, one, two, d1, d2, eng = traced
    assert [t.tier for t in one] == ["backend", "backend"]
    # the second session's answer clears the bar in wave 1, the reused
    # one (a third retriever of the same docs) in wave 2
    assert eng.shared.n_promoted == 2
    assert [t.tier for t in two] == ["l1", "l2_reuse", "l2", "backend"]
    assert {k: d2[k] for k in TIERS} == dict.fromkeys(TIERS, 1)
    for d in (d1, d2):
        assert sum(d[k] for k in TIERS) == d["wave_rows"]


def test_l2_spans_carry_their_args_inside_their_parents(traced):
    spans, *_ = traced
    named = {}
    for sp in spans:
        named.setdefault(sp[0], []).append(sp)
    bucket = 4
    (memo1, memo2) = named["l2.memo"]
    assert memo1[4] == {"rows": 2, "hits": 0}
    assert memo2[4] == {"rows": 3, "hits": 1}
    assert len(named["l2.route"]) == 2
    # the shard probe, then for the L2 hit the answer query: the shard
    # index staged by each (twice by the query), the hit mask and the
    # answer ids read back
    probe = named["l2.probe"][-1][4]
    assert probe == {"rows": 2, "shards": 1, "h2d_bytes": 3 * 4 * bucket,
                     "d2h_bytes": bucket + bucket * K * 4}
    assert named["l2.rows"][0][4] == {"rows": 2}
    flushes = [sp[4] for sp in named["l2.flush"]]
    assert [f["admitted"] for f in flushes] == [1, 1]
    assert [f["h2d_bytes"] for f in flushes] == [_flush_h2d()] * 2
    assert [f["d2h_bytes"] for f in flushes] == [4 + 4] * 2
    parent = {"l2.memo": "engine.probe.l2", "l2.route": "engine.probe.l2",
              "l2.probe": "engine.probe.l2",
              "l2.rows": "engine.probe.l2", "l2.flush": "engine.fill.l2_flush"}
    for child, head in parent.items():
        for sp in named[child]:
            assert any(p[3] == sp[3] and p[1] <= sp[1] and sp[2] <= p[2]
                       for p in named[head]), (child, sp)


def _flush_h2d(n_shards=1):
    """psi, radius, emb, ids and do of one flush of the whole state."""
    return n_shards * (D * 4 + 4 + K_C * D * 4 + K_C * 4 + 1)


def test_l2_bytes_are_in_the_counters(world):
    """The same two waves on an untiered engine move fewer bytes by
    exactly the tier's transfers: the shard rows' index (per probe, and
    twice for the answer query), the hit mask and answer ids read back,
    and the flush of each wave's admitted answer."""
    docs, first, mixed = world
    moved = []
    for tiered in (True, False):
        eng = _engine(docs, tiered)
        c0 = _counters(eng)
        eng.answer_batch([0, 1], first)
        c1 = _counters(eng)
        eng.answer_batch([0, 2, 3, 4], mixed)
        c2 = _counters(eng)
        moved.append((_delta(c0, c1), _delta(c1, c2)))
    (t1, t2), (u1, u2) = moved
    # wave 1 (bucket 2): probe index and hit mask; the flush of one
    # answer (n_queries and dropped read back)
    assert t1["h2d_bytes"] - u1["h2d_bytes"] == 2 * 4 + _flush_h2d()
    assert t1["d2h_bytes"] - u1["d2h_bytes"] == 2 + 4 + 4
    # wave 2 (bucket 4): the engine's own transfers are of bucket shape,
    # whichever tier takes each row
    tier_h2d = 4 * 4 + 2 * 4 * 4 + _flush_h2d()  # probe, query, flush
    tier_d2h = 4 + 4 * K * 4 + 4 + 4             # hit, ids, flush reads
    assert t2["h2d_bytes"] - tier_h2d == u2["h2d_bytes"]
    assert t2["d2h_bytes"] - tier_d2h == u2["d2h_bytes"]


def test_l2_spans_record_nothing_without_a_profiler(world, tmp_path):
    assert not TraceAnnotation.is_enabled()
    docs, first, mixed = world
    eng = _engine(docs)
    eng.answer_batch([0, 1], first)
    eng.answer_batch([0, 2, 3, 4], mixed)
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("repro.test.marker"):
        pass
    jax.profiler.stop_trace()
    assert [sp[0] for sp in _load(tmp_path)] == ["test.marker"]


def test_l2_s_is_the_tier_time_and_zero_without_it(world):
    docs, first, mixed = world
    for tiered in (True, False):
        eng = _engine(docs, tiered)
        turns = eng.answer_batch([0, 1], first) + \
            eng.answer_batch([0, 2, 3, 4], mixed)
        l2 = [t.spans.l2_s for t in turns]
        if tiered:
            assert all(0 < s < t.spans.probe_s + t.spans.insert_s
                       for s, t in zip(l2, turns))
        else:
            assert l2 == [0.0] * len(turns)


def _filled(cache, slots, first_id):
    """Write into each of ``slots`` an empty cache holding one answer of 3
    rows, with ids from ``first_id`` on."""
    from repro.core import cache as C
    rng = np.random.default_rng(first_id)
    n = len(slots)
    sub = C.init_batched_cache(cache.cfg, n)
    psi = _unit(rng.standard_normal((n, D)))
    emb = _unit(rng.standard_normal((n, 3, D)))
    ids = first_id + np.arange(n * 3, dtype=np.int32).reshape(n, 3)
    sub, _ = C.insert_batched(sub, cache.cfg, jnp.asarray(psi),
                              jnp.full((n,), 0.5, jnp.float32),
                              jnp.asarray(emb), jnp.asarray(ids))
    cache.scatter(np.asarray(slots, np.int32), sub)


def _host(cache):
    return jax.tree_util.tree_map(np.asarray, cache.read(
        lambda st: jax.tree_util.tree_map(lambda x: x + 0, st)))


def test_a_donated_reset_leaves_every_other_slot_bit_equal():
    from repro.core import cache as C
    cache = C.BatchedMetricCache(C.CacheConfig(capacity=16, dim=D), 6)
    _filled(cache, [0, 1, 2, 3, 4, 5], 100)
    # copies: a host view of a live leaf (on the CPU) would keep it alive
    before = _host(cache)
    old = cache.state
    cache.reset([1, 4])
    assert old.doc_emb.is_deleted()       # updated in place, not copied
    after = _host(cache)
    fresh = jax.tree_util.tree_map(np.asarray, C.init_cache(cache.cfg))
    for name, a, b, f in zip(C.CacheState._fields, before, after, fresh):
        for s in (0, 2, 3, 5):
            np.testing.assert_array_equal(a[s], b[s], err_msg=name)
        for s in (1, 4):
            np.testing.assert_array_equal(b[s], f, err_msg=name)


def test_a_reset_beside_a_write_back_loses_neither():
    """Sessions open (a donated reset) on callers' threads while the
    worker writes waves back (a donated scatter) into other slots: every
    reset slot reads empty, and every written slot holds its write."""
    import sys
    import threading
    import time

    from repro.core import cache as C
    cache = C.BatchedMetricCache(C.CacheConfig(capacity=16, dim=D), 8)
    errors = []

    def writer(stop):             # the worker: waves into slots 0-3
        wave = 0
        while time.perf_counter() < stop:
            wave += 1
            _filled(cache, [0, 1, 2, 3], 1000 * wave)
            ids = np.asarray(cache.gather([0, 1, 2, 3]).doc_ids)
            if not (ids[:, 0] == 1000 * wave + 3 * np.arange(4)).all():
                errors.append(("write lost", wave))

    def opener(slot, stop):       # a caller: fill its slot, then reset
        while time.perf_counter() < stop:
            _filled(cache, [slot], 7)
            cache.reset([slot])
            if int(np.asarray(cache.gather([slot]).n_docs)[0]) != 0:
                errors.append(("reset lost", slot))

    def caught(fn, *a):           # a read of a donated state raises
        try:
            fn(*a)
        except Exception as e:    # noqa: BLE001
            errors.append(repr(e)[:200])

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        stop = time.perf_counter() + 2.0
        threads = [threading.Thread(target=caught, args=(writer, stop))] + [
            threading.Thread(target=caught, args=(opener, s, stop))
            for s in (4, 5, 6, 7)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[:5]
