"""Equivalence suite: session-batched (vmapped) cache ops must reproduce the
per-session scalar ops exactly — probe hit/r_hat/nearest_q, query results,
and every leaf of the post-insert state — across mixed hit/miss waves,
gated records, and all eviction policies."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cache as C

jax.config.update("jax_platform_name", "cpu")

DIM = 8


def _unit(rng, n, d=DIM):
    x = rng.standard_normal((n, d)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def _stack_states(states):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def _assert_states_equal(ref: C.CacheState, got: C.CacheState):
    for name, a, b in zip(C.CacheState._fields, ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"leaf {name} diverged")


@pytest.mark.parametrize("eviction", ["none", "lru", "ball"])
def test_batched_ops_equal_scalar_loop(eviction):
    """Five waves of probe -> masked insert -> query over 4 sessions, with
    per-session do/record masks, against the scalar ops run per session."""
    cfg = C.CacheConfig(capacity=32, dim=DIM, max_queries=4, eviction=eviction)
    S, KC, K = 4, 10, 5
    rng = np.random.default_rng(7)
    scalar = [C.init_cache(cfg) for _ in range(S)]
    batched = C.init_batched_cache(cfg, S)

    for wave in range(5):
        psi = jnp.asarray(_unit(rng, S))
        emb = jnp.asarray(_unit(rng, S * KC).reshape(S, KC, DIM))
        ids = jnp.asarray(rng.integers(0, 60, (S, KC)).astype(np.int32))
        radius = jnp.asarray(rng.uniform(0.4, 1.0, S).astype(np.float32))
        do = (jnp.ones((S,), bool) if wave == 0 else
              jnp.asarray(rng.integers(0, 2, S).astype(bool)))
        record = jnp.asarray(rng.integers(0, 2, S).astype(bool))

        bp = C.probe_batched(batched, psi, cfg.epsilon)
        batched, bdrop = C.insert_batched(batched, cfg, psi, radius, emb, ids,
                                          do=do, record=record)
        (bs, bd, bi, bsl), batched = C.query_batched(batched, psi, K)

        for s in range(S):
            sp = C.probe(scalar[s], psi[s], cfg.epsilon)
            np.testing.assert_array_equal(np.asarray(sp.hit), np.asarray(bp.hit[s]))
            np.testing.assert_array_equal(np.asarray(sp.r_hat), np.asarray(bp.r_hat[s]))
            np.testing.assert_array_equal(np.asarray(sp.nearest_q), np.asarray(bp.nearest_q[s]))
            if bool(do[s]):
                scalar[s], sdrop = C.insert(scalar[s], cfg, psi[s], radius[s],
                                            emb[s], ids[s], record[s])
                np.testing.assert_array_equal(np.asarray(sdrop), np.asarray(bdrop[s]))
            else:
                assert int(bdrop[s]) == 0
            (ss, sd, si, ssl), scalar[s] = C.query(scalar[s], psi[s], K)
            np.testing.assert_array_equal(np.asarray(si), np.asarray(bi[s]))
            np.testing.assert_array_equal(np.asarray(ss), np.asarray(bs[s]))
            np.testing.assert_array_equal(np.asarray(sd), np.asarray(bd[s]))
            np.testing.assert_array_equal(np.asarray(ssl), np.asarray(bsl[s]))

    _assert_states_equal(_stack_states(scalar), batched)


def test_batched_hit_sessions_state_untouched():
    """do=False sessions keep their state bitwise across an insert wave."""
    cfg = C.CacheConfig(capacity=16, dim=DIM)
    S, KC = 3, 6
    rng = np.random.default_rng(1)
    state = C.init_batched_cache(cfg, S)
    psi = jnp.asarray(_unit(rng, S))
    emb = jnp.asarray(_unit(rng, S * KC).reshape(S, KC, DIM))
    ids = jnp.asarray(np.arange(S * KC, dtype=np.int32).reshape(S, KC))
    radius = jnp.asarray(np.full(S, 0.7, np.float32))
    state, _ = C.insert_batched(state, cfg, psi, radius, emb, ids)
    before = jax.tree_util.tree_map(lambda x: np.asarray(x[1]), state)
    do = jnp.asarray([True, False, True])
    state, _ = C.insert_batched(state, cfg, psi, radius, emb, ids, do=do)
    after = jax.tree_util.tree_map(lambda x: np.asarray(x[1]), state)
    for name, a, b in zip(C.CacheState._fields, before, after):
        np.testing.assert_array_equal(a, b, err_msg=f"leaf {name} changed")
    # the do=True sessions did advance
    assert int(state.step[0]) == 2 and int(state.step[1]) == 1


def test_reset_sessions_isolates_one_session():
    cfg = C.CacheConfig(capacity=16, dim=DIM)
    S, KC = 3, 4
    rng = np.random.default_rng(2)
    cache = C.BatchedMetricCache(cfg, S)
    cache.insert(jnp.asarray(_unit(rng, S)),
                 jnp.asarray(np.full(S, 0.5, np.float32)),
                 jnp.asarray(_unit(rng, S * KC).reshape(S, KC, DIM)),
                 jnp.asarray(np.arange(S * KC, dtype=np.int32).reshape(S, KC)))
    assert np.asarray(cache.n_docs).tolist() == [KC] * S
    cache.reset([1])
    assert np.asarray(cache.n_docs).tolist() == [KC, 0, KC]
    assert np.asarray(cache.n_queries).tolist() == [1, 0, 1]
    fresh = C.init_cache(cfg)
    got1 = jax.tree_util.tree_map(lambda x: x[1], cache.state)
    _assert_states_equal(fresh, got1)


def test_gather_scatter_roundtrip_leaves_others_alone():
    cfg = C.CacheConfig(capacity=8, dim=DIM)
    rng = np.random.default_rng(3)
    cache = C.BatchedMetricCache(cfg, 4)
    psi = jnp.asarray(_unit(rng, 4))
    cache.insert(psi, jnp.asarray(np.full(4, 0.5, np.float32)),
                 jnp.asarray(_unit(rng, 4 * 3).reshape(4, 3, DIM)),
                 jnp.asarray(np.arange(12, dtype=np.int32).reshape(4, 3)))
    before = jax.tree_util.tree_map(np.asarray, cache.state)
    sub = cache.gather([0, 2])
    (scores, dists, ids, slots), sub = C.query_batched(sub, psi[jnp.asarray([0, 2])], 2)
    cache.scatter([0, 2], sub)
    after = jax.tree_util.tree_map(np.asarray, cache.state)
    # untouched sessions bitwise identical; touched sessions advanced a step
    for name, a, b in zip(C.CacheState._fields, before, after):
        np.testing.assert_array_equal(a[1], b[1], err_msg=name)
        np.testing.assert_array_equal(a[3], b[3], err_msg=name)
    assert after.step[0] == before.step[0] + 1     # step leaf


def test_reset_and_scatter_from_two_threads_lose_no_write():
    """Sessions open (``reset``) on callers' threads while the serving
    worker writes waves back (``scatter``) into the same stacked state.
    Each is a read-modify-write of the whole state: a reset that lands
    inside a write-back must not be undone by it.  Both update the state
    in place, so the threads read it through ``gather``, under its lock."""
    import sys
    import threading
    import time

    cfg = C.CacheConfig(capacity=16, dim=DIM)
    cache = C.BatchedMetricCache(cfg, 8)
    mark = lambda v: jax.tree_util.tree_map(     # noqa: E731
        lambda x: jnp.full((1,) + x.shape[1:], v, x.dtype), cache.gather([0]))
    dirty, clean = mark(7), mark(0)
    for s in range(1, 5):                        # compile before racing
        cache.scatter(np.array([s], np.int32), dirty)
        cache.reset([s])
    lost = []

    def writer(stop):             # the worker: waves into slot 0 only
        while time.perf_counter() < stop:
            cache.scatter(np.array([0], np.int32), clean)
            jax.block_until_ready(cache.gather([0]))

    def opener(slot, stop):       # a caller: dirty its slot, then reset
        while time.perf_counter() < stop:
            cache.scatter(np.array([slot], np.int32), dirty)
            cache.reset([slot])
            if int(np.asarray(cache.gather([slot]).doc_ids[0, 0])) == 7:
                lost.append(slot)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        stop = time.perf_counter() + 3.0
        threads = [threading.Thread(target=writer, args=(stop,))
                   for _ in range(2)] + \
            [threading.Thread(target=opener, args=(s, stop))
             for s in range(1, 5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(was)
    assert not lost, f"{len(lost)} resets undone by a concurrent write-back"
