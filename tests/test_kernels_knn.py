"""Interpret-mode validation of the fused kNN kernel vs. the pure-jnp oracle.

Shape x dtype sweep per the kernel-testing contract. Tie-handling: scores are
compared with allclose; ids are compared as top-k *sets* scored identically
(argmax tie order may legally differ between kernel and lax.top_k).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.knn.ops import knn_search
from repro.kernels.knn.ref import knn_ref

jax.config.update("jax_platform_name", "cpu")

pytestmark = pytest.mark.kernels  # fast CI kernel gate: pytest -m kernels


def _check(docs, queries, k, tile_n=256):
    ids = jnp.arange(docs.shape[0], dtype=jnp.int32)
    s_k, i_k = knn_search(docs, ids, queries, k, tile_n=tile_n, interpret=True)
    s_r, i_r = knn_ref(docs, queries, k)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=2e-5, atol=2e-5)
    # id agreement where scores are unique per row
    sk, sr = np.asarray(s_k), np.asarray(s_r)
    ik, ir = np.asarray(i_k), np.asarray(i_r)
    for b in range(sk.shape[0]):
        uniq = np.concatenate([[True], np.abs(np.diff(sr[b])) > 1e-5])
        run_ok = uniq & np.append(uniq[1:], True)  # not part of any tie run
        np.testing.assert_array_equal(ik[b][run_ok], ir[b][run_ok])
        assert set(ik[b]) == set(ir[b]) or np.allclose(sorted(sk[b]), sorted(sr[b]), atol=2e-5)


@pytest.mark.parametrize("n,d,b,k", [
    (1000, 769, 4, 10),       # paper geometry: STAR 768(+1)-d
    (4096, 128, 16, 64),
    (300, 32, 1, 5),          # ragged corpus, single query
    (257, 65, 3, 17),         # nothing aligned
    (512, 256, 8, 128),       # k == tile limit region
])
def test_knn_matches_ref_f32(n, d, b, k):
    rng = np.random.default_rng(n + d + b + k)
    docs = rng.standard_normal((n, d)).astype(np.float32)
    docs /= np.linalg.norm(docs, axis=1, keepdims=True)
    q = rng.standard_normal((b, d)).astype(np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    _check(jnp.asarray(docs), jnp.asarray(q), k)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_knn_dtypes(dtype):
    rng = np.random.default_rng(0)
    docs = rng.standard_normal((512, 64)).astype(np.float32)
    q = rng.standard_normal((4, 64)).astype(np.float32)
    ids = jnp.arange(512, dtype=jnp.int32)
    s_k, i_k = knn_search(jnp.asarray(docs, dtype), ids, jnp.asarray(q, dtype),
                          8, tile_n=128, interpret=True)
    s_r, i_r = knn_ref(jnp.asarray(docs, dtype), jnp.asarray(q, dtype), 8)
    # bf16 inputs, f32 accumulate in both paths
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=1e-2, atol=1e-2)
    assert (np.asarray(i_k) == np.asarray(i_r)).mean() > 0.9


def test_knn_k_larger_than_tile():
    """k > tile_n: every tile emits all rows; merge must still be exact."""
    rng = np.random.default_rng(3)
    docs = rng.standard_normal((256, 32)).astype(np.float32)
    q = rng.standard_normal((2, 32)).astype(np.float32)
    ids = jnp.arange(256, dtype=jnp.int32)
    s_k, i_k = knn_search(jnp.asarray(docs), ids, jnp.asarray(q), 100,
                          tile_n=64, interpret=True)
    s_r, i_r = knn_ref(jnp.asarray(docs), jnp.asarray(q), 100)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=2e-5, atol=2e-5)


def test_knn_property_monotone_scores():
    """Property: returned scores are descending and are true inner products."""
    rng = np.random.default_rng(9)
    docs = rng.standard_normal((777, 48)).astype(np.float32)
    q = rng.standard_normal((5, 48)).astype(np.float32)
    ids = jnp.arange(777, dtype=jnp.int32)
    s, i = knn_search(jnp.asarray(docs), ids, jnp.asarray(q), 20, interpret=True)
    s, i = np.asarray(s), np.asarray(i)
    assert (np.diff(s, axis=1) <= 1e-6).all()
    recomputed = np.take_along_axis(q @ docs.T, i, axis=1)
    np.testing.assert_allclose(s, recomputed, rtol=1e-5, atol=1e-5)


def test_metric_index_kernel_path_agrees():
    from repro.core.metric_index import MetricIndex
    rng = np.random.default_rng(4)
    raw = rng.standard_normal((900, 64)).astype(np.float32)
    idx_ref = MetricIndex(jnp.asarray(raw))
    idx_ker = MetricIndex(jnp.asarray(raw), use_kernel=True)
    q = idx_ref.transform_queries(jnp.asarray(rng.standard_normal((6, 64)).astype(np.float32)))
    r1 = idx_ref.search(q, 15)
    r2 = idx_ker.search(q, 15)
    np.testing.assert_allclose(np.asarray(r1.scores), np.asarray(r2.scores),
                               rtol=1e-5, atol=1e-5)


# --- the fused kernel's threshold-gated merge, held to the ref tier exactly.
# Integer-valued corpora and queries make every dot product exact in f32,
# so the two tiers must agree bit for bit on scores and ids whatever order
# they sum in; ties then resolve to the earliest corpus position on both.

ORDERS = ("shuffled", "ascending", "descending", "topic_blocked")
TILE = 2048


def _int_world(order, n, d, b, seed, span=40):
    """(docs (n, d), queries (b, d)) f32 holding small integers, the corpus
    laid out in ``order`` of its score against query 0 — or, for
    ``topic_blocked``, in contiguous topic blocks as the benchmark's world
    draws them."""
    rng = np.random.default_rng(seed)
    if order == "topic_blocked":
        block = n // 8
        centres = rng.integers(-span, span + 1, (8, d))
        docs = np.repeat(centres, block, axis=0) + rng.integers(
            -span // 4, span // 4 + 1, (n, d))
        q = centres[rng.integers(0, 8, b)] + rng.integers(-2, 3, (b, d))
        return docs.astype(np.float32), q.astype(np.float32)
    docs = rng.integers(-span, span + 1, (n, d)).astype(np.float32)
    q = rng.integers(-span, span + 1, (b, d)).astype(np.float32)
    key = docs @ q[0]
    if order == "ascending":
        docs = docs[np.argsort(key, kind="stable")]
    elif order == "descending":
        docs = docs[np.argsort(-key, kind="stable")]
    return docs, q


def _assert_tiers_equal(docs, ids, q, k, tile_n=TILE, **kw):
    s_i, i_i = knn_search(jnp.asarray(docs), jnp.asarray(ids), jnp.asarray(q),
                          k, tile_n=tile_n, backend="interpret", **kw)
    s_r, i_r = knn_search(jnp.asarray(docs), jnp.asarray(ids), jnp.asarray(q),
                          k, backend="ref", **kw)
    np.testing.assert_array_equal(np.asarray(s_i), np.asarray(s_r))
    np.testing.assert_array_equal(np.asarray(i_i), np.asarray(i_r))


@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("k,b", [(10, 8), (1000, 32), (2000, 8),
                                 (TILE + 452, 1)])        # last: k > tile_n
def test_fused_merge_equals_ref_by_corpus_order(order, k, b):
    docs, q = _int_world(order, 8 * TILE, 64, b, seed=k + b)
    _assert_tiers_equal(docs, np.arange(len(docs), dtype=np.int32), q, k)


@pytest.mark.parametrize("k", [10, 1000, 2000])
def test_fused_merge_duplicates_across_a_tile_boundary(k):
    """Copies of the best documents straddle tile boundaries and fall on
    both sides of the k-th place: the earliest copies win, in order."""
    docs, q = _int_world("shuffled", 4 * TILE, 64, 8, seed=k)
    top = np.argsort(-(docs @ q.T), axis=0, kind="stable")[k - 1]  # k-th
    for j, t in enumerate((TILE, 2 * TILE, 3 * TILE)):
        docs[t - 3:t + 3] = docs[top[j]]              # 3 each side
    _assert_tiers_equal(docs, np.arange(len(docs), dtype=np.int32), q, k)


@pytest.mark.parametrize("k,b", [(10, 1), (1000, 8), (2000, 32)])
def test_fused_merge_ties_at_the_kth_value(k, b):
    """Four features of {-1, 0, 1}: a few dozen distinct scores over 8,192
    documents, so hundreds tie at the k-th value of every row."""
    rng = np.random.default_rng(k)
    docs = rng.integers(-1, 2, (4 * TILE, 4)).astype(np.float32)
    q = rng.integers(-1, 2, (b, 4)).astype(np.float32)
    q[:, 0] = 1.0                                     # no all-zero query
    _assert_tiers_equal(docs, np.arange(len(docs), dtype=np.int32), q, k)


@pytest.mark.parametrize("int8_dot", [False, True])
@pytest.mark.parametrize("order", ["shuffled", "topic_blocked"])
def test_fused_merge_int8_equals_ref(order, int8_dot):
    from repro.core import quant
    docs, q = _int_world(order, 4 * TILE, 64, 8, seed=5)
    qc = quant.quantize(jnp.asarray(docs), "int8")
    _assert_tiers_equal(np.asarray(qc.data), np.arange(len(docs),
                                                       dtype=np.int32),
                        q, 1000, scale=qc.scale, int8_dot=int8_dot)


@pytest.mark.parametrize("k", [10, 1000, 1600])       # last: > 1,536 valid
def test_fused_merge_all_sentinel_tile(k):
    """Tile 1 is all sentinel rows (id -1): none of it is ever returned,
    and past the valid documents the answer is -inf with id -1."""
    tile_n = 512
    docs, q = _int_world("descending", 4 * tile_n, 64, 8, seed=k)
    ids = np.arange(len(docs), dtype=np.int32)
    ids[tile_n:2 * tile_n] = -1
    _assert_tiers_equal(docs, ids, q, k, tile_n=tile_n)
    s, i = knn_search(jnp.asarray(docs), jnp.asarray(ids), jnp.asarray(q), k,
                      tile_n=tile_n, backend="interpret")
    i = np.asarray(i)
    assert not np.isin(i, np.arange(tile_n, 2 * tile_n)).any()
    assert ((i == -1) == np.isneginf(np.asarray(s))).all()


def _fused_steps(docs, q, k, tile_n):
    from repro.kernels.knn.knn import knn_fused_topk
    ids = jnp.arange(docs.shape[0], dtype=jnp.int32)
    return int(knn_fused_topk(jnp.asarray(docs), ids, jnp.asarray(q), k,
                              tile_n=tile_n, interpret=True)[2])


@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("k", [100, 300])                 # 300 > tile_n
def test_fused_merge_steps_bounded(order, k):
    """The merge never runs more than min(k, tile_n) steps a tile; on an
    ascending corpus (one query, distinct scores) it runs exactly that,
    on a descending one only the fill of the first tiles."""
    tile_n, tiles = 256, 16
    rng = np.random.default_rng(k)
    docs, q = _int_world(order, tiles * tile_n, 128, 1, seed=k)
    docs = docs + rng.uniform(-0.25, 0.25, docs.shape).astype(np.float32)
    key = docs @ q[0]
    if order in ("ascending", "descending"):               # re-sort, no ties
        docs = docs[np.argsort(key if order == "ascending" else -key)]
    steps = _fused_steps(docs, q, k, tile_n)
    bound = tiles * min(k, tile_n)
    assert 0 < steps <= bound
    if order == "ascending":
        assert steps == bound
    if order == "descending":
        assert steps == -(-k // tile_n) * min(k, tile_n)


def test_fused_merge_steps_on_a_shuffled_corpus_are_few():
    """Shuffled documents: after tile 0 only the few scores that beat the
    running k-th best are merged, under 5% of tiles * k steps."""
    tile_n, tiles, k = 256, 512, 100
    rng = np.random.default_rng(0)
    docs = rng.standard_normal((tiles * tile_n, 128)).astype(np.float32)
    q = rng.standard_normal((8, 128)).astype(np.float32)
    steps = _fused_steps(docs, q, k, tile_n)
    assert k <= steps < 0.05 * tiles * k
