"""Exact nearest-neighbor metric index — the FAISS ``IndexFlat`` analogue.

The back-end of the paper's architecture (Fig. 2): the whole collection's
transformed embeddings, answering ``NN(M, psi, k)`` queries exactly.

``scan_topk`` is THE corpus-scan contract: one signature, one sentinel
convention (id -1 rows masked out, -inf result positions carry id -1),
dispatched across the ``repro.kernels.dispatch`` tiers —

  * ``ref``       — ``streaming_topk``: a ``lax.scan`` over corpus chunks
                    with a running top-k carry (peak memory O(B*chunk));
                    the production path on CPU and the oracle in tests.
  * ``interpret`` / ``compiled`` — the fused Pallas scan+top-k
                    (``kernels.knn``) with its cross-tile merge on chip.

``MetricIndex.search``, the per-shard body of ``dist.retrieval.sharded_nn``,
and ``dist.retrieval.DeviceShard`` all route through it, so single-device
and device-sharded search share one scan implementation.  ``exact_nn``
remains the one-shot full-matrix oracle for small corpora.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import embedding as emb
from repro.core import quant
from repro.kernels import dispatch as kdispatch

__all__ = ["SearchResult", "exact_nn", "chunked_nn", "masked_chunked_nn",
           "streaming_topk", "scan_topk", "MetricIndex"]


class SearchResult(NamedTuple):
    scores: jax.Array     # (q, k) inner products, descending
    distances: jax.Array  # (q, k) Euclidean distances, ascending
    ids: jax.Array        # (q, k) int32 document ids


def _as_result(scores: jax.Array, ids: jax.Array) -> SearchResult:
    return SearchResult(scores, emb.distance_from_scores(scores), ids)


def exact_nn(docs: jax.Array, doc_ids: jax.Array, queries: jax.Array, k: int) -> SearchResult:
    """Reference exact k-NN: materializes the full (q, n) score matrix."""
    scores = emb.pairwise_scores(queries, docs)
    top_scores, top_idx = jax.lax.top_k(scores, k)
    return _as_result(top_scores, doc_ids[top_idx])


def streaming_topk(docs: jax.Array, doc_ids: jax.Array, queries: jax.Array,
                   k: int, chunk: int, masked: bool = False,
                   scale: jax.Array | None = None, int8_dot: bool = False):
    """Raw streaming top-k scan shared by ``chunked_nn``, the padded-corpus
    index path, and ``dist.retrieval``'s per-shard search.

    Scans corpus chunks with a running (scores, ids) carry; peak live memory
    is O(q*chunk + q*k).  ``n`` must be a multiple of ``chunk``.  When
    ``masked`` (static), rows with sentinel id < 0 score -inf, so padded
    corpora never win top-k.  ``docs`` may be a quantized payload (bf16 /
    int8) with ``scale`` its (n,) f32 per-document score multiplier —
    dequantization is chunk-local (payload cast to f32, f32 dot, score-side
    scale), the same rule the Pallas tiers apply per tile, so peak memory
    stays O(q*chunk) and tiers agree.  ``int8_dot`` (int8 payloads only)
    switches to the native-narrow scoring rule of the kernel tiers: the
    queries quantize per-row to int8 once, each chunk's dot runs int8 x
    int8 with int32 accumulation, and both fp32 scales apply score-side in
    the kernels' association order — the ref tier of the int8-MXU path.
    Returns (scores (q, k), ids (q, k)).
    """
    n = docs.shape[0]
    assert n % chunk == 0, f"corpus size {n} not divisible by chunk {chunk}"
    int8_dot = bool(int8_dot) and docs.dtype == jnp.int8
    docs_c = docs.reshape(n // chunk, chunk, docs.shape[1])
    ids_c = doc_ids.reshape(n // chunk, chunk)
    scale_c = (None if scale is None else
               scale.astype(jnp.float32).reshape(n // chunk, chunk))
    q = queries.shape[0]
    queries = queries.astype(jnp.float32)
    if int8_dot:
        qq = quant.quantize(queries, "int8")
        q_payload, q_scale_col = qq.data, qq.scale[:, None]

    init = (jnp.full((q, k), -jnp.inf, queries.dtype),
            jnp.full((q, k), -1, jnp.int32))

    def step(carry, chunk_data):
        best_s, best_i = carry
        cd, ci, cs = chunk_data
        if int8_dot:
            acc = jax.lax.dot_general(
                q_payload, cd, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)                # (q, chunk)
            scores = acc.astype(jnp.float32) * q_scale_col
        else:
            scores = jnp.matmul(queries, cd.astype(jnp.float32).T,
                                precision=jax.lax.Precision.HIGHEST)
        scores = quant.scale_scores(scores, cs)
        if masked:
            scores = jnp.where(ci[None, :] < 0, -jnp.inf, scores)
        cand_s = jnp.concatenate([best_s, scores], axis=1)
        cand_i = jnp.concatenate([best_i, jnp.broadcast_to(ci, (q, chunk))], axis=1)
        top_s, top_pos = jax.lax.top_k(cand_s, k)
        top_i = jnp.take_along_axis(cand_i, top_pos, axis=1)
        return (top_s, top_i), None

    xs = (docs_c, ids_c, scale_c)
    if scale_c is None:
        xs = (docs_c, ids_c)
        step_fn = lambda c, x: step(c, (x[0], x[1], None))
    else:
        step_fn = step
    (best_s, best_i), _ = jax.lax.scan(step_fn, init, xs)
    return best_s, best_i


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def chunked_nn(docs: jax.Array, doc_ids: jax.Array, queries: jax.Array, k: int,
               chunk: int = 4096) -> SearchResult:
    """Streaming exact k-NN over an unpadded corpus (see ``streaming_topk``)."""
    return _as_result(*streaming_topk(docs, doc_ids, queries, k, chunk))


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def masked_chunked_nn(docs: jax.Array, doc_ids: jax.Array, queries: jax.Array,
                      k: int, chunk: int = 4096) -> SearchResult:
    """``chunked_nn`` over a sentinel-padded corpus (id < 0 rows masked)."""
    return _as_result(*streaming_topk(docs, doc_ids, queries, k, chunk,
                                      masked=True))


def scan_topk(docs: jax.Array, doc_ids: jax.Array, queries: jax.Array, k: int,
              *, chunk: int = 4096, backend: str | None = None,
              tile_n: int | None = None, scale: jax.Array | None = None,
              int8_dot: bool | None = None):
    """The one corpus-scan contract (see module docstring).

    docs (N, D) with N a ``chunk`` multiple on the ref tier (the kernel
    tiers pad internally) — fp32, or a quantized payload (bf16 / int8,
    ``repro.core.quant``) with ``scale`` its (N,) f32 per-document score
    multiplier; doc_ids (N,) int32, -1 on sentinel rows; queries (B, D)
    f32.  Returns raw (scores (B, k), ids (B, k)) — descending scores,
    sentinel id -1 wherever the score is -inf — identical in ranking
    across tiers at a fixed dtype (rank equality vs the fp32 corpus is
    tolerance-bound; see tests/test_kernel_equivalence.py).  ``int8_dot``
    (None = the ``REPRO_INT8_DOT`` policy; int8 corpora only) switches
    every tier to the native int8-MXU scoring rule — tiers still agree
    with each other exactly, rankings vs fp32 are gated at the int8 floor.
    Trace-safe: usable inside jit and ``shard_map`` bodies (``backend``
    must then be a concrete tier, resolved outside).
    """
    be = kdispatch.resolve(backend)
    use_i8 = quant.resolve_int8_dot(int8_dot, docs.dtype)
    if be == "ref":
        return _streaming_topk_masked(docs, doc_ids, queries, scale, k=k,
                                      chunk=chunk, int8_dot=use_i8)
    from repro.kernels.knn import ops as knn_ops
    return knn_ops.knn_search(docs, doc_ids, queries, k, tile_n=tile_n,
                              backend=be, scale=scale, int8_dot=use_i8)


_streaming_topk_masked = jax.jit(
    lambda docs, doc_ids, queries, scale, *, k, chunk, int8_dot: (
        streaming_topk(docs, doc_ids, queries, k, chunk, masked=True,
                       scale=scale, int8_dot=int8_dot)),
    static_argnames=("k", "chunk", "int8_dot"))


class MetricIndex:
    """Host-side handle over a (possibly padded) corpus of transformed embeddings.

    Accepts *raw* (l-dim) or *transformed* (l+1-dim, unit norm) embeddings.
    Raw input is transformed with Eq. 1 and the corpus max-norm M is kept so
    queries/documents added later share the same geometry.

    ``use_kernel`` selects the scan tier: ``None`` (default) follows
    ``kernels.dispatch.default_backend()`` — the compiled Pallas kernel on
    TPU, the jnp streaming scan elsewhere; ``True`` pins the kernel
    (interpret mode off-TPU); ``False`` pins the jnp scan.

    ``dtype`` selects the corpus storage format (``repro.core.quant``):
    ``None`` follows ``quant.default_dtype()`` (the ``REPRO_CORPUS_DTYPE``
    policy, fp32 when unset); "bf16" / "int8" store the corpus quantized —
    2x / 4x more documents per HBM byte through the bandwidth-bound scan —
    and every tier dequantizes with the shared score-side-scale rule, so
    rankings stay tier-identical at the chosen dtype.
    """

    def __init__(self, doc_emb, doc_ids=None, *, transformed: bool = False,
                 chunk: int = 4096, use_kernel: bool | None = None,
                 sharded: bool = False, mesh=None, dtype: str | None = None,
                 int8_dot: bool | None = None):
        doc_emb = jnp.asarray(doc_emb)
        if doc_ids is None:
            doc_ids = jnp.arange(doc_emb.shape[0], dtype=jnp.int32)
        doc_ids = jnp.asarray(doc_ids, jnp.int32)
        if transformed:
            self.max_norm = jnp.asarray(1.0, doc_emb.dtype)
            emb_t = doc_emb
        else:
            emb_t, self.max_norm = emb.transform_documents(doc_emb)
        self.dim = emb_t.shape[1]
        self.n_docs = int(emb_t.shape[0])
        self.chunk = int(min(chunk, max(8, self.n_docs)))
        # Pad to a chunk multiple with sentinels that can never win top-k:
        # zero vectors (score 0 with any query is beaten by any real doc on the
        # unit sphere only if scores > 0; use id -1 + -inf masking instead).
        pad = (-self.n_docs) % self.chunk
        if pad:
            emb_t = jnp.concatenate([emb_t, jnp.zeros((pad, self.dim), emb_t.dtype)])
            doc_ids = jnp.concatenate([doc_ids, jnp.full((pad,), -1, jnp.int32)])
        self._pad = pad
        self.dtype = quant.resolve_dtype(dtype)
        qc = quant.quantize(emb_t, self.dtype)
        self.doc_emb = qc.data
        self.doc_scale = qc.scale
        self.doc_ids = doc_ids
        # int8-MXU-dot policy pinned at construction (None follows
        # REPRO_INT8_DOT) so every search over this index scores one way
        self.int8_dot = quant.resolve_int8_dot(int8_dot, self.doc_emb.dtype)
        self.use_kernel = use_kernel
        if use_kernel is None:
            self.backend = kdispatch.default_backend()
        elif use_kernel:
            self.backend = kdispatch.kernel_backend()
        else:
            self.backend = "ref"
        self.sharded = sharded
        self.mesh = mesh
        if sharded:
            # Lay the corpus out across the mesh once at construction so
            # every search hits the shard_map fast path (no per-query pad
            # or host->mesh re-layout).
            from repro.dist import retrieval as dist_retrieval
            (self.doc_emb, self.doc_ids, self.doc_scale, self.mesh,
             self._shard_chunk) = dist_retrieval.shard_corpus(
                self.doc_emb, self.doc_ids, scale=self.doc_scale, mesh=mesh,
                chunk=self.chunk)

    def transform_queries(self, psi: jax.Array) -> jax.Array:
        return emb.transform_queries(psi)

    def search(self, queries: jax.Array, k: int) -> SearchResult:
        """queries: (q, l+1) transformed embeddings."""
        if queries.ndim == 1:
            queries = queries[None]
        k = min(k, self.n_docs)
        if self.sharded:
            # Device-sharded corpus: the same scan per shard under
            # shard_map, all-gather + merge (see repro.dist.retrieval).
            from repro.dist import retrieval as dist_retrieval
            return dist_retrieval.sharded_nn(self.doc_emb, self.doc_ids,
                                             queries, k, mesh=self.mesh,
                                             chunk=self._shard_chunk,
                                             backend=self.backend,
                                             scale=self.doc_scale,
                                             int8_dot=self.int8_dot)
        return _as_result(*scan_topk(self.doc_emb, self.doc_ids, queries, k,
                                     chunk=self.chunk, backend=self.backend,
                                     scale=self.doc_scale,
                                     int8_dot=self.int8_dot))

    def cluster(self, n_clusters: int = 64, *, iters: int = 10, seed: int = 0,
                max_width: int = 256, backend: str | None = None, path=None):
        """Build (and memoize) a topical ``ClusterIndex`` over this corpus.

        Parameters mirror ``repro.core.cluster.build_cluster_index``.
        ``path`` persists the artifact: an existing ``.npz`` at ``path`` is
        loaded instead of rebuilding, otherwise the fresh index is saved
        there.  Builds are memoized per parameter tuple — the corpus is
        immutable after construction, so a rebuild can never differ.
        """
        import os

        from repro.core.cluster import ClusterIndex, build_cluster_index
        key = (int(n_clusters), int(iters), int(seed), int(max_width), backend)
        memo = getattr(self, "_clusters", None)
        if memo is None:
            memo = self._clusters = {}
        if key not in memo:
            if path is not None and os.path.exists(path):
                memo[key] = ClusterIndex.load(path)
            else:
                memo[key] = build_cluster_index(
                    self, n_clusters, iters=iters, seed=seed,
                    max_width=max_width, backend=backend)
                if path is not None:
                    memo[key].save(path)
        return memo[key]

    def dequantized(self) -> jax.Array:
        """f32 view of the (padded) transformed corpus — the exact values
        every scan tier scores against.  Host-side tooling (benchmark shard
        construction, engine doc-embedding lookups) should use this rather
        than ``doc_emb``, whose dtype follows the storage policy.  The view
        is memoized: the corpus is immutable after construction."""
        if getattr(self, "_dequant", None) is None:
            self._dequant = quant.dequantize(
                quant.QuantizedCorpus(self.doc_emb, self.doc_scale,
                                      self.dtype))
        return self._dequant

    def __hash__(self):  # allow use as a static jit argument
        return id(self)

    def __eq__(self, other):
        return self is other
