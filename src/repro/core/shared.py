"""Cross-session shared cache tier (L2) with semantic result reuse.

The paper's metric cache is session-private, but its premise — temporal
and topical locality of conversational queries — holds *across* users at
scale: the same topics recur in many concurrent sessions, so the real
hit-rate ceiling is global.  ``SharedTier`` is that global tier: a
sharded, TTL'd embedding cache sitting between the per-session L1 caches
and the back-end router (probe order: L1 -> L2 -> back-end).

It is deliberately NOT a new cache implementation.  An L2 shard is one
row of the same stacked, tile-aligned ``CacheState`` the L1 tier uses,
driven by the same tier-agnostic ops (``repro.core.cache_ops``): the L2
probe is ``probe_batched`` (one fused ``cache_probe_batched`` launch over
the gathered shard rows), L2 answers come from ``query_batched``, and
admission inserts ride ``insert_batched`` — same kernels, same dispatch
tiers, no new kernel contract.  Shards use the beyond-paper LRU eviction
(``eviction="lru"``) because a shared tier, unlike a per-conversation
cache, must run indefinitely under churn.

Three mechanisms distinguish the tier from a big L1:

* **Shard routing.**  A query goes to ``argmax(psi @ R)`` for a fixed
  seeded Gaussian ``R`` (dim, n_shards) — a locality-sensitive split, so
  topically close queries from different sessions land in the same shard
  and see each other's promotions.

* **Admission policy.**  A back-end answer is *offered* to the tier, not
  inserted: per-document we count the distinct session tokens that
  retrieved it, and only when at least ``admission_frac`` of an answer's
  documents have been retrieved by >= ``admission_sessions`` distinct
  sessions is the whole answer — the (psi, r_a) coverage claim plus all
  k_c documents together — promoted.  Promoting the answer wholesale
  keeps the claim sound: a claim whose documents were partially admitted
  could serve a future hit from an incomplete document set.  One-off
  off-topic queries never clear the bar, so they cannot pollute the
  shared tier (the admission-control direction in ROADMAP).

  With a ``repro.core.cluster.ClusterIndex`` attached (``cluster=``), the
  popularity unit coarsens from the document to its *topical cluster*:
  distinct sessions are counted per cluster id, so two sessions
  retrieving different documents of the same topic still clear the bar
  together.  That matches how conversational reuse actually arrives —
  sessions share topics, rarely exact result sets — and lets the tier
  warm a topic after ``admission_sessions`` sessions touch it from any
  angle, while one-session topics still never promote.

* **Semantic result reuse.**  The tier memoizes recent
  ``(query embedding, top-k_c result)`` pairs from fresh back-end
  retrievals.  A near-duplicate query from ANOTHER session — cosine
  similarity >= ``memo_sim`` (embeddings are unit-norm after the Eq. 1
  transform, so the dot product IS the cosine) — is served the memoized
  result set directly, skipping the back-end entirely.  The similarity
  floor is calibrated against the rank-overlap quality gate (reused
  result sets must overlap >= 0.95 with fresh retrieval; gated in tests
  and ``check_regression``).  Reuse feeds admission too, with the
  triangle-corrected claim radius ``r_a - delta(psi_a, psi)`` — exactly
  the paper's Eq. 3 bound, so the promoted claim stays sound.

**TTL.**  Shared coverage claims go stale as the corpus and topic mix
drift, so every claim and memo entry carries the wave number when it was
recorded; ``tick()`` (called once per serving wave) retires claims older
than ``ttl_waves`` by restoring their ring slots' -inf radius sentinel.
Documents themselves are not TTL'd: a document embedding never goes
stale, claims do; cold documents age out through LRU eviction instead
(expiring a doc mid-array would also break the append-only occupied-
prefix invariant the insert positions rely on).

Host-side bookkeeping (admission counts, the memo ring, claim stamps) is
numpy; everything touching embeddings at scale is the shared kernel path.
``h2d_bytes`` / ``d2h_bytes`` count the ``nbytes`` of every array the tier
itself stages on the device or reads back (shard indices, expiry masks,
admitted answers, ring positions, dropped counts), so a caller can add
the tier's transfers to its own.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.core.cache_ops import (CacheConfig, CacheState, ProbeResult,
                                  init_batched_cache, insert_batched,
                                  probe_batched, query_batched)
from repro.kernels import dispatch as kdispatch

__all__ = ["SharedTier"]

_NEVER = -(2 ** 62)  # claim/memo stamp for "never written"


class SharedTier:
    """Sharded, TTL'd, cross-session L2 embedding cache + result memo."""

    def __init__(self, *, dim: int, n_shards: int = 4, capacity: int = 4096,
                 max_queries: int = 256, epsilon: float = 0.04,
                 ttl_waves: Optional[int] = 512,
                 admission_sessions: int = 2, admission_frac: float = 0.5,
                 admission_table_max: int = 1_000_000,
                 memo_size: int = 256, memo_sim: float = 0.995,
                 cluster=None,
                 dtype: Optional[str] = None, backend: Optional[str] = None,
                 seed: int = 0):
        self.cfg = CacheConfig(capacity=capacity, dim=dim,
                               max_queries=max_queries, epsilon=epsilon,
                               eviction="lru",
                               store_dtype=quant.resolve_dtype(dtype))
        self.n_shards = n_shards
        self.backend = kdispatch.resolve(backend)
        self.state: CacheState = init_batched_cache(self.cfg, n_shards)
        # locality-sensitive shard router: fixed so a topic always routes to
        # the same shard across sessions and process restarts
        self._router = np.random.default_rng(seed).standard_normal(
            (dim, n_shards)).astype(np.float32)
        self.ttl_waves = ttl_waves
        self.wave = 0
        # per-ring-slot wave stamp for claim TTL (host; (n_shards, Qp))
        qp = self.cfg.phys_max_queries
        self._claim_wave = np.full((n_shards, qp), _NEVER, np.int64)
        self._claim_alive = np.zeros((n_shards, qp), bool)
        # admission: popularity unit -> distinct session tokens (capped —
        # once the bar is met there is nothing more to learn).  The unit
        # is the doc id, or its topical cluster id when a ClusterIndex is
        # attached (cluster-aware admission; see module docstring).
        self.admission_sessions = admission_sessions
        self.admission_frac = admission_frac
        self.admission_table_max = admission_table_max
        self.cluster = cluster
        self._seen: dict[int, set] = {}
        self._pending: list[tuple] = []
        # semantic result memo: ring of (psi, ids, scores, r_a, token, wave)
        self.memo_size = memo_size
        self.memo_sim = memo_sim
        self._memo_psi: Optional[np.ndarray] = None   # (M, dim) f32
        self._memo_ids: Optional[np.ndarray] = None   # (M, k_c)
        self._memo_scores: Optional[np.ndarray] = None
        self._memo_radius = np.zeros((memo_size,), np.float32)
        self._memo_token: list = [None] * memo_size
        self._memo_wave = np.full((memo_size,), _NEVER, np.int64)
        self._memo_n = 0
        # counters (reported by serve_bench)
        self.n_promoted = 0          # answers admitted into the shard caches
        self.n_offered = 0
        self.n_memo_served = 0
        self.n_stale_served = 0      # memo serves under allow_stale outage
        self.total_dropped = 0
        self.h2d_bytes = 0           # staged on the device (monotone)
        self.d2h_bytes = 0           # read back from it (monotone)

    # ---------------------------------------------------------------- waves

    def tick(self) -> None:
        """Advance the wave clock; retire coverage claims past their TTL by
        restoring the ring slot's -inf radius sentinel (the document
        payload stays — embeddings don't go stale, claims do)."""
        self.wave += 1
        if self.ttl_waves is None:
            return
        stale = np.logical_and(
            self._claim_alive,
            self.wave - self._claim_wave > self.ttl_waves)
        if stale.any():
            mask = jnp.asarray(stale)
            self.h2d_bytes += mask.nbytes
            self.state = self.state._replace(
                q_radius=jnp.where(mask, -jnp.inf, self.state.q_radius))
            self._claim_alive[stale] = False

    # -------------------------------------------------------------- routing

    def route(self, psi: np.ndarray) -> np.ndarray:
        """Shard index per query row: argmax over the fixed Gaussian
        projections (locality-sensitive — near-duplicate queries always
        agree on the shard)."""
        return np.argmax(np.asarray(psi, np.float32) @ self._router, axis=1)

    def _gather(self, shards: np.ndarray) -> CacheState:
        idx = jnp.asarray(shards)
        self.h2d_bytes += idx.nbytes
        return jax.tree_util.tree_map(lambda x: x[idx], self.state)

    def _scatter(self, shards: np.ndarray, sub: CacheState) -> None:
        idx = jnp.asarray(shards)
        self.h2d_bytes += idx.nbytes
        self.state = jax.tree_util.tree_map(
            lambda full, part: full.at[idx].set(part), self.state, sub)

    # ------------------------------------------------------------ probe path

    def probe_rows(self, psi, shards: np.ndarray,
                   backend: Optional[str] = None) -> ProbeResult:
        """The L2 LowQuality test for a wave: one ``cache_probe_batched``
        launch over the gathered shard rows (duplicate shards in one wave
        just gather the same row twice — the probe is read-only)."""
        sub = self._gather(shards)
        return probe_batched(sub, psi, self.cfg.epsilon,
                             backend=backend or self.backend,
                             max_queries=self.cfg.max_queries)

    def query_rows(self, psi, shards: np.ndarray, k: int,
                   backend: Optional[str] = None):
        """Top-k cached docs per wave row from its shard (one fused launch).
        LRU touches are scattered back best-effort; when one wave queries
        the same shard twice, one row's stamp refresh wins — acceptable
        for an eviction heuristic, and the payload is read-only."""
        assert k <= self.cfg.capacity, "L2 answer k exceeds shard capacity"
        sub = self._gather(shards)
        out, sub = query_batched(sub, psi, k, backend=backend or self.backend)
        self._scatter(shards, sub)
        return out

    # ------------------------------------------------------------- admission

    def offer(self, token, psi, radius: float, emb, ids) -> bool:
        """Offer one back-end (or reused) answer for promotion.

        Counts ``token`` toward every document in the answer; when at
        least ``admission_frac`` of the answer's documents have been
        retrieved by >= ``admission_sessions`` distinct sessions, the
        WHOLE answer — claim and documents together — is queued for
        promotion (flushed at end of wave by ``flush_admissions`` so
        admission never adds launches to the serving wave itself).
        Returns whether the answer was queued.
        """
        ids = np.asarray(ids)
        real = ids >= 0
        if not real.any():
            return False
        self.n_offered += 1
        if len(self._seen) > self.admission_table_max:
            # coarse pressure valve: restart the popularity counts rather
            # than let the host table grow without bound
            self._seen.clear()
        if self.cluster is not None:
            # cluster-aware: vote once per distinct topical cluster, then
            # count a doc promotable iff its CLUSTER cleared the bar
            # (out-of-corpus ids fall back to per-doc keys, negated so
            # they can never collide with cluster ids)
            cids = self.cluster.cluster_of(ids[real])
            keys = [int(c) if c >= 0 else -(int(d) + 1)
                    for c, d in zip(cids, ids[real])]
            for ck in set(keys):
                s = self._seen.setdefault(ck, set())
                if len(s) < self.admission_sessions:
                    s.add(token)
            promotable = sum(
                1 for ck in keys
                if len(self._seen[ck]) >= self.admission_sessions)
        else:
            promotable = 0
            for d in ids[real].tolist():
                s = self._seen.setdefault(d, set())
                if len(s) < self.admission_sessions:
                    s.add(token)
                if len(s) >= self.admission_sessions:
                    promotable += 1
        if promotable < self.admission_frac * int(real.sum()):
            return False
        shard = int(self.route(np.asarray(psi, np.float32)[None])[0])
        self._pending.append((shard, np.asarray(psi, np.float32),
                              float(radius), np.asarray(emb),
                              ids.astype(np.int32)))
        return True

    def flush_admissions(self, backend: Optional[str] = None) -> int:
        """Insert the wave's admitted answers into their shards.

        Answers bound for distinct shards batch into one
        ``insert_batched`` launch; same-shard answers split into ordered
        launches.  Claim ring slots are wave-stamped for TTL.
        """
        pending, self._pending = self._pending, []
        promoted = 0
        while pending:
            seen: set = set()
            now, later = [], []
            for p in pending:
                (now if p[0] not in seen else later).append(p)
                seen.add(p[0])
            self._insert(now, len(now[0][4]), backend)
            promoted += len(now)
            pending = later
        self.n_promoted += promoted
        return promoted

    def warm(self, *, k: int, k_c: int, rows, backend: Optional[str] = None
             ) -> None:
        """Compile, before serving, the programs a wave runs here only once
        the tier holds claims: the top-``k`` answer query at each wave size
        in ``rows`` and the admission flush of ``k_c``-doc answers.  The
        tier's state is left as it was."""
        state = self.state
        for b in rows:
            psi = np.zeros((b, self.cfg.dim), np.float32)
            self.query_rows(psi, self.route(psi), k, backend=backend)
        self._insert([], k_c, backend)
        self.state = state

    def _insert(self, answers: list, k_c: int,
                backend: Optional[str]) -> None:
        """One ``insert_batched`` launch over the whole stacked state, one
        row per shard.  ``answers`` holds at most one pending answer per
        shard; every other row passes ``do=False`` and keeps its state
        bit-identically, so each flush runs the same program whatever the
        number of admitted answers."""
        n, dim = self.n_shards, self.cfg.dim
        psi = np.zeros((n, dim), np.float32)
        radius = np.zeros((n,), np.float32)
        emb = np.zeros((n, k_c, dim), np.float32)
        ids = np.full((n, k_c), -1, np.int32)
        do = np.zeros((n,), bool)
        for shard, p, r, e, i in answers:
            psi[shard], radius[shard], emb[shard], ids[shard] = p, r, e, i
            do[shard] = True
        n_queries = np.asarray(self.state.n_queries)
        slots = n_queries % self.cfg.max_queries
        self.state, dropped = insert_batched(
            self.state, self.cfg, psi, radius, emb, ids, do=do,
            backend=backend or self.backend)
        dropped = np.asarray(dropped)
        self.h2d_bytes += sum(x.nbytes for x in (psi, radius, emb, ids, do))
        self.d2h_bytes += n_queries.nbytes + dropped.nbytes
        shards = np.nonzero(do)[0]
        self._claim_wave[shards, slots[shards]] = self.wave
        self._claim_alive[shards, slots[shards]] = True
        self.total_dropped += int(dropped.sum())

    # ------------------------------------------------------------ result memo

    def memo_record(self, token, psi, ids, scores, radius: float) -> None:
        """Memoize one fresh retrieval's full (psi, top-k_c) result set."""
        psi = np.asarray(psi, np.float32)
        ids = np.asarray(ids)
        scores = np.asarray(scores, np.float32)
        if self._memo_psi is None:
            self._memo_psi = np.zeros((self.memo_size, psi.shape[-1]),
                                      np.float32)
            self._memo_ids = np.full((self.memo_size, ids.shape[-1]), -1,
                                     np.int64)
            self._memo_scores = np.full((self.memo_size, ids.shape[-1]),
                                        -np.inf, np.float32)
        slot = self._memo_n % self.memo_size
        self._memo_psi[slot] = psi
        self._memo_ids[slot] = ids
        self._memo_scores[slot] = scores
        self._memo_radius[slot] = radius
        self._memo_token[slot] = token
        self._memo_wave[slot] = self.wave
        self._memo_n += 1

    def memo_lookup(self, token, psi, *, allow_stale: bool = False):
        """Serve a near-duplicate query from another session's memoized
        result set, or None.

        Gates: cosine similarity >= ``memo_sim`` (the quality floor
        calibrated against the rank-overlap gate), the entry is from a
        DIFFERENT session (a same-session near-duplicate is the L1 tier's
        job), and the entry is fresher than ``ttl_waves``.
        Returns ``(ids, scores, claim_radius)`` where ``claim_radius`` is
        the triangle-corrected ``r_a - delta(psi_a, psi)`` (Eq. 3) the
        caller may soundly record as its own coverage claim.

        ``allow_stale`` is the stale-while-error mode the engine uses
        when the back end is fenced off: the TTL and other-session gates
        are waived (any written entry qualifies — stale results beat no
        results during an outage), but the similarity floor is NOT —
        staleness is about time, never about serving the wrong topic.
        Callers must treat a stale serve as degraded and never record
        its claim.
        """
        if self._memo_psi is None:
            return None
        psi = np.asarray(psi, np.float32)
        fresh = (self._memo_wave != _NEVER
                 if (allow_stale or self.ttl_waves is None)
                 else self.wave - self._memo_wave <= self.ttl_waves)
        other = np.array([t is not None and (allow_stale or t != token)
                          for t in self._memo_token])
        valid = np.logical_and(fresh, other)
        if not valid.any():
            return None
        sims = self._memo_psi @ psi  # unit-norm embeddings: dot == cosine
        sims = np.where(valid, sims, -np.inf)
        best = int(np.argmax(sims))
        if sims[best] < self.memo_sim:
            return None
        self.n_memo_served += 1
        if allow_stale:
            self.n_stale_served += 1
        delta = float(np.sqrt(max(2.0 - 2.0 * float(sims[best]), 0.0)))
        claim = float(self._memo_radius[best]) - delta
        return (self._memo_ids[best].copy(),
                self._memo_scores[best].copy(), claim)

    # ------------------------------------------------------------- inspection

    def contains(self, doc_ids) -> np.ndarray:
        """Membership of each id in ANY shard's cached documents (tests)."""
        cached = np.asarray(self.state.doc_ids).ravel()
        cached = cached[cached >= 0]
        return np.isin(np.asarray(doc_ids), cached)

    @property
    def n_docs(self) -> np.ndarray:
        return np.asarray(self.state.n_docs)

    def memory_bytes(self) -> int:
        s = self.state
        return sum(int(x.size) * x.dtype.itemsize for x in
                   (s.doc_emb, s.doc_ids, s.doc_stamp, s.q_emb, s.q_radius,
                    s.doc_scale, s.q_scale))
