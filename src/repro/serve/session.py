"""Session-batched serving: many concurrent conversational sessions per wave.

The single-session ``ConversationalEngine`` pays one encoder call, one cache
probe, one router round-trip, and one cache query *per turn*.  Under heavy
traffic the same work batches: ``BatchedEngine`` holds one stacked
``CacheState`` for S session slots and answers a wave of concurrent turns
with

  * one (batched) encoder call,
  * one ``probe_batched`` over the wave's cache slices,
  * one ``router.search`` for the whole miss subset (the paper batches 216
    queries into FAISS for the same reason), scattered back per session,
  * one ``insert_query_batched`` — the gated insert (per-session
    ``do``/``record`` masks) FUSED with the answer query.

On the kernel dispatch tiers every one of those cache steps is a single
Pallas launch, so a whole L1-only wave is exactly THREE kernel launches —
probe -> miss-search -> insert+query — with no vmap-of-scalar fallback (a
missless wave is two: probe -> query).  Per session the cache ops match
the scalar ops bit for bit on every tier, so a wave produces results
identical to running a sequential ``ConversationalEngine`` loop over the
same turn stream (tested).  One semantic difference is inherent to
batching: the router degrades per *call*, so a degraded back-end wave
marks every miss in that wave degraded (and, like the sequential engine,
skips their (psi, r_a) records so the caches are never poisoned).

**Split wave contract.**  A wave executes in three explicit phases so the
continuous scheduler can pipeline them across waves:

  * ``probe_wave``   — encoder + L1 probe + (tiered) L2 memo / shard
    probe.  Touches only cache state; never mutates L1.
  * ``backend_wave`` — ``router.search`` over the residual miss subset
    (host + router threads only; the miss-search kernel launch happens
    inside the router's shards).
  * ``fill_wave``    — the fused insert+query launch, the L1 scatter, the
    shared-tier admission flush, and per-turn ``EngineTurn`` assembly.

``answer_batch`` is exactly ``probe -> backend -> fill`` run inline, so
its kernel-launch contract is unchanged (3 launches L1-only, 4 tiered
full-miss).  Under the scheduler, wave *t+1*'s probe overlaps wave *t*'s
back-end search: the probe reads only cache state of *disjoint* session
slots (the scheduler admits at most one in-flight turn per slot), and all
cache launches stay on the scheduler's worker thread, so per-session
results remain bit-identical to the sequential engine.

**Cache hierarchy.**  With a ``repro.core.shared.SharedTier`` attached,
the miss wave becomes tiered: probe-L1 -> probe-L2 -> back-end search on
the residual misses -> insert both tiers.  L1 misses first try the shared
tier's semantic result memo (host-side; a near-duplicate query from
another session reuses its full result set), then the shared shard caches
via the SAME ``cache_probe_batched`` kernel over the gathered shard rows
— so a full-miss tiered wave is exactly FOUR launches (L1 probe -> L2
probe -> miss-search -> fused insert+query; an L2 answer query or an
end-of-wave admission flush adds one only when L2 actually serves or
promotes).  Every tier-served answer also warms the session's L1 cache
through the same fused insert+query launch, with the (psi, r_a) coverage
claim recorded only when it is sound: fresh un-degraded back-end radii,
or the memo's triangle-corrected Eq. 3 claim.

**Latency attribution.**  Each turn reports admission-to-resolution
latency (``EngineTurn.latency_s``) with its queue wait broken out
(``queue_wait_s``) and the wave-level probe / backend / insert spans
attached (``spans``, a ``repro.serve.telemetry.TurnSpans``).  A wave used
to stamp every member with the whole wave's wall clock and queue wait was
invisible — SLO numbers were unmeasurable.  On the profiler's trace each
phase is a span (``engine.probe`` / ``engine.backend`` / ``engine.fill``)
carrying the wave's id (``wave_id``, also ``TurnSpans.wave``), its rows,
its bucket and the bytes it staged on and read back from the device, with
a child span per step; ``docs/architecture.md`` lists the tree.

``SessionManager`` puts an asynchronous front door on the engine: it maps
external session keys to engine slots and admits ``submit``-ed turns into
continuously scheduled waves via ``repro.serve.scheduler
.ContinuousScheduler`` — callers get a Future per turn, resolved when its
wave's fill phase completes.  ``close(key)`` drains only that key's
pending turns (per-slot drain); it no longer flushes the global queue.
It is a context manager: leaving the ``with`` block (or calling
``shutdown()``) drains pending turns and stops the scheduler's worker.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import Counter
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.core.cache import (BatchedMetricCache, CacheConfig,
                              insert_query_batched, probe_batched,
                              query_batched, validate_state)
from repro.core.embedding import distance_from_scores
from repro.core.shared import SharedTier
from repro.kernels import dispatch as kdispatch
from repro.serve.engine import EngineTurn
from repro.serve.router import ShardedRouter
from repro.serve.scheduler import ContinuousScheduler
from repro.serve.telemetry import ServeTelemetry, TurnSpans, span

__all__ = ["BatchedEngine", "SessionManager", "WaveState"]


@dataclasses.dataclass
class WaveState:
    """One wave in flight between the probe, backend, and fill phases.

    Buffers are bucket-sized (the wave padded to its power-of-two jit
    bucket); masks carry which rows are real, which still need the
    back-end, and which tier answered each.  ``admitted_at`` holds the
    per-turn admission stamps the latency attribution derives from.
    """

    sids: np.ndarray                 # (wave,) real session slots
    pad_sids: np.ndarray             # (bucket,) padded slot row
    wave: int
    bucket: int
    psi: jax.Array                   # (bucket, dim) transformed queries
    psi_np: np.ndarray
    sub: object                      # gathered CacheState rows
    need: np.ndarray                 # (bucket,) rows still needing backend
    tier: np.ndarray                 # (bucket,) serving tier per row
    reuse: np.ndarray                # (bucket,) L2 memo reuse rows
    l2hit: np.ndarray                # (bucket,) L2 shard-probe hit rows
    new_ids: np.ndarray              # (bucket, k_c + prefetch_width) inserts
    new_emb: np.ndarray              # (bucket, k_c + prefetch_width, dim)
    rad: np.ndarray                  # (bucket,) claim radii
    rec_np: np.ndarray               # (bucket,) record the (psi, r_a) claim
    backend_ok: np.ndarray           # (bucket,) rows the backend answered
    failed: np.ndarray               # (bucket,) empty-cache outage rows
    stale: np.ndarray                # (bucket,) stale-while-error memo rows
    admitted_at: np.ndarray          # (wave,) perf_counter admission stamps
    t_start: float                   # wave (probe-phase) start stamp
    wave_id: int = 0                 # the engine's monotone wave id
    degraded: bool = False
    shed: bool = False               # back end fenced: load-shed wave
    outage: Optional[BaseException] = None
    probe_s: float = 0.0
    backend_s: float = 0.0
    l2_s: float = 0.0                # inside the L2 tier: probe and flush
    h2d_bytes: int = 0               # bytes the wave staged on the device
    d2h_bytes: int = 0               # ... and read back, so far


class BatchedEngine:
    """S concurrent client sessions over one stacked metric cache."""

    def __init__(self, router: ShardedRouter, doc_embeddings: np.ndarray,
                 *, dim: int, n_sessions: int, k: int = 10, k_c: int = 1000,
                 epsilon: float = 0.04, capacity: Optional[int] = None,
                 encoder: Optional[Callable] = None,
                 dtype: Optional[str] = None,
                 backend: Optional[str] = None,
                 shared: Optional[SharedTier] = None,
                 cluster=None, prefetch_width: int = 0,
                 telemetry: Optional[ServeTelemetry] = None,
                 validate_every: int = 0):
        self.router = router
        self.doc_embeddings = doc_embeddings
        self.n_sessions = n_sessions
        self.k, self.k_c, self.epsilon = k, k_c, epsilon
        self.encoder = encoder
        # cluster + prefetch_width: the topical-locality prefetch path
        # (repro.core.cluster).  On a backend miss the fill phase appends
        # up to prefetch_width nearest-to-centroid docs to the answer
        # inside the SAME fused insert+query launch, and widens the
        # recorded claim by the triangle inequality (see fill_wave).
        self.cluster = cluster
        self.prefetch_width = int(prefetch_width) if cluster is not None else 0
        if self.cluster is not None \
                and self.prefetch_width > self.cluster.max_width:
            raise ValueError(
                f"prefetch_width {self.prefetch_width} exceeds the cluster "
                f"index's neighbor tables (max_width {self.cluster.max_width})")
        # per-slot ids brought in by prefetch (for warm-hit attribution)
        self._prefetched: list[set] = [set() for _ in range(n_sessions)]
        self.prefetch_issued = 0       # docs inserted via prefetch
        self.prefetch_warm_hits = 0    # prefetched docs in cache-served results
        self.insert_traffic_docs = 0   # docs offered to the L1 insert launch
        # backend: the kernels.dispatch tier the wave's cache ops run on
        # (None follows the process default — compiled Pallas on TPU, jnp
        # ref elsewhere).  Resolved once so every wave of this engine rides
        # one tier.
        self.backend = kdispatch.resolve(backend)
        # dtype: stacked-cache storage format (quant.DTYPES; None follows
        # the REPRO_CORPUS_DTYPE policy).  S sessions' caches share one
        # device allocation, so a bf16 / int8 store cuts the resident
        # serving state 2x / 4x.
        self.cache = BatchedMetricCache(CacheConfig(
            capacity=capacity or 16 * k_c, dim=dim, epsilon=epsilon,
            store_dtype=quant.resolve_dtype(dtype)),
            n_sessions)
        # shared: the optional cross-session L2 tier (None = the paper's
        # private-cache model).  Probe order: L1 -> L2 memo -> L2 shards ->
        # back-end.
        self.shared = shared
        if shared is not None:
            assert shared.cfg.dim == dim, "shared tier dim mismatch"
        # the shared tier's host structures are touched from the probe/fill
        # phases (scheduler worker) AND the backend phase (side thread)
        # when waves overlap; its sections serialize on this lock
        self._shared_lock = threading.Lock()
        self.telemetry = telemetry if telemetry is not None \
            else ServeTelemetry()
        # a router with no telemetry of its own counts into the engine's
        # (faults, and the bytes its device shards move)
        if getattr(router, "telemetry", False) is None:
            router.telemetry = self.telemetry
        # validate_every: run the cache_ops.validate_state integrity check
        # over the stacked caches every N waves (0 disables) and
        # quarantine-reset any slot whose invariants are broken, instead
        # of letting a corrupted slot poison (or crash) its next wave
        self.validate_every = int(validate_every)
        self.quarantined = 0
        self.wave_id = 0                 # id of the last wave probed
        self.turns: list[list[EngineTurn]] = [[] for _ in range(n_sessions)]
        # admission identity: (slot, generation) — bumped on start_session
        # so a recycled slot never inherits its predecessor's popularity
        # votes in the shared tier's >= 2-distinct-sessions counts
        self._gen = np.zeros((n_sessions,), np.int64)

    def start_session(self, session: int):
        with span("engine.reset", slot=session):
            self.cache.reset([session])
        self.turns[session] = []
        self._prefetched[session].clear()
        self._gen[session] += 1

    def quarantine_invalid(self) -> np.ndarray:
        """Integrity sweep: run ``cache_ops.validate_state`` over the
        stacked session caches and QUARANTINE any slot whose invariants
        are broken — the slot is reset to an empty cache (its next turn
        is a compulsory miss) instead of the corruption poisoning or
        crashing the wave.  Returns the reset slot indices.  Runs
        automatically every ``validate_every`` waves when that knob is
        set; callable directly after any suspected corruption."""
        ok, _problems = self.cache.read(lambda st: validate_state(
            st, self.cache.cfg, n_corpus=len(self.doc_embeddings)))
        bad = np.nonzero(~np.asarray(ok))[0]
        if bad.size:
            self.cache.reset(bad.tolist())
            for s in bad:
                self._prefetched[int(s)].clear()
            self.quarantined += int(bad.size)
            self.telemetry.record_fault("quarantined_slots", int(bad.size))
        return bad

    def _token(self, slot) -> tuple:
        """The slot's current admission identity for the shared tier."""
        return (int(slot), int(self._gen[int(slot)]))

    def _bucket(self, n: int) -> int:
        """Pad wave sizes to powers of two (capped at n_sessions): the
        batched ops are jitted per shape, so free-running traffic would
        otherwise pay a fresh XLA compile for every distinct wave size."""
        b = 1
        while b < n:
            b *= 2
        return min(b, self.n_sessions)

    # ------------------------------------------------------- probe phase
    def probe_wave(self, sessions, queries,
                   admitted_at: Optional[Sequence[float]] = None
                   ) -> WaveState:
        """Phase 1 of a wave: encoder + L1 probe + (tiered) L2 lookups.

        Touches only cache state — L1 rows are gathered and probed, the
        shared tier's memo and shard caches are consulted for L1 misses —
        and never writes L1, so it may run while the *previous* wave's
        back-end search is still in flight (the scheduler guarantees the
        two waves' session slots are disjoint).

        sessions: sequence of distinct session-slot indices.
        queries: matching sequence of raw queries (or pre-transformed psi
        when no encoder is configured).
        admitted_at: optional per-turn admission stamps
        (``time.perf_counter`` clock); defaults to now, i.e. zero queue
        wait for directly-invoked waves.
        """
        t_start = time.perf_counter()
        self.wave_id += 1
        sids = np.asarray(sessions, np.int32)
        if np.unique(sids).size != sids.size:
            raise ValueError("one turn per session per wave")
        wave = len(sids)
        bucket = self._bucket(wave)
        with span("engine.probe", wave=self.wave_id, rows=wave,
                  bucket=bucket) as sp:
            ws = self._probe(sids, queries, admitted_at, t_start, bucket)
            sp.set_metadata(h2d_bytes=ws.h2d_bytes, d2h_bytes=ws.d2h_bytes)
            took = Counter(ws.tier[:wave])
            self.telemetry.count(
                h2d_bytes=ws.h2d_bytes, d2h_bytes=ws.d2h_bytes,
                wave_rows=wave, bucket_rows=bucket,
                l1_hit_rows=took["l1"], l2_hit_rows=took["l2"],
                l2_reuse_rows=took["l2_reuse"], backend_rows=took["backend"])
        ws.probe_s = time.perf_counter() - t_start
        return ws

    def _probe(self, sids, queries, admitted_at, t_start, bucket
               ) -> WaveState:
        if self.validate_every and self.wave_id % self.validate_every == 0:
            self.quarantine_invalid()
        wave = len(sids)
        admitted = (np.full((wave,), t_start, np.float64)
                    if admitted_at is None
                    else np.asarray(admitted_at, np.float64))
        # pad the wave with copies of row 0 (probe-only: do/need are forced
        # False and padded rows are never scattered back or reported)
        pad_sids = np.concatenate([sids, np.repeat(sids[:1], bucket - wave)])
        with span("engine.probe.stage_queries"):
            # assembled on the host and sent once at the bucket shape, so a
            # new wave size compiles no program of its own
            q = np.stack([np.asarray(x) for x in queries])
            q = jnp.asarray(np.concatenate(
                [q, np.broadcast_to(q[:1], (bucket - wave,) + q.shape[1:])]))
            psi = self.encoder(q) if self.encoder else q

        with span("engine.probe.l1_gather"):
            sub = self.cache.gather(pad_sids)
        with span("engine.probe.l1_probe"):
            # launch 1: the L1 LowQuality probe over the wave's session
            # slices
            pr = probe_batched(sub, psi, self.epsilon, backend=self.backend,
                               max_queries=self.cache.cfg.max_queries)
            n_queries = np.asarray(sub.n_queries)
            hit = np.asarray(pr.hit)
            psi_np = np.asarray(psi)
        need = np.logical_or(n_queries == 0, ~hit)
        need[wave:] = False
        tier = np.where(need, "backend", "l1").astype(object)

        # rows that insert into L1 this wave fill these buffers; tier-served
        # rows (memo reuse / L2 shard hits) ride the same fused insert+query
        # launch as back-end rows, so warming L1 from L2 costs no extra
        # launch and every answer is re-scored against THIS query's psi
        reuse = np.zeros((bucket,), bool)
        l2hit = np.zeros((bucket,), bool)
        # insert buffers carry prefetch_width extra columns so the fill
        # phase can fold cluster neighbors into the same fused launch
        width = self.k_c + self.prefetch_width
        new_ids = np.full((bucket, width), -1, np.int64)
        new_emb = np.zeros((bucket, width, self.doc_embeddings.shape[1]),
                           self.doc_embeddings.dtype)
        rad = np.zeros((bucket,), np.float32)
        rec_np = np.zeros((bucket,), bool)

        l2_s, l2_h2d, l2_d2h = 0.0, 0, 0
        if self.shared is not None:
            t_l2 = time.perf_counter()
            with span("engine.probe.l2"), self._shared_lock:
                moved = (self.shared.h2d_bytes, self.shared.d2h_bytes)
                self.shared.tick()
                if need.any():
                    need, l2_d2h = self._probe_shared(
                        pad_sids, psi, psi_np, need, reuse, l2hit, new_ids,
                        new_emb, rad, rec_np)
                l2_h2d = self.shared.h2d_bytes - moved[0]
                l2_d2h += self.shared.d2h_bytes - moved[1]
            l2_s = time.perf_counter() - t_l2
            tier[reuse] = "l2_reuse"
            tier[l2hit] = "l2"

        return WaveState(
            sids=sids, pad_sids=pad_sids, wave=wave, bucket=bucket,
            psi=psi, psi_np=psi_np, sub=sub, need=need, tier=tier,
            reuse=reuse, l2hit=l2hit, new_ids=new_ids, new_emb=new_emb,
            rad=rad, rec_np=rec_np,
            backend_ok=np.zeros((bucket,), bool),
            failed=np.zeros((bucket,), bool),
            stale=np.zeros((bucket,), bool),
            admitted_at=admitted, t_start=t_start, wave_id=self.wave_id,
            l2_s=l2_s,
            # the staged queries and slot row; the probe's reads; the L2
            # tier's own transfers and the reads of its answers
            h2d_bytes=q.nbytes + pad_sids.nbytes + l2_h2d,
            d2h_bytes=n_queries.nbytes + hit.nbytes + psi_np.nbytes + l2_d2h)

    def _probe_shared(self, pad_sids, psi, psi_np, need, reuse, l2hit,
                      new_ids, new_emb, rad, rec_np) -> tuple:
        """Tiered lookups for L1 misses (caller holds the shared lock).
        Returns the residual miss mask after memo reuse and L2 hits, and
        the bytes read back of the tier's answers."""
        l2 = self.shared
        read = 0
        width = {}                   # row -> answer columns it takes
        claims = {}                  # memo row -> its Eq. 3 claim
        # L2a — semantic result reuse (host-side memo; no launch): a
        # near-duplicate query from ANOTHER session reuses its full
        # k_c result set, and records the triangle-corrected Eq. 3
        # claim r_a - delta(psi_a, psi) when it still clears epsilon
        rows = np.nonzero(need)[0]
        with span("l2.memo", rows=len(rows)) as sp:
            for i in rows:
                m = l2.memo_lookup(self._token(pad_sids[i]), psi_np[i])
                if m is None:
                    continue
                m_ids, _m_scores, claim = m
                reuse[i] = True
                claims[i] = claim
                width[i] = min(self.k_c, m_ids.shape[0])
                new_ids[i, :width[i]] = m_ids[:width[i]]
                if claim >= self.epsilon:
                    rad[i] = claim
                    rec_np[i] = True
            sp.set_metadata(hits=len(claims))
        rem = np.logical_and(need, ~reuse)
        if rem.any():
            # L2b — launch 2: the SAME LowQuality probe kernel over the
            # gathered shard rows of the shared tier (whole bucket, one
            # jitted shape; results masked to the residual misses)
            with span("l2.route"):
                shards = l2.route(psi_np)
            with span("l2.probe", rows=int(rem.sum()),
                      shards=len(np.unique(shards[rem]))) as sp:
                moved = (l2.h2d_bytes, l2.d2h_bytes)
                hit = np.asarray(
                    l2.probe_rows(psi, shards, backend=self.backend).hit)
                read += hit.nbytes
                l2hit[:] = np.logical_and(hit, rem)
                if l2hit.any():
                    # covered by a shared claim: answer from the shard's
                    # cached docs (one fused wave-query launch, only when
                    # L2 actually serves someone)
                    (_s2, _d2, i2, _sl2) = l2.query_rows(
                        psi, shards, self.k, backend=self.backend)
                    i2_np = np.asarray(i2)
                    read += i2_np.nbytes
                sp.set_metadata(h2d_bytes=l2.h2d_bytes - moved[0],
                                d2h_bytes=l2.d2h_bytes - moved[1] + read)
            for i in np.nonzero(l2hit)[0]:
                row = i2_np[i][i2_np[i] >= 0]
                width[i] = min(self.k_c, row.shape[0])
                new_ids[i, :width[i]] = row[:width[i]]
        if width:
            # the served answers' rows, from the host copy of the corpus
            with span("l2.rows", rows=len(width)):
                for i, n in width.items():
                    new_emb[i, :n] = self.doc_embeddings[
                        np.maximum(new_ids[i, :n], 0)]
        # the reusing session is a distinct retriever of these docs — it
        # counts toward the >= 2-sessions admission bar
        for i, claim in claims.items():
            l2.offer(self._token(pad_sids[i]), psi_np[i], claim,
                     new_emb[i], new_ids[i])
        return np.logical_and(rem, ~l2hit), read

    # ----------------------------------------------------- backend phase
    def backend_wave(self, ws: WaveState) -> WaveState:
        """Phase 2: ``router.search`` over the residual miss subset.

        Host + router work only (the miss-search kernel launch lives
        inside the router's shards), so the scheduler may run it on a side
        thread while the next wave probes.  A total back-end failure marks
        empty-cache miss rows failed; raises only when *every* real row in
        the wave is in that state (the same per-session failure a
        sequential engine loop raises).

        **Degradation ladder.**  When the router reports ``backend_open``
        (every shard's circuit breaker open) the wave is LOAD-SHED: the
        search — and its whole deadline wait — is skipped, and miss rows
        walk the same fallback ladder a failed search does: (1) a warm
        cache answers from cached embeddings, (2) an empty-cache row is
        served stale-while-error from the L2 memo (claims never
        recorded), (3) only a row with neither fails.  A shed wave with
        no tier-served rows runs probe -> query, the 2-launch contract
        (jaxpr-guarded in tests).
        """
        t0 = time.perf_counter()
        moved = (ws.h2d_bytes, ws.d2h_bytes)
        with span("engine.backend", wave=ws.wave_id, rows=ws.wave,
                  bucket=ws.bucket) as sp:
            try:
                return self._backend(ws)
            finally:
                ws.backend_s = time.perf_counter() - t0
                h2d = ws.h2d_bytes - moved[0]
                d2h = ws.d2h_bytes - moved[1]
                sp.set_metadata(h2d_bytes=h2d, d2h_bytes=d2h)
                self.telemetry.count(h2d_bytes=h2d, d2h_bytes=d2h)

    def _backend(self, ws: WaveState) -> WaveState:
        need, bucket, wave = ws.need, ws.bucket, ws.wave
        if need.any():
            if getattr(self.router, "backend_open", False):
                ws.shed = True
                self.telemetry.record_fault("shed_waves")
                self.telemetry.record_fault(
                    "shed_turns", int(need[:wave].sum()))
                self._outage_fallback(ws, TimeoutError(
                    "back end fenced: load-shed wave"))
                if ws.failed[:wave].all():
                    raise ws.outage
                return ws
            miss = np.nonzero(need)[0]
            try:
                ans, degraded = self.router.search(
                    ws.psi_np[miss], self.k_c)
                ws.degraded = degraded
                n_valid = (ans.ids >= 0).sum(axis=1)
                if (n_valid == 0).any():
                    raise TimeoutError(
                        "back-end answer holds no valid docs")
                # r_a per row from the last *valid* column (short
                # merges are sentinel-padded); same guard as the
                # sequential engine.  Computed at the bucket length,
                # so each bucket compiles it once, not each miss count
                with span("engine.backend.radii"):
                    last = np.take_along_axis(
                        ans.scores, n_valid[:, None] - 1, axis=1)[:, 0]
                    staged = jnp.asarray(np.pad(last, (0, bucket - len(last))))
                    back = np.asarray(distance_from_scores(staged))
                    radii = back[:len(last)]
                ws.h2d_bytes += staged.nbytes
                ws.d2h_bytes += back.nbytes
                with span("engine.backend.gather_rows"):
                    ws.new_ids[miss, :self.k_c] = ans.ids
                    ws.new_emb[miss, :self.k_c] = self.doc_embeddings[
                        np.maximum(ans.ids, 0)]
                ws.rad[miss] = radii
                # a degraded merge is missing shards: keep the docs,
                # skip the (psi, r_a) record so no cache learns a
                # false claim
                ws.rec_np[miss] = not degraded
                ws.backend_ok = need.copy()
                if self.shared is not None and not degraded:
                    # fresh retrievals feed the shared tier: memoized
                    # for semantic reuse, offered toward admission
                    with span("engine.backend.l2_record"), self._shared_lock:
                        for j, i in enumerate(miss):
                            tok = self._token(ws.pad_sids[i])
                            self.shared.memo_record(
                                tok, ws.psi_np[i], ans.ids[j],
                                ans.scores[j], float(radii[j]))
                            self.shared.offer(
                                tok, ws.psi_np[i], float(radii[j]),
                                ws.new_emb[i], ws.new_ids[i])
            except TimeoutError as e:
                # total back-end failure: miss sessions fall back to
                # their caches (or the stale memo); one with neither
                # fails alone, like its sequential counterpart — not
                # the whole wave
                self._outage_fallback(ws, e)
                if ws.failed[:wave].all():
                    raise
        return ws

    def _outage_fallback(self, ws: WaveState, e: BaseException) -> None:
        """Walk the degradation ladder for a wave whose back-end search
        was shed or failed entirely: warm-cache rows answer from their
        caches (fill_wave's query path), empty-cache rows try the L2
        memo *stale-while-error* (TTL and same-session gates waived;
        served docs warm L1 but the claim is never recorded, so nothing
        learns from stale data), and only rows with neither fail."""
        ws.degraded = True
        ws.outage = e
        failed = np.logical_and(ws.need, np.asarray(ws.sub.n_docs) == 0)
        if self.shared is not None and failed.any():
            with self._shared_lock:
                for i in np.nonzero(failed)[0]:
                    m = self.shared.memo_lookup(
                        self._token(ws.pad_sids[i]), ws.psi_np[i],
                        allow_stale=True)
                    if m is None:
                        continue
                    m_ids, _m_scores, _claim = m
                    ws.reuse[i] = True
                    ws.stale[i] = True
                    ws.tier[i] = "l2_reuse"
                    n = min(self.k_c, m_ids.shape[0])
                    ws.new_ids[i, :n] = m_ids[:n]
                    ws.new_emb[i, :n] = self.doc_embeddings[
                        np.maximum(m_ids[:n], 0)]
                    failed[i] = False        # rec_np stays False: no claim
                    self.telemetry.record_fault("stale_served")
        ws.failed = failed

    # -------------------------------------------------------- fill phase
    def fill_wave(self, ws: WaveState) -> list:
        """Phase 3: fused insert+query launch, L1 scatter, admission
        flush, and per-turn assembly.  Returns one entry per real session
        in input order: an ``EngineTurn``, or a ``TimeoutError`` instance
        for a session whose back-end failed entirely while its cache was
        still empty.
        """
        moved = (ws.h2d_bytes, ws.d2h_bytes)
        with span("engine.fill", wave=ws.wave_id, rows=ws.wave,
                  bucket=ws.bucket) as sp:
            out = self._fill(ws)
            h2d = ws.h2d_bytes - moved[0]
            d2h = ws.d2h_bytes - moved[1]
            sp.set_metadata(h2d_bytes=h2d, d2h_bytes=d2h)
        self.telemetry.count(h2d_bytes=h2d, d2h_bytes=d2h)
        return out

    def _fill(self, ws: WaveState) -> list:
        t0 = time.perf_counter()
        if self.prefetch_width and self.cluster is not None:
            # Topical prefetch: expand each fresh back-end answer with its
            # cluster's nearest-to-centroid docs (the prefetch_width extra
            # buffer columns), riding the same fused launch below.  With
            # the whole ball(centroid, d_w) cached, the triangle
            # inequality makes ball(psi, d_w - ||psi - c||) cached too, so
            # the recorded claim widens to max(r_a, d_w - ||psi - c||).
            # (Like the r_a claim itself, this assumes capacity headroom —
            # dropped inserts void claims; size L1 >= k_c + width.)
            with span("engine.fill.prefetch"):
                for i in np.nonzero(ws.backend_ok)[0]:
                    extra, bound = self.cluster.prefetch(
                        ws.psi_np[i], ws.new_ids[i, :self.k_c],
                        self.prefetch_width)
                    if extra.size:
                        cols = slice(self.k_c, self.k_c + extra.size)
                        ws.new_ids[i, cols] = extra
                        ws.new_emb[i, cols] = self.doc_embeddings[extra]
                        self.prefetch_issued += int(extra.size)
                        self._prefetched[int(ws.pad_sids[i])].update(
                            extra.tolist())
                    if ws.rec_np[i] and bound > ws.rad[i]:
                        ws.rad[i] = bound
        fill = np.logical_or(np.logical_or(ws.reuse, ws.l2hit),
                             ws.backend_ok)
        if fill.any():
            self.insert_traffic_docs += int((ws.new_ids[fill] >= 0).sum())
            with span("engine.fill.stage_inserts"):
                staged = [jnp.asarray(x) for x in (
                    ws.rad, ws.new_emb, ws.new_ids, fill, ws.rec_np)]
            ws.h2d_bytes += sum(x.nbytes for x in staged)
            rad, new_emb, new_ids, do, record = staged
            with span("engine.fill.insert_query"):
                # insert + answer query FUSED: one kernel launch closes the
                # wave (L1-only: launch 3 of 3, probe -> miss-search ->
                # insert+query; tiered: launch 4 of 4, after the L2 probe)
                (scores, _dists, ids, _slots), sub, dropped = \
                    insert_query_batched(
                        ws.sub, self.cache.cfg, ws.psi, rad, new_emb,
                        new_ids, self.k, do=do, record=record,
                        backend=self.backend)
                dropped = np.asarray(dropped)
            ws.d2h_bytes += dropped.nbytes
            self.cache.total_dropped += int(dropped.sum())
        else:  # missless (or outage) wave: probe -> query
            with span("engine.fill.query"):
                (scores, _dists, ids, _slots), sub = query_batched(
                    ws.sub, ws.psi, self.k, backend=self.backend)
        # the launch consumed the gathered rows: release them before the
        # write-back, which holds old and new stacked state at once
        ws.sub = None
        # write back only real, answerable rows (padded rows are shadows of
        # row 0; failed rows must stay exactly as they were, like a
        # sequential engine raising before its cache query) — every other
        # row routes to the drop index
        rows = np.full((ws.bucket,), self.n_sessions, np.int32)
        able = np.nonzero(~ws.failed[:ws.wave])[0]
        rows[able] = ws.sids[able]
        with span("engine.fill.scatter"):
            self.cache.scatter(rows, sub)
        ws.h2d_bytes += rows.nbytes
        if self.shared is not None:
            # end-of-wave: promote the wave's admitted answers into their
            # shards (deferred so admission never adds launches mid-wave)
            t_l2 = time.perf_counter()
            with span("engine.fill.l2_flush"), self._shared_lock, \
                    span("l2.flush") as sp:
                moved = (self.shared.h2d_bytes, self.shared.d2h_bytes)
                admitted = self.shared.flush_admissions(backend=self.backend)
                h2d = self.shared.h2d_bytes - moved[0]
                d2h = self.shared.d2h_bytes - moved[1]
                sp.set_metadata(admitted=admitted, h2d_bytes=h2d,
                                d2h_bytes=d2h)
            ws.h2d_bytes += h2d
            ws.d2h_bytes += d2h
            ws.l2_s += time.perf_counter() - t_l2

        resolved = time.perf_counter()
        insert_s = resolved - t0
        out: list = []
        with span("engine.fill.assemble"):
            for i, s in enumerate(ws.sids):
                if ws.failed[i]:
                    self.telemetry.record_fault("failed_turns")
                    out.append(TimeoutError(
                        f"session {int(s)}: back-end down and cache empty"
                        f" ({ws.outage})"))
                    continue
                # drop (id -1, score -inf) sentinel slots of a short cache,
                # the same trim the sequential engine applies
                row_ids = np.asarray(ids[i])
                row_scores = np.asarray(scores[i])
                ws.d2h_bytes += row_ids.nbytes + row_scores.nbytes
                real = row_ids >= 0
                row_tier = str(ws.tier[i])
                pre = self._prefetched[int(s)]
                n_pre = (sum(1 for d in row_ids[real].tolist() if d in pre)
                         if pre else 0)
                if n_pre and row_tier != "backend":
                    self.prefetch_warm_hits += n_pre
                admitted = float(ws.admitted_at[i])
                spans = TurnSpans(
                    queue_wait_s=max(ws.t_start - admitted, 0.0),
                    probe_s=ws.probe_s, backend_s=ws.backend_s,
                    insert_s=insert_s, l2_s=ws.l2_s,
                    total_s=resolved - admitted, tier=row_tier,
                    wave=ws.wave_id)
                # a degraded wave degrades its backend-tier rows AND any
                # row served stale-while-error (fresh tier hits stay
                # first-class)
                turn = EngineTurn(
                    ids=row_ids[real], scores=row_scores[real],
                    hit=row_tier != "backend",
                    degraded=bool(ws.degraded and (row_tier == "backend"
                                                   or ws.stale[i])),
                    latency_s=spans.total_s, tier=row_tier,
                    queue_wait_s=spans.queue_wait_s, spans=spans,
                    prefetch_hits=n_pre)
                if turn.degraded:
                    self.telemetry.record_fault("degraded_turns")
                self.telemetry.record_turn(spans)
                self.turns[int(s)].append(turn)
                out.append(turn)
        return out

    def answer_batch(self, sessions, queries) -> list:
        """Answer one concurrent turn per listed session (a wave), inline:
        ``probe_wave -> backend_wave -> fill_wave``.  Raises only when
        *every* session in the wave is an empty-cache back-end failure.
        """
        ws = self.probe_wave(sessions, queries)
        self.backend_wave(ws)
        return self.fill_wave(ws)

    def hit_rate(self, session: Optional[int] = None) -> float:
        """Cache hit rate, excluding each session's compulsory first turn.

        With a session index: that session's rate (NaN for sessions of
        <= 1 turn, which have no eligible turns).  With no argument: the
        aggregate across ALL sessions' eligible turns — the engine-level
        number serve_bench reports, well-defined as long as any session
        has a second turn.
        """
        if session is not None:
            turns = self.turns[session]
            if len(turns) <= 1:
                return float("nan")
            return float(np.mean([t.hit for t in turns[1:]]))
        flags = [t.hit for turns in self.turns for t in turns[1:]]
        if not flags:
            return float("nan")
        return float(np.mean(flags))

    def tier_counts(self, skip_first: bool = True) -> dict:
        """Turns served per hierarchy tier (``l1`` / ``l2`` / ``l2_reuse``
        / ``backend``), excluding each session's compulsory first turn by
        default (matching ``hit_rate`` accounting)."""
        counts = {"l1": 0, "l2": 0, "l2_reuse": 0, "backend": 0}
        for turns in self.turns:
            for t in (turns[1:] if skip_first else turns):
                counts[t.tier] += 1
        return counts

    def prefetch_stats(self) -> dict:
        """Cluster-prefetch accounting: ``issued`` docs inserted via
        prefetch, ``warm_hits`` prefetched docs that later appeared in a
        cache-served result, ``insert_traffic_docs`` total docs offered to
        the L1 insert launch (the cache-traffic axis of the Pareto sweep),
        and the configured ``width``."""
        return {"issued": self.prefetch_issued,
                "warm_hits": self.prefetch_warm_hits,
                "insert_traffic_docs": self.insert_traffic_docs,
                "width": self.prefetch_width}


class SessionManager:
    """Asynchronous front door: session keys -> engine slots -> waves.

    ``submit(key, query)`` returns a Future[EngineTurn]; turns are
    admitted into continuously scheduled ``BatchedEngine`` waves by a
    ``ContinuousScheduler`` — an arrival joins the next wave the engine
    can take (no fixed window), wave sizes adapt to the EWMA'd arrival
    rate, and wave *t+1*'s cache probe overlaps wave *t*'s back-end
    search.  Two turns of the same session are never in flight together
    (the scheduler defers the later one), preserving arrival order.

    Knobs: ``min_slots``/``max_slots`` bound the adaptive wave-size limit,
    ``ewma_horizon_s`` sets the arrival-rate memory, ``target_p99_s``
    backs wave sizes off when the measured turn p99 overshoots, and
    ``window_s > 0`` recovers the deprecated fixed-window admission for
    A/B comparison (serve_bench's baseline mode).
    """

    def __init__(self, engine: BatchedEngine, *, window_s: float = 0.0,
                 max_batch: Optional[int] = None, min_slots: int = 1,
                 max_slots: Optional[int] = None,
                 adaptive: Optional[bool] = None, headroom: float = 1.5,
                 ewma_horizon_s: float = 1.0,
                 target_p99_s: Optional[float] = None,
                 overlap: bool = True):
        self.engine = engine
        self._slots: dict = {}
        self._free = list(range(engine.n_sessions - 1, -1, -1))
        self.scheduler = ContinuousScheduler(
            engine, min_wave=min_slots,
            max_wave=max_slots or max_batch or engine.n_sessions,
            window_s=window_s, adaptive=adaptive, headroom=headroom,
            ewma_horizon_s=ewma_horizon_s, target_p99_s=target_p99_s,
            overlap=overlap)

    @property
    def batcher(self) -> ContinuousScheduler:
        """Deprecated alias for ``scheduler`` (pre-ISSUE-8 name)."""
        return self.scheduler

    @property
    def telemetry(self) -> ServeTelemetry:
        return self.scheduler.telemetry

    def open(self, key) -> int:
        """Start a session for ``key``; returns its engine slot."""
        with span("session.open"):
            if key in self._slots:
                raise KeyError(f"session {key!r} already open")
            if not self._free:
                raise RuntimeError("no free session slots")
            slot = self._free.pop()
            self.engine.start_session(slot)
            self._slots[key] = slot
            return slot

    def close(self, key):
        """End a session and recycle its slot, draining only THIS key's
        pending turns first (so a turn already submitted for it cannot
        execute against the slot's next occupant).  Other sessions'
        queued and in-flight turns are untouched — closing a session no
        longer force-flushes the global wave."""
        with span("session.close"):
            if key not in self._slots:
                raise KeyError(f"unknown session key {key!r}")
            self.scheduler.drain_slot(self._slots[key])
            self._free.append(self._slots.pop(key))

    def shutdown(self):
        """Drain pending turns and stop the scheduler's worker thread.
        Idempotent; further ``submit`` calls raise.  Benchmarks and tests
        that spin up many managers must call this (or use the manager as a
        context manager) so worker threads don't leak across runs."""
        self.scheduler.close()

    def __enter__(self) -> "SessionManager":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.shutdown()
        return False

    @property
    def active_sessions(self) -> int:
        return len(self._slots)

    def submit(self, key, query):
        """Admit one turn; returns a Future resolved with its EngineTurn.
        The admission timestamp is stamped here, so the resolved turn's
        ``latency_s`` covers queue wait + wave execution."""
        return self.scheduler.submit(query, slot=self._slots[key])

    def flush(self):
        """Force everything queued now to execute (tests, shutdown)."""
        self.scheduler.flush()
