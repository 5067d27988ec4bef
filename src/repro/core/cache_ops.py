"""Tier-agnostic metric-cache ops: the contract BOTH cache tiers share.

Extracted from ``repro.core.cache`` (ISSUE 7) so the probe path is no
longer monolithic: the per-session L1 tier (``cache.MetricCache`` /
``cache.BatchedMetricCache``) and the cross-session shared L2 tier
(``repro.core.shared.SharedTier``) are both thin owners of the SAME
functional ops over the SAME tile-aligned ``CacheState`` — an L2 shard is
just one more stacked-state row, so the fused Pallas wave kernels
(``kernels.cache_probe`` / ``kernels.cache_wave``) serve both tiers with
no new kernel contract.

State layout (all pre-allocated; ``-1`` ids / ``-inf`` radii mark empty
slots).  The leaves are allocated at the PHYSICAL extents (``Cp`` =
``cfg.phys_capacity``, ``Dp`` = ``cfg.phys_dim``, ``Qp`` =
``cfg.phys_max_queries`` — capacity rounded to the wave-kernel tile
multiple, dim to the lane multiple, the ring to the sublane multiple; see
``repro.core.layout``) so every kernel launch is zero-copy; the ops mask
on the *logical* extents in ``CacheConfig`` and padded slots permanently
hold the empty-slot sentinels:
  doc_emb   (Cp, Dp)          cached transformed document embeddings, stored
                              in ``cfg.store_dtype`` (fp32 / bf16 / int8 —
                              ``repro.core.quant`` formats)
  doc_ids   (Cp,)             global document ids, -1 = empty
  doc_stamp (Cp,)             last-use step (for the beyond-paper LRU policy)
  q_emb     (Qp, Dp)          embeddings of queries answered by the back-end
                              (same storage format as doc_emb)
  q_radius  (Qp,)             r_a — distance of the k_c-th doc retrieved
  n_docs, step                scalars
  n_queries                   total queries ever recorded (monotone); the
                              query records live in a ring over the LOGICAL
                              ``max_queries`` slots, so the number of
                              *valid* records is min(n_queries, max_queries)
  doc_scale (Cp,)             f32 per-document score multipliers (all ones
                              unless store_dtype == "int8")
  q_scale   (Qp,)             f32 per-record score multipliers, ditto

Quantized storage rides the same dequantization rule as the corpus scan
(``quant.scale_scores``): probe / query / insert cast the payload to f32,
run the arithmetic in f32, and apply the per-row scale score-side — so at
store_dtype "fp32" the scales are exactly 1.0 and every op is bit-identical
to the unquantized cache, while bf16 / int8 caches hold 2x / 4x the
documents per byte of client memory (paper RQ1.C).

Paper-faithful behaviour: no eviction (overflowing inserts are an error in
strict mode / dropped otherwise); the LowQuality test of Eq. 3/4 decides
hits.  Beyond-paper extensions (flagged, off by default): LRU eviction and
distance-based ("ball") eviction so unbounded conversations stay bounded.

Batched multi-axis variants: every op also ships in a batched variant
(``probe_batched`` / ``query_batched`` / ``insert_batched`` / the fused
``insert_query_batched``) over a ``CacheState`` whose leaves carry a
leading axis (``init_batched_cache``) — SESSIONS for the L1 tier, SHARDS
for the L2 tier.  The ref tier of each is a ``vmap`` of the scalar op —
per row it computes exactly the same result — while the kernel tiers run
the whole wave as ONE fused Pallas launch, bit-identical per row to the
vmap path; per-row ``do`` / ``record`` masks make a wave of concurrent
turns with mixed hits and misses update only the rows that actually
missed.

The write-position logic (``insert_positions`` — dedup, append, and the
eviction policies) lives HERE, once: the L1 batched scatter and the L2
admission path both call it, so L2 admission semantics can never drift
from L1 eviction semantics.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import embedding as emb
from repro.core import layout
from repro.core import quant
from repro.kernels import dispatch as kdispatch

__all__ = ["CacheState", "CacheConfig", "ProbeResult", "init_cache",
           "init_batched_cache", "reset_sessions", "probe", "query",
           "insert", "probe_batched", "query_batched", "insert_batched",
           "insert_query_batched", "pad_features", "store_rows",
           "dedup_mask", "evicting_positions", "insert_positions",
           "validate_state"]


class CacheState(NamedTuple):
    doc_emb: jax.Array
    doc_ids: jax.Array
    doc_stamp: jax.Array
    q_emb: jax.Array
    q_radius: jax.Array
    n_docs: jax.Array
    n_queries: jax.Array
    step: jax.Array
    doc_scale: jax.Array
    q_scale: jax.Array


class CacheConfig(NamedTuple):
    capacity: int              # logical doc-slot count (mask extent)
    dim: int                   # logical feature width
    max_queries: int = 64      # logical query-record ring length
    epsilon: float = 0.04      # the paper's tuned default (Fig. 4)
    dedup: bool = True
    eviction: str = "none"     # "none" (paper) | "lru" | "ball" (beyond-paper)
    dtype: object = jnp.float32
    store_dtype: str = "fp32"  # quant.DTYPES embedding storage format

    # Physical allocation extents (derived, so the config stays a hashable
    # static-jit argument): the CacheState leaves are allocated at these at
    # init and every kernel launch rides them unchanged — zero-copy.
    @property
    def phys_capacity(self) -> int:
        return layout.phys_capacity(self.capacity)

    @property
    def phys_dim(self) -> int:
        return layout.phys_dim(self.dim)

    @property
    def phys_max_queries(self) -> int:
        return layout.phys_queries(self.max_queries)


def init_cache(cfg: CacheConfig) -> CacheState:
    """Allocate one cache row at the PHYSICAL extents.

    Padded doc columns / ring slots are written with their empty-slot
    sentinels exactly once, here: id -1, scale 1.0, radius -inf, stamp 0,
    zero payload.  Every op masks on the logical extents (or relies on
    those sentinels), and dropped insert positions route past
    ``phys_capacity``, so no launch ever rewrites a padded slot — LRU
    stamps of padded columns stay 0 forever (regression-tested).
    """
    store = quant.storage_dtype(cfg.store_dtype)
    cp, dp, qp = cfg.phys_capacity, cfg.phys_dim, cfg.phys_max_queries
    return CacheState(
        doc_emb=jnp.zeros((cp, dp), store),
        doc_ids=jnp.full((cp,), -1, jnp.int32),
        doc_stamp=jnp.zeros((cp,), jnp.int32),
        q_emb=jnp.zeros((qp, dp), store),
        q_radius=jnp.full((qp,), -jnp.inf, cfg.dtype),
        n_docs=jnp.zeros((), jnp.int32),
        n_queries=jnp.zeros((), jnp.int32),
        step=jnp.zeros((), jnp.int32),
        doc_scale=jnp.ones((cp,), jnp.float32),
        q_scale=jnp.ones((qp,), jnp.float32),
    )


def pad_features(x: jax.Array, width: int) -> jax.Array:
    """Zero-pad the trailing feature axis to the state's physical width —
    a per-wave O(rows * dim) copy, never O(capacity).  No-op (and no
    traced pad) when already aligned."""
    short = width - x.shape[-1]
    if short == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, short)])


def store_rows(x: jax.Array, store_dtype: str):
    """Quantize rows into the cache storage format; scales always an array
    (ones when the format carries none), so CacheState leaves are uniform
    across dtypes."""
    qc = quant.quantize(x, store_dtype)
    if qc.scale is None:
        return qc.data, jnp.ones(x.shape[:-1], jnp.float32)
    return qc.data, qc.scale


class ProbeResult(NamedTuple):
    hit: jax.Array        # bool — r_hat >= epsilon for some cached query
    r_hat: jax.Array      # max over cached queries of (r_a - delta(psi_a, psi))
    nearest_q: jax.Array  # arg of that max (int32), -1 if cache has no queries


@functools.partial(jax.jit, static_argnames=("max_queries",))
def probe(state: CacheState, psi: jax.Array, epsilon: jax.Array | float,
          max_queries: int | None = None) -> ProbeResult:
    """The LowQuality test (Eq. 3/4). Cost: O(n_queries * dim) — a few us.

    Returns hit=False when the cache holds no queries (compulsory miss).
    ``max_queries`` is the LOGICAL ring length from ``CacheConfig``; ring
    slots past it are allocation padding and masked out.  When None (a
    caller without the config) the padded slots' permanent -inf radius
    sentinels keep them out of the argmax anyway.
    """
    n_slots = state.q_emb.shape[0]
    mq = n_slots if max_queries is None else max_queries
    idx = jnp.arange(n_slots)
    valid = jnp.logical_and(idx < state.n_queries, idx < mq)
    psi_p = pad_features(psi, state.q_emb.shape[-1])
    scores = quant.scale_scores(
        state.q_emb.astype(jnp.float32) @ psi_p, state.q_scale)
    dist = emb.distance_from_scores(scores)                      # (Qp,)
    r_hat = jnp.where(valid, state.q_radius - dist, -jnp.inf)
    best = jnp.argmax(r_hat)
    best_r = r_hat[best]
    hit = jnp.logical_and(state.n_queries > 0, best_r >= epsilon)
    return ProbeResult(hit, best_r, jnp.where(state.n_queries > 0, best, -1))


@functools.partial(jax.jit, static_argnames=("k",))
def query(state: CacheState, psi: jax.Array, k: int):
    """NN(C, psi, k): top-k cached docs. Returns (scores, distances, ids, slots).

    A cache holding fewer than k docs pads the answer with (id -1, score
    -inf) sentinel slots; callers must drop those rows before ranking-metric
    or result use (``serve.engine`` does).  The scan runs over the physical
    columns; padded columns carry id -1 so they score -inf, and the stable
    top-k (ascending empty slots) can never reach them while k <= the
    logical capacity.
    """
    psi_p = pad_features(psi, state.doc_emb.shape[-1])
    scores = quant.scale_scores(
        state.doc_emb.astype(jnp.float32) @ psi_p, state.doc_scale)  # (Cp,)
    scores = jnp.where(state.doc_ids >= 0, scores, -jnp.inf)
    top_s, slots = jax.lax.top_k(scores, k)
    ids = state.doc_ids[slots]
    # touch LRU stamps of returned docs — real ones only: refreshing the
    # stamp of an empty sentinel slot would make LRU eviction prefer
    # evicting live documents over reusing the untouched empty slot
    touch = jnp.where(ids >= 0, slots, state.doc_stamp.shape[0])
    new_stamp = state.doc_stamp.at[touch].set(state.step, mode="drop")
    state = state._replace(doc_stamp=new_stamp, step=state.step + 1)
    return (top_s, emb.distance_from_scores(top_s), ids, slots), state


def dedup_mask(new_ids: jax.Array, existing_ids: jax.Array) -> jax.Array:
    """True for the first occurrence of each id not already cached."""
    in_cache = (new_ids[:, None] == existing_ids[None, :]).any(axis=1)
    # dup_later[j]: some i < j carries the same id — a (kc, kc) compare
    # under a strict lower-triangle mask (an index-pair scatter would embed
    # O(kc^2) constant indices in the program and compile for tens of
    # seconds at k_c = 1000)
    kc = new_ids.shape[0]
    earlier = (jax.lax.broadcasted_iota(jnp.int32, (kc, kc), 0)
               < jax.lax.broadcasted_iota(jnp.int32, (kc, kc), 1))
    same = new_ids[:, None] == new_ids[None, :]
    dup_later = jnp.logical_and(same, earlier).any(axis=0)
    return jnp.logical_and(~in_cache, ~dup_later)


def evicting_positions(state: CacheState, capacity: int, keep: jax.Array,
                       evict_key: jax.Array, evictable: jax.Array,
                       drop: int):
    """Write positions for kept docs under an eviction policy.

    Appends fill the empty tail ([n_docs, capacity)); once the tail is
    exhausted, the remaining kept docs overwrite ``evictable`` slots in
    ascending ``evict_key`` order.  Non-evictable slots (empty ones, and
    occupied slots protected by the caller) rank last and are out of reach
    of the placeable range, so an append target can never double as an
    eviction target of the same call — the write sets are disjoint by
    construction.  Kept docs beyond what appends + evictions can place are
    dropped and counted, never collapsed onto one slot.

    ``capacity`` is the LOGICAL capacity (occupied slots only ever live in
    [0, capacity)); ``drop`` is the drop sentinel, the PHYSICAL capacity —
    a dropped doc must route past the allocation padding, because a padded
    column is a real column of a kernel launch and a doc written there
    would leak into the query scan as a live id.
    """
    rank = jnp.cumsum(keep) - 1                       # dense rank among kept
    append_pos = state.n_docs + rank
    evict_order = jnp.argsort(jnp.where(evictable, evict_key, jnp.inf))
    evict_rank = rank - (capacity - state.n_docs)     # 0-based among evictions
    evict_pos = evict_order[jnp.clip(evict_rank, 0, capacity - 1)]
    pos = jnp.where(append_pos < capacity, append_pos, evict_pos)
    placeable = evict_rank < evictable.sum()          # appends are < 0 here
    pos = jnp.where(jnp.logical_and(keep, placeable), pos, drop)
    dropped = jnp.logical_and(keep, ~placeable).sum().astype(jnp.int32)
    return pos, dropped


def insert_positions(state: CacheState, cfg: CacheConfig, psi: jax.Array,
                     new_ids: jax.Array):
    """Write positions for one insert batch: (keep, pos, dropped, new_n).

    THE position logic of the scalar ``insert`` — dedup, append, and the
    eviction policies — shared by the kernel-tier batched scatter
    (``kernels.cache_wave``) AND the L2 shared-tier admission path, so all
    of them stay bit-identical to the scalar path by construction.
    ``pos[j] == cfg.phys_capacity`` marks a dropped (or non-kept)
    document: the drop sentinel routes past the PHYSICAL capacity so it
    can neither land in a real column nor in an allocation-padding column
    of the pre-padded state.
    """
    kc = new_ids.shape[0]
    drop = cfg.phys_capacity
    keep = dedup_mask(new_ids, state.doc_ids) if cfg.dedup else jnp.ones((kc,), bool)
    keep = jnp.logical_and(keep, new_ids >= 0)

    if cfg.eviction in ("lru", "ball"):
        # Slots holding ids that this batch re-retrieved are part of the
        # (psi, r_a) coverage claim being recorded right now (dedup keeps
        # them out of the batch precisely because they are already cached);
        # evicting one in the same call would break the claim.
        occupied = state.doc_ids >= 0
        in_batch = (state.doc_ids[:, None] == new_ids[None, :]).any(axis=1)
        evictable = jnp.logical_and(occupied, ~in_batch)
        if cfg.eviction == "lru":
            # Beyond-paper: overflow overwrites the stalest occupied slots.
            key = state.doc_stamp.astype(state.q_radius.dtype)
        else:
            # Beyond-paper: overflow evicts docs farthest from the query.
            psi_p = pad_features(psi, state.doc_emb.shape[-1])
            key = -emb.distance_from_scores(quant.scale_scores(
                state.doc_emb.astype(jnp.float32) @ psi_p, state.doc_scale))
        pos, dropped = evicting_positions(state, cfg.capacity, keep, key,
                                          evictable, drop)
    else:  # paper-faithful: append, drop overflow (and report it)
        append_pos = state.n_docs + jnp.cumsum(keep) - 1
        fits = append_pos < cfg.capacity
        pos = jnp.where(jnp.logical_and(keep, fits), append_pos, drop)
        dropped = jnp.logical_and(keep, ~fits).sum().astype(jnp.int32)
    new_n = jnp.minimum(state.n_docs + keep.sum(), cfg.capacity)
    return keep, pos, dropped, new_n


@functools.partial(jax.jit, static_argnames=("cfg",))
def insert(state: CacheState, cfg: CacheConfig, psi: jax.Array, radius: jax.Array,
           new_emb: jax.Array, new_ids: jax.Array,
           record: jax.Array | bool = True) -> tuple[CacheState, jax.Array]:
    """Insert the k_c back-end results for a missed query ``psi``.

    Records (psi, r_a) for future LowQuality probes — unless ``record`` is
    False (degraded back-end answers carry an inflated r_a that would poison
    the cache with false coverage claims; the docs are still worth keeping).
    Then appends the new document embeddings (deduplicated by id when
    cfg.dedup; ids < 0 are sentinel padding and never inserted).  Returns
    (new_state, n_dropped) where n_dropped counts docs that did not fit
    (always 0 under the paper's sizing assumption; eviction policies only
    drop when a single batch exceeds the whole capacity).
    """
    _keep, pos, dropped, new_n = insert_positions(state, cfg, psi, new_ids)

    # embeddings enter the cache in the storage format: quantize the LOGICAL
    # rows (identity at fp32; int8 scales come from the real features), then
    # zero-pad to the physical width — the zero pad equals the init pad in
    # every storage format — and scatter payload + per-row scale together
    emb_q, emb_scale = store_rows(new_emb, cfg.store_dtype)
    emb_q = pad_features(emb_q, state.doc_emb.shape[-1])
    doc_emb = state.doc_emb.at[pos].set(emb_q, mode="drop")
    doc_scale = state.doc_scale.at[pos].set(emb_scale, mode="drop")
    doc_ids = state.doc_ids.at[pos].set(new_ids, mode="drop")
    doc_stamp = state.doc_stamp.at[pos].set(state.step, mode="drop")

    # query records live in a ring over the LOGICAL max_queries slots:
    # slot = total-count mod max_queries, so a full cache overwrites the
    # *oldest* record, not the most recent one — and the padded ring slots
    # past cfg.max_queries are never written
    rec = jnp.asarray(record, bool)
    qslot = jnp.mod(state.n_queries, cfg.max_queries)
    psi_q, psi_scale = store_rows(psi, cfg.store_dtype)
    psi_q = pad_features(psi_q, state.q_emb.shape[-1])
    q_emb = state.q_emb.at[qslot].set(
        jnp.where(rec, psi_q, state.q_emb[qslot]))
    q_scale = state.q_scale.at[qslot].set(
        jnp.where(rec, psi_scale, state.q_scale[qslot]))
    q_radius = state.q_radius.at[qslot].set(
        jnp.where(rec, radius, state.q_radius[qslot]))

    new_state = CacheState(
        doc_emb=doc_emb, doc_ids=doc_ids, doc_stamp=doc_stamp,
        q_emb=q_emb, q_radius=q_radius,
        n_docs=new_n.astype(jnp.int32),
        n_queries=(state.n_queries + rec.astype(jnp.int32)),
        step=state.step + 1,
        doc_scale=doc_scale, q_scale=q_scale,
    )
    return new_state, dropped


# --------------------------------------------------------------------------
# Batched variants: one stacked CacheState for S concurrent rows — L1
# sessions or L2 shards; the ops are tier-agnostic.  The ref tier of each
# op is a vmap of the scalar op, so per row the arithmetic — matmuls,
# argsorts, scatters — is the same program and the results match the
# scalar path exactly.  The kernel tiers run each op as ONE fused Pallas
# launch over the stacked state (``kernels.cache_probe`` for the probe,
# ``kernels.cache_wave`` for query/insert — and the fused
# ``insert_query_batched`` collapses the wave tail into a single launch),
# reusing the scalar ops' jnp position/ring logic so they stay
# bit-identical per row.  ``do``/``record`` masks make a mixed hit/miss
# wave update only the rows that missed (hit rows keep their state
# bitwise, LRU stamps included).
# --------------------------------------------------------------------------

def init_batched_cache(cfg: CacheConfig, n_sessions: int) -> CacheState:
    """A CacheState whose every leaf carries a leading (n_sessions,) axis."""
    one = init_cache(cfg)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n_sessions,) + x.shape), one)


def reset_sessions(state: CacheState, cfg: CacheConfig,
                   mask: jax.Array) -> CacheState:
    """Re-initialize the rows where ``mask`` is True; others untouched."""
    fresh = init_batched_cache(cfg, mask.shape[0])
    return jax.tree_util.tree_map(
        lambda f, s: jnp.where(mask.reshape(mask.shape + (1,) * (s.ndim - 1)),
                               f, s), fresh, state)


@functools.partial(jax.jit, static_argnames=("backend", "max_queries"))
def probe_batched(state: CacheState, psi: jax.Array,
                  epsilon: jax.Array | float,
                  backend: str | None = None,
                  max_queries: int | None = None) -> ProbeResult:
    """One LowQuality test per row: psi is (S, dim).

    Dispatches on the kernel backend tier (``repro.kernels.dispatch``):
    the ref tier is a vmap of the scalar ``probe``; interpret/compiled run
    the whole wave as ONE fused Pallas launch over the stacked state
    (``cache_probe_batched``), ring-buffer validity included.  Both tiers
    agree bitwise on hit/nearest_q and to float tolerance on r_hat.
    ``max_queries`` is the LOGICAL ring length from ``CacheConfig`` (the
    ring of a pre-padded state is longer; its padded slots hold -inf
    radius sentinels, so omitting it stays correct, just unmasked).
    """
    be = kdispatch.resolve(backend)
    if be == "ref":
        one = functools.partial(probe, max_queries=max_queries)
        return ProbeResult(*jax.vmap(one, in_axes=(0, 0, None))(
            state, psi, epsilon))
    from repro.kernels.cache_probe.ops import cache_probe_batched
    hit, r_hat, idx = cache_probe_batched(
        state.q_emb, psi, state.q_radius, state.n_queries, epsilon,
        q_scale=state.q_scale, max_queries=max_queries,
        interpret=kdispatch.interpret_flag(be))
    return ProbeResult(hit, r_hat, idx)


@functools.partial(jax.jit, static_argnames=("k", "backend"))
def query_batched(state: CacheState, psi: jax.Array, k: int,
                  backend: str | None = None):
    """Per-row top-k over (S,)-stacked caches.

    The ref tier is a vmap of the scalar ``query``; the kernel tiers run
    the whole wave as ONE fused Pallas launch (``kernels.cache_wave``) —
    scores, ids, *and* slot ordering (stable top-k, empty slots ascending)
    match the ref tier, and the LRU-stamp touch / step bump applied here
    are the scalar op's exact jnp updates.
    """
    be = kdispatch.resolve(backend)
    if be == "ref":
        return jax.vmap(query, in_axes=(0, 0, None))(state, psi, k)
    from repro.kernels.cache_wave import ops as wave_ops
    vals, ids, slots = wave_ops.wave_query_topk(
        state.doc_emb, state.doc_ids, state.doc_scale, psi, k,
        interpret=kdispatch.interpret_flag(be))
    new_state = _apply_query_touch(state, ids, slots)
    return (vals, emb.distance_from_scores(vals), ids, slots), new_state


def _apply_query_touch(state: CacheState, ids: jax.Array,
                       slots: jax.Array) -> CacheState:
    """The scalar ``query``'s state update after a kernel-tier wave top-k:
    refresh the LRU stamps of the returned REAL docs (empty-slot answers
    route to the capacity drop-sentinel) at the current step, then bump
    the step — shared by ``query_batched`` and ``insert_query_batched`` so
    the touch invariant lives in one place."""
    capacity = state.doc_stamp.shape[1]
    touch = jnp.where(ids >= 0, slots, capacity)
    new_stamp = jax.vmap(
        lambda st, tch, sv: st.at[tch].set(sv, mode="drop"))(
            state.doc_stamp, touch, state.step)
    return state._replace(doc_stamp=new_stamp, step=state.step + 1)


def _gated_batch(new_ids, do, record):
    n = new_ids.shape[0]
    do = jnp.ones((n,), bool) if do is None else jnp.asarray(do, bool)
    record = do if record is None else jnp.asarray(record, bool)
    return do, record


def _insert_batched_ref(state, cfg, psi, radius, new_emb, new_ids, do, record):
    def _one(s, p, r, e, i, d, rec):
        new_s, dropped = insert(s, cfg, p, r, e, i, rec)
        merged = jax.tree_util.tree_map(
            lambda a, b: jnp.where(d, a, b), new_s, s)
        return merged, jnp.where(d, dropped, 0)

    return jax.vmap(_one)(state, psi, radius, new_emb, new_ids, do, record)


def _insert_batched_kernel(state, cfg, psi, radius, new_emb, new_ids, do,
                           record, interpret, query_psi=None, k=None):
    """Kernel-tier batched insert (optionally fused with the wave query).

    Positions/ring slots come from the scalar ops' exact jnp logic
    (``insert_positions``, vmapped), gated per row by ``do`` — a masked
    row's positions all point at the drop sentinel, so its payload, ids,
    and LRU stamps pass through the scatter bit-identically.  The kernel
    does the heavy part: one pass over the stacked cache payload,
    scattering the k_c batch and (when ``query_psi`` is given) scoring the
    freshly blended tiles for the post-insert top-k.
    """
    from repro.kernels.cache_wave import ops as wave_ops
    _keep, pos, dropped, new_n = jax.vmap(
        lambda s, p, i: insert_positions(s, cfg, p, i))(state, psi, new_ids)
    pos = jnp.where(do[:, None], pos, cfg.phys_capacity)
    dropped = jnp.where(do, dropped, 0)
    rec_g = jnp.logical_and(do, record)
    emb_q, emb_scale = store_rows(new_emb, cfg.store_dtype)
    psi_q, psi_scale = store_rows(psi, cfg.store_dtype)
    qslot = jnp.mod(state.n_queries, cfg.max_queries)
    args = (state.doc_emb, state.doc_ids, state.doc_stamp, state.doc_scale,
            state.q_emb, state.q_radius, state.q_scale,
            emb_q, emb_scale, new_ids, pos, psi_q, psi_scale,
            jnp.asarray(radius, jnp.float32), rec_g, qslot, state.step)
    if query_psi is None:
        outs, q_out = wave_ops.wave_insert_scatter(
            *args, interpret=interpret), None
    else:
        outs, q_out = wave_ops.wave_insert_query(
            *args, psi=query_psi, k=k, interpret=interpret)
    demb, dids, dstamp, dscale, qemb, qrad, qsc = outs
    new_state = CacheState(
        doc_emb=demb, doc_ids=dids, doc_stamp=dstamp,
        q_emb=qemb, q_radius=qrad.astype(state.q_radius.dtype),
        n_docs=jnp.where(do, new_n, state.n_docs).astype(jnp.int32),
        n_queries=state.n_queries + rec_g.astype(jnp.int32),
        step=jnp.where(do, state.step + 1, state.step),
        doc_scale=dscale, q_scale=qsc,
    )
    return new_state, dropped.astype(jnp.int32), q_out


@functools.partial(jax.jit, static_argnames=("cfg", "backend"))
def insert_batched(state: CacheState, cfg: CacheConfig, psi: jax.Array,
                   radius: jax.Array, new_emb: jax.Array, new_ids: jax.Array,
                   do: jax.Array | None = None,
                   record: jax.Array | None = None,
                   backend: str | None = None):
    """Row-batched ``insert`` with per-row gating.

    psi (S, dim), radius (S,), new_emb (S, kc, dim), new_ids (S, kc).
    ``do`` masks which rows insert at all (hit rows pass False and keep
    their state unchanged — LRU stamps included); ``record`` masks the
    (psi, r_a) query record per row (False for degraded back-end
    answers).  The ref tier is a vmap of the scalar ``insert``; the kernel
    tiers run the whole wave's scatter as ONE fused Pallas launch,
    bit-identical per row to the scalar path.
    """
    do, record = _gated_batch(new_ids, do, record)
    be = kdispatch.resolve(backend)
    if be == "ref":
        return _insert_batched_ref(state, cfg, psi, radius, new_emb,
                                   new_ids, do, record)
    new_state, dropped, _ = _insert_batched_kernel(
        state, cfg, psi, radius, new_emb, new_ids, do, record,
        kdispatch.interpret_flag(be))
    return new_state, dropped


@functools.partial(jax.jit, static_argnames=("cfg", "k", "backend"))
def insert_query_batched(state: CacheState, cfg: CacheConfig, psi: jax.Array,
                         radius: jax.Array, new_emb: jax.Array,
                         new_ids: jax.Array, k: int,
                         do: jax.Array | None = None,
                         record: jax.Array | None = None,
                         backend: str | None = None):
    """The serving wave's tail: gated batched insert + post-insert top-k
    query, semantically ``insert_batched`` followed by ``query_batched``.

    On the kernel tiers the pair is ONE fused Pallas launch — the query
    scan scores each cache tile as the insert scatter blends it, so a
    whole ``BatchedEngine`` L1-only miss wave is exactly three launches
    (probe -> miss-search -> insert+query) and a tiered wave four (L1
    probe -> L2 probe -> miss-search -> insert+query).  Returns
    ``((scores, dists, ids, slots), new_state, dropped)``.
    """
    do, record = _gated_batch(new_ids, do, record)
    be = kdispatch.resolve(backend)
    if be == "ref":
        new_state, dropped = _insert_batched_ref(
            state, cfg, psi, radius, new_emb, new_ids, do, record)
        out, new_state = query_batched(new_state, psi, k, backend="ref")
        return out, new_state, dropped
    new_state, dropped, (vals, ids, slots) = _insert_batched_kernel(
        state, cfg, psi, radius, new_emb, new_ids, do, record,
        kdispatch.interpret_flag(be), query_psi=psi, k=k)
    # the scalar query's LRU touch, applied at the post-insert step value
    new_state = _apply_query_touch(new_state, ids, slots)
    return ((vals, emb.distance_from_scores(vals), ids, slots),
            new_state, dropped)


def validate_state(state: CacheState, cfg: CacheConfig, *,
                   n_corpus: int | None = None):
    """Integrity check of a (batched) ``CacheState`` against its layout
    invariants — the fault-domain guard a corrupted session slot is
    quarantined by (``BatchedEngine.quarantine_invalid``) instead of
    poisoning its next wave.

    Checked per row:

    * **counters** — ``0 <= n_docs <= capacity``, ``n_queries >= 0``,
      ``step >= 0``;
    * **occupied prefix** — doc slots ``[0, n_docs)`` hold real ids
      (``>= 0``, and ``< n_corpus`` when given); slots ``[n_docs,
      capacity)`` hold the ``-1`` sentinel;
    * **pad region** — padded doc columns keep their init sentinels
      (id ``-1``, stamp ``0``, scale ``1``) and padded ring slots their
      ``-inf`` radius (the zero-copy launch contract relies on these);
    * **finite payloads** — no NaN/inf in stored embeddings (float
      formats), scales finite and positive, claim radii never NaN or
      ``+inf`` (``-inf`` is the empty/expired-claim sentinel).

    Host-side (numpy) and read-only — call it off the wave hot path.
    Returns ``(ok, problems)``: ``ok`` a bool array over rows (scalar
    for an unbatched state), ``problems`` a list of human-readable
    violation strings.
    """
    batched = np.ndim(np.asarray(state.n_docs)) > 0
    leaves = {f: np.asarray(getattr(state, f)) for f in state._fields}
    if not batched:
        leaves = {f: v[None] for f, v in leaves.items()}
    rows = leaves["n_docs"].shape[0]
    cap, qmax = cfg.capacity, cfg.max_queries
    ok = np.ones((rows,), bool)
    problems: list[str] = []

    def flag(mask, what):
        bad = np.asarray(mask, bool)
        if bad.any():
            ok[bad] = False
            problems.extend(f"row {int(r)}: {what}"
                            for r in np.nonzero(bad)[0])

    n_docs, n_queries, step = (leaves["n_docs"], leaves["n_queries"],
                               leaves["step"])
    flag((n_docs < 0) | (n_docs > cap), "n_docs outside [0, capacity]")
    flag(n_queries < 0, "negative n_queries")
    flag(step < 0, "negative step")
    nd = np.clip(n_docs, 0, cap)[:, None]

    ids = leaves["doc_ids"]
    col = np.arange(ids.shape[1])[None, :]
    occupied, vacant = col < nd, (col >= nd) & (col < cap)
    flag((occupied & (ids < 0)).any(axis=1),
         "sentinel id inside the occupied prefix")
    if n_corpus is not None:
        flag((occupied & (ids >= n_corpus)).any(axis=1),
             "doc id beyond the corpus")
    flag((vacant & (ids != -1)).any(axis=1),
         "non-sentinel id in a vacant slot")
    flag((ids[:, cap:] != -1).any(axis=1), "pad doc slot lost its -1 id")
    flag((leaves["doc_stamp"][:, cap:] != 0).any(axis=1),
         "pad doc slot carries an LRU stamp")
    flag((leaves["doc_scale"][:, cap:] != 1.0).any(axis=1),
         "pad doc slot scale != 1")

    scale = leaves["doc_scale"][:, :cap].astype(np.float32)
    flag((~np.isfinite(scale) | (scale <= 0)).any(axis=1),
         "non-finite or non-positive doc scale")
    qscale = leaves["q_scale"].astype(np.float32)
    flag((~np.isfinite(qscale) | (qscale <= 0)).any(axis=1),
         "non-finite or non-positive query scale")

    rad = leaves["q_radius"].astype(np.float32)
    flag((np.isnan(rad) | (rad == np.inf)).any(axis=1),
         "NaN or +inf claim radius")
    flag((rad[:, qmax:] != -np.inf).any(axis=1),
         "pad ring slot lost its -inf radius sentinel")

    if np.issubdtype(leaves["doc_emb"].dtype, np.integer):
        pass            # int8 payloads cannot encode NaN/inf
    else:
        emb = leaves["doc_emb"][:, :cap].astype(np.float32)
        flag(~np.isfinite(emb).all(axis=(1, 2)),
             "non-finite cached document embedding")
        qemb = leaves["q_emb"].astype(np.float32)
        flag(~np.isfinite(qemb).all(axis=(1, 2)),
             "non-finite claim query embedding")

    return (ok if batched else ok[0]), problems
