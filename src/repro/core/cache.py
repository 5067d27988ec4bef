"""The paper's contribution: a client-side document-embedding metric cache.

This module is the **L1 tier** of the cache hierarchy: the stateful host
wrappers (``MetricCache`` for one conversation, ``BatchedMetricCache`` for
a stacked wave of concurrent sessions) over the tier-agnostic functional
ops that now live in ``repro.core.cache_ops`` — probe / query / insert
over a tile-aligned ``CacheState``.  The cross-session **L2 tier**
(``repro.core.shared.SharedTier``) owns the same ops over the same state
layout, so the hierarchy shares one kernel contract end to end.

Everything that used to be defined here (``CacheState``, ``CacheConfig``,
``init_cache``, the scalar and batched ops) is re-exported below for
backward compatibility — ``from repro.core.cache import probe_batched``
keeps working — but new code should import the functional ops from
``repro.core.cache_ops`` and reserve this module for the host wrappers.

See ``cache_ops`` for the state layout, quantized-storage rules, the
paper-faithful semantics (LowQuality test of Eq. 3/4; no eviction), and
the batched-variant / kernel-dispatch contract.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp

from repro.core.cache_ops import (  # noqa: F401  (re-exported contract)
    CacheConfig,
    CacheState,
    ProbeResult,
    dedup_mask,
    evicting_positions,
    init_batched_cache,
    init_cache,
    insert,
    insert_batched,
    insert_positions,
    insert_query_batched,
    pad_features,
    probe,
    probe_batched,
    query,
    query_batched,
    reset_sessions,
    store_rows,
    validate_state,
)
from repro.core.cache_ops import (  # noqa: F401  (internal helpers kernels use)
    _apply_query_touch,
    _gated_batch,
    _insert_batched_kernel,
    _insert_batched_ref,
)
from repro.kernels import dispatch as kdispatch

# Pre-extraction private names, kept so downstream code and docstrings that
# referred to e.g. ``core.cache._insert_positions`` stay truthful.
_pad_features = pad_features
_store_rows = store_rows
_dedup_mask = dedup_mask
_evicting_positions = evicting_positions
_insert_positions = insert_positions

__all__ = ["CacheState", "CacheConfig", "ProbeResult", "init_cache",
           "probe", "query", "insert", "MetricCache", "init_batched_cache",
           "reset_sessions", "probe_batched", "query_batched",
           "insert_batched", "insert_query_batched", "BatchedMetricCache",
           "validate_state"]


@jax.jit
def _scatter_rows(state: CacheState, idx: jax.Array,
                  sub: CacheState) -> CacheState:
    return jax.tree_util.tree_map(
        lambda full, part: full.at[idx].set(part, mode="drop"), state, sub)


class MetricCache:
    """Stateful host wrapper over the functional cache ops."""

    def __init__(self, cfg: CacheConfig):
        self.cfg = cfg
        self.state = init_cache(cfg)
        self.total_dropped = 0

    def reset(self):
        self.state = init_cache(self.cfg)
        self.total_dropped = 0

    @property
    def n_docs(self) -> int:
        return int(self.state.n_docs)

    @property
    def n_queries(self) -> int:
        """Number of *valid* query records (the ring holds the newest)."""
        return int(min(int(self.state.n_queries), self.cfg.max_queries))

    @property
    def total_queries(self) -> int:
        """Total queries ever recorded, including ring-overwritten ones."""
        return int(self.state.n_queries)

    def probe(self, psi, epsilon=None, use_kernel: bool | None = None
              ) -> ProbeResult:
        eps = self.cfg.epsilon if epsilon is None else epsilon
        be = kdispatch.default_backend()
        if use_kernel is None:  # serving default: follow the dispatch tier
            use_kernel = be != "ref"
        if use_kernel:  # fused Pallas probe (TPU; interpret elsewhere)
            from repro.kernels.cache_probe.ops import cache_probe
            st = self.state
            hit, r_hat, idx = cache_probe(
                st.q_emb, psi, st.q_radius, st.n_queries, eps,
                q_scale=st.q_scale, max_queries=self.cfg.max_queries,
                interpret=(None if be == "ref"
                           else kdispatch.interpret_flag(be)))
            return ProbeResult(hit, r_hat, idx)
        return probe(self.state, psi, eps, max_queries=self.cfg.max_queries)

    def query(self, psi, k: int):
        out, self.state = query(self.state, psi, k)
        return out

    def insert(self, psi, radius, new_emb, new_ids, record=True):
        self.state, dropped = insert(self.state, self.cfg, psi, radius,
                                     new_emb, new_ids, record)
        self.total_dropped += int(dropped)

    def memory_bytes(self) -> int:
        """Worst-case occupancy (paper RQ1.C): embeddings dominate — a
        bf16 / int8 ``store_dtype`` cuts the dominant term 2x / 4x."""
        s = self.state
        return sum(int(x.size) * x.dtype.itemsize for x in
                   (s.doc_emb, s.doc_ids, s.doc_stamp, s.q_emb, s.q_radius,
                    s.doc_scale, s.q_scale))


class BatchedMetricCache:
    """Stateful host wrapper over the row-batched functional ops.

    The rows of the stacked ``CacheState`` are SESSIONS here (the L1 tier);
    ``repro.core.shared.SharedTier`` stacks the same state over SHARDS —
    same ops, same kernels, different row meaning.

    Every write of ``state`` is a read-modify-write of the whole stack, and
    the serving path makes them from several threads (a session opens on
    its caller's thread while the scheduler's worker writes a wave back),
    so each holds one lock: without it one write is lost, and a session
    is answered from its slot's previous occupant.
    """

    def __init__(self, cfg: CacheConfig, n_sessions: int):
        self.cfg = cfg
        self.n_sessions = n_sessions
        self.state = init_batched_cache(cfg, n_sessions)
        self.total_dropped = 0
        self._write = threading.Lock()

    def reset(self, sessions=None):
        """Reset all sessions, or just the given session indices."""
        if sessions is None:
            with self._write:
                self.state = init_batched_cache(self.cfg, self.n_sessions)
                self.total_dropped = 0
            return
        # write only the target rows (a fresh full stacked state per open
        # would make opening S sessions O(S^2) in state traffic)
        idx = jnp.asarray(sessions)
        fresh = init_cache(self.cfg)
        with self._write:
            self.state = jax.tree_util.tree_map(
                lambda full, one: full.at[idx].set(one), self.state, fresh)

    @property
    def n_docs(self):
        return jax.device_get(self.state.n_docs)

    @property
    def n_queries(self):
        return jax.device_get(
            jnp.minimum(self.state.n_queries, self.cfg.max_queries))

    def gather(self, sessions) -> CacheState:
        """Sub-state holding only the given session indices (a wave)."""
        idx = jnp.asarray(sessions)
        return jax.tree_util.tree_map(lambda x: x[idx], self.state)

    def scatter(self, sessions, sub: CacheState):
        """Write a wave's updated sub-state back into the stacked state:
        row j of ``sub`` lands in session ``sessions[j]``; an entry equal
        to ``n_sessions`` drops its row (padded or failed wave rows), so
        the caller never gathers a trimmed copy of ``sub`` first."""
        idx = jnp.asarray(sessions)
        with self._write:
            self.state = _scatter_rows(self.state, idx, sub)

    def probe(self, psi, epsilon=None, backend=None) -> ProbeResult:
        eps = self.cfg.epsilon if epsilon is None else epsilon
        return probe_batched(self.state, psi, eps, backend=backend,
                             max_queries=self.cfg.max_queries)

    def query(self, psi, k: int, backend=None):
        with self._write:
            out, self.state = query_batched(self.state, psi, k,
                                            backend=backend)
        return out

    def insert(self, psi, radius, new_emb, new_ids, do=None, record=None,
               backend=None):
        with self._write:
            self.state, dropped = insert_batched(
                self.state, self.cfg, psi, radius, new_emb, new_ids, do,
                record, backend=backend)
        self.total_dropped += int(dropped.sum())

    def memory_bytes(self) -> int:
        s = self.state
        return sum(int(x.size) * x.dtype.itemsize for x in
                   (s.doc_emb, s.doc_ids, s.doc_stamp, s.q_emb, s.q_radius,
                    s.doc_scale, s.q_scale))
