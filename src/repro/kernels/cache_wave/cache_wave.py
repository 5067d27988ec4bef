"""Fused per-wave cache-op Pallas kernels: batched insert scatter + top-k.

The last two per-wave ops of a ``BatchedEngine`` turn — the k_c-document
insert into each missed session's cache and the top-k query over every
session's cached docs — used to be vmaps of the scalar jnp ops.  Here they
are ONE Pallas launch over the stacked ``CacheState``:

  grid = (sessions, capacity tiles); for each session the kernel streams
  the cache payload through VMEM once, and per tile

    1. **insert blend** (when inserting): a one-hot scatter computed on the
       MXU — ``hit[j, c] = (pos[j] == c)`` over the tile's column range,
       new rows land via ``one_hotᵀ @ new_emb`` and everything else passes
       through — writing the post-insert payload/ids/stamps/scales tile.
       Write positions are *precomputed* by ``core.cache`` with the exact
       jnp position logic of the scalar ``insert`` (dedup, append,
       LRU/ball eviction), so the kernel is a pure scatter and supports
       every eviction policy; a session whose ``do`` mask is False gets
       all-dropped positions and passes through bit-identically (its LRU
       stamps are untouched by construction).  The (psi, r_a) query-record
       ring update — payload row, radius, scale at the ring slot — happens
       on the first tile, gated by the per-session ``record`` flag.
    2. **query scan** (when querying): the freshly blended tile is scored
       against the session's psi (f32 dot + score-side scale, the shared
       quant rule) and merged into a (1, k) VMEM carry — the same
       on-chip cross-tile merge as the fused kNN scan, so the whole
       per-session top-k costs one pass over the cache payload that the
       insert already paid for.

Empty/sentinel slots must surface in the *same order* the ref tier's
stable ``lax.top_k`` yields (ascending slot index after all finite
scores), so the merge uses finite sentinels instead of -inf: empty slots
carry ``BIG_NEG``, the carry initializes to ``INIT`` (< BIG_NEG, so real
empty slots outrank unfilled carry entries), and extracted candidates are
knocked to ``KNOCK`` (< INIT).  argmax's first-match tie-break then walks
empty slots in ascending order across tiles — exactly the ref order — and
the wrapper maps keys <= BIG_NEG back to (-inf, id -1) on emit.

Real scores are inner products of unit-norm embeddings times ~1.0 scales;
anything below -1e37 is physically impossible, so the sentinel bands are
unreachable by data.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.knn.knn import HIGHEST, extract_topk

NEG_INF = float("-inf")
BIG_NEG = -1.0e38    # empty/sentinel slot key (extracted after all finite)
INIT = -2.0e38       # carry initialization (never outranks an empty slot)
KNOCK = -3.0e38      # already-extracted candidate
KC_CHUNK = 128       # batch rows per insert-scatter step (lane multiple)


def make_wave_kernel(*, tile_c: int, tiles: int, kc: int, k: int,
                     with_insert: bool, with_query: bool):
    """Build the fused wave kernel body for a static mode/shape set.

    The ref operand list depends on the static flags; see
    ``repro.kernels.cache_wave.ops`` for the exact ordering (inputs,
    then outputs, then scratch).  Per-row vectors arrive as (1, 1, X)
    blocks of (S, 1, X) arrays and the two scalar tables as whole (S, 8)
    SMEM arrays indexed by the session grid axis — the block layouts the
    TPU lowering accepts.  Every single-element update (ring slot, carry
    column) is a full-width masked select, never a store at a dynamic
    lane offset.
    """

    def kernel(*refs):
        it = iter(refs)
        ints_ref = next(it)                       # SMEM (S, 8) int32
        demb_ref = next(it)                       # (1, TILE_C, D) payload
        dids_ref = next(it)                       # (1, 1, TILE_C) int32
        dscale_ref = next(it)                     # (1, 1, TILE_C) f32
        if with_insert:
            dstamp_ref = next(it)                 # (1, 1, TILE_C) int32
            floats_ref = next(it)                 # SMEM (S, 8) f32
            emb_ref = next(it)                    # (1, KC, D) payload
            escale_ref = next(it)                 # (1, 1, KC) f32
            nids_ref = next(it)                   # (1, 1, KC) int32
            pos_ref = next(it)                    # (1, 1, KC) int32
            psis_ref = next(it)                   # (1, 8, D) payload, row 0
            qemb_ref = next(it)                   # (1, QMAX, D) payload
            qrad_ref = next(it)                   # (1, 1, QMAX) radius dtype
            qsc_ref = next(it)                    # (1, 1, QMAX) f32
        if with_query:
            psi_ref = next(it)                    # (1, 8, D) f32, row 0 live
        if with_insert:
            o_demb = next(it)
            o_dids = next(it)
            o_dstamp = next(it)
            o_dscale = next(it)
            o_qemb = next(it)
            o_qrad = next(it)
            o_qsc = next(it)
        if with_query:
            o_vals = next(it)                     # (1, 1, Kp) f32
            o_ids = next(it)                      # (1, 1, Kp) int32
            o_slots = next(it)                    # (1, 1, Kp) int32
        if with_insert:
            scat_ref = next(it)                   # VMEM (TILE_C, D) f32
        if with_query:
            carry_v = next(it)                    # VMEM (1, Kp) f32
            carry_i = next(it)                    # VMEM (1, Kp) int32
            carry_s = next(it)                    # VMEM (1, Kp) int32

        row = pl.program_id(0)
        t = pl.program_id(1)
        old_emb = demb_ref[0]                     # (TILE_C, D) payload
        old_ids = dids_ref[0]                     # (1, TILE_C)
        old_scale = dscale_ref[0]                 # (1, TILE_C)

        if with_insert:
            chunk_n = min(kc, KC_CHUNK)       # the wrapper pads kc to it
            rec = ints_ref[row, 1]
            qslot = ints_ref[row, 2]
            step_ins = ints_ref[row, 3]
            col = t * tile_c + jax.lax.broadcasted_iota(
                jnp.int32, (chunk_n, tile_c), 1)
            scat_ref[...] = jnp.zeros(scat_ref.shape, jnp.float32)

            def scatter_chunk(c, acc):
                # one chunk_n-row slice of the batch per step: a loop over
                # small one-hot blocks instead of one (KC, TILE_C) block
                # keeps the compiled kernel (and its compile time) small
                hits, ids_acc, sc_acc = acc
                lo = pl.multiple_of(c * chunk_n, chunk_n)
                chunk = pl.ds(lo, chunk_n)
                hit = pos_ref[0, :, chunk].reshape(chunk_n, 1) == col
                # MXU scatter: exactly one hit per written column (positions
                # are unique among kept docs), so the full-precision f32
                # matmul reproduces the row values exactly — including
                # int8/bf16 payloads, whose values round-trip f32 exactly
                scat_ref[...] += jax.lax.dot_general(
                    hit.astype(jnp.float32),
                    emb_ref[0, chunk, :].astype(jnp.float32),
                    (((0,), (0,)), ((), ())), precision=HIGHEST,
                    preferred_element_type=jnp.float32)       # (TILE_C, D)
                ids_c = nids_ref[0, :, chunk].reshape(chunk_n, 1)
                sc_c = escale_ref[0, :, chunk].reshape(chunk_n, 1)
                return (
                    hits + jnp.sum(hit.astype(jnp.int32), axis=0,
                                   keepdims=True),
                    ids_acc + jnp.sum(jnp.where(hit, ids_c, 0), axis=0,
                                      keepdims=True),
                    sc_acc + jnp.sum(jnp.where(hit, sc_c, 0.0), axis=0,
                                     keepdims=True))

            hits, scat_ids, scat_sc = jax.lax.fori_loop(
                0, kc // chunk_n, scatter_chunk,
                (jnp.zeros((1, tile_c), jnp.int32),
                 jnp.zeros((1, tile_c), jnp.int32),
                 jnp.zeros((1, tile_c), jnp.float32)))
            written = hits > 0                                # (1, TILE_C)
            # (1, TILE_C) -> (TILE_C, 1) through int32: Mosaic can re-lay a
            # 32-bit row as a column, not a mask
            written_col = hits.reshape(tile_c, 1) > 0
            blended = jnp.where(written_col, scat_ref[...],
                                old_emb.astype(jnp.float32))
            ids_bl = jnp.where(written, scat_ids, old_ids)
            scale_bl = jnp.where(written, scat_sc, old_scale)
            o_demb[0] = blended.astype(o_demb.dtype)
            o_dids[0] = ids_bl
            o_dscale[0] = scale_bl
            o_dstamp[0] = jnp.where(written, step_ins, dstamp_ref[0])

            @pl.when(t == 0)
            def _ring():                          # (psi, r_a) record ring
                slot_rows = jax.lax.broadcasted_iota(
                    jnp.int32, qemb_ref.shape[1:], 0) == qslot
                slot_lanes = jax.lax.broadcasted_iota(
                    jnp.int32, qrad_ref.shape[1:], 1) == qslot
                put_row = jnp.logical_and(slot_rows, rec == 1)
                put_lane = jnp.logical_and(slot_lanes, rec == 1)
                o_qemb[0] = jnp.where(put_row, psis_ref[0, :1, :],
                                      qemb_ref[0])
                o_qrad[0] = jnp.where(put_lane,
                                      floats_ref[row, 0].astype(o_qrad.dtype),
                                      qrad_ref[0])
                o_qsc[0] = jnp.where(put_lane, floats_ref[row, 1],
                                     qsc_ref[0])
        else:
            blended = old_emb.astype(jnp.float32)
            ids_bl = old_ids
            scale_bl = old_scale

        if with_query:
            @pl.when(t == 0)
            def _init():
                carry_v[...] = jnp.full(carry_v.shape, INIT, jnp.float32)
                carry_i[...] = jnp.full(carry_i.shape, -1, jnp.int32)
                carry_s[...] = jnp.full(carry_s.shape, -1, jnp.int32)

            psi_row = psi_ref[0, :1, :]                        # (1, D)
            scores = jax.lax.dot_general(
                psi_row, blended, (((1,), (1,)), ((), ())),
                precision=HIGHEST,
                preferred_element_type=jnp.float32)            # (1, TILE_C)
            scores = scores * scale_bl
            key = jnp.where(ids_bl < 0, BIG_NEG, scores)
            slot_row = (t * tile_c
                        + jax.lax.broadcasted_iota(jnp.int32, key.shape, 1))

            cand_v = jnp.concatenate([carry_v[...], key], axis=1)
            cand_i = jnp.concatenate([carry_i[...], ids_bl], axis=1)
            cand_s = jnp.concatenate([carry_s[...], slot_row], axis=1)
            carry_v[...], (carry_i[...], carry_s[...]) = extract_topk(
                cand_v, (cand_i, cand_s), carry_v[...],
                (carry_i[...], carry_s[...]), k=k, knock=KNOCK)

            @pl.when(t == tiles - 1)
            def _emit():
                v = carry_v[...]
                live = v > BIG_NEG
                o_vals[0] = jnp.where(live, v, NEG_INF)
                o_ids[0] = jnp.where(live, carry_i[...], -1)
                o_slots[0] = carry_s[...]

    return kernel
