"""Public wrappers for the fused wave kernels: zero-copy launch assembly.

Three entry points, all ONE ``pallas_call`` each (the launch-count contract
of the kernel-tier serving wave: probe -> miss-search -> insert+query is
exactly three launches):

  * ``wave_insert_query``   — the serving path: batched insert scatter
                              fused with the post-insert top-k query.
  * ``wave_query_topk``     — query-only (a wave with no misses).
  * ``wave_insert_scatter`` — insert-only (the ``insert_batched`` kernel
                              tier when no query follows).

The wrappers take plain stacked arrays (``core.cache`` orchestrates state
assembly and precomputes write positions/ring slots with the scalar ops'
exact jnp logic).  Since the pre-padded layout (``repro.core.layout``),
the STATE arrays arrive already at the physical extents — capacity a
multiple of the wave tile, feature dim a multiple of the lane, the query
ring a multiple of the sublane, scales f32 — and pass straight into the
launch: no per-launch pad of the O(S * capacity * dim) payload, no slice
back out.  Only per-wave INPUTS (the k_c new documents, the per-session
psi rows) still get lane/sublane-padded, which is O(wave).  Dropped write
positions arrive pre-routed past the physical capacity
(``core.cache._insert_positions``), so a dropped document can never land
in a padded column and leak into the query scan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.layout import LANE, SUBLANE, wave_tile
from repro.kernels.cache_wave.cache_wave import KC_CHUNK, make_wave_kernel
from repro.kernels.knn.knn import lane_pad

__all__ = ["LANE", "SUBLANE", "wave_tile", "wave_query_topk",
           "wave_insert_scatter", "wave_insert_query"]


def _pad_axis(x, axis, mult, value=0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _check_state(doc_emb, doc_scale, tile_c):
    """The zero-copy contract: state arrays must arrive pre-padded (see
    ``core.cache.init_cache``) — the wave wrappers no longer pad them."""
    s, capacity, d = doc_emb.shape
    assert capacity % tile_c == 0, (
        f"capacity {capacity} not a multiple of the wave tile {tile_c}: "
        "pass a pre-padded CacheState (init_cache allocates phys_capacity)")
    assert d % LANE == 0, (
        f"feature dim {d} not a multiple of the lane {LANE}: pass a "
        "pre-padded CacheState (init_cache allocates phys_dim)")
    assert doc_scale.dtype == jnp.float32, (
        f"doc_scale must be stored f32, got {doc_scale.dtype}")


VMEM_LIMIT = 64 * 2 ** 20


def _rows(x):
    """(S, X) -> (S, 1, X): per-row vectors ride (1, 1, X-tile) blocks,
    whose last two dims the TPU lowering accepts (a (1, X) block over an
    (S, X) array it refuses)."""
    return x[:, None, :]


def _launch(*, s, capacity, dp, kc, qmax, k, tile_c, store_dtype,
            radius_dtype, with_insert, with_query, interpret, operands,
            name):
    tiles = capacity // tile_c
    kp = lane_pad(k)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)           # whole (S, 8)
    tile_row = pl.BlockSpec((1, 1, tile_c), lambda i, t: (i, 0, t))
    in_specs = [
        smem,                                                      # ints
        pl.BlockSpec((1, tile_c, dp), lambda i, t: (i, t, 0)),     # payload
        tile_row,                                                  # doc ids
        tile_row,                                                  # scales
    ]
    out_specs, out_shape, scratch = [], [], []
    if with_insert:
        def whole_row(n):
            return pl.BlockSpec((1, 1, n), lambda i, t: (i, 0, 0))

        in_specs += [
            tile_row,                                              # stamps
            smem,                                                  # floats
            pl.BlockSpec((1, kc, dp), lambda i, t: (i, 0, 0)),     # new emb
            whole_row(kc),                                         # emb scale
            whole_row(kc),                                         # new ids
            whole_row(kc),                                         # positions
            pl.BlockSpec((1, 8, dp), lambda i, t: (i, 0, 0)),      # psi store
            pl.BlockSpec((1, qmax, dp), lambda i, t: (i, 0, 0)),   # q_emb
            whole_row(qmax),                                       # q_radius
            whole_row(qmax),                                       # q_scale
        ]
        out_specs += [
            pl.BlockSpec((1, tile_c, dp), lambda i, t: (i, t, 0)),
            tile_row, tile_row, tile_row,
            pl.BlockSpec((1, qmax, dp), lambda i, t: (i, 0, 0)),
            whole_row(qmax), whole_row(qmax),
        ]
        out_shape += [
            jax.ShapeDtypeStruct((s, capacity, dp), store_dtype),
            jax.ShapeDtypeStruct((s, 1, capacity), jnp.int32),
            jax.ShapeDtypeStruct((s, 1, capacity), jnp.int32),
            jax.ShapeDtypeStruct((s, 1, capacity), jnp.float32),
            jax.ShapeDtypeStruct((s, qmax, dp), store_dtype),
            jax.ShapeDtypeStruct((s, 1, qmax), radius_dtype),
            jax.ShapeDtypeStruct((s, 1, qmax), jnp.float32),
        ]
    if with_insert:
        scratch += [pltpu.VMEM((tile_c, dp), jnp.float32)]      # scatter acc
    if with_query:
        in_specs += [
            pl.BlockSpec((1, 8, dp), lambda i, t: (i, 0, 0)),      # psi f32
        ]
        out_specs += [pl.BlockSpec((1, 1, kp), lambda i, t: (i, 0, 0))] * 3
        out_shape += [
            jax.ShapeDtypeStruct((s, 1, kp), jnp.float32),
            jax.ShapeDtypeStruct((s, 1, kp), jnp.int32),
            jax.ShapeDtypeStruct((s, 1, kp), jnp.int32),
        ]
        scratch += [
            pltpu.VMEM((1, kp), jnp.float32),
            pltpu.VMEM((1, kp), jnp.int32),
            pltpu.VMEM((1, kp), jnp.int32),
        ]
    kernel = make_wave_kernel(tile_c=tile_c, tiles=tiles, kc=kc, k=k,
                              with_insert=with_insert, with_query=with_query)
    # one pass over the (S, capacity, D) cache payload, read + (on insert)
    # written back, plus the k_c batch and the tiny per-session blocks
    itemsize = jnp.dtype(store_dtype).itemsize
    payload = s * capacity * (dp * itemsize * (2 if with_insert else 1) + 12)
    batch = s * kc * (dp * itemsize + 12) if with_insert else 0
    cost = pl.CostEstimate(
        flops=2 * s * capacity * dp * ((kc if with_insert else 0)
                                       + (1 if with_query else 0)),
        bytes_accessed=payload + batch, transcendentals=0)
    outs = list(pl.pallas_call(
        kernel,
        grid=(s, tiles),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        cost_estimate=cost,
        # a double-buffered fp32 payload tile in and out plus the k_c batch
        # block (~24 MB at 512 x 896 x 1000) passes the 16 MB default scoped
        # VMEM limit; v5e has 128 MB of VMEM
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(*operands))
    # back to the CacheState row layout; query answers trimmed to k
    if with_insert:
        outs[1:4] = [x.reshape(s, capacity) for x in outs[1:4]]
        outs[5:7] = [x.reshape(s, qmax) for x in outs[5:7]]
    if with_query:
        outs[-3:] = [x.reshape(s, kp)[:, :k] for x in outs[-3:]]
    return outs


def _psi_block(psi, dp):
    """(S, D) -> (S, 8, Dp): sublane-friendly single-row block, row 0 live.
    A per-wave O(S * dim) pad — one pad, never O(capacity)."""
    return jnp.pad(psi[:, None, :],
                   ((0, 0), (0, SUBLANE - 1), (0, dp - psi.shape[1])))


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def wave_query_topk(doc_emb, doc_ids, doc_scale, psi, k: int,
                    interpret: bool = False):
    """Batched top-k over cached docs, one launch.  doc_emb (S, Cp, Dp)
    pre-padded payload (any storage dtype), doc_ids (S, Cp) with -1 empties
    (padded columns included), doc_scale (S, Cp) f32, psi (S, dim) f32 —
    the one per-wave input, lane-padded here.  Returns (vals (S, k) f32 —
    -inf past the cached docs, ids (S, k) int32 — -1 there, slots (S, k)
    int32) with the ref tier's exact slot ordering (stable top-k, empties
    ascending — so padded columns, which sit past every logical slot, are
    unreachable while k <= the logical capacity)."""
    s, capacity, d = doc_emb.shape
    assert k <= capacity, f"k={k} > capacity={capacity} (ref tier errors too)"
    tile_c = wave_tile(capacity)
    _check_state(doc_emb, doc_scale, tile_c)
    ints = jnp.zeros((s, 8), jnp.int32)
    operands = (ints, doc_emb, _rows(doc_ids), _rows(doc_scale),
                _psi_block(psi.astype(jnp.float32), d))
    return _launch(
        s=s, capacity=capacity, dp=d, kc=0, qmax=0, k=k,
        tile_c=tile_c, store_dtype=doc_emb.dtype, radius_dtype=jnp.float32,
        with_insert=False, with_query=True, interpret=interpret,
        name="wave_query_topk",
        operands=operands)


def _insert_operands(doc_emb, doc_ids, doc_stamp, doc_scale, q_emb, q_radius,
                     q_scale, emb_q, emb_scale, new_ids, pos, psi_q,
                     psi_scale, radius, rec, qslot, step_ins, tile_c):
    """Assemble the insert launch operands.  State arrays pass through
    untouched (pre-padded layout); only the per-wave inputs — the k_c new
    rows, their metadata, and the psi record row — get lane/sublane pads.
    ``pos`` arrives with drops already routed to the PHYSICAL capacity, so
    the pad value for the position block is simply ``capacity``."""
    s, capacity, d = doc_emb.shape
    _check_state(doc_emb, doc_scale, tile_c)
    assert q_emb.shape[1] % SUBLANE == 0 and q_emb.shape[2] == d, (
        f"query ring {q_emb.shape} not pre-padded to (*, {SUBLANE}-multiple,"
        f" {d}): pass a pre-padded CacheState")
    assert q_scale.dtype == jnp.float32, "q_scale must be stored f32"
    assert emb_q.shape[2] <= d and psi_q.shape[1] <= d
    # one fused pad per wave input (rows to the kernel's scatter step: the
    # sublane for a batch of at most KC_CHUNK rows, KC_CHUNK above that;
    # features to the state's physical width) — two chained pads would
    # materialize twice
    kc = emb_q.shape[1]
    mult = SUBLANE if kc <= KC_CHUNK else KC_CHUNK
    emb_p = jnp.pad(emb_q, ((0, 0), (0, (-kc) % mult),
                            (0, d - emb_q.shape[2])))
    kc_p = emb_p.shape[1]
    escale = _pad_axis(emb_scale.astype(jnp.float32), 1, mult,
                       value=1.0)[:, None, :]
    nids = _pad_axis(new_ids.astype(jnp.int32), 1, mult,
                     value=-1)[:, None, :]
    pos_p = _pad_axis(pos.astype(jnp.int32), 1, mult,
                      value=capacity)[:, None, :]
    psis = jnp.pad(psi_q[:, None, :],
                   ((0, 0), (0, SUBLANE - 1), (0, d - psi_q.shape[1])))
    ints = jnp.stack([
        jnp.zeros((s,), jnp.int32),
        jnp.asarray(rec, jnp.int32),
        jnp.asarray(qslot, jnp.int32),
        jnp.asarray(step_ins, jnp.int32),
    ] + [jnp.zeros((s,), jnp.int32)] * 4, axis=1)
    floats = jnp.stack([
        jnp.asarray(radius, jnp.float32),
        jnp.asarray(psi_scale, jnp.float32),
    ] + [jnp.zeros((s,), jnp.float32)] * 6, axis=1)
    operands = (ints, doc_emb, _rows(doc_ids), _rows(doc_scale),
                _rows(doc_stamp), floats, emb_p, escale, nids, pos_p, psis,
                q_emb, _rows(q_radius), _rows(q_scale))
    dims = dict(s=s, capacity=capacity, dp=d, kc=kc_p, qmax=q_emb.shape[1],
                tile_c=tile_c, store_dtype=doc_emb.dtype,
                radius_dtype=q_radius.dtype)
    return operands, dims


@functools.partial(jax.jit, static_argnames=("interpret",))
def wave_insert_scatter(doc_emb, doc_ids, doc_stamp, doc_scale, q_emb,
                        q_radius, q_scale, emb_q, emb_scale, new_ids, pos,
                        psi_q, psi_scale, radius, rec, qslot, step_ins,
                        interpret: bool = False):
    """Batched insert scatter, one launch over the pre-padded state.
    ``pos`` (S, kc) are precomputed write positions (== the physical
    capacity for dropped/masked docs); ``psi_q`` / ``psi_scale`` /
    ``radius`` the per-session query record, written at ring slot
    ``qslot`` when ``rec``; ``step_ins`` stamps the written rows.
    Returns the 7 post-insert doc/q arrays at the physical extents,
    unsliced (counters stay with the caller)."""
    tile_c = wave_tile(doc_emb.shape[1])
    operands, dims = _insert_operands(
        doc_emb, doc_ids, doc_stamp, doc_scale, q_emb, q_radius, q_scale,
        emb_q, emb_scale, new_ids, pos, psi_q, psi_scale, radius, rec,
        qslot, step_ins, tile_c)
    return _launch(**dims, k=0, with_insert=True, with_query=False,
                   interpret=interpret, operands=operands,
                   name="wave_insert_scatter")


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def wave_insert_query(doc_emb, doc_ids, doc_stamp, doc_scale, q_emb,
                      q_radius, q_scale, emb_q, emb_scale, new_ids, pos,
                      psi_q, psi_scale, radius, rec, qslot, step_ins,
                      psi, k: int, interpret: bool = False):
    """The fused serving wave: insert scatter + post-insert top-k query in
    ONE launch — the query scan scores each freshly blended tile, so the
    whole wave costs a single pass over the cache payload.  Returns
    (doc/q arrays as ``wave_insert_scatter``, (vals, ids, slots))."""
    s, capacity, d = doc_emb.shape
    assert k <= capacity, f"k={k} > capacity={capacity} (ref tier errors too)"
    tile_c = wave_tile(capacity)
    operands, dims = _insert_operands(
        doc_emb, doc_ids, doc_stamp, doc_scale, q_emb, q_radius, q_scale,
        emb_q, emb_scale, new_ids, pos, psi_q, psi_scale, radius, rec,
        qslot, step_ins, tile_c)
    operands = operands + (_psi_block(psi.astype(jnp.float32), d),)
    outs = _launch(**dims, k=k, with_insert=True, with_query=True,
                   interpret=interpret, operands=operands,
                   name="wave_insert_query")
    return tuple(outs[:7]), tuple(outs[7:])
