"""Fused corpus-scan + top-k Pallas TPU kernels — the paper's hot spot.

The exhaustive FAISS scan (paper Table 3, ~1 s / 216-query batch on a Xeon)
is re-thought for the TPU memory hierarchy:

  * stream the corpus through VMEM one (TILE_N, D) tile at a time,
  * scores = Q @ tile.T on the MXU (D is zero-padded to a lane multiple by
    the wrapper, which leaves inner products unchanged),
  * a running top-k kept on chip, so the full (B, N) score matrix is NEVER
    materialized in HBM.

Two merge strategies:

  * ``knn_fused_topk`` — the serving kernel, rebuilt (ISSUE 5) as an
    explicitly *double-buffered DMA pipeline*: the corpus, ids, and scales
    stay in HBM (``memory_space=ANY``) and the kernel issues its own
    ``make_async_copy`` HBM->VMEM transfers into two scratch slots — tile
    t+1's ``(docs, ids, scale)`` copy is launched *before* tile t is
    scored, so data movement overlaps the MXU/VPU work instead of
    serializing with it.  The running global top-k is an *unsorted* (B, k)
    carry held in VMEM scratch across tiles.  A tile merges only the
    scores that beat its row's current k-th best: the tile's candidate
    count above that threshold (the max over rows) bounds a loop that
    moves the best remaining tile score into the carry's worst lane, so a
    late tile whose scores all lose costs no merge step at all.  The carry
    is ordered best first once, at the end, and only the final (B, k)
    answer is ever written to HBM; a third output counts the merge steps
    run.  The corpus is read exactly once and the candidate traffic of
    the two-stage scheme (O(tiles * B * k) rows through HBM plus a second
    launch to merge) disappears entirely.  A ``pl.CostEstimate`` sized
    from the quant-aware byte counts tells the scheduler the launch is
    bandwidth-bound.  Validity is data-driven — scores at sentinel rows
    (id < 0) are masked to -inf — so one kernel serves unpadded, padded,
    and device-sharded corpora, and extracted -inf candidates report id
    -1, never a clipped real id.
  * ``knn_tile_topk`` — the original two-stage scheme (per-tile top-k
    candidates by iterative max-extract on the VPU, written to HBM;
    cross-tile ``lax.top_k`` merge in the wrapper), kept
    as the A/B baseline for ``kernel_bench`` and for the k > tile_n regime.
    Its tile stream rides the grid pipeline (which Mosaic double-buffers
    automatically) with the same ``pl.CostEstimate`` hints attached.

Arithmetic intensity of the scan is ~2*B flops per corpus byte, so for
serving batches (B <= 256 at fp32) the kernel is HBM-bandwidth bound; the
design goal is to stream at full bandwidth, which the single-pass pipelined
structure achieves.  Quantized corpora (``repro.core.quant``: bf16
payloads, or int8 payloads with an fp32 per-document scale) stream 2x / 4x
more documents per HBM byte.  Two scoring rules:

  * dequantize-first (the default, and the ref/parity tier): the payload is
    cast to f32 *in VMEM*, the dot runs in f32, and the per-document scale
    is applied score-side — every dispatch tier is rank-identical at a
    fixed dtype.
  * native int8 MXU dot (``int8_dot=True``, int8 corpora only): queries are
    quantized per-row to int8 by the wrapper and the dot runs int8 x int8
    with int32 accumulation (``preferred_element_type=jnp.int32``) — the
    MXU's native narrow mode — then both the per-query and per-document
    fp32 scales are applied score-side.  Rankings vs the fp32 corpus are
    gated at the established int8 floor (>= 0.90 rank overlap); ref and
    kernel tiers still agree exactly with *each other* because they share
    this rule bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")
INT_MIN = int(jnp.iinfo(jnp.int32).min)
INT_MAX = int(jnp.iinfo(jnp.int32).max)
LANE = 128
# f32 dots run at full f32 precision on every tier (the MXU's default would
# round f32 operands to bf16), so kernel and ref rankings agree on the chip
HIGHEST = jax.lax.Precision.HIGHEST


def lane_pad(k: int) -> int:
    """Carry width: k rounded up to the lane multiple, so every carry
    update is a full-width masked select (Mosaic refuses stores at a
    dynamic lane offset that it cannot prove 128-aligned)."""
    return -(-k // LANE) * LANE


def extract_topk(pool_v, pool_ids, out_v, out_ids, *, k: int, knock: float,
                 empty_id=None):
    """Move the ``k`` best entries of a candidate pool, best first, into
    columns 0..k-1 of the lane-padded ``out`` carries.

    ``pool_v`` (B, P) holds the values and ``pool_ids`` a tuple of int32
    (B, P) payloads riding with them (ids, slots); ``out_v`` / ``out_ids``
    are the matching (B, Kp) carries.  Iterative max-extract on the VPU;
    argmax picks among equal values (the first in the interpreter, not
    always the first on the chip).  Each step writes
    its column with a full-width ``jnp.where`` (lane-aligned, unlike a
    one-column store), then knocks the winner out of the pool with
    ``knock``.  ``empty_id`` (when given) replaces the payloads of an
    extracted -inf value.  Returns the new carries.
    """
    col = jax.lax.broadcasted_iota(jnp.int32, pool_v.shape, 1)
    out_col = jax.lax.broadcasted_iota(jnp.int32, out_v.shape, 1)

    def extract(j, state):
        s, ov, ois = state
        m = jnp.max(s, axis=1, keepdims=True)                   # (B, 1)
        a = jnp.argmax(s, axis=1).astype(jnp.int32)[:, None]    # (B, 1)
        hit = col == a
        put = out_col == j
        new_ois = []
        for pool_i, oi in zip(pool_ids, ois):
            # one-hot reduce instead of a gather: payload at the extracted
            # column
            picked = jnp.sum(jnp.where(hit, pool_i, 0), axis=1,
                             keepdims=True).astype(jnp.int32)
            if empty_id is not None:
                picked = jnp.where(m == NEG_INF, empty_id, picked)
            new_ois.append(jnp.where(put, picked, oi))
        return jnp.where(hit, knock, s), jnp.where(put, m, ov), tuple(new_ois)

    _, out_v, out_ids = jax.lax.fori_loop(
        0, k, extract, (pool_v, out_v, tuple(out_ids)))
    return out_v, out_ids


def _masked_scores(q, docs, ids, scale, q_scale=None, *, int8_dot=False):
    """(B, TILE_N) scores with sentinel rows (id < 0) masked to -inf.

    Dequantize-first rule (default): ``docs`` (fp32 / bf16 / int8 payload)
    is cast to f32 before the dot (dequantization happens here, in VMEM)
    and ``scale`` — the (1, TILE_N) per-document f32 score multiplier,
    all-ones for unquantized corpora — is applied to the scores, matching
    the shared ``quant.scale_scores`` rule of the ref tier bit for bit.

    int8-MXU rule (``int8_dot``): ``q`` is an int8 payload with
    ``q_scale`` its (B, 1) f32 per-query multiplier; the dot runs int8 x
    int8 with int32 accumulation and both scales apply score-side, in a
    fixed association order — ``(f32(acc) * q_scale) * scale`` — shared
    with the ref tier so tiers agree bitwise.
    """
    if int8_dot:
        acc = jax.lax.dot_general(
            q, docs, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)          # (B, TILE_N) exact
        scores = (acc.astype(jnp.float32) * q_scale) * scale
    else:
        scores = jax.lax.dot_general(
            q.astype(jnp.float32), docs.astype(jnp.float32),
            (((1,), (1,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32)        # (B, TILE_N)
        scores = scores * scale
    return jnp.where(ids < 0, NEG_INF, scores)


def _merge_tile_into_carry(scores, ids, base, pool, carry_v, carry_i,
                           carry_p, *, k: int):
    """Merge one tile's (B, TILE_N) scores into the unsorted (B, Kp) carry.

    The carry holds each row's k best ``(score, id)`` seen so far in no
    order, with ``carry_p`` the corpus position of each (``base`` is the
    tile's first position).  Lanes k..Kp-1 are lane padding: they never
    take a candidate and never count as the row's worst entry.  Only a
    score strictly above the row's worst carry entry can enter, so the
    loop runs ``min(k, max over rows of count(score > worst))`` steps —
    none at all for a tile whose scores all lose.  Each step takes every
    row's best remaining tile score from ``pool`` (of equal ones, the
    earliest) and, where it beats the row's worst entry, writes it over
    that entry; among equal worst entries the one at the latest position
    goes.  The carry therefore always holds the k best by (score
    descending, position ascending) — the stable ``lax.top_k`` rule of the
    ref tier.  Returns the number of steps run.
    """
    live = jax.lax.broadcasted_iota(jnp.int32, (1, carry_v.shape[1]), 1) < k
    col = jax.lax.broadcasted_iota(jnp.int32, ids.shape, 1)

    def worst():
        return jnp.min(jnp.where(live, carry_v[...], jnp.inf), axis=1,
                       keepdims=True)

    pool[...] = scores
    above = jnp.sum((scores > worst()).astype(jnp.int32), axis=1,
                    keepdims=True)
    steps = jnp.minimum(jnp.max(above), k)

    def step(_, unused):
        s = pool[...]
        best = jnp.max(s, axis=1, keepdims=True)                # (B, 1)
        # the first column holding it: argmax breaks ties by column in the
        # interpreter but not on the chip
        a = jnp.min(jnp.where(s == best, col, s.shape[1]), axis=1,
                    keepdims=True)                              # (B, 1)
        hit = col == a
        pool[...] = jnp.where(hit, NEG_INF, s)
        w = worst()
        cv, cp = carry_v[...], carry_p[...]
        evict = jnp.max(jnp.where(live & (cv == w), cp, INT_MIN), axis=1,
                        keepdims=True)
        put = (best > w) & (cp == evict)
        # one-hot reduce instead of a gather: the id at the taken column
        new_id = jnp.sum(jnp.where(hit, ids, 0), axis=1, keepdims=True)
        carry_v[...] = jnp.where(put, best, cv)
        carry_i[...] = jnp.where(put, new_id, carry_i[...])
        carry_p[...] = jnp.where(put, base + a, cp)
        return unused

    jax.lax.fori_loop(0, steps, step, 0)
    return steps


def _order_carry(cv, ci, cp, out_v, out_i, *, k: int):
    """Write the carry's k best, best first, into columns 0..k-1 of the
    (B, Kp) outputs; equal scores go in corpus-position order.

    Positions are distinct in every lane (empty lanes hold negative
    ones), so each step takes exactly one lane.  Empty lanes hold -inf
    with id -1, which is what a row with fewer than k valid documents
    reports past its last one.
    """
    col = jax.lax.broadcasted_iota(jnp.int32, cv.shape, 1)

    def extract(j, state):
        v, p, ov, oi = state
        m = jnp.max(v, axis=1, keepdims=True)
        first = jnp.min(jnp.where(v == m, p, INT_MAX), axis=1, keepdims=True)
        hit = p == first
        picked = jnp.sum(jnp.where(hit, ci, 0), axis=1, keepdims=True)
        put = col == j
        return (jnp.where(hit, NEG_INF, v), jnp.where(hit, INT_MAX, p),
                jnp.where(put, m, ov), jnp.where(put, picked, oi))

    _, _, out_v[...], out_i[...] = jax.lax.fori_loop(
        0, k, extract, (cv, cp, jnp.full(cv.shape, NEG_INF, jnp.float32),
                        jnp.full(ci.shape, -1, jnp.int32)))


def _fused_kernel(q_ref, qscale_ref, docs_hbm, ids_hbm, scale_hbm,
                  out_vals_ref, out_idx_ref, out_steps_ref,
                  docs_buf, ids_buf, scale_buf, pool, carry_v, carry_i,
                  carry_p, docs_sem, ids_sem, scale_sem,
                  *, k: int, tile_n: int, tiles: int, int8_dot: bool):
    """Single launch: double-buffered HBM->VMEM tile pipeline + on-chip merge.

    The corpus operands live in HBM (``memory_space=ANY``); two VMEM
    scratch slots per operand hold the in-flight and the in-use tile.  Tile
    t+1's three DMAs start before tile t is scored, so the MXU never waits
    on HBM except for the very first tile (and the autotuner budgets VMEM
    for exactly these two resident tiles).  ``out_steps_ref`` (SMEM)
    receives the merge steps run over all tiles.
    """
    carry_v[...] = jnp.full(carry_v.shape, NEG_INF, jnp.float32)
    carry_i[...] = jnp.full(carry_i.shape, -1, jnp.int32)
    # distinct negative positions for the empty lanes
    carry_p[...] = -1 - jax.lax.broadcasted_iota(jnp.int32, carry_p.shape, 1)
    out_steps_ref[0] = 0

    def tile_dmas(slot, t):
        return (
            pltpu.make_async_copy(
                docs_hbm.at[pl.ds(t * tile_n, tile_n)],
                docs_buf.at[slot], docs_sem.at[slot]),
            pltpu.make_async_copy(
                ids_hbm.at[t], ids_buf.at[slot], ids_sem.at[slot]),
            pltpu.make_async_copy(
                scale_hbm.at[t], scale_buf.at[slot], scale_sem.at[slot]),
        )

    for dma in tile_dmas(0, 0):                            # warm-up: tile 0
        dma.start()

    def step(t, _):
        cur = jax.lax.rem(t, 2)
        nxt = jax.lax.rem(t + 1, 2)

        @pl.when(t + 1 < tiles)
        def _prefetch():                                   # overlap t+1 copy
            for dma in tile_dmas(nxt, t + 1):
                dma.start()

        for dma in tile_dmas(cur, t):                      # tile t landed?
            dma.wait()

        scores = _masked_scores(
            q_ref[...], docs_buf[cur], ids_buf[cur], scale_buf[cur],
            qscale_ref[...], int8_dot=int8_dot)            # (B, TILE_N)
        out_steps_ref[0] += _merge_tile_into_carry(
            scores, ids_buf[cur], t * tile_n, pool, carry_v, carry_i, carry_p,
            k=k)
        return 0

    jax.lax.fori_loop(0, tiles, step, 0)
    _order_carry(carry_v[...], carry_i[...], carry_p[...], out_vals_ref,
                 out_idx_ref, k=k)


def _scan_cost(n: int, d: int, b: int, k: int, itemsize: int,
               int8_dot: bool) -> pl.CostEstimate:
    """Quant-aware cost hint: the scan streams the corpus payload once
    (``itemsize`` bytes/element — this is what bf16/int8 shrink), plus the
    int32 id and f32 scale columns, the resident query block, and the
    (B, k) answer; ~2*B*N*D flops (int8-MXU dots cost the same flop count
    at higher native throughput)."""
    q_item = 1 if int8_dot else 4
    return pl.CostEstimate(
        flops=2 * b * n * d,
        bytes_accessed=(n * (d * itemsize + 4 + 4)
                        + b * (d * q_item + 4) + b * k * 8),
        transcendentals=0,
    )


@functools.partial(jax.jit, static_argnames=("k", "tile_n", "interpret",
                                             "int8_dot"))
def knn_fused_topk(docs: jax.Array, doc_ids: jax.Array, queries: jax.Array,
                   k: int, tile_n: int = 1024, interpret: bool = False,
                   scale: jax.Array | None = None,
                   q_scale: jax.Array | None = None, int8_dot: bool = False):
    """Single-launch exact top-k: double-buffered DMA scan, merge on chip.

    docs: (N, D) payload (fp32 / bf16 / int8) padded to a tile_n multiple
    and lane-aligned D; doc_ids: (N,) int32 with -1 on padded/sentinel
    rows; queries: (B, D) f32 — or the (B, D) int8 query payload when
    ``int8_dot`` (with ``q_scale`` its (B,) f32 per-query multiplier);
    scale: (N,) f32 per-document score multipliers (None for an
    unquantized corpus).  Returns (scores (B, k) f32 descending, ids
    (B, k) int32, -1 at -inf positions, and the int32 count of merge steps
    run over all tiles — at most ``tiles * min(k, tile_n)``).  Equal
    scores rank by corpus position.
    """
    n, d = docs.shape
    b = queries.shape[0]
    assert n % tile_n == 0
    tiles = n // tile_n
    # (tiles, 1, TILE_N): each tile's id / scale row is a slice of the
    # untiled leading axis — a one-row slice of a tiled axis is not a legal
    # DMA source on the chip
    ids_3d = doc_ids.reshape(tiles, 1, tile_n)
    if scale is None:
        scale = jnp.ones((n,), jnp.float32)
    scale_3d = scale.astype(jnp.float32).reshape(tiles, 1, tile_n)
    if q_scale is None:
        q_scale = jnp.ones((b,), jnp.float32)
    qscale_col = q_scale.astype(jnp.float32).reshape(b, 1)
    kernel = functools.partial(_fused_kernel, k=k, tile_n=tile_n,
                               tiles=tiles, int8_dot=int8_dot)
    itemsize = jnp.dtype(docs.dtype).itemsize
    kp = lane_pad(k)
    vals, idx, steps = pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),         # queries: resident
            pl.BlockSpec(memory_space=pltpu.VMEM),         # per-query scales
            pl.BlockSpec(memory_space=pl.ANY),             # corpus: HBM
            pl.BlockSpec(memory_space=pl.ANY),             # tile ids: HBM
            pl.BlockSpec(memory_space=pl.ANY),             # doc scales: HBM
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),         # merge steps
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kp), jnp.float32),
            jax.ShapeDtypeStruct((b, kp), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, tile_n, d), docs.dtype),        # double tile buf
            pltpu.VMEM((2, 1, tile_n), jnp.int32),         # double id buf
            pltpu.VMEM((2, 1, tile_n), jnp.float32),       # double scale buf
            pltpu.VMEM((b, tile_n), jnp.float32),          # tile's candidates
            pltpu.VMEM((b, kp), jnp.float32),              # running top-k vals
            pltpu.VMEM((b, kp), jnp.int32),                # running top-k ids
            pltpu.VMEM((b, kp), jnp.int32),                # their positions
            pltpu.SemaphoreType.DMA((2,)),                 # docs DMA sems
            pltpu.SemaphoreType.DMA((2,)),                 # ids DMA sems
            pltpu.SemaphoreType.DMA((2,)),                 # scale DMA sems
        ],
        cost_estimate=_scan_cost(n, d, b, k, itemsize, int8_dot),
        interpret=interpret,
        name="knn_fused_topk",
    )(queries, qscale_col, docs, ids_3d, scale_3d)
    return vals[:, :k], idx[:, :k], steps[0]


def _knn_kernel(q_ref, qscale_ref, docs_ref, ids_ref, scale_ref, out_vals_ref,
                out_idx_ref, *, k: int, tile_n: int, int8_dot: bool):
    """One grid step: score one corpus tile against all queries; emit top-k."""
    tile = pl.program_id(0)
    q = q_ref[...]                      # (B, D) f32 — or int8 payload
    docs = docs_ref[...]                # (TILE_N, D) any dtype
    ids = ids_ref[0]                    # (1, TILE_N) int32
    scale = scale_ref[0]                # (1, TILE_N) f32
    # same data-driven validity as the fused kernel: sentinel rows (id < 0)
    # can never win a per-tile extraction, wherever they sit in the corpus
    scores = _masked_scores(q, docs, ids, scale, qscale_ref[...],
                            int8_dot=int8_dot)            # (B, TILE_N)
    pos = tile * tile_n + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    out_v, (out_i,) = extract_topk(
        scores, (pos,), jnp.full(out_vals_ref.shape[1:], NEG_INF, jnp.float32),
        (jnp.zeros(out_idx_ref.shape[1:], jnp.int32),), k=k, knock=NEG_INF)
    out_vals_ref[0] = out_v
    out_idx_ref[0] = out_i


@functools.partial(jax.jit, static_argnames=("k", "tile_n", "interpret",
                                             "int8_dot"))
def knn_tile_topk(docs: jax.Array, doc_ids: jax.Array, queries: jax.Array,
                  k: int, tile_n: int = 1024, interpret: bool = False,
                  scale: jax.Array | None = None,
                  q_scale: jax.Array | None = None, int8_dot: bool = False):
    """Per-tile top-k candidates (two-stage scheme). docs: (N, D) payload
    (fp32 / bf16 / int8) padded to a tile_n multiple and lane-aligned D;
    doc_ids: (N,) int32 with -1 on sentinel/padded rows (masked to -inf,
    same contract as the fused kernel); queries: (B, D) f32 (int8 payload
    + ``q_scale`` under ``int8_dot``); scale: (N,) f32 per-document score
    multipliers or None. Returns (tiles, B, k) vals + idx; idx are
    *positions* in the padded corpus (a fully-masked extraction can emit
    any position at a -inf value — the wrapper must sentinel those on
    merge).  The tile stream rides the grid pipeline (auto double-buffered
    by Mosaic) with the same quant-aware cost hint as the fused path."""
    n, d = docs.shape
    b = queries.shape[0]
    assert n % tile_n == 0 and k <= tile_n
    tiles = n // tile_n
    # (tiles, 1, TILE_N) rows: a (1, 1, TILE_N) block keeps the last two
    # block dims whole-or-lane-aligned, as the TPU lowering requires
    ids_3d = doc_ids.reshape(tiles, 1, tile_n)
    if scale is None:
        scale = jnp.ones((n,), jnp.float32)
    scale_3d = scale.astype(jnp.float32).reshape(tiles, 1, tile_n)
    if q_scale is None:
        q_scale = jnp.ones((b,), jnp.float32)
    qscale_col = q_scale.astype(jnp.float32).reshape(b, 1)
    kernel = functools.partial(_knn_kernel, k=k, tile_n=tile_n,
                               int8_dot=int8_dot)
    itemsize = jnp.dtype(docs.dtype).itemsize
    kp = lane_pad(k)
    vals, idx = pl.pallas_call(
        kernel,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((b, d), lambda i: (0, 0)),        # queries: resident
            pl.BlockSpec((b, 1), lambda i: (0, 0)),        # per-query scales
            pl.BlockSpec((tile_n, d), lambda i: (i, 0)),   # corpus tile stream
            pl.BlockSpec((1, 1, tile_n), lambda i: (i, 0, 0)),  # tile ids
            pl.BlockSpec((1, 1, tile_n), lambda i: (i, 0, 0)),  # tile scales
        ],
        out_specs=[
            pl.BlockSpec((1, b, kp), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, b, kp), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tiles, b, kp), jnp.float32),
            jax.ShapeDtypeStruct((tiles, b, kp), jnp.int32),
        ],
        cost_estimate=_scan_cost(n, d, b, k, itemsize, int8_dot),
        interpret=interpret,
        name="knn_tile_topk",
    )(queries, qscale_col, docs, ids_3d, scale_3d)
    return vals[..., :k], idx[..., :k]
