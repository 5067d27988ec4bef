"""jit'd public wrapper for the fused kNN Pallas kernels.

Handles backend dispatch (``repro.kernels.dispatch`` tiers: ref / interpret
/ compiled), quantized corpora (``repro.core.quant``: bf16 / int8 payloads
with an optional per-document f32 ``scale`` applied score-side, identically
in every tier), the native int8-MXU-dot tier (``int8_dot``: queries are
quantized per-row to int8 here, in the wrapper, so ref and kernel tiers
score the SAME payloads and stay bit-identical with each other), padding
(corpus rows to the tile multiple with sentinel id -1, feature dim to the
lane multiple, batch to the sublane multiple — all score-preserving), the
width-aware ``tile_n``/``k_eff`` autotuner (the VMEM budget is element-size
dependent AND double-buffered: the pipelined kernel keeps TWO tiles
resident, so an int8 tile still holds ~4x the documents of an fp32 tile
but every dtype's tile halves vs the single-buffered budget), and
sentinel-id hygiene: any -inf candidate (k > n_valid, fully-masked tiles)
reports id -1 — never a padded-row position clipped onto a real document.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.kernels import dispatch
from repro.kernels.knn.knn import NEG_INF, knn_fused_topk, knn_tile_topk

LANE = 128
SUBLANE = 8


def _pad_to(x: jax.Array, axis: int, mult: int, value=0) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.lru_cache(maxsize=None)
def autotune_knn(n: int, d: int, b: int, k: int,
                 itemsize: int = 4) -> tuple[int, int]:
    """Pick (tile_n, k_eff) for a corpus of shape (n, d) and batch (b, k).

    tile_n: largest power of two (<= 4096, >= the sublane multiple, no
    larger than the padded corpus) whose VMEM working set fits a ~6 MB
    budget (half of VMEM).  The working set is sized for the
    double-buffered DMA pipeline: TWO resident corpus tiles at
    ``itemsize`` bytes/element (4 fp32, 2 bf16, 1 int8) plus their id and
    scale columns — tile t+1 streams in while tile t is scored — plus the
    resident f32 query block, the (b, k) carry pair, and the f32 merge
    candidate pool.  Narrower corpus elements buy bigger tiles: the
    streamed-tile term dominates at serving shapes, so tile_n roughly
    doubles at bf16 and again at int8 (and halves across the board vs the
    old single-buffered budget — the price of the prefetch overlap).
    k_eff is the per-tile candidate count of the two-stage scheme
    (min(k, tile_n)).
    """
    dp = d + (-d) % LANE
    bp = b + (-b) % SUBLANE
    cap = max(SUBLANE, 1 << max(n - 1, 1).bit_length())
    tile = min(4096, cap)
    budget = 6 * 2 ** 20

    def working_set(t: int) -> int:
        # 2 payload tiles + 2 (id, scale) column pairs; query block; carry
        # vals+ids; merge pool (vals, ids, col iota) over (b, k + t)
        return (2 * t * (itemsize * dp + 8)
                + 4 * bp * dp + 8 * bp * k + 12 * bp * (k + t))

    while tile > SUBLANE and working_set(tile) > budget:
        tile //= 2
    return tile, min(k, tile)


def _ref_search(docs, doc_ids, queries, k, scale=None, int8_dot=False):
    """Oracle tier: one masked (B, N) score matrix + stable top-k.

    Shares the scan contract's scoring rules: dequantize-first (payload
    cast to f32, f32 dot, per-document ``scale`` applied to the *scores*)
    or, under ``int8_dot``, the int8 x int8 -> int32 dot with both fp32
    scales applied score-side in the kernel's association order.
    """
    if int8_dot:
        qq = quant.quantize(queries, "int8")
        acc = jax.lax.dot_general(
            qq.data, docs, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)
        scores = acc.astype(jnp.float32) * qq.scale[:, None]
    else:
        scores = jnp.matmul(queries.astype(jnp.float32),
                            docs.astype(jnp.float32).T,
                            precision=jax.lax.Precision.HIGHEST)
    if scale is not None:
        scores = scores * scale.astype(jnp.float32)[None, :]
    scores = jnp.where(doc_ids[None, :] < 0, NEG_INF, scores)
    ids = doc_ids
    if k > scores.shape[1]:
        pad = k - scores.shape[1]
        scores = jnp.pad(scores, ((0, 0), (0, pad)), constant_values=NEG_INF)
        ids = jnp.pad(ids, (0, pad), constant_values=-1)
    top_s, pos = jax.lax.top_k(scores, k)
    top_i = jnp.where(jnp.isneginf(top_s), -1, ids[pos])
    return top_s, top_i


@functools.partial(jax.jit, static_argnames=(
    "k", "tile_n", "interpret", "backend", "two_stage", "int8_dot"))
def knn_search(docs: jax.Array, doc_ids: jax.Array, queries: jax.Array, k: int,
               tile_n: int | None = None, interpret: bool | None = None,
               backend: str | None = None, two_stage: bool = False,
               scale: jax.Array | None = None,
               int8_dot: bool | None = None):
    """Top-k MIPS over the corpus. Returns (scores (B, k), ids (B, k)).

    docs: (N, D) transformed embeddings — fp32, or a quantized payload
    (bf16 / int8 from ``repro.core.quant.quantize``) with ``scale`` its
    (N,) f32 per-document score multiplier; doc_ids: (N,) int32 with -1
    marking sentinel/padded rows (use arange for positional); queries:
    (B, D) f32.  Sentinel rows never win top-k; -inf result positions
    carry id -1.  ``backend``: a ``repro.kernels.dispatch`` tier (default:
    compiled on TPU, interpret elsewhere — an explicit kernel call never
    silently degrades to the jnp path; pass backend="ref" for the oracle).
    ``interpret`` is the legacy spelling of backend="interpret".
    ``two_stage`` opts out of the on-chip cross-tile merge (A/B baseline);
    both merge paths share the id-driven validity masking and the
    score-side scale rule.  ``int8_dot`` (None = the ``REPRO_INT8_DOT``
    policy) switches an int8 corpus to the native int8 MXU dot — queries
    quantized per-row here so every tier scores identical payloads;
    ignored on fp32/bf16 corpora.
    """
    if backend is None and interpret is not None:
        backend = "interpret" if interpret else "compiled"
    be = dispatch.resolve(backend, kernel=True)
    use_i8 = quant.resolve_int8_dot(int8_dot, docs.dtype)
    if be == "ref":
        return _ref_search(docs, doc_ids, queries, k, scale=scale,
                           int8_dot=use_i8)

    n, d = docs.shape
    b = queries.shape[0]
    itemsize = jnp.dtype(docs.dtype).itemsize
    if tile_n is None:
        tile_n, k_eff = autotune_knn(n, d, b, k, itemsize)
    else:
        tile_n = min(tile_n, max(SUBLANE, 1 << max(n - 1, 1).bit_length()))
        k_eff = min(k, tile_n)

    docs_p = _pad_to(_pad_to(docs, 1, LANE), 0, tile_n)
    ids_p = _pad_to(doc_ids.astype(jnp.int32), 0, tile_n, value=-1)
    if use_i8:
        # quantize queries ONCE here — kernel and ref tiers then share the
        # exact payload, keeping tier parity bit-for-bit under int8_dot
        qq = quant.quantize(queries, "int8")
        q_p = _pad_to(_pad_to(qq.data, 1, LANE), 0, SUBLANE)
        qscale_p = _pad_to(qq.scale, 0, SUBLANE, value=1.0)
    else:
        q_p = _pad_to(_pad_to(queries, 1, LANE), 0, SUBLANE)
        qscale_p = None
    scale_p = (None if scale is None else
               _pad_to(scale.astype(jnp.float32), 0, tile_n, value=1.0))
    interp = dispatch.interpret_flag(be)

    if not two_stage:
        vals, idx, _ = knn_fused_topk(docs_p, ids_p, q_p, k, tile_n=tile_n,
                                   interpret=interp, scale=scale_p,
                                   q_scale=qscale_p, int8_dot=use_i8)
        return vals[:b], idx[:b]

    vals, idx = knn_tile_topk(docs_p, ids_p, q_p, k_eff, tile_n=tile_n,
                              interpret=interp, scale=scale_p,
                              q_scale=qscale_p, int8_dot=use_i8)
    tiles = vals.shape[0]
    assert tiles * k_eff >= k, (
        f"two-stage candidate pool {tiles}x{k_eff} < k={k}; "
        f"use the fused merge (two_stage=False)")
    vals = vals.transpose(1, 0, 2).reshape(q_p.shape[0], tiles * k_eff)
    idx = idx.transpose(1, 0, 2).reshape(q_p.shape[0], tiles * k_eff)

    top_s, pos = jax.lax.top_k(vals, k)
    top_i = jnp.take_along_axis(idx, pos, axis=1)
    # a fully-masked extraction emits an arbitrary position at a -inf value;
    # sentinel it instead of letting the id lookup alias a real document
    ids = jnp.where(jnp.isneginf(top_s), -1, ids_p[top_i])
    return top_s[:b], ids[:b]
