"""Distributed back-end retrieval: the sharded dense index of Fig. 2.

Three layers, smallest to largest deployment:

  * ``make_batched_scorer`` — a table-sharded MIPS top-k closure for use
    *inside* jitted serving cells (recsys retrieval_cand / serve shapes):
    candidate tables stay sharded where their params live, the (B, V) score
    matrix never materializes unsharded.
  * ``sharded_nn`` — exact k-NN with the corpus sharded across a device
    mesh: each device runs the same ``scan_topk`` contract over its slice
    under ``shard_map`` (the jnp streaming scan on the ref tier, the fused
    Pallas kernel on TPU — the SAME implementation single-device search
    uses), then the per-shard top-k are all-gathered and merged.  The merge
    is the device-level analogue of ``serve.router.ShardedRouter._merge``
    and is *bit-identical* in ranking to ``exact_nn`` (contiguous row
    sharding + stable top-k tie-breaking).
  * ``DeviceShard`` / ``make_device_shards`` — host-callable shard handles
    over device-resident corpus slices, signature-compatible with the
    callables ``ShardedRouter`` fronts, so the serving layer's hedging /
    degraded-answer machinery runs unchanged on real device shards.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import layout, quant
from repro.core.metric_index import SearchResult, _as_result, scan_topk
from repro.dist.api import active_mesh
from repro.kernels import dispatch as kdispatch
from repro.serve.telemetry import span

__all__ = ["make_batched_scorer", "sharded_nn", "shard_corpus",
           "DeviceShard", "make_device_shards", "ShardTopK"]


# ------------------------------------------------------- batched scoring

def make_batched_scorer(mesh: Mesh, k: int, table_axes: Sequence[str] = ("model",),
                        batch_axes: Sequence[str] = ()):
    """Build ``scorer(queries, table, n_valid=None) -> (scores, ids)``.

    ``table`` (V, D) is constrained to shard its rows over ``table_axes``,
    ``queries`` (B, D) over ``batch_axes`` — SPMD then keeps the (B, V)
    score matrix sharded over both and lowers the top-k to per-shard top-k
    plus a merge collective.  ``n_valid`` masks trailing table rows (an
    unevenly-sized candidate set scored against a shard-divisible table).
    For use inside jitted cells; ids are row positions in ``table``.
    """
    t_entry = tuple(table_axes) or None
    b_entry = tuple(batch_axes) or None

    def scorer(queries: jax.Array, table: jax.Array,
               n_valid: Optional[int] = None):
        queries = jax.lax.with_sharding_constraint(
            queries, NamedSharding(mesh, P(b_entry, None)))
        table = jax.lax.with_sharding_constraint(
            table, NamedSharding(mesh, P(t_entry, None)))
        scores = queries @ table.T                              # (B, V)
        if n_valid is not None:
            col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
            scores = jnp.where(col < n_valid, scores, -jnp.inf)
        return jax.lax.top_k(scores, min(k, table.shape[0]))

    return scorer


# ----------------------------------------------------- sharded exact k-NN

def _flat_mesh() -> Mesh:
    """A 1-axis mesh over every local device (the default retrieval mesh)."""
    return Mesh(np.asarray(jax.devices()), ("shard",))


def _resolve(mesh: Optional[Mesh], axes: Optional[Sequence[str]]):
    mesh = mesh if mesh is not None else (active_mesh() or _flat_mesh())
    axes = tuple(axes) if axes is not None else tuple(mesh.axis_names)
    n_dev = 1
    for a in axes:
        n_dev *= mesh.shape[a]
    return mesh, axes, n_dev


def _slice_layout(n: int, n_dev: int, chunk: int):
    """(rows per device, effective chunk): equal, chunk-divisible slices."""
    per = -(-n // n_dev)
    chunk_eff = min(chunk, per)
    per = -(-per // chunk_eff) * chunk_eff
    return per, chunk_eff


def _pad_corpus(docs: jax.Array, doc_ids: jax.Array, rows: int,
                scale: Optional[jax.Array] = None):
    """Sentinel-pad (id -1, masked to -inf) to exactly ``rows`` rows."""
    pad = rows - docs.shape[0]
    if pad:
        docs = jnp.concatenate(
            [docs, jnp.zeros((pad, docs.shape[1]), docs.dtype)])
        doc_ids = jnp.concatenate(
            [doc_ids, jnp.full((pad,), -1, jnp.int32)])
        if scale is not None:
            scale = jnp.concatenate(
                [scale, jnp.ones((pad,), scale.dtype)])
    return docs, doc_ids, scale


def _pad_rows(x: np.ndarray, rows: int, value) -> np.ndarray:
    """Host-side sentinel pad of the leading axis to exactly ``rows``."""
    pad = rows - x.shape[0]
    if not pad:
        return x
    return np.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1),
                  constant_values=value)


def shard_corpus(docs, doc_ids, *, scale: Optional[jax.Array] = None,
                 mesh: Optional[Mesh] = None,
                 axes: Optional[Sequence[str]] = None, chunk: int = 4096):
    """Pad a corpus to equal per-device slices and commit it to the mesh.

    ``docs`` may be a quantized payload (bf16 / int8) with ``scale`` its
    per-document f32 score multiplier, which shards row-aligned with it.
    Returns (docs, doc_ids, scale, mesh, chunk_eff) with the rows already
    laid out P(axes) across devices, so repeated ``sharded_nn`` calls (a
    serving index) pay no per-query re-pad or host->mesh re-layout.  The
    padding happens in host memory and each device receives only its own
    slice: no device holds (or computes on) the whole corpus.
    """
    mesh, axes, n_dev = _resolve(mesh, axes)
    docs = np.asarray(docs)
    per, chunk_eff = _slice_layout(docs.shape[0], n_dev, chunk)
    rows = per * n_dev
    docs = _pad_rows(docs, rows, 0)
    doc_ids = _pad_rows(np.asarray(doc_ids, np.int32), rows, -1)
    if scale is not None:
        scale = _pad_rows(np.asarray(scale, np.float32), rows, 1.0)
    entry = axes if len(axes) > 1 else axes[0]
    docs = jax.device_put(docs, NamedSharding(mesh, P(entry, None)))
    doc_ids = jax.device_put(doc_ids, NamedSharding(mesh, P(entry)))
    if scale is not None:
        scale = jax.device_put(scale, NamedSharding(mesh, P(entry)))
    return docs, doc_ids, scale, mesh, chunk_eff


@functools.lru_cache(maxsize=None)
def _sharded_search_fn(mesh: Mesh, axes: Tuple[str, ...], k: int, chunk: int,
                       backend: str, quantized: bool, int8_dot: bool):
    """jit(shard_map) factory, cached per (mesh, axes, k, chunk, backend,
    quantized, int8_dot).

    Per device: the shared ``scan_topk`` contract over the local corpus
    slice (jnp streaming scan or the fused Pallas kernel, per ``backend``;
    a quantized slice carries its per-document scale shard-aligned), then
    an all-gather of the (q, k) partials over the corpus axes and a local
    merge — every device ends with the identical global top-k (replicated
    out).
    """
    axis_entry = axes if len(axes) > 1 else axes[0]

    def merge(part_s, part_i):
        # shard order == row order (contiguous row sharding), so the
        # concatenated candidate list preserves global id order and the
        # stable top_k below breaks ties exactly like a global top_k.
        all_s = jax.lax.all_gather(part_s, axes, axis=1, tiled=True)
        all_i = jax.lax.all_gather(part_i, axes, axis=1, tiled=True)
        top_s, pos = jax.lax.top_k(all_s, k)
        return top_s, jnp.take_along_axis(all_i, pos, axis=1)

    if quantized:
        def local(docs, ids, scale, queries):
            return merge(*scan_topk(docs, ids, queries, k, chunk=chunk,
                                    backend=backend, scale=scale,
                                    int8_dot=int8_dot))
        in_specs = (P(axis_entry, None), P(axis_entry), P(axis_entry),
                    P(None, None))
    else:
        def local(docs, ids, queries):
            return merge(*scan_topk(docs, ids, queries, k, chunk=chunk,
                                    backend=backend, int8_dot=int8_dot))
        in_specs = (P(axis_entry, None), P(axis_entry), P(None, None))

    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=(P(None, None), P(None, None)),
                       check_vma=False)
    return jax.jit(fn)


def sharded_nn(docs, doc_ids, queries, k: int, *, mesh: Optional[Mesh] = None,
               axes: Optional[Sequence[str]] = None, chunk: int = 4096,
               backend: Optional[str] = None,
               scale: Optional[jax.Array] = None,
               int8_dot: Optional[bool] = None) -> SearchResult:
    """Exact k-NN with the corpus sharded over ``mesh`` (all its axes by
    default; the active ``sharding_rules`` mesh, else one flat axis over
    every local device, when ``mesh`` is None).

    The corpus is padded with sentinel rows (id -1, masked to -inf) so each
    device gets an equal, chunk-divisible slice — a no-op when the corpus
    was pre-laid-out with ``shard_corpus`` (the serving-index fast path).
    ``backend`` picks the per-shard scan tier (``kernels.dispatch``; the
    default is compiled-kernel-on-TPU / jnp elsewhere).  ``docs`` may be a
    quantized payload (bf16 / int8) with ``scale`` its (n,) f32
    per-document score multiplier, sharded row-aligned with the corpus;
    ``int8_dot`` (None = the ``REPRO_INT8_DOT`` policy) switches int8
    shards to the native int8-MXU scoring rule, resolved here so every
    shard of one search scores identically.  Rankings are bit-identical to
    ``exact_nn`` on the unpadded corpus at fp32 (tolerance-bound rank
    equality at quantized dtypes).
    """
    mesh, axes, n_dev = _resolve(mesh, axes)
    docs = jnp.asarray(docs)
    doc_ids = jnp.asarray(doc_ids, jnp.int32)
    queries = jnp.asarray(queries)
    if queries.ndim == 1:
        queries = queries[None]

    n = docs.shape[0]
    per, chunk_eff = _slice_layout(n, n_dev, chunk)
    docs, doc_ids, scale = _pad_corpus(docs, doc_ids, per * n_dev, scale)

    fn = _sharded_search_fn(mesh, axes, int(min(k, n)), chunk_eff,
                            kdispatch.resolve(backend), scale is not None,
                            quant.resolve_int8_dot(int8_dot, docs.dtype))
    if scale is not None:
        scores, ids = fn(docs, doc_ids, scale, queries)
    else:
        scores, ids = fn(docs, doc_ids, queries)
    return _as_result(scores, ids)


# ------------------------------------------------- host-side shard handles

class ShardTopK(NamedTuple):
    """Host-side per-shard answer (duck-compatible with serve's ShardAnswer)."""
    scores: np.ndarray     # (B, k)
    ids: np.ndarray        # (B, k) global doc ids, -1 past the shard's corpus


QUANT_BLOCK = 1 << 16     # corpus rows quantized per step on the device


@functools.partial(jax.jit, static_argnames=("dtype",), donate_argnums=(0, 1))
def _write_block(payload, scale, block, start, *, dtype: str):
    """Quantize one block of raw f32 rows and write it into the device-
    resident payload / scale at row ``start`` (buffers donated: in place).
    Rows quantize independently, so block-wise equals whole-corpus
    quantization; feature columns past the block's width stay zero."""
    qc = quant.quantize(block, dtype)
    data = jnp.pad(qc.data, ((0, 0), (0, payload.shape[1] - block.shape[1])))
    payload = jax.lax.dynamic_update_slice(payload, data, (start, 0))
    if scale is not None:
        scale = jax.lax.dynamic_update_slice(scale, qc.scale, (start,))
    return payload, scale


def _place_slice(docs, doc_ids, rows: int, width: int, dtype: str, device):
    """Quantize a corpus slice straight onto ``device``.

    The slice streams from host memory in ``QUANT_BLOCK``-row blocks; each
    block is quantized on ``device`` itself and written into a payload
    pre-allocated there at (``rows``, ``width``) — rows padded with
    sentinels (zero payload, scale 1, id -1), features zero-padded.  No
    other device is touched and the f32 slice is never resident whole.
    """
    n = docs.shape[0]
    store = quant.storage_dtype(dtype)
    payload = jnp.zeros((rows, width), store, device=device)
    scale = None
    if dtype == "int8":
        scale = jnp.ones((rows,), jnp.float32, device=device)
    for lo in range(0, n, QUANT_BLOCK):
        block = jax.device_put(
            np.asarray(docs[lo:lo + QUANT_BLOCK], np.float32), device)
        payload, scale = _write_block(payload, scale, block,
                                      jnp.int32(lo), dtype=dtype)
        # one block in flight: host staging stays O(block), not O(slice)
        payload.block_until_ready()
    ids = np.full((rows,), -1, np.int32)
    ids[:n] = np.asarray(doc_ids, np.int32)
    return payload, jax.device_put(ids, device), scale


class DeviceShard:
    """A host-callable index shard pinned to one device.

    ``shard(queries, k) -> ShardTopK`` — the exact callable signature
    ``serve.router.ShardedRouter`` fronts, so hedging, deadlines, and
    degraded merges apply unchanged.  Concurrent router threads run their
    shards on distinct devices in parallel.  The scan is the shared
    ``scan_topk`` contract (``backend`` pins a ``kernels.dispatch`` tier;
    ``dtype`` the corpus storage format — None follows the
    ``REPRO_CORPUS_DTYPE`` policy).  The slice is quantized once, at
    construction, on its own device (``_place_slice``) and stored at the
    lane-aligned width the kernel tiers stream, so a search copies nothing
    of the corpus; queries are zero-padded to that width per call, and
    their rows to a power of two.  A search is three spans —
    ``shard.stage`` (the query put), ``shard.scan`` (the dispatch, not the
    device time) and ``shard.fetch`` (the blocking read of scores and ids)
    — and counts the bytes it moves into ``telemetry`` when one is set
    (the router hands its own on).
    """

    telemetry = None

    def __init__(self, docs, doc_ids, device=None, chunk: int = 4096,
                 backend: Optional[str] = None, dtype: Optional[str] = None):
        n, dim = docs.shape
        self.chunk = int(min(chunk, max(8, n)))
        self.dtype = quant.resolve_dtype(dtype)
        self.device = device
        self.backend = kdispatch.resolve(backend)
        # the int8-MXU-dot policy is resolved once per shard, so a shard's
        # scoring rule never flips mid-deployment under an env change
        self.int8_dot = quant.resolve_int8_dot(None, self.docs_dtype())
        self.n_docs = n
        self.docs, self.doc_ids, self.scale = _place_slice(
            docs, doc_ids, n + (-n) % self.chunk, layout.phys_dim(dim),
            self.dtype, device)

    def docs_dtype(self):
        return quant.storage_dtype(self.dtype)

    def __call__(self, queries, k: int) -> ShardTopK:
        q = np.asarray(queries, np.float32)
        if q.ndim == 1:
            q = q[None]
        b = q.shape[0]
        # rows pad to a power of two and features to the stored width: a
        # free-running miss count compiles at most log2(wave) scan programs
        rows = 1 << max(b - 1, 0).bit_length()
        with span("shard.stage") as sp:
            q = np.pad(q, ((0, rows - b),
                           (0, self.docs.shape[1] - q.shape[1])))
            q = jax.device_put(q, self.device)
            sp.set_metadata(h2d_bytes=q.nbytes)
        with span("shard.scan"):
            scores, ids = scan_topk(self.docs, self.doc_ids, q, int(k),
                                    chunk=self.chunk, backend=self.backend,
                                    scale=self.scale, int8_dot=self.int8_dot)
        with span("shard.fetch") as sp:
            scores, ids = np.asarray(scores), np.asarray(ids)
            sp.set_metadata(d2h_bytes=scores.nbytes + ids.nbytes)
        if self.telemetry is not None:
            self.telemetry.count(h2d_bytes=q.nbytes,
                                 d2h_bytes=scores.nbytes + ids.nbytes)
        return ShardTopK(scores[:b], ids[:b])


def make_device_shards(docs, doc_ids=None, *, devices=None,
                       chunk: int = 4096, dtype: Optional[str] = None) -> list:
    """Split a corpus into one ``DeviceShard`` per device (equal, padded
    slices so every shard shares a single jit trace).  Each slice goes
    from host memory straight to its own device: a device-resident
    ``docs`` is fetched to the host first, never sliced on device 0."""
    docs = np.asarray(docs)
    n = docs.shape[0]
    if doc_ids is None:
        doc_ids = np.arange(n, dtype=np.int32)
    doc_ids = np.asarray(doc_ids, np.int32)
    devices = list(devices if devices is not None else jax.devices())
    per = -(-n // len(devices))
    shards = []
    for i, dev in enumerate(devices):
        lo, hi = i * per, min((i + 1) * per, n)
        if lo >= n:
            break
        shards.append(DeviceShard(docs[lo:hi], doc_ids[lo:hi], device=dev,
                                  chunk=min(chunk, per), dtype=dtype))
    return shards
