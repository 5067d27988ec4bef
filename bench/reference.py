"""The plain reference: exact k-NN over the stored corpus and Algorithm 1.

Written in plain ``jax.numpy`` and imports nothing of the program.  The
configuration states how the corpus is stored (``int8``: symmetric per
document, the f32 scale chosen so a dequantized row keeps the row's norm)
and how it is scored (f32, the payload cast to f32 and the per-document
scale applied to the score).  The reference quantizes the generated
corpus by that rule itself, and scores at f32 ``HIGHEST`` precision.

Two lower precisions, each written out so it means the same on every
platform: ``precision="high"`` computes every dot product as three bf16
passes (``hi*hi + hi*lo + lo*hi``, each operand split into a bf16 head
and a bf16 tail), the TPU's ``HIGH``; ``precision="bf16"`` rounds both
operands to bf16 once and accumulates in f32, the MXU's default for f32
operands.  ``bf16`` is the control that the comparison has to fail:
``high`` is f32 to rounding over an int8 payload (``PERF.md``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("highest", "high", "bf16")
BLOCK = 1 << 18            # corpus rows per scan block
CHUNK_ELEMS = 1 << 27      # gathered row elements per scoring step


def quantize_int8(x):
    """(payload int8, scale f32) of f32 rows, the configuration's rule."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    step = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / step), -127, 127).astype(jnp.int8)
    qn = jnp.sqrt(jnp.sum(jnp.square(q.astype(jnp.float32)), axis=-1))
    xn = jnp.sqrt(jnp.sum(jnp.square(x), axis=-1))
    scale = jnp.where(qn > 0, xn / jnp.maximum(qn, 1e-30), 1.0)
    return q, scale.astype(jnp.float32)


def _split(x):
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def dot(a, b, precision: str):
    """a (m, d) @ b (n, d).T in f32 at ``precision``."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "highest":
        return jnp.dot(a, b.T, precision=HIGHEST)
    if precision == "bf16":
        return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16).T,
                       preferred_element_type=jnp.float32, precision=HIGHEST)
    if precision != "high":
        raise ValueError(f"precision {precision!r}: expected {PRECISIONS}")
    (ah, al), (bh, bl) = _split(a), _split(b)
    # the three passes as one bf16 contraction over [hi, hi, lo] x
    # [hi, lo, hi]: products exact, sums in f32, and no pass the compiler
    # could merge away
    lhs = jnp.concatenate([ah, ah, al], axis=-1)
    rhs = jnp.concatenate([bh, bl, bh], axis=-1)
    return jnp.dot(lhs, rhs.T, preferred_element_type=jnp.float32,
                   precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def _block_topk(q, payload, scale, lo, *, k: int, precision: str):
    s = dot(q, payload, precision) * scale[None, :]
    v, i = jax.lax.top_k(s, min(k, s.shape[1]))
    return v, i.astype(jnp.int32) + lo


@functools.partial(jax.jit, static_argnames=("k",))
def _merge(v, i, *, k: int):
    top, pos = jax.lax.top_k(v, k)
    return top, jnp.take_along_axis(i, pos, axis=1)


_quantize = jax.jit(quantize_int8)


class Reference:
    """The stored corpus as the reference holds it, on one device."""

    def __init__(self, corpus: np.ndarray, device=None):
        self.device = device or jax.devices()[0]
        self.n = corpus.shape[0]
        self.host = corpus
        self.host_payload = self.host_scale = None
        self.f32 = jax.device_put(corpus, self.device)
        blocks = [_quantize(self.f32[lo:lo + BLOCK])
                  for lo in range(0, self.n, BLOCK)]
        self.payload = jnp.concatenate([b[0] for b in blocks])
        self.scale = jnp.concatenate([b[1] for b in blocks])

    def knn(self, queries: np.ndarray, k: int, precision: str = "highest"):
        """Exact top-``k`` (scores, ids) over the int8 corpus, best first;
        equal scores keep the lower id first."""
        q = jax.device_put(np.asarray(queries, np.float32), self.device)
        vs, is_ = [], []
        for lo in range(0, self.n, BLOCK):
            v, i = _block_topk(q, self.payload[lo:lo + BLOCK],
                               self.scale[lo:lo + BLOCK], jnp.int32(lo),
                               k=k, precision=precision)
            vs.append(v)
            is_.append(i)
        v, i = _merge(jnp.concatenate(vs, 1), jnp.concatenate(is_, 1), k=k)
        return np.asarray(v), np.asarray(i)

    def stored_scores(self, queries: np.ndarray, ids: np.ndarray,
                      precision: str = "exact") -> np.ndarray:
        """(m, c) scores of ``ids`` (m, c) against the int8 corpus."""
        return self._scores(queries, ids, precision, stored=True)

    def f32_scores(self, queries: np.ndarray, ids: np.ndarray,
                   precision: str = "exact") -> np.ndarray:
        """(m, c) scores of ``ids`` (m, c) against the f32 corpus rows —
        the rows a cache that stores f32 holds."""
        return self._scores(queries, ids, precision, stored=False)

    def _scores(self, queries, ids, precision, stored):
        ids = np.asarray(ids)
        valid = (ids >= 0) & (ids < self.n)
        if precision == "exact":
            out = self._exact(np.asarray(queries, np.float64),
                              np.clip(ids, 0, self.n - 1), stored)
        else:
            out = self._device(queries, ids, precision, stored)
        return np.where(valid, out, np.nan)

    def _exact(self, q, ids, stored):
        """f64 scores on the host, in blocks of queries."""
        if stored and self.host_payload is None:
            self.host_payload = np.asarray(self.payload)
            self.host_scale = np.asarray(self.scale, np.float64)
        out = np.empty(ids.shape, np.float64)
        step = max(1, CHUNK_ELEMS // (8 * max(1, ids.shape[1])
                                      * self.f32.shape[1]))
        for lo in range(0, len(ids), step):
            i = ids[lo:lo + step]
            rows = (self.host_payload[i] if stored
                    else self.host[i]).astype(np.float64)
            s = np.einsum("mcd,md->mc", rows, q[lo:lo + step])
            out[lo:lo + step] = s * self.host_scale[i] if stored else s
        return out

    def _device(self, queries, ids, precision, stored):
        m, c = ids.shape
        width = max(16, 1 << (c - 1).bit_length())     # few programs
        rows = max(1, min(256, CHUNK_ELEMS // (width * self.f32.shape[1])))
        rows = 1 << (rows.bit_length() - 1)
        q = np.zeros((-(-m // rows) * rows, self.f32.shape[1]), np.float32)
        q[:m] = queries
        i = np.zeros((len(q), width), np.int32)
        i[:m, :c] = np.clip(ids, 0, self.n - 1)
        return np.concatenate([np.asarray(_row_scores(
            jax.device_put(q[lo:lo + rows], self.device),
            jax.device_put(i[lo:lo + rows], self.device),
            self.payload, self.scale, self.f32, precision=precision,
            stored=stored)) for lo in range(0, len(q), rows)])[:m, :c]

    def answer(self, queries: np.ndarray, cand: np.ndarray, k: int,
               precision: str = "exact"):
        """Algorithm 1's answer step: the top-``k`` (scores, ids) of each
        query over its candidate ids ``cand`` (m, c; -1 pads) by f32-row
        score — the best ``k`` of what the cache holds."""
        s = self.f32_scores(queries, cand, precision)
        s = np.where(np.asarray(cand) >= 0, s, -np.inf)
        order = np.argsort(-s, axis=1, kind="stable")[:, :k]
        return (np.take_along_axis(s, order, 1),
                np.take_along_axis(np.asarray(cand), order, 1))


@functools.partial(jax.jit, static_argnames=("precision", "stored"))
def _row_scores(q, ids, payload, scale, f32, *, precision, stored):
    if stored:
        rows = payload[ids].astype(jnp.float32)           # (m, c, d)
    else:
        rows = f32[ids]

    def one(qi, ri):
        return dot(qi[None], ri, precision)[0]
    s = jax.vmap(one)(q, rows)
    return s * scale[ids] if stored else s
