"""The benchmark's data: a CAsT-shaped corpus and its conversations.

The corpus follows the geometry of ``repro.data.conversations.make_world``
(which is host numpy and f64): half the documents sit in topics of
``docs_per_topic``, each topic a unit centre plus a ``subspace_dim``-d
orthonormal frame, the other half are background directions; document
norms are jittered by ``norm_jitter`` and Eq. 1 maps them onto the unit
sphere in ``dim + 1``.  Here the whole corpus is drawn on the device in
one jitted call from the seed, in f32, and handed to the program as the
host array its API takes.  Queries are built on the host from the
topics' centres and frames and the draws of a ``traffic`` plan.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Geometry:
    """Sizes and spreads of one configuration's world (its ``world``
    group), all spreads total-norm scales as in ``make_world``."""

    n_docs: int
    dim: int = 768
    docs_per_topic: int = 5000
    subspace_dim: int = 16
    doc_sigma: float = 0.35
    query_sigma: float = 0.12
    drift_sigma: float = 0.08
    subtopic_sigma: float = 0.45
    norm_jitter: float = 0.15

    def __post_init__(self):
        if self.n_docs % self.docs_per_topic or \
                self.n_docs < 2 * self.docs_per_topic:
            raise ValueError(
                f"n_docs {self.n_docs} must be a multiple of docs_per_topic "
                f"{self.docs_per_topic}, at least two of them")

    @property
    def n_topics(self) -> int:
        return max(1, self.n_docs // (2 * self.docs_per_topic))

    @classmethod
    def from_config(cls, cfg: dict) -> "Geometry":
        w = dict(cfg["world"])
        return cls(n_docs=int(cfg["n_docs"]), dim=int(cfg["raw_dim"]), **w)


def _unit(x, axis=-1):
    return x / jnp.linalg.norm(x, axis=axis, keepdims=True)


@functools.partial(jax.jit, static_argnames=("g",))
def _generate(key, g: Geometry):
    """(corpus (n, dim + 1) f32 after Eq. 1, centres (T, dim), frames
    (T, dim, s)), all drawn from ``key`` on the device."""
    k_c, k_f, k_t, k_b, k_n = jax.random.split(key, 5)
    t, s, d = g.n_topics, g.subspace_dim, g.dim
    centres = _unit(jax.random.normal(k_c, (t, d), jnp.float32))
    frames, _ = jnp.linalg.qr(jax.random.normal(k_f, (t, d, s), jnp.float32))
    jitter = 1.0 + g.norm_jitter * (
        2.0 * jax.random.uniform(k_n, (g.n_docs,), jnp.float32) - 1.0)
    m = jnp.max(jitter)             # every direction is unit: M = max norm

    def eq1(unit_rows, norms):
        scaled = unit_rows * (norms / m)[:, None]
        extra = jnp.sqrt(jnp.clip(1.0 - (norms / m) ** 2, 0.0, None))
        return jnp.concatenate([scaled, extra[:, None]], axis=1)

    def block(i):
        # one block of ``docs_per_topic`` rows: topic i's documents for
        # i < T, background directions after; one lax.map writes every
        # block into the output, so the corpus is the only large buffer
        b = g.docs_per_topic
        z = jax.random.normal(jax.random.fold_in(k_t, i), (b, s), jnp.float32)
        z = z * (g.doc_sigma / np.sqrt(s))
        j = jnp.minimum(i, t - 1)
        topic = centres[j] + jnp.dot(z, frames[j].T, precision=HIGHEST)
        bg = jax.random.normal(jax.random.fold_in(k_b, i), (b, d), jnp.float32)
        rows = _unit(jnp.where(i < t, topic, bg))
        return eq1(rows, jax.lax.dynamic_slice(jitter, (i * b,), (b,)))

    blocks = g.n_docs // g.docs_per_topic
    corpus = jax.lax.map(block, jnp.arange(blocks)).reshape(g.n_docs, d + 1)
    return corpus, centres, frames


@dataclass
class World:
    corpus: np.ndarray      # (n, dim + 1) f32, the program's input
    centres: np.ndarray     # (T, dim) f32
    frames: np.ndarray      # (T, dim, s) f32
    geometry: Geometry

    def queries(self, topic: np.ndarray, draws: np.ndarray,
                jump: np.ndarray) -> np.ndarray:
        """Transformed queries (n_turns, dim + 1) f32 of conversations.

        ``topic`` (C,) is each conversation's topic; ``draws`` (C, L, 3, s)
        the standard-normal draws of each turn (start or sub-topic point,
        drift, utterance noise); ``jump`` (C, L) marks the turns that move
        to a new sub-topic point.  Turn 0 starts at ``doc_sigma / 2``
        around the centre, a jump re-draws the point at ``subtopic_sigma``,
        every turn drifts by ``drift_sigma`` and the utterance adds
        ``query_sigma`` of noise — ``make_world``'s conversation walk.
        Returns (C, L, dim + 1)."""
        g = self.geometry
        s = g.subspace_dim
        scale = 1.0 / np.sqrt(s)
        c = self.centres[topic]                             # (C, d)
        f = self.frames[topic]                              # (C, d, s)

        def sub(z, sigma):                                  # (C, s) -> (C, d)
            return np.einsum("cds,cs->cd", f,
                             (z * (sigma * scale)).astype(np.float32))

        def unit(x):
            return x / np.linalg.norm(x, axis=-1, keepdims=True)

        n_conv, turns = draws.shape[:2]
        point = unit(c + sub(draws[:, 0, 0], g.doc_sigma * 0.5))
        out = np.empty((n_conv, turns, g.dim + 1), np.float32)
        for t in range(turns):
            if t > 0:
                fresh = unit(c + sub(draws[:, t, 0], g.subtopic_sigma))
                point = np.where(jump[:, t, None], fresh, point)
            point = unit(point + sub(draws[:, t, 1], g.drift_sigma))
            q = unit(point + sub(draws[:, t, 2], g.query_sigma))
            out[:, t, :g.dim] = q
            out[:, t, g.dim] = 0.0
        return out


def make_world(g: Geometry, seed: int, device=None) -> World:
    """Draw the world of ``seed`` on ``device`` (default: the first) and
    bring it to the host; the device copy is freed before returning."""
    device = device or jax.devices()[0]
    key = jax.device_put(jax.random.key(seed), device)
    corpus, centres, frames = _generate(key, g)
    out = World(np.asarray(corpus), np.asarray(centres), np.asarray(frames),
                g)
    corpus.delete()
    return out
