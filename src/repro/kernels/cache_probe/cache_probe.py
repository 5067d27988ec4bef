"""Fused LowQuality-probe Pallas kernels (paper Eq. 3/4).

The probe runs on EVERY utterance, fused with the query encoder on the
serving chip: one (Qmax, D) x (D,) matvec on the MXU, the sqrt/subtract on
the VPU, emitting per-cached-query r_hat = r_a - delta(psi_a, psi).
Single-tile (Qmax <= 64 cached queries by the paper's design: one per cache
miss in a <=13-turn conversation), so the whole working set sits in VMEM.

Record embeddings may be stored quantized (``repro.core.quant``: bf16, or
int8 with an fp32 per-record scale): the payload is cast to f32 in VMEM and
the scale multiplies the score before the distance — the same score-side
rule as the corpus scan, so the kernel agrees with the jnp ref probe at any
storage dtype (the wrapper always passes a scale column, all-ones for
unquantized records; x * 1.0f is bit-exact).

Two entry points:

  * ``probe_rhat``         — one session (the original scalar kernel).
  * ``probe_rhat_batched`` — S sessions in ONE launch: grid over the
    session axis of a stacked cache, each step probing one (Qmax, D) record
    block against that session's psi.  This is the serving hot path for
    ``BatchedEngine`` waves — one kernel launch per wave instead of S
    matvecs, with the ring-buffer validity mask (slot < n_queries, where
    n_queries counts *total* records and the ring keeps the newest
    min(n_queries, Qmax)) already folded into the radius operand.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _probe_kernel(q_emb_ref, psi_ref, radius_ref, scale_ref, out_ref):
    q = q_emb_ref[...].astype(jnp.float32)               # (Qmax, D)
    psi = psi_ref[...]                                   # (8, D) row 0 live
    scores = jax.lax.dot_general(
        q, psi, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (Qmax, 8)
    scores = scores[:, :1] * scale_ref[...]              # (Qmax, 1)
    dist = jnp.sqrt(jnp.clip(2.0 - 2.0 * scores, 0.0, None))
    out_ref[...] = radius_ref[...] - dist                # (Qmax, 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def probe_rhat(q_emb: jax.Array, psi: jax.Array, radius: jax.Array,
               scale: jax.Array, interpret: bool = False) -> jax.Array:
    """q_emb: (Qmax, D) unit rows (fp32 / bf16 / int8 payload); psi: (8, D)
    (row 0 = query); radius: (Qmax, 1) with -inf on empty slots; scale:
    (Qmax, 1) f32 per-record score multipliers. Returns r_hat (Qmax, 1)
    f32."""
    qmax, d = q_emb.shape
    return pl.pallas_call(
        _probe_kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((qmax, d), lambda i: (0, 0)),
                  pl.BlockSpec((8, d), lambda i: (0, 0)),
                  pl.BlockSpec((qmax, 1), lambda i: (0, 0)),
                  pl.BlockSpec((qmax, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((qmax, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((qmax, 1), jnp.float32),
        interpret=interpret,
        name="probe_rhat",
    )(q_emb, psi, radius, scale)


def _probe_batched_kernel(q_emb_ref, psi_ref, radius_ref, scale_ref, out_ref):
    q = q_emb_ref[0].astype(jnp.float32)                 # (Qmax, D)
    psi = psi_ref[0]                                     # (8, D) row 0 live
    scores = jax.lax.dot_general(
        q, psi, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (Qmax, 8)
    scores = scores[:, :1] * scale_ref[0]                # (Qmax, 1)
    dist = jnp.sqrt(jnp.clip(2.0 - 2.0 * scores, 0.0, None))
    out_ref[0] = radius_ref[0] - dist                    # (Qmax, 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def probe_rhat_batched(q_emb: jax.Array, psi: jax.Array, radius: jax.Array,
                       scale: jax.Array,
                       interpret: bool = False) -> jax.Array:
    """One launch over a stacked cache. q_emb: (S, Qmax, D) unit rows (any
    storage dtype); psi: (S, 8, D) (row 0 = that session's query); radius:
    (S, Qmax, 1) with -inf on empty/invalid slots; scale: (S, Qmax, 1) f32
    per-record score multipliers. Returns r_hat (S, Qmax, 1) f32."""
    s, qmax, d = q_emb.shape
    return pl.pallas_call(
        _probe_batched_kernel,
        grid=(s,),
        in_specs=[pl.BlockSpec((1, qmax, d), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, 8, d), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, qmax, 1), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, qmax, 1), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, qmax, 1), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((s, qmax, 1), jnp.float32),
        interpret=interpret,
        name="probe_rhat_batched",
    )(q_emb, psi, radius, scale)
