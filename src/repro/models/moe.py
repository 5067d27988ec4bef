"""Mixture-of-Experts FFN: top-k routing + capacity-bucketed dispatch.

Expert-parallel-friendly: the (E, C, d) dispatch buffer is sharded over the
"model" mesh axis (expert parallelism) so the token scatter lowers to an
all-to-all; expert weights are additionally FSDP-sharded over "data".

Routing: softmax top-k with optional normalization of the selected gates
(DeepSeek style) and a Switch/GShard auxiliary load-balancing loss.  Shared
experts (DeepSeek) run densely next to the routed path.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.dist.api import constrain


class MoEConfig(NamedTuple):
    n_experts: int
    top_k: int
    d_ff: int                    # per routed expert
    n_shared: int = 0
    d_ff_shared: int = 0         # defaults to d_ff * n_shared when 0
    capacity_factor: float = 1.25
    aux_loss_weight: float = 1e-3
    norm_topk: bool = True       # renormalize selected gates (DeepSeek)
    router_dtype: object = jnp.float32


def init_moe(key: jax.Array, cfg: MoEConfig, d_model: int, dtype) -> dict:
    ks = jax.random.split(key, 5)
    scale_in = d_model ** -0.5
    scale_out = cfg.d_ff ** -0.5
    p = {
        "router": jax.random.normal(ks[0], (d_model, cfg.n_experts),
                                    jnp.float32) * scale_in,
        "wi": jax.random.normal(ks[1], (cfg.n_experts, d_model, 2 * cfg.d_ff),
                                dtype) * scale_in,
        "wo": jax.random.normal(ks[2], (cfg.n_experts, cfg.d_ff, d_model),
                                dtype) * scale_out,
    }
    if cfg.n_shared:
        dff_s = cfg.d_ff_shared or cfg.d_ff * cfg.n_shared
        p["shared_wi"] = jax.random.normal(ks[3], (d_model, 2 * dff_s),
                                           dtype) * scale_in
        p["shared_wo"] = jax.random.normal(ks[4], (dff_s, d_model),
                                           dtype) * dff_s ** -0.5
    return p


def _swiglu(x: jax.Array, wi: jax.Array, wo: jax.Array) -> jax.Array:
    h = x @ wi
    gate, up = jnp.split(h, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ wo


class MoEOut(NamedTuple):
    y: jax.Array
    aux_loss: jax.Array


def moe_ffn(params: dict, x: jax.Array, cfg: MoEConfig,
            capacity: Optional[int] = None) -> MoEOut:
    """x: (T, d) token-major. Returns combined output + aux loss."""
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    if capacity is None:
        capacity = int(t * k / e * cfg.capacity_factor) + 1
    # pad capacity to a friendly multiple for the batched expert matmul
    capacity = max(8, -(-capacity // 8) * 8)

    logits = (x.astype(cfg.router_dtype) @ params["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)                   # (T, K)
    if cfg.norm_topk:
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # ---- aux load-balancing loss (Switch/GShard) ----
    me = probs.mean(axis=0)                                            # (E,)
    onehot = jax.nn.one_hot(expert_ids, e, dtype=jnp.float32)          # (T,K,E)
    ce = onehot.sum(axis=(0, 1)) / (t * k)                             # fraction
    aux = cfg.aux_loss_weight * e * jnp.sum(me * ce)

    # ---- capacity-bucketed dispatch ----
    # position of each (token, choice) in its expert's queue
    flat_ids = expert_ids.reshape(-1)                                  # (T*K,)
    flat_oh = jax.nn.one_hot(flat_ids, e, dtype=jnp.int32)             # (T*K,E)
    pos_in_e = (jnp.cumsum(flat_oh, axis=0) - 1)                       # (T*K,E)
    pos = jnp.take_along_axis(pos_in_e, flat_ids[:, None], axis=1)[:, 0]
    keep = pos < capacity
    gates = gate_vals.reshape(-1) * keep

    tok_idx = jnp.repeat(jnp.arange(t), k)
    buf = jnp.zeros((e, capacity, d), x.dtype)
    safe_pos = jnp.where(keep, pos, capacity)                          # OOB drop
    buf = buf.at[flat_ids, safe_pos].add(x[tok_idx], mode="drop")
    buf = constrain(buf, "moe_buf")     # EP: experts over "model" (all-to-all)

    # ---- expert compute: batched over E (shardable over "model") ----
    h = jnp.einsum("ecd,edf->ecf", buf, params["wi"])
    h = constrain(h, "moe_hidden")
    gate_h, up_h = jnp.split(h, 2, axis=-1)
    act = jax.nn.silu(gate_h) * up_h
    out_buf = jnp.einsum("ecf,efd->ecd", act, params["wo"])
    out_buf = constrain(out_buf, "moe_buf")

    # ---- combine ----
    gathered = out_buf[flat_ids, safe_pos]                              # (T*K, d)
    y = jnp.zeros((t, d), x.dtype).at[tok_idx].add(
        gathered * gates[:, None].astype(x.dtype))
    y = constrain(y, "moe_out")

    if "shared_wi" in params:
        y = y + _swiglu(x, params["shared_wi"], params["shared_wo"])
    return MoEOut(y, aux.astype(jnp.float32))


def moe_ffn_sharded(params: dict, x: jax.Array, cfg: MoEConfig, mesh,
                    capacity: Optional[int] = None) -> MoEOut:
    """Expert-parallel MoE via shard_map (EP over "model", DP over the rest).

    GSPMD replicates data-dependent scatters, so the jnp-level dispatch in
    ``moe_ffn`` silently loses expert parallelism under pjit (verified in
    the dry-run: per-device flops == global flops).  Here the dispatch is
    *per-device code*: tokens are sharded over the data axes and replicated
    over "model"; every model-rank routes the same local tokens but keeps
    only assignments that land in its own expert slice, runs its local
    (E/TP) experts, and the partial combines are psum'd over "model" —
    Megatron-style EP+TP hybrid with no all-to-all (the psum replaces it;
    an a2a variant is a recorded §Perf candidate).
    """
    from jax.sharding import PartitionSpec as P
    from repro.dist.api import data_axes

    dp = tuple(data_axes(mesh))
    tp = mesh.shape["model"]
    e, k = cfg.n_experts, cfg.top_k
    assert e % tp == 0, f"experts {e} not divisible by model axis {tp}"
    e_loc = e // tp
    t = x.shape[0]
    t_loc = t // _axis_prod(mesh, dp)
    if capacity is None:
        capacity = int(t_loc * k / e * cfg.capacity_factor) + 1
    capacity = max(8, -(-capacity // 8) * 8)

    def local_fn(x, router, wi, wo):
        # x: (t_loc, d) — same on every model-rank; wi/wo: local expert slice
        rank = jax.lax.axis_index("model")
        e0 = rank * e_loc
        # route in the activation dtype (f32 cotangents of a pref-f32 dot
        # were a dominant backward temp); softmax still runs in f32.
        logits = (x @ router.astype(x.dtype)).astype(cfg.router_dtype)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, k)
        if cfg.norm_topk:
            gate_vals = gate_vals / jnp.maximum(
                gate_vals.sum(-1, keepdims=True), 1e-9)

        me = probs.mean(axis=0)
        onehot_f = jax.nn.one_hot(expert_ids, e, dtype=jnp.float32)
        ce = onehot_f.sum(axis=(0, 1)) / (x.shape[0] * k)
        aux = cfg.aux_loss_weight * e * jnp.sum(me * ce)
        if dp:
            aux = jax.lax.pmean(aux, dp)

        # position of each (token, choice) in its expert's queue — computed
        # on a transposed (K, T, E) layout then flattened back
        flat_ids = expert_ids.reshape(-1)
        flat_oh = jax.nn.one_hot(flat_ids, e, dtype=jnp.int32)
        pos_in_e = jnp.cumsum(flat_oh, axis=0) - 1
        pos = jnp.take_along_axis(pos_in_e, flat_ids[:, None], axis=1)[:, 0]
        mine = jnp.logical_and(flat_ids >= e0, flat_ids < e0 + e_loc)
        keep = jnp.logical_and(pos < capacity, mine)

        eid_k = jnp.where(keep, flat_ids - e0, e_loc).reshape(-1, k)
        pos_k = jnp.where(keep, pos, capacity).reshape(-1, k)
        gates_k = (gate_vals.reshape(-1) * keep).reshape(-1, k)

        # dispatch/combine one routing choice at a time: K scatters/gathers
        # of (T_loc, d) instead of one (T_loc*K, d) gather — the big-gather
        # residual was the dominant per-layer temp in the dry-run.
        buf = jnp.zeros((e_loc, capacity, x.shape[1]), x.dtype)
        for kk in range(k):
            buf = buf.at[eid_k[:, kk], pos_k[:, kk]].add(
                x * (gates_k[:, kk] > 0)[:, None].astype(x.dtype),
                mode="drop")

        h = jnp.einsum("ecd,edf->ecf", buf, wi)
        gate_h, up_h = jnp.split(h, 2, axis=-1)
        act = jax.nn.silu(gate_h) * up_h
        out_buf = jnp.einsum("ecf,efd->ecd", act, wo)

        y = jnp.zeros_like(x)
        for kk in range(k):
            got = out_buf[eid_k[:, kk].clip(0, e_loc - 1),
                          pos_k[:, kk].clip(0, capacity - 1)]
            y = y + got * gates_k[:, kk][:, None].astype(x.dtype)
        y = jax.lax.psum(y, "model")        # combine expert partials (EP)
        return y, aux

    sharded = jax.shard_map(
        jax.checkpoint(local_fn, prevent_cse=False), mesh=mesh,
        in_specs=(P(dp if dp else None, None), P(None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P(dp if dp else None, None), P()),
        check_vma=False)
    y, aux = sharded(x, params["router"], params["wi"], params["wo"])
    if "shared_wi" in params:
        y = y + _swiglu(x, params["shared_wi"], params["shared_wo"])
    return MoEOut(y, aux.astype(jnp.float32))


def _axis_prod(mesh, axes) -> int:
    out = 1
    for a in axes:
        out *= mesh.shape[a]
    return out
