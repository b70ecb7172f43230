"""Mixture-of-Experts FFN with token-choice top-k routing.

Dispatch avoids the GShard (T, E, C) one-hot cube: positions-in-expert come
from a cumsum over a (T·k, E) one-hot, tokens are *scattered* into per-expert
capacity buffers (E, C, D) and gathered back — O(T·E + E·C·D) memory. Expert
weight tensors are stacked (E, ...) and sharded over the "model" axis (EP);
the scatter/gather pair is what XLA lowers to the dispatch all-to-all.
Shared experts (DeepSeek-style) run as one fused dense SwiGLU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import MoEConfig
from .layers import swiglu
from .params import Spec
from jax.sharding import PartitionSpec as P


def moe_schema(d_model: int, moe: MoEConfig) -> dict:
    e, f = moe.n_experts, moe.d_ff_expert
    sch = {
        "router": Spec((d_model, e), P("data", None)),
        "w_gate": Spec((e, d_model, f), P("model", "data", None)),
        "w_in":   Spec((e, d_model, f), P("model", "data", None)),
        "w_out":  Spec((e, f, d_model), P("model", None, "data")),
    }
    if moe.n_shared:
        fs = f * moe.n_shared
        sch.update({
            "sh_gate": Spec((d_model, fs), P("data", "model")),
            "sh_in":   Spec((d_model, fs), P("data", "model")),
            "sh_out":  Spec((fs, d_model), P("model", "data")),
        })
    return sch


def moe_ffn(x: jnp.ndarray, p: dict, moe: MoEConfig) -> jnp.ndarray:
    """x (B, T, D) → (B, T, D). Token-choice top-k with capacity drop.

    Under mesh sharding (dry-run/production) dispatches to the explicit
    expert-parallel shard_map path — the partitioner's lowering of the
    scatter/gather dispatch all-reduces multi-GB expert buffers
    (EXPERIMENTS.md §Perf headroom note); the EP path reduces exactly one
    (B, T, D) partial sum per layer."""
    from repro.dist import sharding as shmod
    if shmod.mesh() is not None and shmod.batch_axes() is not None \
            and shmod.model_axis() > 1 \
            and moe.n_experts % shmod.model_axis() == 0:
        return _moe_ffn_ep(x, p, moe)
    return _moe_ffn_dense(x, p, moe)


def _moe_ffn_ep(x: jnp.ndarray, p: dict, moe: MoEConfig) -> jnp.ndarray:
    """Expert-parallel shard_map: tokens replicated over "model", each model
    shard dispatches ONLY to its E/16 local experts and contributes a
    partial combine; one psum over "model" finishes the layer."""
    from jax.sharding import PartitionSpec as P
    from repro.dist import sharding as shmod

    b, t, d = x.shape
    n_exp_local = moe.n_experts // shmod.model_axis()
    batch = shmod.batch_axes()

    def local(xl, router, wg, wi, wo):
        nl = xl.shape[0] * xl.shape[1]
        tokens = xl.reshape(nl, d)
        k = moe.top_k
        cap = max(8, int(moe.capacity_factor * nl * k / moe.n_experts))
        cap = -(-cap // 8) * 8

        logits = (tokens @ router.astype(xl.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, expert_idx = jax.lax.top_k(probs, k)              # global ids
        gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)

        my_lo = jax.lax.axis_index("model") * n_exp_local
        flat_e = expert_idx.reshape(-1) - my_lo                 # local ids
        mine = (flat_e >= 0) & (flat_e < n_exp_local)
        flat_e = jnp.clip(flat_e, 0, n_exp_local - 1)
        flat_g = gate.reshape(-1) * mine
        token_of_slot = jnp.repeat(jnp.arange(nl, dtype=jnp.int32), k)

        oh = jax.nn.one_hot(flat_e, n_exp_local, dtype=jnp.int32) \
            * mine[:, None]
        pos = jnp.sum((jnp.cumsum(oh, axis=0) - 1) * oh, axis=-1)
        keep = (pos < cap) & mine
        pos_c = jnp.minimum(pos, cap - 1)

        vals = tokens[token_of_slot] * keep[:, None].astype(xl.dtype)
        buf = jnp.zeros((n_exp_local, cap, d), xl.dtype
                        ).at[flat_e, pos_c].add(vals)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg.astype(xl.dtype))
                        ) * jnp.einsum("ecd,edf->ecf", buf,
                                       wi.astype(xl.dtype))
        out = jnp.einsum("ecf,efd->ecd", h, wo.astype(xl.dtype))

        slot_out = out[flat_e, pos_c]
        w = (flat_g * keep).astype(xl.dtype)[:, None]
        y = jnp.zeros((nl, d), xl.dtype).at[token_of_slot].add(slot_out * w)
        y = jax.lax.psum(y, "model")
        return y.reshape(xl.shape)

    y = jax.shard_map(
        local, mesh=shmod.mesh(),
        in_specs=(P(batch, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=P(batch, None, None), check_vma=False)(
        x, p["router"], p["w_gate"], p["w_in"], p["w_out"])

    if moe.n_shared:
        y = y + swiglu(x.reshape(b * t, d), p["sh_gate"].astype(x.dtype),
                       p["sh_in"].astype(x.dtype),
                       p["sh_out"].astype(x.dtype)).reshape(b, t, d)
    return y


def _moe_ffn_dense(x: jnp.ndarray, p: dict, moe: MoEConfig) -> jnp.ndarray:
    """Single-device / no-mesh path (semantics of record)."""
    b, t, d = x.shape
    n = b * t
    k = moe.top_k
    e = moe.n_experts
    cap = max(8, int(moe.capacity_factor * n * k / e))
    cap = -(-cap // 8) * 8

    tokens = x.reshape(n, d)
    logits = (tokens @ p["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                   # (N, E)
    gate, expert_idx = jax.lax.top_k(probs, k)                # (N, k)
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)

    flat_e = expert_idx.reshape(-1)                           # (N·k,)
    flat_g = gate.reshape(-1)
    token_of_slot = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)

    oh = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)           # (N·k, E)
    pos = jnp.sum((jnp.cumsum(oh, axis=0) - 1) * oh, axis=-1)  # (N·k,)
    keep = pos < cap
    pos_c = jnp.minimum(pos, cap - 1)

    # scatter tokens into expert buffers (dropped tokens contribute zero)
    vals = tokens[token_of_slot] * keep[:, None].astype(x.dtype)
    buf = jnp.zeros((e, cap, d), x.dtype
                    ).at[flat_e, pos_c].add(vals)             # (E, C, D)

    # expert compute — einsum over stacked expert weights (EP-shardable).
    # Constrain weights to EP-only sharding here: with the d_model dim left
    # FSDP-sharded, the partitioner partial-sums the (E,C,F) ACTIVATIONS
    # over "data" (measured 2.7 GB f32 all-reduces/layer on deepseek-v2-lite
    # — §Perf headroom note); gathering the 0.4 GB/layer weights instead is
    # the right trade by ~7×.
    from repro.dist.sharding import batch_axes, model_axis
    if batch_axes() is not None and model_axis() > 1:
        from jax.sharding import PartitionSpec as _P
        ep = _P("model", None, None)
        p = dict(p, w_gate=jax.lax.with_sharding_constraint(p["w_gate"], ep),
                 w_in=jax.lax.with_sharding_constraint(p["w_in"], ep),
                 w_out=jax.lax.with_sharding_constraint(p["w_out"], ep))
    wg = p["w_gate"].astype(x.dtype)
    wi = p["w_in"].astype(x.dtype)
    wo = p["w_out"].astype(x.dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * \
        jnp.einsum("ecd,edf->ecf", buf, wi)
    out = jnp.einsum("ecf,efd->ecd", h, wo)                   # (E, C, D)

    # gather back + weighted combine
    slot_out = out[flat_e, pos_c]                             # (N·k, D)
    w = (flat_g * keep).astype(x.dtype)[:, None]
    y = jnp.zeros((n, d), x.dtype).at[token_of_slot].add(slot_out * w)

    if moe.n_shared:
        y = y + swiglu(tokens, p["sh_gate"].astype(x.dtype),
                       p["sh_in"].astype(x.dtype),
                       p["sh_out"].astype(x.dtype))
    return y.reshape(b, t, d)
