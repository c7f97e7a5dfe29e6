"""Mixture-of-Experts FFN — sort-based top-k dispatch with static capacity.

Formulation (MegaBlocks-lite / dropping):
  1. router → top-k (expert_id, gate) per token → T·K assignments;
  2. sort assignments by expert id; position-in-expert = rank within the
     sorted run (i - searchsorted(sorted_ids, id));
  3. scatter token indices into an [E·C] slot table (drop beyond capacity
     C = ceil(T·K·cf / E) — static);
  4. gather x rows into x_e [E, C, d], batched expert GEMMs (MXU),
     gather-back + gate-weighted segment-sum into [T, d].

Why not the GShard one-hot-einsum dispatch: its [T, E, C] cube is
quadratic in tokens (C ∝ T) — at the assigned olmoe train cell
(T=1M tokens, E=64) that cube is ~10^14 elements.  The sort form is
O(T·K log(T·K) + E·C·d) memory and shards cleanly: tokens on (pod, data),
experts on model (EP), with the gathers lowering to all-to-alls.

Aux losses: Switch load-balance + router z-loss.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .layers import dense_init

__all__ = ["MoEConfig", "moe_init", "moe_apply"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    capacity_factor: float = 2.0
    router_aux_weight: float = 0.01
    router_z_weight: float = 1e-3
    # dispatch groups: tokens are slotted within fixed-size groups that
    # align with the data-parallel shards, so the dispatch gather stays
    # group-local and only the expert dim crosses devices (EP all-to-all).
    # Group count is chosen at apply time as min(n_groups, T // 4096).
    n_groups: int = 16


def moe_init(key, d_model: int, d_ff: int, cfg: MoEConfig, ffn_type: str,
             dtype=jnp.float32) -> dict:
    """Expert-stacked FFN params: leaves have a leading [E] axis (EP shard)."""
    kr, kg, ku, kd = jax.random.split(key, 4)
    E = cfg.n_experts
    scale_in = 1.0 / math.sqrt(d_model)
    scale_ff = 1.0 / math.sqrt(d_ff)
    p = {
        "router": dense_init(kr, d_model, E, dtype=jnp.float32),  # router in f32
        "w_up": (jax.random.normal(ku, (E, d_model, d_ff), jnp.float32) * scale_in).astype(dtype),
        "w_down": (jax.random.normal(kd, (E, d_ff, d_model), jnp.float32) * scale_ff).astype(dtype),
    }
    if ffn_type == "swiglu":
        p["w_gate"] = (jax.random.normal(kg, (E, d_model, d_ff), jnp.float32) * scale_in).astype(dtype)
    return p


def _expert_ffn(p: dict, x_e: jnp.ndarray, ffn_type: str) -> jnp.ndarray:
    """x_e: [E, C, d] -> [E, C, d], batched einsum over experts (MXU)."""
    up = jnp.einsum("ecd,edf->ecf", x_e, p["w_up"])
    if ffn_type == "swiglu":
        gate = jnp.einsum("ecd,edf->ecf", x_e, p["w_gate"])
        h = jax.nn.silu(gate) * up
    elif ffn_type == "gelu":
        h = jax.nn.gelu(up)
    elif ffn_type == "relu2":
        h = jnp.square(jax.nn.relu(up))
    else:
        raise ValueError(ffn_type)
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def moe_apply(p: dict, x: jnp.ndarray, cfg: MoEConfig, ffn_type: str,
              capacity: Optional[int] = None):
    """x: [T, d] (flattened tokens) -> (y [T, d], aux_losses dict).

    Tokens are dispatched within groups (vmap over the group dim, which is
    sharded over the batch axes): all token-indexed gathers/scatters stay
    inside one data shard, and only the [G, E, C, d] expert buffers cross
    devices on the expert dim.
    """
    T, d = x.shape
    G = max(1, min(cfg.n_groups, T // 4096)) if T >= 8192 else 1
    while T % G:
        G -= 1
    if G > 1:
        xg = x.reshape(G, T // G, d)
        yg, aux = jax.vmap(
            lambda xx: _moe_apply_flat(p, xx, cfg, ffn_type, capacity))(xg)
        aux = jax.tree_util.tree_map(jnp.mean, aux)
        return yg.reshape(T, d), aux
    return _moe_apply_flat(p, x, cfg, ffn_type, capacity)


def _moe_apply_flat(p: dict, x: jnp.ndarray, cfg: MoEConfig, ffn_type: str,
                    capacity: Optional[int] = None):
    T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    C = capacity or max(math.ceil(T * K * cfg.capacity_factor / E), 4)
    C = min(C, T * K)

    logits = x.astype(jnp.float32) @ p["router"]["w"]          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)            # [T, K]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    # --- sort-based slotting -------------------------------------------
    flat_e = expert_idx.reshape(T * K)                          # assignment -> expert
    flat_tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)    # assignment -> token
    flat_gate = gate_vals.reshape(T * K).astype(jnp.float32)

    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    st = flat_tok[order]
    sg = flat_gate[order]
    # rank within expert run
    first_of_run = jnp.searchsorted(se, jnp.arange(E, dtype=se.dtype),
                                    side="left")                # [E]
    pos = jnp.arange(T * K, dtype=jnp.int32) - first_of_run[se].astype(jnp.int32)
    keep = pos < C
    slot = se.astype(jnp.int32) * C + jnp.where(keep, pos, 0)   # [T*K]

    # slot tables: token id (or T = sentinel) and gate per slot
    slot_tok = jnp.full((E * C,), T, jnp.int32)
    slot_tok = slot_tok.at[slot].set(jnp.where(keep, st, T), mode="drop")
    slot_gate = jnp.zeros((E * C,), jnp.float32)
    slot_gate = slot_gate.at[slot].set(jnp.where(keep, sg, 0.0), mode="drop")

    # gather -> expert GEMMs -> weighted scatter-back
    xp = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])       # sentinel row
    x_e = xp[slot_tok].reshape(E, C, d)
    y_e = _expert_ffn(p, x_e, ffn_type)                         # [E, C, d]
    y_flat = (y_e.reshape(E * C, d).astype(jnp.float32)
              * slot_gate[:, None])
    y = jax.ops.segment_sum(y_flat, slot_tok, num_segments=T + 1)[:T]

    # --- aux losses -----------------------------------------------------
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32), axis=0)
    aux = {
        "load_balance": E * jnp.sum(me * ce) * cfg.router_aux_weight,
        "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
                    * cfg.router_z_weight,
    }
    return y.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# shard_map expert parallelism — the production dispatch at pod scale
# ---------------------------------------------------------------------------
def moe_apply_sharded(p: dict, x: jnp.ndarray, cfg: MoEConfig, ffn_type: str,
                      rules) -> tuple:
    """Explicit EP schedule under shard_map (tokens x experts device grid).

    GSPMD handles the dense transformer well but falls over on the MoE
    scatter/gather (it replicates the combine buffers).  This path writes
    the textbook EP schedule by hand:

      per device (tokens sharded over EVERY mesh axis; experts over model):
        local router -> top-k -> local sort -> slot table [E, C_l, d]
        all_to_all over 'model'        (tokens -> their expert's column)
        local batched expert GEMMs     [E/M, C_l*M, d]
        all_to_all back                (results -> token owners)
        local gate-weighted combine    -> y [T_local, d]

    Collective volume: 2 x T_loc*K*cf*d bf16 per device — the honest EP
    all-to-all, visible as exactly two ops in the §Roofline collective
    table.  Experts are data-parallel across rows (grads all-reduce with
    the rest of the model).  E is padded up to a multiple of the model-axis
    size with never-routed dummy experts (router bias -inf), e.g. 40 -> 48
    for granite-moe on a 16-wide model axis (pad slots noted in DESIGN.md).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = rules.mesh
    M = mesh.shape["model"]
    token_axes = tuple(mesh.axis_names)           # tokens over every axis
    n_tok_shards = mesh.size
    T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    E_pad = ((E + M - 1) // M) * M

    if T % n_tok_shards or (T // n_tok_shards) < 8:
        return moe_apply(p, x, cfg, ffn_type)     # tiny-token fallback (decode)

    T_loc = T // n_tok_shards
    C_l = max(math.ceil(T_loc * K * cfg.capacity_factor / E_pad), 1)

    def local_moe(x_loc, wr, w_up, w_down, w_gate):
        # x_loc [1?, T_loc, d] squeezed by shard_map already: [T_loc, d]
        logits = x_loc.astype(jnp.float32) @ wr                 # [T_loc, E]
        if E_pad != E:
            pad = jnp.full((logits.shape[0], E_pad - E), -1e30, jnp.float32)
            logits_p = jnp.concatenate([logits, pad], axis=-1)
        else:
            logits_p = logits
        probs = jax.nn.softmax(logits_p, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

        flat_e = expert_idx.reshape(T_loc * K)
        flat_tok = jnp.repeat(jnp.arange(T_loc, dtype=jnp.int32), K)
        flat_gate = gate_vals.reshape(T_loc * K).astype(jnp.float32)
        order = jnp.argsort(flat_e, stable=True)
        se, st, sg = flat_e[order], flat_tok[order], flat_gate[order]
        first = jnp.searchsorted(se, jnp.arange(E_pad, dtype=se.dtype), side="left")
        pos = jnp.arange(T_loc * K, dtype=jnp.int32) - first[se].astype(jnp.int32)
        keep = pos < C_l
        slot = se.astype(jnp.int32) * C_l + jnp.where(keep, pos, 0)

        slot_tok = jnp.full((E_pad * C_l,), T_loc, jnp.int32)
        slot_tok = slot_tok.at[slot].set(jnp.where(keep, st, T_loc), mode="drop")
        slot_gate = jnp.zeros((E_pad * C_l,), jnp.float32)
        slot_gate = slot_gate.at[slot].set(jnp.where(keep, sg, 0.0), mode="drop")

        xp = jnp.concatenate([x_loc, jnp.zeros((1, d), x_loc.dtype)])
        x_send = xp[slot_tok].reshape(E_pad, C_l, d)
        # dispatch: experts split over model columns, slots concat
        x_recv = jax.lax.all_to_all(x_send, "model", split_axis=0,
                                    concat_axis=1, tiled=True)  # [E_pad/M, C_l*M, d]
        pe = {"w_up": w_up, "w_down": w_down}
        if w_gate is not None:
            pe["w_gate"] = w_gate
        y_recv = _expert_ffn(pe, x_recv, ffn_type)
        y_send = jax.lax.all_to_all(y_recv, "model", split_axis=1,
                                    concat_axis=0, tiled=True)  # [E_pad, C_l, d]
        y_flat = (y_send.reshape(E_pad * C_l, d).astype(jnp.float32)
                  * slot_gate[:, None])
        y_loc = jax.ops.segment_sum(y_flat, slot_tok, num_segments=T_loc + 1)[:T_loc]

        me = jnp.mean(probs[:, :E], axis=0)
        ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32), axis=0)
        lb = E * jnp.sum(me * ce) * cfg.router_aux_weight
        rz = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * cfg.router_z_weight
        axes = tuple(mesh.axis_names)
        aux = {
            "load_balance": jax.lax.pmean(lb, axes),
            "router_z": jax.lax.pmean(rz, axes),
        }
        return y_loc.astype(x_loc.dtype), aux

    def pad_experts(w):
        if w is None or E_pad == E:
            return w
        pad_shape = (E_pad - E, *w.shape[1:])
        return jnp.concatenate([w, jnp.zeros(pad_shape, w.dtype)], axis=0)

    w_gate = pad_experts(p.get("w_gate"))
    sm = shard_map(
        local_moe, mesh=mesh,
        in_specs=(P(token_axes, None), P(), P("model", None, None),
                  P("model", None, None),
                  (P("model", None, None) if w_gate is not None else P())),
        out_specs=(P(token_axes, None),
                   {"load_balance": P(), "router_z": P()}),
        check_vma=False,
    )
    return sm(x, p["router"]["w"], pad_experts(p["w_up"]),
              pad_experts(p["w_down"]), w_gate)
