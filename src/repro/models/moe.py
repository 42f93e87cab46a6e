"""Mixture-of-Experts FFN with token-choice top-k routing.

Dispatch is *gather-based* (argsort-free scatter of token slots into per-expert
capacity buffers) rather than the classic one-hot einsum dispatch: the einsum
formulation costs O(T * E * C * d) MACs which at trillion-token scale dwarfs
the expert FLOPs themselves, whereas gathers are bandwidth-only.  This is the
first beyond-paper efficiency decision — see DESIGN.md §3.

Sharding contract (see parallel/sharding.py): expert dim E is sharded over the
``model`` mesh axis, expert hidden dim over ``data``; tokens enter sharded over
``data`` — XLA inserts the all-to-all at the gather.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as _P

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models.layers import DTYPE, dense_init

Params = Dict[str, jnp.ndarray]


def capacity(cfg: ModelConfig, num_tokens: int) -> int:
    m = cfg.moe
    c = int(math.ceil(m.num_experts_per_tok * num_tokens / m.num_experts
                      * m.capacity_factor))
    return max(8, -(-c // 8) * 8)  # round up to 8, min 8


def init_moe(cfg: ModelConfig, key) -> Params:
    m = cfg.moe
    d, f, E = cfg.d_model, cfg.d_ff, m.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, E), dtype=jnp.float32),
        "w_gate": dense_init(ks[1], (E, d, f), in_axis=-2),
        "w_up": dense_init(ks[2], (E, d, f), in_axis=-2),
        "w_down": dense_init(ks[3], (E, f, d), in_axis=-2),
    }
    if m.shared_d_ff:
        p["shared"] = layers.init_mlp(cfg, ks[4], d_ff=m.shared_d_ff)
    return p


def route(cfg: ModelConfig, router_w: jnp.ndarray, x: jnp.ndarray):
    """x: (T, d) -> (weights (T,k), experts (T,k), aux_loss scalar)."""
    m = cfg.moe
    logits = (x.astype(jnp.float32) @ router_w)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, m.num_experts_per_tok)
    weights = weights / jnp.maximum(jnp.sum(weights, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balancing auxiliary loss.
    T = x.shape[0]
    me = jnp.mean(probs, axis=0)  # mean router prob per expert
    one_hot = jax.nn.one_hot(experts[:, 0], m.num_experts, dtype=jnp.float32)
    ce = jnp.mean(one_hot, axis=0)  # fraction of tokens routed (top-1)
    aux = m.num_experts * jnp.sum(me * ce)
    return weights.astype(jnp.float32), experts, aux


def _num_groups(B: int, S: int) -> int:
    """Dispatch groups: one per data shard so slot assignment stays local."""
    from repro.parallel import sharding as _sh
    dp = _sh._axes_size_hint(_sh.data_axes()) or 1
    if B % dp == 0:
        return dp
    return 1


def _dispatch_indices(cfg: ModelConfig, experts: jnp.ndarray, C: int,
                      valid: jnp.ndarray = None):
    """Assign each (group, token, k) a slot in its expert capacity buffer.

    experts: (G, T, k) int32.  valid: optional (G, T) bool — tokens marked
    False (pad tokens, retired continuous-batching lanes) are routed to the
    drop bin and consume NO expert capacity, so they cannot displace live
    tokens.  Returns (slot (G,T,k) in [0,C] (C = dropped),
    buf_tok (G, E, C) int32 index into tokens of that group, T = empty).
    """
    m = cfg.moe
    G, T, k = experts.shape
    E = m.num_experts
    flat_e = experts.reshape(G, T * k)  # token-major, k-minor
    # FIFO position of each assignment within its expert — local cumsum.
    one_hot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (G, T*k, E)
    if valid is not None:
        flat_v = jnp.repeat(valid, k, axis=1)  # token-major matches flat_e
        one_hot = one_hot * flat_v[..., None].astype(jnp.int32)
    pos_in_e = jnp.cumsum(one_hot, axis=1) - 1
    slot = jnp.take_along_axis(pos_in_e, flat_e[..., None], axis=2)[..., 0]
    slot_c = jnp.where(slot < C, slot, C)  # dropped -> sentinel C
    if valid is not None:
        slot_c = jnp.where(flat_v, slot_c, C)  # dead -> drop bin
    # Scatter token ids into (G, E, C+1); column C is the drop bin.
    buf = jnp.full((G, E, C + 1), T, jnp.int32)
    g_idx = jnp.arange(G, dtype=jnp.int32)[:, None]
    tok_ids = (jnp.arange(T * k, dtype=jnp.int32) // k)[None, :]
    buf = buf.at[jnp.broadcast_to(g_idx, flat_e.shape), flat_e, slot_c].set(
        jnp.broadcast_to(tok_ids, flat_e.shape), mode="drop")
    return slot_c.reshape(G, T, k), buf[:, :, :C]


def apply_moe(cfg: ModelConfig, p: Params, x: jnp.ndarray,
              valid: jnp.ndarray = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (out (B, S, d), aux_loss).

    valid: optional (B, S) bool — False tokens (pads, retired serving
    slots) consume no expert capacity and get zero expert output (the
    shared expert still runs on them; their output is dead anyway).
    Only the auto-partitioned path supports it — the manual-collective
    path is skipped when a mask is given.
    """
    m = cfg.moe
    B, S, d = x.shape
    if valid is None and manual_path_available(cfg, B * S):
        return apply_moe_manual(cfg, p, x)
    E = m.num_experts
    G = _num_groups(B, S)
    T = (B * S) // G  # tokens per group
    xt = x.reshape(G, T, d)
    C = capacity(cfg, T)

    weights, experts, aux = route(cfg, p["router"], xt.reshape(G * T, d))
    weights = weights.reshape(G, T, -1)
    experts = experts.reshape(G, T, -1)
    slot, buf_tok = _dispatch_indices(
        cfg, experts, C,
        valid.reshape(G, T) if valid is not None else None)

    # Gather tokens into per-expert buffers: (G, E, C, d).  Clip+mask instead
    # of a sentinel pad row: padding (T+1) would break the GSPMD tiling of the
    # token dim and force an all-gather.
    empty = buf_tok >= T  # (G, E, C)
    idx = jnp.minimum(buf_tok, T - 1)
    expert_in = jnp.take_along_axis(
        xt[:, :, None, :], idx.reshape(G, E * C, 1, 1), axis=1
    ).reshape(G, E, C, d)
    expert_in = jnp.where(empty[..., None], 0, expert_in)

    # Anchor the expert-parallel layout: E over ``data`` (matches the expert
    # weight sharding), hidden over ``model``.  The gather above is therefore
    # the all-to-all from token-sharding to expert-sharding.
    from repro.parallel import sharding as _sh
    ep = "data" if _sh.axis_size("data") > 1 else None
    tp = _sh.tp_axis()
    expert_in = _sh.constrain(expert_in, _P(None, ep, None, None))

    # Expert FFN (SwiGLU), batched over (group, expert).
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", expert_in, p["w_gate"])) * \
        jnp.einsum("gecd,edf->gecf", expert_in, p["w_up"])
    h = _sh.constrain(h, _P(None, ep, None, tp))
    expert_out = jnp.einsum("gecf,efd->gecd", h, p["w_down"])  # (G, E, C, d)
    expert_out = _sh.constrain(expert_out, _P(None, ep, None, None))

    # Combine: scatter-add each expert slot's weighted output back to its
    # token.  A gather formulation (token -> slot) makes GSPMD all-gather
    # the expert-sharded outputs; the scatter formulation reshards the
    # updates from expert-sharding to token-sharding — an all-to-all, the
    # same wire pattern as the dispatch.  bf16 throughout (k <= 8 terms).
    k = weights.shape[-1]
    w_flat = weights.reshape(G, T * k).astype(x.dtype)
    flat_e = experts.reshape(G, T * k)
    slot_f = slot.reshape(G, T * k)
    g_idx = jnp.arange(G, dtype=jnp.int32)[:, None]
    w_slot = jnp.zeros((G, E, C + 1), x.dtype)
    w_slot = w_slot.at[jnp.broadcast_to(g_idx, flat_e.shape), flat_e,
                       slot_f].set(w_flat, mode="drop")[:, :, :C]
    contrib = expert_out * w_slot[..., None]  # (G, E, C, d), E-sharded

    tok_idx = jnp.minimum(buf_tok, T - 1).reshape(G, E * C)
    updates = jnp.where((buf_tok < T).reshape(G, E * C, 1),
                        contrib.reshape(G, E * C, d), 0)
    out = jnp.zeros((G, T, d), x.dtype).at[
        jnp.broadcast_to(g_idx, tok_idx.shape), tok_idx].add(updates)

    if "shared" in p:
        out = out + apply_shared(cfg, p["shared"], xt.reshape(G * T, d)
                                 ).reshape(G, T, d)
    return out.reshape(B, S, d), aux


def apply_shared(cfg: ModelConfig, p: Params, xt: jnp.ndarray) -> jnp.ndarray:
    h = jax.nn.silu(xt @ p["w_gate"]) * (xt @ p["w_up"])
    return h @ p["w_down"]


def E_total(cfg: ModelConfig) -> int:
    return cfg.moe.num_experts


# ---------------------------------------------------------------------------
# Manual-collective MoE (shard_map): the §Perf H8 optimization.
#
# The auto-partitioned path pays two structural penalties at scale:
#   1. GSPMD cannot infer the token<->expert redistribution as an all-to-all
#      in every direction (the combine gather becomes an all-gather of the
#      full expert output buffer);
#   2. the tensor-parallel psum of the expert FFN runs on the
#      capacity-expanded slot space (E*C*d ~ top_k * cf * token volume).
#
# This path makes both explicit: local top-k -> lax.all_to_all over the
# expert-parallel axes -> manual-TP expert FFN (NO psum) -> reverse
# all_to_all -> local combine -> ONE psum over `model` in token space.
# Wire bytes per layer: 2 * T*d (a2a) + 2 * T*d (psum) instead of
# ~10-80x that.
# ---------------------------------------------------------------------------

def _manual_axes():
    from repro.parallel import sharding as _sh
    st = _sh.axis_state()
    ep = tuple(a for a in ("pod", "data") if st.size(a) > 1)
    tp = st.tp if st.size(st.tp) > 1 else None
    ep_n = 1
    for a in ep:
        ep_n *= st.size(a)
    tp_n = st.size(tp) if tp else 1
    return ep, ep_n, tp, tp_n


def manual_path_available(cfg: ModelConfig, T: int) -> bool:
    ep, ep_n, tp, tp_n = _manual_axes()
    m = cfg.moe
    return (ep_n > 1 and tp is not None
            and m.num_experts % ep_n == 0
            and T % ep_n == 0
            and cfg.d_ff % tp_n == 0
            and cfg.d_model % tp_n == 0)


def apply_moe_manual(cfg: ModelConfig, p: Params, x: jnp.ndarray):
    """x: (B, S, d) -> (out, aux). Requires manual_path_available()."""
    from repro.parallel import sharding as _sh

    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E = m.num_experts
    ep, ep_n, tp, tp_n = _manual_axes()
    mesh = _sh.current_mesh()
    T_loc = T // ep_n
    C = capacity(cfg, T_loc)
    E_loc = E // ep_n

    router_w = p["router"]
    wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]

    d_loc = d // tp_n

    def local(xt, rw, wg_l, wu_l, wd_l):
        # xt: (T_loc, d_loc) — the dispatch payload is sharded over `model`
        # so the expert all-to-all is NOT replicated across TP shards
        # (H8 residual (a): 16x wire saving on the dispatch direction).
        # wg_l/wu_l: (E_loc, d, f_loc); wd_l: (E_loc, f_loc, d).
        tp_i = jax.lax.axis_index(tp)

        # Routing needs full-d logits: psum of the partial router matmul
        # ((T_loc, E) fp32 — tiny). All TP shards then agree on the top-k.
        logits = jax.lax.psum(xt.astype(jnp.float32) @
                              jax.lax.dynamic_slice_in_dim(
                                  rw, tp_i * d_loc, d_loc, 0), tp)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(probs, m.num_experts_per_tok)
        weights = weights / jnp.maximum(
            jnp.sum(weights, axis=-1, keepdims=True), 1e-9)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(experts[:, 0], E, dtype=jnp.float32),
                      axis=0)
        aux = E * jnp.sum(me * ce)

        slot, buf_tok = _dispatch_indices(
            cfg, experts[None], C)  # add a singleton group dim
        slot, buf_tok = slot[0], buf_tok[0]  # (T_loc, k), (E, C)

        # Local dispatch into (E, C, d_loc).
        empty = buf_tok >= T_loc
        idx = jnp.minimum(buf_tok, T_loc - 1)
        expert_in = xt[idx.reshape(-1)].reshape(E, C, d_loc)
        expert_in = jnp.where(empty[..., None], 0, expert_in)

        # token-shards -> expert-shards (payload d-sharded over tp).
        expert_in = jax.lax.all_to_all(
            expert_in, ep, split_axis=0, concat_axis=1, tiled=True
        )  # (E_loc, C*ep_n, d_loc)

        # Manual-TP expert FFN. Weights are d-sharded over tp (matching the
        # payload): the up-projections are d-partial and reduced ONCE at
        # h-volume; the down-projection is then exact with a d_loc-sliced
        # output, so the reverse all-to-all also carries d/tp payloads and
        # no further reduction is needed.
        g_part = jnp.einsum("ecd,edf->ecf", expert_in, wg_l)
        u_part = jnp.einsum("ecd,edf->ecf", expert_in, wu_l)
        g_full, u_full = jax.lax.psum((g_part, u_part), tp)
        h = jax.nn.silu(g_full) * u_full
        y_part = jnp.einsum("ecf,efd->ecd", h, wd_l)  # exact, d_loc output

        # expert-shards -> token-shards (d_loc payload).
        y_exact = jax.lax.all_to_all(
            y_part, ep, split_axis=1, concat_axis=0, tiled=True
        )  # (E, C, d_loc)

        # Local combine: scatter-add of weighted slots. No trailing psum —
        # y is exact, sharded over tp along d like the input.
        k = weights.shape[-1]
        w_flat = weights.reshape(T_loc * k).astype(x.dtype)
        flat_e = experts.reshape(T_loc * k)
        slot_f = slot.reshape(T_loc * k)
        w_slot = jnp.zeros((E, C + 1), x.dtype)
        w_slot = w_slot.at[flat_e, slot_f].set(w_flat, mode="drop")[:, :C]
        contrib = (y_exact * w_slot[..., None]).reshape(E * C, d_loc)
        tok_idx = jnp.minimum(buf_tok, T_loc - 1).reshape(E * C)
        contrib = jnp.where((buf_tok < T_loc).reshape(E * C, 1), contrib, 0)
        y = jnp.zeros((T_loc, d_loc), x.dtype).at[tok_idx].add(contrib)
        aux = jax.lax.pmean(aux, ep + (tp,))
        return y, aux

    P_ = _P
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P_(ep, tp), P_(None, None),
                  P_(ep, tp, None), P_(ep, tp, None), P_(ep, None, tp)),
        out_specs=(P_(ep, tp), P_()),
        check_vma=False)
    out, aux = fn(x.reshape(T, d), router_w, wg, wu, wd)
    out = out.reshape(B, S, d)
    if "shared" in p:
        out = out + apply_shared(cfg, p["shared"], x.reshape(T, d)
                                 ).reshape(B, S, d)
    return out, aux
