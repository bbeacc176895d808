"""Expert-parallel MoE dispatch via shard_map + all_to_all (the EP path).

Why this exists: the global sort-based dispatch in ``moe.py`` is correct
single-device but does NOT partition — GSPMD resolves its cross-shard
gathers by materializing (T*K, d) tensors with all-reduces (measured:
~16 TB/step/device collective traffic on deepseek-v2 train_4k).  The
production dispatch is explicit:

  per device (tokens sharded over pod x data, experts over model):
    1. local top-k routing,
    2. bucket tokens by OWNING EXPERT SHARD -> all_to_all over 'model',
    3. local second-stage dispatch (sort by local expert, capacity C),
    4. batched expert FFN einsum,
    5. reverse all_to_all, weighted combine at the source slots.

Token overflow at either stage is dropped-and-counted (standard capacity
semantics).  Differentiable end-to-end (all_to_all / take / scatter-add
have transposes); validated against the global path in tests.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig, MoECfg
from repro.models.layers import glu_act
from repro.models import sharding as shlib


def _segment_positions(sorted_keys):
    """Position of each element within its equal-key run."""
    n = sorted_keys.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    heads = jnp.concatenate(
        [jnp.array([True]), sorted_keys[1:] != sorted_keys[:-1]])
    seg_start = jax.lax.cummax(jnp.where(heads, idx, 0), axis=0)
    return idx - seg_start


def _local_moe(x, router, w_gate, w_up, w_down, *, cfg: ModelConfig,
               ep_axis: str, n_ep: int, dp_axes):
    """Per-device body.  x: (T_loc, d); experts: (E_loc, d, h)."""
    m: MoECfg = cfg.moe
    T_loc, d = x.shape
    E, K = m.n_experts, m.top_k
    E_loc = E // n_ep

    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)       # (T_loc, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # Aux losses (local shard contribution; caller pmeans).
    density = jnp.mean(
        jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32), axis=0)
    mean_probs = jnp.mean(probs, axis=0)
    lb = E * jnp.sum(density * mean_probs) * m.lb_coef
    zl = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2) * m.router_z_coef

    flat_e = expert_ids.reshape(-1).astype(jnp.int32)     # (T_loc*K,)
    flat_t = (jnp.arange(T_loc * K, dtype=jnp.int32) // K)
    flat_g = gate_vals.reshape(-1)
    dest = flat_e // E_loc                                # owning shard

    # --- stage 1: bucket by destination shard, all_to_all ------------------
    cap_send = max(1, math.ceil(T_loc * K * m.capacity_factor / n_ep))
    order = jnp.argsort(dest * jnp.int32(E) + flat_e, stable=True)
    s_dest = dest[order]
    s_tok = flat_t[order]
    s_exp = flat_e[order]
    pos = _segment_positions(s_dest)
    ok = pos < cap_send
    slot = jnp.where(ok, s_dest * cap_send + pos, n_ep * cap_send)
    drop1 = jnp.sum(~ok)

    send_x = jnp.zeros((n_ep * cap_send, d), x.dtype).at[slot].set(
        x[s_tok], mode="drop")
    send_le = jnp.full((n_ep * cap_send,), -1, jnp.int32).at[slot].set(
        s_exp % E_loc, mode="drop")
    if n_ep > 1:
        recv_x = jax.lax.all_to_all(
            send_x.reshape(n_ep, cap_send, d), ep_axis, 0, 0)
        recv_le = jax.lax.all_to_all(
            send_le.reshape(n_ep, cap_send), ep_axis, 0, 0)
    else:
        recv_x = send_x.reshape(1, cap_send, d)
        recv_le = send_le.reshape(1, cap_send)
    R = n_ep * cap_send
    recv_x = recv_x.reshape(R, d)
    recv_le = recv_le.reshape(R)

    # --- stage 2: local dispatch by local expert id -------------------------
    C = max(1, math.ceil(R * 1.0 / E_loc))
    key = jnp.where(recv_le >= 0, recv_le, E_loc)         # invalid last
    order2 = jnp.argsort(key, stable=True)
    s_le = key[order2]
    pos2 = _segment_positions(s_le)
    ok2 = (pos2 < C) & (s_le < E_loc)
    slot2 = jnp.where(ok2, s_le * C + pos2, E_loc * C)
    drop2 = jnp.sum((~ok2) & (s_le < E_loc))

    buf = jnp.zeros((E_loc * C, d), x.dtype).at[slot2].set(
        recv_x[order2], mode="drop").reshape(E_loc, C, d)
    h = glu_act(cfg.mlp if cfg.mlp != "none" else "swiglu",
                jnp.einsum("ecd,edh->ech", buf, w_gate),
                jnp.einsum("ecd,edh->ech", buf, w_up))
    out_buf = jnp.einsum("ech,ehd->ecd", h, w_down).reshape(E_loc * C, d)

    # Return to recv slots, reverse all_to_all, combine at source.
    back = jnp.zeros((R, d), x.dtype)
    back = back.at[order2].set(
        out_buf.at[slot2].get(mode="fill", fill_value=0))
    if n_ep > 1:
        ret = jax.lax.all_to_all(
            back.reshape(n_ep, cap_send, d), ep_axis, 0, 0)
    else:
        ret = back.reshape(1, cap_send, d)
    ret = ret.reshape(n_ep * cap_send, d)

    per_assign = ret.at[slot].get(mode="fill", fill_value=0)  # sorted order
    weights = jnp.where(ok, flat_g[order], 0.0).astype(x.dtype)
    out = jnp.zeros((T_loc, d), x.dtype).at[s_tok].add(
        per_assign * weights[:, None])

    drop_frac = (drop1 + drop2).astype(jnp.float32) / (T_loc * K)
    # Mean aux across all devices.
    all_axes = tuple(dp_axes) + (ep_axis,)
    lb = jax.lax.pmean(lb, all_axes)
    zl = jax.lax.pmean(zl, all_axes)
    drop_frac = jax.lax.pmean(drop_frac, all_axes)
    return out, lb, zl, drop_frac


def moe_ffn_ep(p, cfg: ModelConfig, x):
    """Expert-parallel MoE.  x: (B, S, d).  Needs an active mesh whose
    rules map 'experts' to a mesh axis; otherwise caller should use the
    dense-global fallback."""
    ctx = getattr(shlib._ACTIVE, "ctx", None)
    assert ctx is not None
    mesh, rules = ctx
    m = cfg.moe
    ep_axis = shlib.resolve_axis(rules, "experts", mesh)
    dp_axes = shlib.resolve_axis(rules, "batch", mesh) or ()
    if isinstance(dp_axes, str):
        dp_axes = (dp_axes,)
    # DP-heavy rules put the EP axis in "batch" too — dedup it here.
    dp_axes = tuple(a for a in dp_axes if a != ep_axis)
    n_ep = mesh.shape[ep_axis] if ep_axis else 1
    assert ep_axis and m.n_experts % n_ep == 0

    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    # Tokens shard over DP axes AND the EP axis for dispatch (DP x EP
    # grid) — each device routes its OWN token slice.  Without the EP
    # axis every model-column would route identical tokens: measured 16x
    # redundant expert compute on the 16x16 mesh (EXPERIMENTS.md §Perf).
    # Decode-sized batches (T < n_devices) shard over the largest prefix
    # that divides T; the residual replication is cheap at decode FLOPs.
    T = B * S
    token_axes = tuple(dp_axes) + (ep_axis,)
    while token_axes:
        n = 1
        for a in token_axes:
            n *= mesh.shape[a]
        if T % n == 0:
            break
        token_axes = token_axes[:-1]
    if not token_axes:
        from repro.models.moe import moe_ffn
        return moe_ffn(p, cfg, x)   # tiny T: dense-global fallback
    body = lambda xt_, r_, wg_, wu_, wd_: _local_moe(
        xt_, r_, wg_, wu_, wd_, cfg=cfg, ep_axis=ep_axis, n_ep=n_ep,
        dp_axes=dp_axes)
    out, lb, zl, dropf = shard_map(
        body, mesh=mesh,
        in_specs=(P(token_axes, None),
                  P(), P(ep_axis), P(ep_axis), P(ep_axis)),
        out_specs=(P(token_axes, None), P(), P(), P()),
        check_vma=False,
    )(xt, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    out = out.reshape(B, S, d)
    if m.n_shared:
        shared = glu_act(
            cfg.mlp if cfg.mlp != "none" else "swiglu",
            xt @ p["ws_gate"], xt @ p["ws_up"]) @ p["ws_down"]
        out = out + shared.reshape(B, S, d)
    aux = {"lb_loss": lb, "z_loss": zl, "drop_frac": dropf}
    return out, aux
