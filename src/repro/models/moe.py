"""Mixture-of-Experts: token-choice top-k routing with capacity-based gather
dispatch (static shapes, GSPMD-friendly).

Two execution modes:
  * "gather"  — production path. Assignments are sorted by expert, truncated
    to a static per-expert capacity C = ceil(T*k/E * cf) (rounded to an MXU
    tile multiple), gathered into [E, C, d] and run through grouped einsums.
    FLOPs scale with *activated* params (top-k), which is what the roofline
    MODEL_FLOPS/HLO_FLOPs ratio checks.
  * "dense"   — every expert over every token, weighted by the (top-k-masked)
    router probs. Exact reference for tests; O(E/k) more FLOPs.

Sharding: expert dim maps to the model axis when divisible (EP — qwen3 128e,
jamba 16e); otherwise the per-expert FFN dim takes the model axis (TP —
mixtral 8e on a 16-way axis). The MeshEnv divisibility rule picks this
automatically per parameter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import MeshEnv, ParamSpec
from repro.models.layers import activation


def moe_specs(cfg: ModelConfig, prefix_layers: tuple = ()) -> dict:
    # TP-over-expert-ff by default ("expert_ff" -> model): every chip holds a
    # f/16 slice of EVERY expert, so dispatch/combine stay batch-local and the
    # only collective is one [B,S,d] psum after the (linear) combine —
    # EXPERIMENTS.md §Perf iteration 3. "experts" -> model (EP) kicks in via
    # the divisibility rule only when f doesn't divide the model axis.
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff, m.n_experts
    lyr = tuple("layers" for _ in prefix_layers)
    dt = jnp.bfloat16
    out = {
        "router": ParamSpec((*prefix_layers, d, e), jnp.float32, lyr + ("embed", None)),
        "wi": ParamSpec((*prefix_layers, e, d, f), dt, lyr + (None, "fsdp_row", "expert_ff")),
        "wo": ParamSpec((*prefix_layers, e, f, d), dt, lyr + (None, "expert_ff", "fsdp_row")),
    }
    if cfg.glu:
        out["wg"] = ParamSpec((*prefix_layers, e, d, f), dt,
                              lyr + (None, "fsdp_row", "expert_ff"))
    return out


def capacity(tokens: int, n_experts: int, top_k: int, cf: float) -> int:
    c = int(np.ceil(tokens * top_k * cf / n_experts))
    return max(8, int(np.ceil(c / 8)) * 8)


def _router(cfg: ModelConfig, p: dict, x2d: jax.Array):
    """x2d: [T, d] -> (weights [T,k], ids [T,k], aux losses)."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, m.top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    # load-balance aux (Switch-style) + router z-loss
    me = jnp.mean(probs, axis=0)                                  # [E]
    ce = jnp.mean(
        jax.nn.one_hot(ids[:, 0], m.n_experts, dtype=jnp.float32), axis=0)
    lb_loss = m.n_experts * jnp.sum(me * ce)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return w, ids, {"lb_loss": lb_loss, "z_loss": z_loss}


def _expert_ffn(cfg: ModelConfig, p: dict, xe: jax.Array, env: MeshEnv):
    """xe: [B, E, C, d] -> [B, E, C, d] through each expert's FFN.

    The group dim B stays sharded over data and E over model (EP) when
    divisible, else the per-expert FFN dim takes the model axis (TP) —
    compute is fully sharded both ways (§Perf iterations 1-2)."""
    xe = env.constrain(xe, "batch", "experts", None, None)
    h = jnp.einsum("becd,edf->becf", xe, p["wi"])
    h = env.constrain(h, "batch", "experts", None, "expert_ff")
    if cfg.glu:
        g = jnp.einsum("becd,edf->becf", xe, p["wg"])
        h = activation(cfg, g) * h
    else:
        h = activation(cfg, h)
    out = jnp.einsum("becf,efd->becd", h, p["wo"])
    return env.constrain(out, "batch", "experts", None, None)


def _dispatch_group(m, tg: int, c: int, d: int, x_row, w_row, id_row):
    """Group-local capacity dispatch: one batch row's tokens -> [E, C, d]."""
    e_flat = id_row.reshape(-1)                           # [Tg*k]
    tok_flat = jnp.repeat(jnp.arange(tg), m.top_k)
    w_flat = w_row.reshape(-1)
    order = jnp.argsort(e_flat, stable=True)
    se, stok, sw = e_flat[order], tok_flat[order], w_flat[order]
    first = jnp.searchsorted(se, se, side="left")
    pos = jnp.arange(tg * m.top_k) - first
    keep = pos < c
    slot = jnp.where(keep, se * c + pos,
                     tg * m.top_k + c * m.n_experts)      # OOB -> drop
    idx = jnp.full((m.n_experts * c,), tg, jnp.int32)     # tg = pad row
    idx = idx.at[slot].set(stok.astype(jnp.int32), mode="drop")
    gate = jnp.zeros((m.n_experts * c,), jnp.float32)
    gate = gate.at[slot].set(sw, mode="drop")
    x_pad = jnp.concatenate([x_row, jnp.zeros((1, d), x_row.dtype)], 0)
    xe = x_pad[idx].reshape(m.n_experts, c, d)
    return xe, idx, gate, jnp.sum(keep)


def _combine_group(m, tg: int, c: int, d: int, ye_row, idx_row, gate_row):
    flat = ye_row.reshape(m.n_experts * c, d).astype(jnp.float32)
    flat = flat * gate_row[:, None]
    return jnp.zeros((tg + 1, d), jnp.float32).at[idx_row].add(flat)[:tg]


def apply_moe_shardmap(cfg: ModelConfig, p: dict, x: jax.Array, env: MeshEnv):
    """TP-f MoE under shard_map: experts' ff dim sharded over the model axis,
    tokens sharded over data. Dispatch and combine are shard-local; the
    partial f-contributions cross chips exactly once, as a psum of the
    *combined* [B, S, d] output (the combine is linear, so reducing after it
    is exact). GSPMD cannot move an all-reduce across a scatter on its own —
    this path encodes the optimization explicitly (§Perf iteration 3)."""
    m = cfg.moe
    b, s, d = x.shape
    mesh = env.mesh
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    data_size = 1
    for a in data_axes:
        data_size *= mesh.shape[a]
    if b % max(data_size, 1):
        data_axes = ()            # batch-1 decode: replicate over data
    P_ = jax.sharding.PartitionSpec

    c = capacity(s, m.n_experts, m.top_k, m.capacity_factor)

    def local_fn(x_loc, router_w, wi, wg, wo, dt_bias_unused):
        del dt_bias_unused
        bl = x_loc.shape[0]
        x2d = x_loc.reshape(bl * s, d)
        w, ids, aux = _router(cfg, {"router": router_w}, x2d)
        wg_r = w.reshape(bl, s, m.top_k)
        ids_r = ids.reshape(bl, s, m.top_k)
        xg = x2d.reshape(bl, s, d)
        xe, idx, gate, kept = jax.vmap(
            lambda xr, wr, ir: _dispatch_group(m, s, c, d, xr, wr, ir)
        )(xg, wg_r, ids_r)                                 # [Bl,E,C,d]
        h = jnp.einsum("becd,edf->becf", xe, wi)
        if wg is not None:
            g = jnp.einsum("becd,edf->becf", xe, wg)
            h = activation(cfg, g) * h
        else:
            h = activation(cfg, h)
        out = jnp.einsum("becf,efd->becd", h, wo)          # partial over f
        y = jax.vmap(lambda yr, ir, gr: _combine_group(m, s, c, d, yr, ir, gr)
                     )(out, idx, gate)                     # [Bl,S,d] partial
        y = jax.lax.psum(y, "model")
        # aux losses: shard-local means, averaged over data shards
        aux = {k: jax.lax.pmean(v, data_axes) if data_axes else v
               for k, v in aux.items()}
        aux["dropped_frac"] = 1.0 - (
            (jax.lax.pmean(jnp.sum(kept) / (bl * s * m.top_k), data_axes))
            if data_axes else jnp.sum(kept) / (bl * s * m.top_k))
        return y.astype(x_loc.dtype), aux

    batch_spec = P_(data_axes if data_axes else None)
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P_(*batch_spec, None, None), P_(None, None),
                  P_(None, None, "model"),
                  P_(None, None, "model") if cfg.glu else P_(),
                  P_(None, "model", None), P_()),
        out_specs=(P_(*batch_spec, None, None),
                   {"lb_loss": P_(), "z_loss": P_(), "dropped_frac": P_()}),
        check_vma=False,
    )
    y, aux = fn(x, p["router"], p["wi"],
                p.get("wg") if cfg.glu else jnp.zeros((), x.dtype),
                p["wo"], jnp.zeros((), x.dtype))
    return y, aux


def apply_moe(cfg: ModelConfig, p: dict, x: jax.Array, env: MeshEnv,
              mode: str = "gather"):
    """x: [B, S, d] -> (y [B, S, d], aux dict)."""
    m = cfg.moe
    b, s, d = x.shape
    if (mode == "gather" and "model" in env.mesh.axis_names
            and m.d_ff % env.mesh.shape["model"] == 0
            and env.rules.get("expert_ff") is not None):
        return apply_moe_shardmap(cfg, p, x, env)
    t = b * s
    x2d = x.reshape(t, d)
    w, ids, aux = _router(cfg, p, x2d)

    if mode == "dense":
        mask = jnp.zeros((t, m.n_experts), jnp.float32)
        mask = jax.vmap(lambda mm, ii, ww: mm.at[ii].add(ww))(mask, ids, w)
        ye = _expert_ffn(
            cfg, p, jnp.broadcast_to(x2d, (m.n_experts, t, d))[None], env)[0]
        y = jnp.einsum("etd,te->td", ye.astype(jnp.float32), mask)
        return y.reshape(b, s, d).astype(x.dtype), aux

    # group-local dispatch: tokens are grouped by batch row and dispatched
    # with per-group capacity under vmap, so the gather/scatter index space
    # never crosses data shards. A single global dispatch makes GSPMD
    # replicate the [E, C, d] gather result (observed: 42.9 GB all-gathers
    # x288 + all-reduces x96 per step — EXPERIMENTS.md §Perf iteration 2);
    # grouped dispatch keeps compute and combine fully batch-sharded at the
    # cost of per-group (vs global) capacity truncation.
    c = capacity(s, m.n_experts, m.top_k, m.capacity_factor)
    wg = w.reshape(b, s, m.top_k)
    idsg = ids.reshape(b, s, m.top_k)
    xg = x2d.reshape(b, s, d)
    xe, idx, gate, kept = jax.vmap(
        lambda xr, wr, ir: _dispatch_group(m, s, c, d, xr, wr, ir)
    )(xg, wg, idsg)                                           # [B,E,C,d]
    ye = _expert_ffn(cfg, p, xe, env)                         # [B,E,C,d]
    y = jax.vmap(lambda yr, ir, gr: _combine_group(m, s, c, d, yr, ir, gr)
                 )(ye, idx, gate)                             # [B,S,d]
    aux["dropped_frac"] = 1.0 - jnp.sum(kept) / (t * m.top_k)
    return y.astype(x.dtype), aux
