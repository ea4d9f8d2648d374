"""Context-parallel prefill (EXPERIMENTS.md §Perf iteration 7).

Megatron-SP prefill reshards the full residual stream 2xAG + 2xRS per layer
— at d_model 16k / seq 32k that is the dominant collective cost
(llama3-405b prefill: 66.8 s of the 101 s bound). Context parallelism
inverts the movement: the sequence stays sharded over the model axis for
the whole forward, and instead each layer all-gathers

  * its WEIGHTS (params/layer, independent of seq len), and
  * the GQA K/V heads (kv_heads * hd << d_model),

both of which are far smaller than the activations at long seq. Causal
masking uses the chunk's absolute offset (axis_index * S_local) via
blocked_attention(q_offset=...). Implemented with shard_map over
(data=batch, model=seq); weights enter sharded exactly as stored, so the
path composes with the standard checkpoint layout.

Trade-offs (recorded, not hidden): attention uses mode="full" inside the
chunk (causal-skip pairing does not apply across chunks), and the causal
prefix makes late chunks do more attention work than early ones — a known
CP imbalance (striping would fix it; out of scope).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.distributed.sharding import MeshEnv
from repro.models import attention as attn
from repro.models.layers import apply_norm
from repro.models.transformer import embed_tokens, logits_fn


def _gather_last(w, axis_name):
    """All-gather a weight sharded on its last dim."""
    return jax.lax.all_gather(w, axis_name, axis=w.ndim - 1, tiled=True)


def _gather_first(w, axis_name):
    return jax.lax.all_gather(w, axis_name, axis=0, tiled=True)


def cp_prefill(cfg: ModelConfig, run: RunConfig, env: MeshEnv, params,
               tokens, *, block_q: int = 1024, block_kv: int = 1024):
    """Dense-family context-parallel prefill -> last-position logits."""
    assert cfg.family == "dense", "CP prefill covers the dense LM family"
    mesh = env.mesh
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    P_ = jax.sharding.PartitionSpec
    b, s = tokens.shape
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads

    x = embed_tokens(cfg, params, tokens, env)        # [B,S,D] seq-sharded

    # stacked block weights enter exactly as stored: qkv/o sharded on the
    # heads (last/first) dim, ffn on the d_ff dim, norms replicated
    blocks = params["blocks"]
    bs = {
        "attn": {"wq": P_(None, None, "model"), "wk": P_(None, None, "model"),
                 "wv": P_(None, None, "model"), "wo": P_(None, "model", None)},
        "mlp": {k: (P_(None, "model", None) if k == "wo"
                    else P_(None, None, "model"))
                for k in blocks["mlp"]},
        "norm1": {k: P_(None, None) for k in blocks["norm1"]},
        "norm2": {k: P_(None, None) for k in blocks["norm2"]},
    }
    if cfg.qkv_bias:
        for k in ("bq", "bk", "bv"):
            bs["attn"][k] = P_(None, "model")
    bs["mlp"] = {"wi": P_(None, None, "model"), "wo": P_(None, "model", None),
                 **({"wg": P_(None, None, "model")} if cfg.glu else {})}

    def local_fn(x_loc, blocks_loc):
        s_loc = x_loc.shape[1]
        offset = jax.lax.axis_index("model") * s_loc
        positions = offset + jnp.arange(s_loc)[None, :]
        positions = jnp.broadcast_to(positions, (x_loc.shape[0], s_loc))

        def body(carry, p):
            xx = carry
            pa = dict(p["attn"])
            wq = _gather_last(pa["wq"], "model")
            wk = _gather_last(pa["wk"], "model")
            wv = _gather_last(pa["wv"], "model")
            wo = _gather_first(pa["wo"], "model")
            pa.update(wq=wq, wk=wk, wv=wv, wo=wo)
            for bias in ("bq", "bk", "bv"):
                if bias in pa:
                    pa[bias] = _gather_last(pa[bias], "model")
            h = apply_norm(cfg, p["norm1"], xx)
            q = attn._project(pa, "wq", h, nq, hd, "bq")
            k = attn._project(pa, "wk", h, nkv, hd, "bk")
            v = attn._project(pa, "wv", h, nkv, hd, "bv")
            q = attn._rope(cfg, q, positions)
            k = attn._rope(cfg, k, positions)
            # gather K/V across the sequence chunks (small: kv heads only)
            k_full = jax.lax.all_gather(k, "model", axis=1, tiled=True)
            v_full = jax.lax.all_gather(v, "model", axis=1, tiled=True)
            a = attn.blocked_attention(q, k_full, v_full, causal=True,
                                       window=cfg.sliding_window,
                                       block_q=block_q, block_kv=block_kv,
                                       mode="full", q_offset=offset)
            a = a.reshape(*a.shape[:2], -1)
            xx = xx + jnp.einsum("bsh,hd->bsd", a, wo)
            h = apply_norm(cfg, p["norm2"], xx)
            pm = {"wi": _gather_last(p["mlp"]["wi"], "model"),
                  "wo": _gather_first(p["mlp"]["wo"], "model")}
            if cfg.glu:
                pm["wg"] = _gather_last(p["mlp"]["wg"], "model")
            hh = jnp.einsum("bsd,df->bsf", h, pm["wi"])
            if cfg.glu:
                g = jnp.einsum("bsd,df->bsf", h, pm["wg"])
                hh = jax.nn.silu(g) * hh if cfg.act == "silu" \
                    else jax.nn.gelu(g) * hh
            else:
                hh = jax.nn.silu(hh) if cfg.act == "silu" else jax.nn.gelu(hh)
            xx = xx + jnp.einsum("bsf,fd->bsd", hh, pm["wo"])
            return xx, None

        x_loc, _ = jax.lax.scan(body, x_loc, blocks_loc)
        return x_loc

    dsize = 1
    for a in data_axes:
        dsize *= mesh.shape[a]
    batch_axes = data_axes if data_axes and b % dsize == 0 else ()

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P_(batch_axes or None, "model", None), bs),
        out_specs=P_(batch_axes or None, "model", None),
        check_vma=False,
    )
    x = fn(x, blocks)
    logits = logits_fn(cfg, params, x, env)
    return logits[:, -1:, :]
