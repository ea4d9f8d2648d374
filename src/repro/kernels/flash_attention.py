"""flash_attention — fused online-softmax attention (causal / sliding
window), grid (batch*heads, Sq/bq, Sk/bkv) with m/l/acc carried in VMEM
scratch across the innermost ("arbitrary") KV dimension.

This is the Pallas replacement for the pure-JAX blocked attention in
models/attention.py: scores never touch HBM, removing the memory-term cost
the roofline analysis attributes to the jnp path (EXPERIMENTS.md §Perf).
GQA is handled by the index map (kv head = q head // group).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, bq: int, bkv: int, nk: int, causal: bool,
            window: int):
    qi = pl.program_id(1)
    jj = pl.program_id(2)

    @pl.when(jj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block validity: any (q,k) pair inside visible?
    rel = qi * bq - jj * bkv
    visible = True
    if causal:
        visible = rel + bq - 1 >= 0
    if window:
        visible = jnp.logical_and(visible, rel - (bkv - 1) < window)

    @pl.when(visible)
    def _step():
        q = q_ref[0].astype(jnp.float32)                 # [bq, hd]
        k = k_ref[0].astype(jnp.float32)                 # [bkv, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bkv]
        di = jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        dj = jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        delta = di - dj                                   # q_idx - k_idx
        mask = jnp.ones((bq, bkv), jnp.bool_)
        if causal:
            mask &= delta >= -rel
        if window:
            mask &= delta < (window - rel)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(jj == nk - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _pick(block: int, dim: int) -> int:
    b = min(block, dim)
    while dim % b:
        b //= 2
    return max(b, 1)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_kv", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 512, block_kv: int = 512,
                    interpret: bool = True) -> jax.Array:
    """q: [B,Sq,Hq,hd]; k/v: [B,Sk,Hkv,hd] -> [B,Sq,Hq,hd]."""
    b, sq, hq, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = 1.0 / np.sqrt(hd)
    bq = _pick(block_q, sq)
    bkv = _pick(block_kv, sk)
    nq, nk = sq // bq, sk // bkv

    # layout: [B*H, S, hd] so the grid's head dim maps kv heads via //g
    qr = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, hd)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bq=bq, bkv=bkv, nk=nk,
                          causal=causal, window=window),
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bkv, hd),
                         lambda h, i, j, g=g: (h // g, j, 0)),
            pl.BlockSpec((1, bkv, hd),
                         lambda h, i, j, g=g: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qr, kr, vr)
    return out.reshape(b, hq, sq, hd).transpose(0, 2, 1, 3)
