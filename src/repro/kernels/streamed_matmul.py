"""streamed_matmul — the paper's rewritten kernel (Fig 5b), TPU-native.

FlashMem's kernel rewriting interleaves weight-tile loading with MAC
compute in a branch-free software pipeline. On TPU that pipeline IS the
Pallas grid pipeline: BlockSpec index maps drive double-buffered HBM->VMEM
DMAs of the *next* (A, B) tiles while the MXU consumes the current ones —
uniform per-grid-step schedule, no divergence hazard (TPU has no warps; the
analogous hazard, serialized DMA bubbles, is removed by the pipeline).

Grid (M/bm, N/bn, K/bk); f32 accumulator lives in VMEM scratch across the
K-steps ("arbitrary" innermost dimension), flushed on the last K step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pick(block: int, dim: int, align: int) -> int:
    b = min(block, dim)
    while dim % b:
        b -= align if b > align else 1
    return max(b, 1)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def streamed_matmul(a: jax.Array, b: jax.Array, *, block_m: int = 256,
                    block_n: int = 256, block_k: int = 512,
                    interpret: bool = True) -> jax.Array:
    """C[M,N] = A[M,K] @ B[K,N] with double-buffered weight streaming."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm = _pick(block_m, m, 8)
    bn = _pick(block_n, n, 128)
    bk = _pick(block_k, k, 128)
    nk = k // bk
    grid = (m // bm, n // bn, nk)

    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(a, b)
