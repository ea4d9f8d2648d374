"""ssd_scan — Mamba-2 SSD chunked scan as a Pallas kernel.

Grid (B*H, S/Q): the chunk dimension is sequential ("arbitrary") and the
inter-chunk state [N, P] lives in VMEM scratch, so the recurrence never
round-trips HBM — the TPU analogue of mamba's fused CUDA scan, but built
from MXU matmuls (the SSD duality) instead of a bandwidth-bound elementwise
scan (DESIGN.md §2).

Per chunk (length Q):
  L      = cumsum(dt * a)                      [Q]
  y_intra= ((C B^T) o decay o dt) X            (tril-masked)
  y_inter= exp(L) C . state
  state  = exp(L_Q) state + sum_j exp(L_Q - L_j) dt_j B_j (x) X_j
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, state_ref, *,
            q: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)        # [Q, P]
    dt = dt_ref[0].astype(jnp.float32)      # [Q, 1]
    a = a_ref[0].astype(jnp.float32)        # [1, 1]
    bb = b_ref[0].astype(jnp.float32)       # [Q, N]
    cc = c_ref[0].astype(jnp.float32)       # [Q, N]
    d = d_ref[0].astype(jnp.float32)        # [1, 1]

    alog = dt * a[0, 0]                     # [Q, 1]
    lcum = jnp.cumsum(alog, axis=0)         # [Q, 1]

    di = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    dj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tril = di >= dj

    # mask the exponent: masked (i<j) entries have positive L_i - L_j that
    # overflow exp() in f32 (inf fwd / nan grads)
    decay = jnp.exp(jnp.where(tril, lcum - lcum[:, 0][None, :], -1e30))
    gmat = jax.lax.dot_general(cc, bb, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)  # [Q,Q]
    m = gmat * decay * dt[:, 0][None, :]                 # [Q, Q]
    y_intra = jax.lax.dot_general(m, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    state = state_ref[...]                               # [N, P]
    y_inter = jax.lax.dot_general(cc, state, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_inter = y_inter * jnp.exp(lcum)

    l_last = lcum[q - 1, 0]
    w = jnp.exp(l_last - lcum[:, 0]) * dt[:, 0]          # [Q]
    s_new = jax.lax.dot_general(bb * w[:, None], x, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [N, P]
    state_ref[...] = jnp.exp(l_last) * state + s_new

    y_ref[0] = (y_intra + y_inter + x * d[0, 0]).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, d_skip: jax.Array, *, chunk: int = 256,
             interpret: bool = True) -> jax.Array:
    """x: [B,S,H,P]; dt: [B,S,H]; a,d_skip: [H]; b/c: [B,S,N].
    Returns y [B,S,H,P] (f32), matching kernels/ref.ssd_ref."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk, s)
    while s % q:
        q //= 2
    nc = s // q

    # [B*H, S, .] layouts; B/C shared across heads via index map
    xr = x.transpose(0, 2, 1, 3).reshape(bsz * h, s, p)
    dtr = dt.transpose(0, 2, 1).reshape(bsz * h, s, 1)
    ar = jnp.broadcast_to(a[None, :], (bsz, h)).reshape(bsz * h, 1, 1)
    dr = jnp.broadcast_to(d_skip[None, :], (bsz, h)).reshape(bsz * h, 1, 1)
    br = b.reshape(bsz, s, n)
    cr = c.reshape(bsz, s, n)

    y = pl.pallas_call(
        functools.partial(_kernel, q=q),
        grid=(bsz * h, nc),
        in_specs=[
            pl.BlockSpec((1, q, p), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, q, 1), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, 1), lambda bh, ci: (bh, 0, 0)),
            pl.BlockSpec((1, q, n), lambda bh, ci, h=h: (bh // h, ci, 0)),
            pl.BlockSpec((1, q, n), lambda bh, ci, h=h: (bh // h, ci, 0)),
            pl.BlockSpec((1, 1, 1), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, q, p), lambda bh, ci: (bh, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz * h, s, p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(xr, dtr, ar, br, cr, dr)
    return y.reshape(bsz, h, s, p).transpose(0, 2, 1, 3)
