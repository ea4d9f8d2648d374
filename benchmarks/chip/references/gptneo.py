"""Plain float32 reference of GPT-Neo as this repository serves it, and the
seeded parameters both the served model and the reference are built from.

It imports nothing of the system under test. What it computes, per request
of ``L`` tokens (no batch, no padding):

    x = E[tokens]                                   (no position term)
    for each layer:
        h = LN1(x);  q, k, v = h Wq, h Wk, h Wv      (no biases)
        a = causal softmax(q k^T / sqrt(head_dim)) v, per head
        x = x + a Wo
        x = x + gelu_tanh(LN2(x) W_in) W_out
    return LN_final(x)                              (the served output)

LayerNorm: population variance, eps from the configuration, gain and bias
as a (2, d) array ``[gain; bias]``. Everything is float32; every matmul
runs at the precision the configuration states (``matmul_precision``,
a ``jax.lax.Precision`` name), nothing else is rounded.

``mode="bf16"`` is the control, one step below what the configurations
state: parameters and activations in bfloat16, so LayerNorm, softmax,
GELU and the residual adds are rounded as well as the matmul operands.

Parameters come from ``instance_key(seed, index)``: layer ``i`` from
``fold_in(key, i + 1)``, the embedding from ``fold_in(key, 0)`` and the
final LayerNorm from ``fold_in(key, num_layers + 1)``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

MODES = ("f32", "bf16")
LAYER_LEAVES = ("norm1", "wq", "wk", "wv", "wo", "norm2", "ffn_in", "ffn_out")


def dims(arch: dict) -> dict:
    """The sizes the forward pass needs, from a Hugging Face GPT-Neo
    config (``intermediate_size`` null means 4 x hidden)."""
    d = int(arch["hidden_size"])
    return {"d": d, "layers": int(arch["num_layers"]),
            "heads": int(arch["num_heads"]),
            "dff": int(arch.get("intermediate_size") or 4 * d),
            "vocab": int(arch["vocab_size"]),
            "eps": float(arch["layer_norm_epsilon"])}


def param_count(dm: dict) -> int:
    d, dff = dm["d"], dm["dff"]
    per_layer = 4 * d * d + 2 * d * dff + 4 * d
    return dm["vocab"] * d + dm["layers"] * per_layer + 2 * d


def instance_key(seed: int, index: int):
    """A threefry key from any non-negative seed (wider than 32 bits is
    fine) and the model instance's index in its configuration."""
    words = np.random.SeedSequence([int(seed), int(index)]).generate_state(
        2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words),
                                    impl="threefry2x32")


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def _norm(key, d):
    kg, kb = jax.random.split(key)
    return jnp.stack([1.0 + 0.1 * jax.random.normal(kg, (d,)),
                      0.1 * jax.random.normal(kb, (d,))])


@partial(jax.jit, static_argnames=("d", "dff"))
def layer_params(key, *, d: int, dff: int) -> dict:
    """One layer's parameters, made on the device in one call."""
    k = jax.random.split(key, len(LAYER_LEAVES))
    return {"norm1": _norm(k[0], d),
            "wq": _normal(k[1], (d, d), d ** -0.5),
            "wk": _normal(k[2], (d, d), d ** -0.5),
            "wv": _normal(k[3], (d, d), d ** -0.5),
            "wo": _normal(k[4], (d, d), d ** -0.5),
            "norm2": _norm(k[5], d),
            "ffn_in": _normal(k[6], (d, dff), d ** -0.5),
            "ffn_out": _normal(k[7], (dff, d), dff ** -0.5)}


@partial(jax.jit, static_argnames=("vocab", "d"))
def embed_params(key, *, vocab: int, d: int):
    return _normal(key, (vocab, d), 0.02)


@partial(jax.jit, static_argnames=("d",))
def final_norm_params(key, *, d: int):
    return _norm(key, d)


def layer_key(ikey, i: int):
    return jax.random.fold_in(ikey, i + 1)


def embed(ikey, dm: dict):
    return embed_params(jax.random.fold_in(ikey, 0), vocab=dm["vocab"],
                        d=dm["d"])


def final_norm(ikey, dm: dict):
    return final_norm_params(jax.random.fold_in(ikey, dm["layers"] + 1),
                             d=dm["d"])


def layer(ikey, dm: dict, i: int) -> dict:
    return layer_params(layer_key(ikey, i), d=dm["d"], dff=dm["dff"])


# ---------------------------------------------------------------------------
# forward pass
# ---------------------------------------------------------------------------

def _layer_norm(x, w, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w[0] + w[1]


def _gelu_tanh(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x * x * x)))


@partial(jax.jit, static_argnames=("heads", "eps", "precision", "mode"))
def block(x, p, *, heads: int, eps: float, precision: str,
          mode: str = "f32"):
    """One decoder layer on one request's (L, d) activations."""
    if mode == "bf16":
        p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
        x = x.astype(jnp.bfloat16)
    prec = jax.lax.Precision[precision.upper()]
    L, d = x.shape
    hd = d // heads
    h = _layer_norm(x, p["norm1"], eps)
    q = jnp.dot(h, p["wq"], precision=prec).reshape(L, heads, hd)
    k = jnp.dot(h, p["wk"], precision=prec).reshape(L, heads, hd)
    v = jnp.dot(h, p["wv"], precision=prec).reshape(L, heads, hd)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=prec) / math.sqrt(hd)
    causal = jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    s = s - jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s)
    prob = e / jnp.sum(e, axis=-1, keepdims=True)
    a = jnp.einsum("hqk,khd->qhd", prob.astype(v.dtype), v,
                   precision=prec).reshape(L, d)
    x = x + jnp.dot(a, p["wo"], precision=prec)
    h = _layer_norm(x, p["norm2"], eps)
    u = _gelu_tanh(jnp.dot(h, p["ffn_in"], precision=prec))
    x = x + jnp.dot(u, p["ffn_out"], precision=prec)
    return x.astype(jnp.float32)


@partial(jax.jit, static_argnames=("mode",))
def embed_lookup(table, tokens, *, mode: str = "f32"):
    if mode == "bf16":
        table = table.astype(jnp.bfloat16).astype(jnp.float32)
    return table[tokens]


@partial(jax.jit, static_argnames=("eps", "mode"))
def output(x, w, *, eps: float, mode: str = "f32"):
    if mode == "bf16":
        x, w = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    return _layer_norm(x, w, eps).astype(jnp.float32)


def forward(ikey, dm: dict, prompts: list, precision: str,
            mode: str = "f32") -> list:
    """Final hidden states of each prompt (1-D int token arrays), one
    layer at a time: each layer's parameters are made once, used on every
    prompt and dropped, so the device holds one layer and the
    activations."""
    if mode not in MODES:
        raise ValueError(f"unknown reference mode {mode!r}")
    table = embed(ikey, dm)
    xs = [embed_lookup(table, jnp.asarray(t, jnp.int32), mode=mode)
          for t in prompts]
    del table
    for i in range(dm["layers"]):
        p = layer(ikey, dm, i)
        xs = [block(x, p, heads=dm["heads"], eps=dm["eps"],
                    precision=precision, mode=mode) for x in xs]
        del p
    w = final_norm(ikey, dm)
    return [np.asarray(output(x, w, eps=dm["eps"], mode=mode)) for x in xs]
