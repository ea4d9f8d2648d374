"""The GPT-Neo family: what the harness needs of a configuration whose
``family`` is ``gptneo``. A configuration names its family by file
(``families/<family>.py``), and this file is the one place the benchmark
touches the system's model objects for it.

It provides:

- ``dims(arch)``: the sizes everything below reads (``layers``, ``d``,
  ``vocab`` and the family's own);
- ``instance_key(seed, index)``: the key one model instance's parameters
  come from;
- ``host_model(name, arch, dims, key, *, seq, batch, programs)``: the
  ``HostModel`` the serving engine runs, over the seeded parameters;
- ``forward(key, dims, token_lists, precision, mode)``: the plain
  reference's served outputs, which decide ``correct``;
- ``batch_calls(dims, batch, seq)`` and ``model_flops(dims, tokens)``:
  the work of one batch per served program and of one request, which
  the rooflines and ``step_mfu`` read;
- ``cpu_arch(arch, *, d, layers, heads, vocab)``: the arch cut to a CPU
  size, with its bytes;
- ``PUBLISHED``: each arch as its source config gives it.

The served program keeps activations and weights in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import counts
from references import gptneo

from repro.configs.base import ModelConfig
from repro.core.graph import build_lm_graph
from repro.core.streaming import HostModel, _build_programs

dims = gptneo.dims
instance_key = gptneo.instance_key
forward = gptneo.forward

_NEO = {"intermediate_size": None, "vocab_size": 50257,
        "max_position_embeddings": 2048, "window_size": 256,
        "activation_function": "gelu_new", "layer_norm_epsilon": 1e-05}
PUBLISHED = {
    "gptneo-1.3b": dict(_NEO, hidden_size=2048, num_layers=24, num_heads=16,
                        attention_types=[[["global", "local"], 12]]),
    "gptneo-2.7b": dict(_NEO, hidden_size=2560, num_layers=32, num_heads=20,
                        attention_types=[[["global", "local"], 16]]),
}


def model_config(name: str, arch: dict) -> ModelConfig:
    """The served model's config: GPT-Neo's widths, with the structure the
    served path implements (no position term, full attention, no GLU)."""
    dm = dims(arch)
    return ModelConfig(
        name=name, family="dense", num_layers=dm["layers"],
        d_model=dm["d"], n_heads=dm["heads"], n_kv_heads=dm["heads"],
        d_ff=dm["dff"], vocab=dm["vocab"], rope="none", norm="layernorm",
        act="gelu", glu=False, tie_embeddings=True, dtype="float32",
        source="EleutherAI/gpt-neo")


def host_weights(ikey, dm: dict) -> dict:
    """The seeded parameters as float32 host arrays, keyed by the served
    graph's weight names. They are made on the device by the reference's
    generator, one jitted call per layer, so set-up never holds more than
    a layer beyond what serving will."""
    out = {"embed.w": np.asarray(jax.device_get(gptneo.embed(ikey, dm)))}
    for i in range(dm["layers"]):
        p = jax.device_get(gptneo.layer(ikey, dm, i))
        for leaf in gptneo.LAYER_LEAVES:
            out[f"L{i}.{leaf}.w"] = np.asarray(p[leaf])
    out[f"L{dm['layers']}.final_norm.w"] = np.asarray(
        jax.device_get(gptneo.final_norm(ikey, dm)))
    return out


def host_model(name: str, arch: dict, dm: dict, ikey, *, seq: int,
               batch: int, programs=None) -> HostModel:
    """A ``HostModel`` over the parameters of ``ikey``, which the system
    then owns and streams as its plans say. Instances of one arch may
    share ``programs`` (the jitted op closures depend on the config
    alone)."""
    cfg = model_config(name, arch)
    weights = host_weights(ikey, dm)
    graph = build_lm_graph(cfg, seq=seq, batch=batch,
                           dtype_bytes=jnp.dtype(cfg.dtype).itemsize)
    missing = set(graph.weights) ^ set(weights)
    if missing:
        raise ValueError(f"weights do not match the served graph: "
                         f"{sorted(missing)[:4]}")
    return HostModel(cfg, seq, batch, graph, weights,
                     programs if programs is not None
                     else _build_programs(cfg))


def batch_calls(dm: dict, batch: int, seq: int) -> dict:
    """Every call of the served op programs one (batch, seq) execution of
    a GPT-Neo stack makes, as (flops, bytes) per call, keyed by program."""
    d, dff, L = dm["d"], dm["dff"], dm["layers"]
    rows = batch * seq
    per_layer = [counts.matmul(rows, d, d)] * 4 + [
        counts.matmul(rows, d, dff), counts.matmul(rows, dff, d)]
    return {"f_matmul": per_layer * L,
            "f_attn": [counts.attention(batch, seq, d)] * L}


def model_flops(dm: dict, tokens: int) -> float:
    """Model operations of one request of ``tokens`` real tokens: the
    layers' matmuls and causal attention (embedding lookup, norms and
    elementwise work not counted)."""
    d, dff, L = dm["d"], dm["dff"], dm["layers"]
    mm = 2.0 * tokens * (4 * d * d + 2 * d * dff)
    att = 2.0 * d * tokens * (tokens + 1)
    return L * (mm + att)


def cpu_arch(arch: dict, *, d: int, layers: int, heads: int, vocab: int
             ) -> tuple:
    """``arch`` cut to width ``d``, depth, heads and vocabulary, and the
    bytes of one model's float32 parameters at that size."""
    cut = dict(arch, hidden_size=d, num_layers=layers, num_heads=heads,
               vocab_size=vocab)
    return cut, 4 * gptneo.param_count(dims(cut))
