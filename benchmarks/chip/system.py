"""The one place the benchmark touches the system under test's model
objects: turn a configuration's sizes and the seeded reference parameters
into the ``HostModel`` the serving engine runs.

The parameters are made on the device by the reference's generator (one
jitted call per layer, so set-up never holds more than a layer beyond what
serving will), copied to host memory, and handed to ``HostModel`` under the
names its op programs read. The system then owns them: it streams them
back to the device as its plans say.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.graph import build_lm_graph
from repro.core.streaming import HostModel, _build_programs

from references import gptneo


def model_config(name: str, arch: dict) -> ModelConfig:
    """The served model's config: GPT-Neo's widths, with the structure the
    served path implements (no position term, full attention, no GLU)."""
    dm = gptneo.dims(arch)
    return ModelConfig(
        name=name, family="dense", num_layers=dm["layers"],
        d_model=dm["d"], n_heads=dm["heads"], n_kv_heads=dm["heads"],
        d_ff=dm["dff"], vocab=dm["vocab"], rope="none", norm="layernorm",
        act="gelu", glu=False, tie_embeddings=True, dtype="float32",
        source="EleutherAI/gpt-neo")


def host_weights(ikey, dm: dict) -> dict:
    """The seeded parameters as float32 host arrays, keyed by the served
    graph's weight names."""
    out = {"embed.w": np.asarray(jax.device_get(gptneo.embed(ikey, dm)))}
    for i in range(dm["layers"]):
        p = jax.device_get(gptneo.layer(ikey, dm, i))
        for leaf in gptneo.LAYER_LEAVES:
            out[f"L{i}.{leaf}.w"] = np.asarray(p[leaf])
    out[f"L{dm['layers']}.final_norm.w"] = np.asarray(
        jax.device_get(gptneo.final_norm(ikey, dm)))
    return out


def host_model(cfg: ModelConfig, weights: dict, *, seq: int, batch: int,
               programs=None) -> HostModel:
    """A ``HostModel`` over ``weights``. Instances of one configuration may
    share ``programs`` (the jitted op closures depend on the config alone)."""
    graph = build_lm_graph(cfg, seq=seq, batch=batch, dtype_bytes=4)
    missing = set(graph.weights) ^ set(weights)
    if missing:
        raise ValueError(f"weights do not match the served graph: "
                         f"{sorted(missing)[:4]}")
    return HostModel(cfg, seq, batch, graph, weights,
                     programs if programs is not None
                     else _build_programs(cfg))
