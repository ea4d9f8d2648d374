"""Logical-axis sharding: ParamSpec trees -> shapes / init / NamedSharding.

Every parameter is declared once as a ``ParamSpec`` carrying its shape, dtype,
initializer and *logical* axis names. Rules map logical names to mesh axes,
MaxText-style, so the same model code drives the single-pod (16,16) mesh, the
multi-pod (2,16,16) mesh, and the 1-device CPU smoke tests.

Two rule sets exist per run:
  * ``param`` rules — storage sharding (may add an FSDP axis on the weight
    row dim; gathered per-layer inside the scan body),
  * ``compute`` rules — activation / in-layer sharding.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax import numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    dtype: Any = jnp.bfloat16
    logical: tuple = ()
    init: str = "normal"        # normal | zeros | ones | ssm_a | arange
    scale: float = 1.0          # stddev multiplier for "normal"

    def __post_init__(self):
        assert len(self.logical) in (0, len(self.shape)), (
            f"logical {self.logical} vs shape {self.shape}")


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def spec_map(fn, tree):
    return jax.tree.map(fn, tree, is_leaf=is_spec)


@dataclass(frozen=True)
class MeshEnv:
    """Mesh + logical rules for one run."""
    mesh: Mesh
    rules: dict                  # logical name -> mesh axis (str|tuple|None)

    def axis_size(self, name: str) -> int:
        ax = self.rules.get(name)
        if ax is None:
            return 1
        if isinstance(ax, str):
            ax = (ax,)
        size = 1
        for a in ax:
            size *= self.mesh.shape[a]
        return size

    def pspec(self, logical: Sequence[Optional[str]], shape=None) -> P:
        """Resolve logical names to a PartitionSpec.

        If ``shape`` is given, any logical axis whose mesh extent does not
        divide the dim size is dropped (replicated) — this is how kv_heads=8
        on a 16-way model axis degrades gracefully.
        """
        parts = []
        used = set()
        for i, name in enumerate(logical):
            ax = self.rules.get(name) if name else None
            if ax is not None:
                axes = (ax,) if isinstance(ax, str) else tuple(ax)
                axes = tuple(a for a in axes if a not in used)
                size = int(np.prod([self.mesh.shape[a] for a in axes])) if axes else 1
                if axes and (shape is None or (shape[i] % size == 0 and shape[i] > 0)):
                    parts.append(axes if len(axes) > 1 else axes[0])
                    used.update(axes)
                else:
                    parts.append(None)
            else:
                parts.append(None)
        while parts and parts[-1] is None:
            parts.pop()
        return P(*parts)

    def sharding(self, logical: Sequence[Optional[str]], shape=None) -> NamedSharding:
        return NamedSharding(self.mesh, self.pspec(logical, shape))

    def constrain(self, x, *logical: Optional[str]):
        """with_sharding_constraint by logical names (no-op off-mesh)."""
        if self.mesh.empty or self.mesh.size == 1:
            return x
        return jax.lax.with_sharding_constraint(
            x, self.sharding(logical, x.shape))

    def constrain_compute(self, x, *logical: Optional[str]):
        """In-layer (scan-body) view of a stored parameter: the ZeRO-3
        storage axis is gathered for compute (fsdp_row -> None), making the
        per-layer weight all-gather explicit instead of GSPMD-chosen."""
        if self.mesh.empty or self.mesh.size == 1:
            return x
        logical = tuple(None if n == "fsdp_row" else n for n in logical)
        return jax.lax.with_sharding_constraint(
            x, self.sharding(logical, x.shape))


# ---------------------------------------------------------------------------
# rule sets
# ---------------------------------------------------------------------------

def make_rules(*, multi_pod: bool = False, fsdp: bool = False,
               seq_shard: bool = True, expert_parallel: bool = False,
               layout: str = "tp") -> dict:
    """Logical-axis rules for LM workloads.

    layout="tp" (default, Megatron-style):
      batch        -> data (and pod)            activations
      seq          -> model between blocks (sequence parallelism)
      kv_seq       -> model (flash-decoding-style sharded KV cache)
      heads/d_ff   -> model (tensor parallelism)
      vocab        -> model (embedding/logits)
      fsdp_row     -> (pod,)data when fsdp (ZeRO-3 storage sharding)

    layout="dp" (pure data parallel + ZeRO-3, for models too small to TP):
      batch + fsdp_row -> ALL axes; no tensor/seq sharding. Weights are
      gathered per layer inside the scan body (constrain_compute) —
      §Perf iteration 8.
    """
    data_axes = ("pod", "data") if multi_pod else ("data",)
    if layout == "dp":
        all_axes = data_axes + ("model",)
        return {
            "batch": all_axes, "seq": None, "kv_seq": None,
            "heads": None, "kv_heads": None, "d_ff": None,
            "vocab": all_axes, "experts": None, "expert_ff": None,
            "embed": None, "layers": None, "fsdp_row": all_axes,
            "conv": None, "state": None, "pos": None,
        }
    rules = {
        "batch": data_axes,
        "seq": "model" if seq_shard else None,
        "kv_seq": "model",
        "heads": "model",
        "kv_heads": "model",
        "d_ff": "model",
        "vocab": "model",
        "experts": "model" if expert_parallel else None,
        "expert_ff": None if expert_parallel else "model",
        "embed": None,
        "layers": None,
        "fsdp_row": data_axes if fsdp else None,
        "conv": None,
        "state": None,
        "pos": None,
    }
    return rules


def single_device_env() -> MeshEnv:
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    rules = {k: None for k in make_rules()}
    return MeshEnv(mesh=mesh, rules=rules)


# ---------------------------------------------------------------------------
# materialization of ParamSpec trees
# ---------------------------------------------------------------------------

def shape_structs(specs, env: Optional[MeshEnv] = None):
    """ShapeDtypeStructs (optionally sharded) for .lower() dry-runs."""
    def mk(s: ParamSpec):
        sharding = env.sharding(s.logical, s.shape) if env is not None else None
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)
    return spec_map(mk, specs)


def shardings(specs, env: MeshEnv):
    return spec_map(lambda s: env.sharding(s.logical, s.shape), specs)


def pspecs(specs, env: MeshEnv):
    return spec_map(lambda s: env.pspec(s.logical, s.shape), specs)


def init_params(specs, key):
    """Materialize real parameters (smoke tests / examples)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    out = []
    for s, k in zip(leaves, keys):
        if s.init == "zeros":
            v = jnp.zeros(s.shape, s.dtype)
        elif s.init == "ones":
            v = jnp.ones(s.shape, s.dtype)
        elif s.init == "ssm_a":
            # mamba A_log init: log of uniform [1, 16]
            v = jnp.log(jnp.linspace(1.0, 16.0, s.shape[-1], dtype=jnp.float32))
            v = jnp.broadcast_to(v, s.shape).astype(s.dtype)
        elif s.init == "arange":
            v = jnp.broadcast_to(
                jnp.arange(1, s.shape[-1] + 1, dtype=jnp.float32), s.shape
            ).astype(s.dtype)
        else:
            fan_in = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
            std = s.scale / np.sqrt(max(1, fan_in))
            v = (jax.random.normal(k, s.shape, jnp.float32) * std).astype(s.dtype)
        out.append(v)
    return jax.tree.unflatten(treedef, out)


def param_bytes(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=is_spec)
    return sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize for s in leaves)


def param_count(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=is_spec)
    return sum(int(np.prod(s.shape)) for s in leaves)
