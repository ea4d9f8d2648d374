"""Ahead-of-time compiles for a described TPU v5e (``v5e:2x2``): the served
op programs at gptneo-1.3b widths and seq 1024, and the Pallas kernels with
``interpret=False``. Nothing runs; the TPU compiler refuses here what it
would refuse on the chip (tiling, VMEM, HBM fit).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and it keeps it until it exits."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core.capacity import PEAKS, HWSpec
from repro.core.streaming import _build_programs
from repro.kernels.flash_attention import flash_attention
from repro.kernels.streamed_matmul import streamed_matmul

CFG = get_arch("gptneo-1.3b").model
SEQ = 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _op_case(one_chip, tag):
    d, ff, s = CFG.d_model, CFG.d_ff, SEQ
    x = _sds(one_chip, (1, s, d))
    u = _sds(one_chip, (1, s, ff))
    return {
        "embed": ({"tokens": _sds(one_chip, (1, s), jnp.int32)},
                  _sds(one_chip, (CFG.vocab, d))),
        "norm1": ({"x": x}, _sds(one_chip, (2, d))),
        "wq": ({"h": x}, _sds(one_chip, (d, d))),
        "attn": ({"q": x, "k": x, "v": x}, None),
        "ffn_in": ({"h": x}, _sds(one_chip, (d, ff))),
        "act": ({"u": u}, None),
        "ffn_out": ({"u": u}, _sds(one_chip, (ff, d))),
        "res2": ({"x": x, "u": x}, None),
    }[tag]


def test_topology_device_kind_has_peaks(topo):
    """The described chip's kind is the peaks table's v5e row."""
    dev = topo.devices[0]
    assert dev.device_kind in PEAKS
    hw = HWSpec.for_device(dev)
    assert hw.peak_flops == PEAKS[dev.device_kind].flops
    assert hw.hbm_bw == PEAKS[dev.device_kind].hbm_bw


@pytest.mark.parametrize("tag", ["embed", "norm1", "wq", "attn", "ffn_in",
                                 "act", "ffn_out", "res2"])
def test_served_op_program_compiles(one_chip, tag):
    programs = _build_programs(CFG)
    regs, w = _op_case(one_chip, tag)
    compiled = jax.jit(programs[tag]).lower(regs, w).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_streamed_matmul_compiles(one_chip, dtype):
    a = _sds(one_chip, (SEQ, CFG.d_model), dtype)
    b = _sds(one_chip, (CFG.d_model, CFG.d_ff), dtype)
    compiled = streamed_matmul.lower(a, b, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    hd = CFG.d_model // CFG.n_heads
    q = _sds(one_chip, (1, SEQ, CFG.n_heads, hd), jnp.bfloat16)
    compiled = flash_attention.lower(q, q, q, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
