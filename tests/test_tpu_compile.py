"""Ahead-of-time compiles for one TPU v5e chip that is described, not
attached: the Pallas kernels at real widths, granite-3-2b's full-width
serving steps and granite-4.0-h-micro's at its cell's shapes. The TPU
compiler refuses here what the chip would refuse — unaligned blocks,
kernels that overflow VMEM, programs that overflow HBM — at no chip
time. Nothing runs, so nothing here is a time or a result.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.jacobi2d import jacobi2d_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.matmul import matmul_pallas
from repro.kernels.stream_triad import triad_pallas
from repro.launch.serve import compile_steps
from repro.models import init_params

V5E_HBM_BYTES = 16e9        # one v5e chip (Google Cloud, "TPU v5e")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


KERNELS = {
    "triad_f32_8192x8192": (
        lambda b, c: triad_pallas(b, c, 2.5),
        [((8192, 8192), jnp.float32)] * 2),
    "jacobi2d_f32_8192x8192": (
        jacobi2d_pallas, [((8192, 8192), jnp.float32)]),
    "matmul_bf16_4096_cubed": (
        matmul_pallas, [((4096, 4096), jnp.bfloat16)] * 2),
    "matmul_bf16_2048x2048_2048x8192": (
        matmul_pallas,
        [((2048, 2048), jnp.bfloat16), ((2048, 8192), jnp.bfloat16)]),
    "flash_attention_1x32x4096x64": (
        flash_attention_pallas, [((1, 32, 4096, 64), jnp.bfloat16)] * 3),
    "flash_attention_1x32x4096x128": (
        flash_attention_pallas, [((1, 32, 4096, 128), jnp.bfloat16)] * 3),
    "mamba_scan_f32_1x4096x8192_n16": (
        mamba_scan_pallas,
        [((1, 4096, 8192), jnp.float32), ((8192, 16), jnp.float32),
         ((1, 4096, 16), jnp.float32), ((1, 4096, 16), jnp.float32),
         ((1, 4096, 8192), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    args = [_sds(s, d, one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def granite_steps(one_chip):
    """granite-3-2b's full-width prefill (B=8, P=512) and decode step,
    compiled by the serving path's own `compile_steps` from shapes."""
    cfg = get_config("granite-3-2b")
    params = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    prompts = _sds((8, 512), jnp.int32, one_chip)
    return compile_steps(cfg, params, prompts, None, 512 + 32)


def _device_bytes(m) -> int:
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_granite_step_fits_one_v5e(step, granite_steps):
    compiled = granite_steps[0 if step == "prefill" else 1]
    assert _device_bytes(compiled.memory_analysis()) < V5E_HBM_BYTES


def test_granite_decode_donates_its_cache(granite_steps):
    """The decode step's cache output reuses its donated input buffers:
    one step holds one copy of the KV cache."""
    m = granite_steps[1].memory_analysis()
    assert m.alias_size_in_bytes > 0


def test_granite_decode_holds_no_second_cache(granite_steps):
    """The decode step writes its cache in place: its scratch space is
    smaller than one layer's K buffer, so no layer's buffer, let alone the
    stack, is copied."""
    cfg = get_config("granite-3-2b")
    layer_k = 8 * (512 + 32) * cfg.n_kv_heads * cfg.resolved_head_dim * 2
    m = granite_steps[1].memory_analysis()
    assert m.temp_size_in_bytes < layer_k, m


@pytest.fixture(scope="module")
def hybrid_steps(one_chip):
    """granite-4.0-h-micro's prefill (B=32, P=512) and decode step over a
    cache of 768, the shapes of its cell, compiled by `compile_steps`."""
    cfg = get_config("granite-4.0-h-micro")
    params = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    prompts = _sds((32, 512), jnp.int32, one_chip)
    return cfg, compile_steps(cfg, params, prompts, None, 768)


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_hybrid_step_fits_one_v5e(step, hybrid_steps):
    compiled = hybrid_steps[1][0 if step == "prefill" else 1]
    assert _device_bytes(compiled.memory_analysis()) < V5E_HBM_BYTES


def test_hybrid_decode_carries_its_state_in_place(hybrid_steps):
    """The decode step donates its cache, the 2.4 GB of Mamba-2 state
    among it, and writes each layer's state in place: its scratch is
    smaller than one layer's state slice (32·64·64·128 float32, 67 MB)."""
    cfg, (_, decode) = hybrid_steps
    state = 32 * cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
    n_mamba = sum(1 for m, _ in cfg.layer_kinds() if m == "mamba2")
    m = decode.memory_analysis()
    assert m.alias_size_in_bytes >= n_mamba * state
    assert m.temp_size_in_bytes < state, m
