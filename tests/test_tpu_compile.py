"""The Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered and compiled against a described
``v5e:2x2`` topology (no chip attached), which refuses what interpret mode
accepts — blocks not aligned to the (8, 128) tiling, or more VMEM than a
kernel may use.  Widths: qwen3-0.6b's MLP matmul and attention, and
jamba-52b's selective scan (d_inner 8192, d_state 16).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True, scope="module")
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the cache
    without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _hlo(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_persistent_matmul_compiles(one_chip):
    bf16 = jnp.bfloat16
    hlo = _hlo(one_chip, ops.pinned_matmul,
               ((1024, 1024), bf16), ((1024, 3072), bf16))
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles(one_chip):
    qkv = ((32, 2048, 128), jnp.bfloat16)
    hlo = _hlo(one_chip,
               lambda q, k, v: flash_attention(q, k, v, scale=128 ** -0.5),
               qkv, qkv, qkv)
    assert "tpu_custom_call" in hlo


def test_selective_scan_compiles_at_jamba_width(one_chip):
    f32 = jnp.float32
    hlo = _hlo(one_chip, ops.mamba_scan,
               ((1, 1024, 8192, 16), f32), ((1, 1024, 8192, 16), f32),
               ((1, 1024, 16), f32))
    assert "tpu_custom_call" in hlo
