"""Compile rehearsals for the TPU v5e, without the chip.

The TPU compiler is installed next to JAX, and it compiles for a chip that
is described rather than attached.  These tests compile the Pallas kernels
at deepseek-7b widths and the served decode and prefill programs at
``chip_smoke.py``'s sizes for one v5e chip: the compiler refuses what the
chip would refuse (misaligned tiles, too much VMEM, a program larger than
the chip's memory) at no chip time.  Nothing runs, so nothing here is a
time or a result.

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test workers import every test file.  Keep these tests in this one file.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    """Describe a v5e:2x2 host with the persistent compile cache off (a
    described-chip compile can be written to it but never read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """ShapeDtypeStruct stand-ins of ``tree``'s leaves placed on ``sharding``."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree
    )


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _fits(compiled) -> int:
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, f"{used / 1e9:.2f} GB does not fit one v5e chip"
    return used


def test_decode_attention_kernel_compiles(one_chip):
    from repro.kernels.decode_attention.kernel import decode_attention_pallas

    q = _sds((8, 32, 128), "bfloat16", one_chip)
    kv = _sds((8, 32, 4096, 128), "bfloat16", one_chip)
    pos = _sds((), "int32", one_chip)
    compiled = jax.jit(decode_attention_pallas).lower(q, kv, kv, pos).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_kernel_compiles(one_chip):
    from repro.kernels.flash_attention.kernel import flash_attention_pallas

    x = _sds((1, 32, 2048, 128), "bfloat16", one_chip)
    compiled = jax.jit(flash_attention_pallas).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rmsnorm_kernel_compiles(one_chip):
    from repro.kernels.rmsnorm.kernel import rmsnorm_pallas

    x = _sds((2048, 4096), "bfloat16", one_chip)
    scale = _sds((4096,), "bfloat16", one_chip)
    compiled = jax.jit(rmsnorm_pallas).lower(x, scale).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def smoke_cfg():
    return get_config(chip_smoke.ARCH).replace(n_layers=chip_smoke.SERVE["n_layers"])


def test_served_decode_step_fits_one_chip(one_chip, smoke_cfg):
    """The engine's own jitted decode step (per-slot positions, cache
    donated) at the smoke's depth, slots and max_seq."""
    from repro.models import abstract_cache, abstract_params
    from repro.serving.engine import _jitted_steps

    n, S = chip_smoke.SERVE["n_slots"], chip_smoke.SERVE["max_seq"]
    decode, _ = _jitted_steps(smoke_cfg)
    compiled = decode.lower(
        _on(one_chip, abstract_params(smoke_cfg)),
        _sds((n, 1), "int32", one_chip),
        _on(one_chip, abstract_cache(smoke_cfg, n, S)),
        _sds((n,), "int32", one_chip),
    ).compile()
    _fits(compiled)


def test_served_prefill_fits_one_chip(one_chip, smoke_cfg):
    """The engine's fused prefill → prime program at the smoke's longest
    prompt."""
    from repro.models import abstract_params
    from repro.serving.engine import _jitted_serve_ops

    prefill_prime, _ = _jitted_serve_ops(smoke_cfg, chip_smoke.SERVE["max_seq"])
    L = max(chip_smoke.SERVE["prompt_lens"])
    compiled = prefill_prime.lower(
        _on(one_chip, abstract_params(smoke_cfg)),
        {"tokens": _sds((1, L), "int32", one_chip)},
    ).compile()
    _fits(compiled)
