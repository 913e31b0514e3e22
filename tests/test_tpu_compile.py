"""Compile-only checks against one TPU v5e chip that is described, not
attached.

What ``chip_smoke.py`` serves — yi-9b at every published width with 24
of its 48 layers — must compile for the chip and fit its 16 GB: the
jitted ``init_params``, the served prefill at both prompt lengths of the
smoke run (dense and blockwise attention branches), and the served
decode step at batch 16, context 2,048.  The Pallas kernels must lower
to a Mosaic custom call at yi-9b head geometry.  Nothing runs, so these
say nothing about results or times.

The topology is described inside a module fixture, never while a module
is imported: only one process may load the TPU compiler library, and
under several test workers only the worker given this file does.  The
persistent compilation cache is off around these compiles (an entry
compiled for a described chip cannot be read back without one).
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_prefill.kernel import flash_prefill
from repro.kernels.kv_pull.kernel import kv_pull_dequant, kv_pull_runs
from repro.kernels.paged_attention.kernel import paged_attention
from repro.models.registry import build_model
from repro.serving.engine import jit_decode_step, jit_prefill

HBM_BYTES = 16e9      # one v5e chip
SERVED_LAYERS = 24    # chip_smoke.py's cut: num_layers 48 -> 24


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    had_log_dir = "TPU_LOG_DIR" in os.environ
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()
    if not had_log_dir:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def yi9b(one_chip):
    cfg = dataclasses.replace(get_config("yi-9b"), num_layers=SERVED_LAYERS)
    model = build_model(cfg)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(model.init_params, key))
    return model, key, params


def on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def fits_one_chip(compiled) -> None:
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total <= HBM_BYTES, (
        f"{total} bytes (args {ma.argument_size_in_bytes}, outputs "
        f"{ma.output_size_in_bytes}, temps {ma.temp_size_in_bytes}) "
        f"exceed one chip's {HBM_BYTES:.0f}")


def test_init_params_fits_one_chip(yi9b, one_chip):
    model, key, params = yi9b
    compiled = jax.jit(model.init_params, out_shardings=one_chip) \
        .lower(key).compile()
    fits_one_chip(compiled)
    weights = sum(p.size * p.dtype.itemsize for p in jax.tree.leaves(params))
    assert compiled.memory_analysis().output_size_in_bytes >= weights


@pytest.mark.parametrize("prompt_len", [512, 2048])
def test_served_prefill_fits_one_chip(yi9b, one_chip, prompt_len):
    model, _, params = yi9b
    tokens = on(one_chip, (1, prompt_len), jnp.int32)
    fits_one_chip(jit_prefill.lower(model, params, tokens).compile())


def test_served_decode_step_fits_one_chip(yi9b, one_chip):
    model, _, params = yi9b
    state = jax.tree.map(
        lambda s: on(one_chip, s.shape, s.dtype),
        model.decode_state_shape(16, 2048, margin=2))
    tokens = on(one_chip, (16,), jnp.int32)
    fits_one_chip(jit_decode_step.lower(model, params, state, tokens).compile())


# yi-9b head geometry: 32 query heads, 4 KV heads, head dim 128, and
# the served page size of 32 tokens.
H, G, D, BS = 32, 4, 128, 32


def assert_kernel(compiled) -> None:
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_attention_compiles(one_chip):
    b, per_seq = 16, 66
    assert_kernel(paged_attention.lower(
        on(one_chip, (b, H, D), jnp.bfloat16),
        on(one_chip, (b, per_seq, BS, G, D), jnp.bfloat16),
        on(one_chip, (b, per_seq, BS, G, D), jnp.bfloat16),
        on(one_chip, (b, per_seq), jnp.int32),
        on(one_chip, (b,), jnp.int32),
        interpret=False).compile())


def test_flash_prefill_compiles(one_chip):
    s = 2048
    assert_kernel(flash_prefill.lower(
        on(one_chip, (1, s, H, D), jnp.bfloat16),
        on(one_chip, (1, s, G, D), jnp.bfloat16),
        on(one_chip, (1, s, G, D), jnp.bfloat16),
        causal=True, interpret=False).compile())


def test_kv_pull_runs_compiles(one_chip):
    pages, runs, run_len = 256, 8, 8
    assert_kernel(kv_pull_runs.lower(
        on(one_chip, (pages, BS, G, D), jnp.bfloat16),
        on(one_chip, (pages, BS, G, D), jnp.bfloat16),
        on(one_chip, (runs,), jnp.int32),
        on(one_chip, (runs,), jnp.int32),
        run_len=run_len, interpret=False).compile())


def test_kv_pull_dequant_compiles(one_chip):
    pages, txns = 256, 64
    assert_kernel(kv_pull_dequant.lower(
        on(one_chip, (pages, BS, G, D), jnp.int8),
        on(one_chip, (pages, BS, G, D), jnp.bfloat16),
        on(one_chip, (txns,), jnp.int32),
        on(one_chip, (txns,), jnp.int32),
        on(one_chip, (txns,), jnp.float32),
        interpret=False).compile())
