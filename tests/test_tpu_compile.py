"""Compile the served path for a described TPU v5e chip, at the published
widths of qwen3_moe_235b_a22b with one layer.  Nothing runs: the TPU
compiler checks the Pallas super-GMM lowers to a Mosaic kernel
(`tpu_custom_call`) and that each step program fits one chip's HBM.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and the test workers
each import every test file.  Keep these tests in this one file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.decode import make_decode_step
from repro.core.executor import make_attn_step, make_moe_step
from repro.kernels.super_gmm.super_gmm import super_gmm
from repro.launch.serve import model_config
from repro.models.lm import init_lm_params

HBM_BYTES = 15.75 * 2**30  # what XLA lets one v5e program use
N_E = 32  # experts per MoE device: 128 experts over E=4 devices


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # keep the TPU compiler's logs (and its lock) out of a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Device 0 of the described topology, with the persistent compile
    cache off: a compile for a chip that is not attached cannot be read
    back, so caching it only produces warnings."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def full(one_chip):
    """(cfg, params as ShapeDtypeStructs on the described chip)."""
    cfg = model_config("qwen3_moe_235b_a22b", layers=1)
    shapes = jax.eval_shape(
        lambda: init_lm_params(jax.random.PRNGKey(0), cfg))
    return cfg, jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                             shapes)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes
            + m.generated_code_size_in_bytes)


@pytest.mark.parametrize("C", [16, 128])
@pytest.mark.parametrize("k_n", [(4096, 1536), (1536, 4096)],
                         ids=["up", "down"])
def test_super_gmm_compiles_for_v5e(one_chip, k_n, C):
    K, N = k_n
    compiled = jax.jit(functools.partial(super_gmm, interpret=False)).lower(
        _sds((1,), jnp.int32, one_chip),
        _sds((2, N_E, K, N), jnp.bfloat16, one_chip),
        _sds((N_E, C, K), jnp.bfloat16, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def test_moe_step_compiles_for_v5e(full, one_chip, monkeypatch):
    """The executor's MoE step: one device's 32 experts addressed by id in
    the shared [1, 128, ...] expert stack.  The step resolves `interpret`
    from the default backend, so the test presents the TPU's."""
    cfg, params = full
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step = make_moe_step(cfg)
    compiled = step.lower(
        params["stages"][0]["ffn"]["experts"],
        _sds((N_E,), jnp.int32, one_chip), _sds((1,), jnp.int32, one_chip),
        _sds((N_E, 128, cfg.d_model), cfg.dtype, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("emit_kv", [False, True])
def test_attention_step_compiles_for_v5e(full, one_chip, emit_kv):
    cfg, params = full
    sp = params["stages"][0]
    stage = {"attn": sp["attn"], "ln_attn": sp["ln_attn"],
             "ln_ffn": sp["ln_ffn"], "router": sp["ffn"]["router"]}
    compiled = make_attn_step(cfg, emit_kv=emit_kv).lower(
        stage, _sds((), jnp.int32, one_chip),
        _sds((1, 1024, cfg.d_model), cfg.dtype, one_chip)).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_decode_step_compiles_for_v5e(full, one_chip):
    cfg, params = full
    slots, max_len = 4, 512
    kv = _sds((cfg.num_layers, slots, max_len, cfg.num_kv_heads,
               cfg.head_dim), cfg.dtype, one_chip)
    row = functools.partial(_sds, (slots,), sharding=one_chip)
    compiled = make_decode_step(cfg).lower(
        params, kv, kv, row(jnp.int32), row(jnp.int32),
        row(jnp.bool_)).compile()
    assert _device_bytes(compiled) < HBM_BYTES
