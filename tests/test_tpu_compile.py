"""Compile-only checks for one described TPU v5e chip: nothing runs, but
the TPU compiler refuses here what it would refuse on the chip (block
tiling, fast-memory limits, programs that do not fit the device).

Covered at LLaMA-7B widths (``celora-llama-7b``): the flash-attention
forward with its logsumexp and its backward, the fused tri-LoRA matmul,
and the federated trainer's own vmapped local fit at 24 of the model's 32
layers, which must fit one chip's HBM with the frozen base as an argument.

The topology is described inside a module fixture, never at import, and
the persistent compilation cache is off around these compiles (an entry
written for a described chip cannot be read back without one).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM = 15.75 * 2 ** 30          # what the compiler lets one v5e hold
HEADS, HEAD_DIM, D_MODEL, D_FF = 32, 128, 4096, 11008


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")   # else it logs under /tmp
            try:
                desc = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:             # noqa: BLE001 - any failure
                pytest.skip(f"no v5e:2x2 topology can be described here: "
                            f"{e}")
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("seq", [256, 2048])
def test_flash_forward_with_lse_compiles(one_chip, seq):
    from repro.kernels.flash_attention.flash_attention import (
        flash_attention_kernel)
    q = _on(one_chip, jax.ShapeDtypeStruct((1, HEADS, seq, HEAD_DIM),
                                           jnp.bfloat16))
    compiled = jax.jit(lambda q, k, v: flash_attention_kernel(
        q, k, v, save_lse=True)).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("seq", [256, 2048])
def test_flash_backward_compiles(one_chip, seq):
    from repro.kernels.flash_attention.flash_attention import (
        flash_attention_bwd_kernel)
    q = _on(one_chip, jax.ShapeDtypeStruct((1, HEADS, seq, HEAD_DIM),
                                           jnp.bfloat16))
    lse = _on(one_chip, jax.ShapeDtypeStruct((1, HEADS, seq), jnp.float32))
    compiled = jax.jit(flash_attention_bwd_kernel).lower(
        q, q, q, q, lse, q).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2   # dq, dk/dv


@pytest.mark.parametrize("d_out", [D_MODEL, D_FF])
def test_tri_lora_matmul_compiles(one_chip, d_out):
    from repro.kernels.tri_lora.tri_lora import tri_lora_matmul_kernel
    m, r = 256, 8
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((m, D_MODEL), jnp.bfloat16),
        jax.ShapeDtypeStruct((D_MODEL, d_out), jnp.bfloat16),
        jax.ShapeDtypeStruct((m, r), jnp.bfloat16),
        jax.ShapeDtypeStruct((r, d_out), jnp.bfloat16)))
    compiled = jax.jit(tri_lora_matmul_kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_local_fit_fits_one_chip(one_chip):
    """The trainer's vmapped local fit (4 clients, batch 1, seq 256, two
    steps) at 24 LLaMA-7B layers: the frozen base arrives as an argument,
    and arguments plus temporaries stay inside one chip's HBM."""
    from repro.core import client_batch
    from repro.launch.train import make_local_fit
    from repro.models import model
    from repro.models.config import get_config
    from repro.optim import adamw
    cfg = get_config("celora-llama-7b").with_overrides(n_layers=24)
    base = _on(one_chip, jax.eval_shape(
        lambda: model.init_base(cfg, jax.random.key(0))))
    stacked = _on(one_chip, jax.eval_shape(lambda: client_batch.stack_states(
        [model.init_adapter(cfg, jax.random.key(i)) for i in range(4)])))
    toks = _on(one_chip, jax.ShapeDtypeStruct((4, 2, 1, 256), jnp.int32))
    fit = jax.vmap(make_local_fit(cfg, adamw(lr=3e-3)),
                   in_axes=(None, 0, 0, 0))
    mem = jax.jit(fit).lower(base, stacked, toks, toks).compile() \
        .memory_analysis()
    base_bytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(base))
    assert mem.argument_size_in_bytes >= base_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM
