"""Compile-only checks for one described TPU v5e chip: nothing runs, but
the TPU compiler refuses here what it would refuse on the chip (block
tiling, fast-memory limits, programs that do not fit the device).

Covered at LLaMA-7B widths (``celora-llama-7b``): the flash-attention
forward with its logsumexp and its backward, the fused tri-LoRA matmul,
and the federated trainer's own vmapped local fit at 24 of the model's 32
layers, which must fit one chip's HBM with the frozen base as an argument.
At Danube's attention shape: the serving step writes its KV ring in place.
At DeepSeek-V2-Lite's widths and its cell's slots and ring: the MLA step
writes its latent ring in place and never builds per-head keys or values.
At both: the prefill program writes a prompt chunk into the ring in place.

The topology is described inside a module fixture, never at import, and
the persistent compilation cache is off around these compiles (an entry
written for a described chip cannot be read back without one).
"""
import math
import re

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


def _ring_traffic(hlo: str, ring_shapes: set):
    """Ring-shaped results the compiled step materialises in HBM, as
    (instruction, opcode) pairs, and the number of in-place ring scatters.
    Instructions inside fused computations are views their fusion reads,
    not buffers; parameters, tuple plumbing and bitcasts move nothing; a
    result in memory space 1 is a layer the compiler streams into on-chip
    memory for a dot, which is that dot's one read of it."""
    fused, roots, comp = set(), {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(", line)
        if head:
            comp = head.group(1)
        fused.update(re.findall(r" fusion\(.*calls=%([\w.\-]+)", line))
        root = re.match(r"\s*ROOT %\S+ = \S+ ([\w\-]+)\(", line)
        if root and comp:
            roots[comp] = root.group(1)
    found, scatters, comp = [], 0, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(", line)
        if head:
            comp = head.group(1)
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\](\S*) ([\w\-]+)\(",
                     line)
        if comp in fused or not m:
            continue
        name, dims, layout, op = m.groups()
        if (tuple(int(d) for d in dims.split(",") if d) not in ring_shapes
                or "S(1)" in layout):
            continue
        if op in ("parameter", "get-tuple-element", "tuple", "bitcast"):
            continue
        called = re.search(r"calls=%([\w.\-]+)", line)
        if op == "fusion" and called and roots.get(called.group(1)) == \
                "scatter":
            scatters += 1
            continue
        found.append((name, op))
    return found, scatters


def test_serve_step_updates_the_ring_in_place(one_chip):
    """The serving step at Danube's attention shape (sliding window, 8 KV
    heads of 120, 2 layers, 8 slots, a ring of 384) writes each new token
    into the donated stacked ring with one scatter and its attention dots
    read the ring where it lies: no ring-shaped slice, copy or re-layout,
    the ring aliased input to output, and less scratch than one layer's
    ring."""
    from repro.core.adapter_bank import random_bank
    from repro.launch import serve
    from repro.models import model
    from repro.models.config import get_config
    cfg = get_config("h2o-danube-3-4b").with_overrides(n_layers=2)
    slots, ring = 8, 384
    base = _on(one_chip, jax.eval_shape(
        lambda: model.init_base(cfg, jax.random.key(0))))
    bank = _on(one_chip, jax.eval_shape(
        lambda: random_bank(cfg, 4, jax.random.key(1)).decode_tree()))
    cache = _on(one_chip, jax.eval_shape(
        lambda: model.init_decode_cache(cfg, slots, ring)))
    ints = _on(one_chip, jax.ShapeDtypeStruct((slots,), jnp.int32))
    toks = _on(one_chip, jax.ShapeDtypeStruct((slots, 1), jnp.int32))
    compiled = serve._serve_step.lower(
        cfg, base, bank, cache, toks, ints, ints).compile()

    stacked = cache["groups"]["0"]["k"].shape
    assert stacked == (2, slots, 8, ring, 128)    # head-major, 128 lanes
    shapes = {stacked, stacked[1:], (1,) + stacked[1:]}
    found, scatters = _ring_traffic(compiled.as_text(), shapes)
    assert not found, found
    assert scatters >= 2                                # k and v
    mem = compiled.memory_analysis()
    rings = 2 * math.prod(stacked) * 2                  # k and v, bf16
    assert mem.alias_size_in_bytes >= rings
    assert mem.temp_size_in_bytes < math.prod(stacked[1:]) * 2


def _results(hlo: str):
    """(name, dims, opcode) of every result outside fused computations."""
    fused = set(re.findall(r" fusion\(.*calls=%([\w.\-]+)", hlo))
    comp = None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(", line)
        if head:
            comp = head.group(1)
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(",
                     line)
        if m and comp not in fused:
            yield (m.group(1), tuple(int(d) for d in m.group(2).split(",")
                                     if d), m.group(3))


def test_mla_serve_step_updates_the_latent_ring_in_place(one_chip):
    """The serving step at DeepSeek-V2-Lite's widths (16 of 64 experts
    held, the leading dense layer and 2 expert layers) with the cell's 64
    slots and ring of 1408: each layer's new latent row goes into the
    donated ring by one scatter, the ring is aliased input to output and
    nothing else of its shape is materialised, no result has a per-head
    key or value ring's shape (slots, heads and ring positions together),
    and the step's scratch is under one layer's ring."""
    from repro.core.adapter_bank import random_bank
    from repro.launch import serve
    from repro.models import model
    from repro.models.config import get_config
    cfg = get_config("deepseek-v2-lite").with_overrides(n_layers=3,
                                                        experts_held=16)
    slots, ring = 64, 1408
    base = _on(one_chip, jax.eval_shape(
        lambda: model.init_base(cfg, jax.random.key(0))))
    bank = _on(one_chip, jax.eval_shape(
        lambda: random_bank(cfg, 4, jax.random.key(1)).decode_tree()))
    cache = _on(one_chip, jax.eval_shape(
        lambda: model.init_decode_cache(cfg, slots, ring)))
    ints = _on(one_chip, jax.ShapeDtypeStruct((slots,), jnp.int32))
    toks = _on(one_chip, jax.ShapeDtypeStruct((slots, 1), jnp.int32))
    compiled = serve._serve_step.lower(
        cfg, base, bank, cache, toks, ints, ints).compile()

    stacked = cache["groups"]["0"]["latent"].shape
    assert stacked == (2, slots, ring, 640)        # 512 + 64 in 640 lanes
    assert cache["lead"][0]["latent"].shape == stacked[1:]
    hlo = compiled.as_text()
    found, scatters = _ring_traffic(
        hlo, {stacked, stacked[1:], (1,) + stacked[1:]})
    assert not found, found
    assert scatters >= 2                           # lead layer and the scan
    # the scores and probabilities are (slots, heads, ring); a per-head key
    # or value ring would add a feature axis
    per_head = [(n, d, op) for n, d, op in _results(hlo)
                if len(d) >= 4 and {slots, cfg.n_heads, ring} <= set(d)]
    assert not per_head, per_head
    mem = compiled.memory_analysis()
    one_layer = math.prod(stacked[1:]) * 2         # bf16
    assert mem.alias_size_in_bytes >= 3 * one_layer
    assert mem.temp_size_in_bytes < one_layer


@pytest.mark.parametrize("arch,layers,slots,ring", [
    ("h2o-danube-3-4b", 2, 8, 384),     # the serving step test's shape
    ("deepseek-v2-lite", 3, 64, 1408),  # its cell's slots and latent ring
])
def test_prefill_writes_the_ring_in_place(one_chip, arch, layers, slots,
                                          ring):
    """The prefill program (one slot's chunk of 128 prompt tokens through
    every layer) at Danube's attention shape and at DeepSeek-V2-Lite's
    widths (16 of 64 experts held): each layer's chunk goes into the
    donated stacked ring by in-place scatters, nothing of the ring's or of
    one layer's shape is materialised (nor their views flattened to rows
    of lanes, which the chunk's scatter writes through), the rings are
    aliased input to output, and the scratch is under one layer's ring."""
    from repro.core.adapter_bank import random_bank
    from repro.launch import serve
    from repro.models import model, transformer
    from repro.models.config import get_config
    cfg = get_config(arch).with_overrides(
        n_layers=layers, **({"experts_held": 16} if arch.startswith("deep")
                            else {}))
    base = _on(one_chip, jax.eval_shape(
        lambda: model.init_base(cfg, jax.random.key(0))))
    bank = _on(one_chip, jax.eval_shape(
        lambda: random_bank(cfg, 4, jax.random.key(1)).decode_tree()))
    cache = _on(one_chip, jax.eval_shape(
        lambda: serve._init_cache(cfg, slots, ring)))
    one = _on(one_chip, jax.ShapeDtypeStruct((), jnp.int32))
    toks = _on(one_chip, jax.ShapeDtypeStruct((1, serve.PREFILL_CHUNK),
                                              jnp.int32))
    compiled = serve._prefill.lower(cfg, base, bank, cache, toks, one, one,
                                    one, one).compile()

    rings = [a for p, a in jax.tree_util.tree_flatten_with_path(cache)[0]
             if getattr(p[-1], "key", None) in transformer.RING_NAMES]
    stacked = max((a.shape for a in rings), key=len)
    assert stacked[0] == layers - cfg.n_dense_layers and \
        stacked[-1] % 128 == 0
    layer = stacked[1:]

    def flat(d):
        return (math.prod(d[:-1]), d[-1])
    found, scatters = _ring_traffic(
        compiled.as_text(), {stacked, layer, (1,) + layer, flat(stacked),
                             flat(layer)})
    assert not found, found
    assert scatters >= 2               # k and v, or the lead layer and scan
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(a.size * 2 for a in rings)
    assert mem.temp_size_in_bytes < math.prod(layer) * 2
