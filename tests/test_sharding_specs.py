"""Sharding-rule unit tests on ABSTRACT meshes (no devices needed):
every param/cache/batch leaf must get a PartitionSpec whose sharded dims
divide the mesh axis, tri-LoRA C must be replicated (it is the federated
payload), and the serving layout must drop the FSDP axis."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ASSIGNED
from repro.launch import sharding as shd
from repro.launch.mesh import batch_axes
from repro.launch.steps import SHAPES, abstract_cache, input_specs, shape_variant
from repro.models import model
from repro.models.config import get_config

def _abstract_mesh(shape, names):
    """AbstractMesh across jax versions: >=0.5 takes (shape, axis_names);
    0.4.x takes one tuple of (name, size) pairs."""
    try:
        return AbstractMesh(shape, names)
    except TypeError:
        return AbstractMesh(tuple(zip(names, shape)))


MESHES = {
    "16x16": _abstract_mesh((16, 16), ("data", "model")),
    "2x16x16": _abstract_mesh((2, 16, 16), ("pod", "data", "model")),
}


def _check_divisible(spec_tree, shape_tree, mesh):
    flat_s = jax.tree.leaves(spec_tree, is_leaf=lambda x: isinstance(x, P))
    flat_x = jax.tree.leaves(shape_tree)
    assert len(flat_s) == len(flat_x)
    for spec, leaf in zip(flat_s, flat_x):
        assert isinstance(spec, P), spec
        assert len(spec) <= len(leaf.shape), (spec, leaf.shape)
        for dim, ax in zip(leaf.shape, spec):
            axes = ax if isinstance(ax, tuple) else ((ax,) if ax else ())
            total = 1
            for a in axes:
                total *= mesh.shape[a]
            assert dim % total == 0, (spec, leaf.shape)


@pytest.mark.parametrize("mesh_name", list(MESHES))
@pytest.mark.parametrize("arch", ASSIGNED)
def test_param_specs_divisible_everywhere(arch, mesh_name):
    mesh = MESHES[mesh_name]
    cfg = get_config(arch)
    params = model.abstract_params(cfg)
    specs = shd.param_specs(params, mesh, cfg)
    _check_divisible(specs, params, mesh)


@pytest.mark.parametrize("arch", ASSIGNED)
def test_c_matrices_replicated(arch):
    mesh = MESHES["16x16"]
    cfg = get_config(arch)
    adapter = model.abstract_params(cfg)["adapter"]
    specs = shd.param_specs(adapter, mesh, cfg)

    def check(path, spec):
        names = shd._path_names(path)
        if names[-1] == "C":
            assert all(s is None for s in spec), (names, spec)
    jax.tree_util.tree_map_with_path(check, specs)


def test_serving_layout_drops_fsdp():
    mesh = MESHES["16x16"]
    cfg = get_config("qwen3-32b")
    base = model.abstract_params(cfg)["base"]
    fsdp = shd.param_specs(base, mesh, cfg, fsdp=True)
    serve = shd.param_specs(base, mesh, cfg, fsdp=False)
    def count_axis(tree, axis):
        n = 0
        for spec in jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, P)):
            for s in spec:
                axes = s if isinstance(s, tuple) else (s,)
                n += axis in axes
        return n
    assert count_axis(fsdp, "data") > 0
    assert count_axis(serve, "data") == 0          # no FSDP gathers
    assert count_axis(serve, "model") == count_axis(fsdp, "model")


@pytest.mark.parametrize("shape_name", list(SHAPES))
@pytest.mark.parametrize("arch", ["qwen2.5-14b", "rwkv6-1.6b",
                                  "whisper-small", "recurrentgemma-2b"])
def test_batch_and_cache_specs(arch, shape_name):
    mesh = MESHES["2x16x16"]
    cfg = shape_variant(get_config(arch), shape_name)
    baxes = batch_axes(mesh) if hasattr(mesh, "axis_names") else ()
    baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    batch = input_specs(cfg, shape_name)
    bspecs = shd.batch_specs(batch, mesh, baxes)
    _check_divisible(bspecs, batch, mesh)
    if SHAPES[shape_name].kind == "decode":
        cache = abstract_cache(cfg, shape_name)
        cspecs = shd.cache_specs(cache, mesh, cfg, baxes)
        _check_divisible(cspecs, cache, mesh)


def test_cache_specs_shard_the_ring_axis(tiny_cfg):
    """K/V rings are head-major, (B, K, ring, hd) per layer: the ring axis
    (index 2) goes to `model`, in the scanned groups' stacked rings and in
    the unscanned tail's alike."""
    mesh = MESHES["16x16"]
    cfg = tiny_cfg.with_overrides(n_layers=3, layer_pattern=("attn", "swa"),
                                  window=32)
    cache = jax.eval_shape(lambda: model.init_decode_cache(cfg, 16, 64))
    specs = shd.cache_specs(cache, mesh, cfg, ("data",))
    for ring in ("k", "v"):
        for i in ("0", "1"):                # the attn and the swa block
            assert specs["groups"][i][ring] == P(None, "data", None,
                                                 "model", None)
        assert specs["tail"][0][ring] == P("data", None, "model", None)


def test_mla_and_expert_specs_have_their_leaves_ranks():
    """DeepSeek-V2-Lite: the latent ring (B, ring, lanes) shards its ring
    axis over `model` in the scanned stack and the leading layer alike;
    every weight's spec has its leaf's rank; the untied output head shards
    as the embedding table does; routed experts shard their expert axis
    over `model`, the shared experts (a dense MLP) do not."""
    mesh = MESHES["16x16"]
    cfg = get_config("deepseek-v2-lite").with_overrides(experts_held=16)
    cache = jax.eval_shape(lambda: model.init_decode_cache(cfg, 16, 64))
    specs = shd.cache_specs(cache, mesh, cfg, ("data",))
    assert specs["groups"]["0"]["latent"] == P(None, "data", "model", None)
    assert specs["lead"][0]["latent"] == P("data", "model", None)
    params = model.abstract_params(cfg)
    pspecs = shd.param_specs(params, mesh, cfg)
    flat = jax.tree_util.tree_flatten_with_path(
        pspecs, is_leaf=lambda x: isinstance(x, P))[0]
    for (path, spec), leaf in zip(flat, jax.tree.leaves(params)):
        assert len(spec) == len(leaf.shape), (shd._path_names(path), spec)
    assert pspecs["base"]["lm_head"] == pspecs["base"]["embed"] \
        == P("model", "data")                   # the untied head, (V, D)
    moe = pspecs["base"]["groups"]["0"]["moe"]
    assert moe["w_gate"] == P(None, "model", "data", None)   # (L, E, d, f)
    assert moe["shared"]["w_gate"] == P(None, "data", "model")
    attn = pspecs["base"]["groups"]["0"]["attn"]
    assert attn["wkv_a"] == P(None, "data", "model")
    assert attn["wkv_b"] == P(None, "data", "model")
    assert attn["wo"] == P(None, "model", "data")
