"""Sharding rules: params (FSDP over `data` × TP over `model`), adapters,
caches and batches — as path/shape-driven PartitionSpec builders.

Strategy (DESIGN.md §5):
- frozen base weights shard BOTH ways: input-dim → `data` (FSDP — needed to
  fit 314B frozen params in 256×16 GB), output-dim → `model` (Megatron TP);
  "out-projections" (wo, w_down, w_out, channel-mix wv) transpose that.
- embeddings and an untied `lm_head` (V, D): V → `model` (sharded
  logits/softmax), D → `data`.
- MoE experts: expert axis → `model` when divisible (expert parallelism),
  else tensor-parallel inside each expert.
- tri-LoRA: A in-dim → `data`, B out-dim → `model`, C REPLICATED — C is the
  federated payload; keeping it replicated makes the cross-pod collective
  exactly the paper's r² traffic.
- KV caches: batch → `data` (+`pod`), cache sequence → `model`
  (flash-decoding style partial softmax, combined by GSPMD collectives);
  MLA's latent ring (B, ring, lanes) likewise.
- MLA weights (wq, wkv_a, wkv_b) are in→out matrices and wo an
  out-projection; shared experts are a dense MLP, not expert-sharded.
- every rule degrades to replication when the dim is not divisible by the
  mesh axis (e.g. whisper's 12 heads vs model=16).

Params are replicated across `pod` (each pod = one federated participant
holding the full frozen model, sharded within the pod).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import transformer
from repro.models.config import ModelConfig

# parameter names whose matrix maps "wide → d_model" (shard in-dim on model)
_OUT_NAMES = {"wo", "w_down", "w_out"}
# 1-D biases on output features
_OUT_BIAS = {"bq", "bk", "bv", "conv_b", "b_a", "b_x"}


def _axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis] if axis in mesh.axis_names else 1


def _fits(dim: int, mesh: Mesh, axis: str | None):
    if axis is None or axis not in mesh.axis_names:
        return None
    return axis if dim % _axis_size(mesh, axis) == 0 else None


def _mat_spec(shape, mesh: Mesh, in_axis: str, out_axis: str):
    """Trailing-2D matrix spec with any number of leading (stack) dims."""
    lead = (None,) * (len(shape) - 2)
    return P(*lead, _fits(shape[-2], mesh, in_axis),
             _fits(shape[-1], mesh, out_axis))


def param_spec(path_names: tuple[str, ...], shape: tuple[int, ...],
               mesh: Mesh, cfg: ModelConfig, *, fsdp: bool = True) -> P:
    name = path_names[-1]
    parent = path_names[-2] if len(path_names) >= 2 else ""
    da = "data" if fsdp else None   # serving layout: no FSDP weight gathers

    # ---- tri-LoRA adapter factors (A/B/C names are adapter-exclusive)
    if name == "A":
        return _mat_spec(shape, mesh, da, None)
    if name == "B":
        return _mat_spec(shape, mesh, None, "model")
    if name == "C":
        return P(*(None,) * len(shape))          # replicated: the payload

    # ---- embeddings
    if name in ("embed", "lm_head"):
        return P(_fits(shape[0], mesh, "model"), _fits(shape[1], mesh, da))
    if name == "pos_embed":
        return P(None, _fits(shape[1], mesh, "model"))

    # ---- MoE
    if name == "router":
        return _mat_spec(shape, mesh, da, None)
    if parent == "moe" or (len(shape) >= 3 and name in
                           {"w_gate", "w_up", "w_in", "w_down", "w_out"}
                           and _is_moe_leaf(path_names, shape, cfg)):
        # (…, E, d, f) expert tensors
        e = shape[-3]
        if _fits(e, mesh, "model"):
            lead = (None,) * (len(shape) - 3)
            if name in _OUT_NAMES:
                return P(*lead, "model", _fits(shape[-2], mesh, da), None)
            return P(*lead, "model", _fits(shape[-2], mesh, da), None)
        if name in _OUT_NAMES:
            return _mat_spec(shape, mesh, "model", da)
        return _mat_spec(shape, mesh, da, "model")

    # ---- scalars / vectors
    if len(shape) <= 1:
        if name in _OUT_BIAS and shape:
            return P(_fits(shape[0], mesh, "model"))
        if name == "lam" and shape:
            return P(_fits(shape[0], mesh, "model"))
        return P(*(None,) * len(shape))

    # ---- channel-mix wv is (f, d): an out-projection despite the name
    if name == "wv" and parent == "cm":
        return _mat_spec(shape, mesh, "model", da)
    if name in _OUT_NAMES:
        return _mat_spec(shape, mesh, "model", da)
    # rwkv ddlerp low-rank: (d, 5, L) / (5, L, d) — tiny, shard the d side only
    if name == "mix_a":
        lead_shard = _fits(shape[-3], mesh, da)
        return P(*(None,) * (len(shape) - 3), lead_shard, None, None)
    if name == "mix_b":
        return P(*(None,) * (len(shape) - 1), _fits(shape[-1], mesh, "model"))
    if name == "conv_w":
        return _mat_spec(shape, mesh, None, "model")
    if len(shape) >= 2:
        # default in→out matrices (wq/wk/wv/wg/wr/w_a/w_x/w_b/mlp in/gate/up)
        return _mat_spec(shape, mesh, da, "model")
    return P(*(None,) * len(shape))


def _is_moe_leaf(path_names, shape, cfg) -> bool:
    """A routed expert tensor; shared experts are a dense MLP."""
    return cfg.is_moe and "moe" in path_names and "shared" not in path_names


# ---------------------------------------------------------------------------
# tree-level builders
# ---------------------------------------------------------------------------

def _path_names(path) -> tuple[str, ...]:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return tuple(out)


def param_specs(tree: Any, mesh: Mesh, cfg: ModelConfig, *,
                fsdp: bool = True) -> Any:
    """PartitionSpec pytree for a params/adapter/opt-state pytree (works on
    ShapeDtypeStructs or arrays).  ``fsdp=False`` = serving layout: weights
    replicated over `data` (no per-step all-gathers), tensor-parallel over
    `model` only — used when the frozen weights fit 1/|model| per chip."""
    def spec(path, leaf):
        names = _path_names(path)
        return param_spec(names, tuple(leaf.shape), mesh, cfg, fsdp=fsdp)
    return jax.tree.map_with_path(spec, tree)


def cache_specs(tree: Any, mesh: Mesh, cfg: ModelConfig,
                batch: tuple[str, ...]) -> Any:
    """KV-cache / recurrent-state PartitionSpecs."""
    total = math.prod(_axis_size(mesh, a) for a in batch)

    def spec(path, leaf):
        names = _path_names(path)
        name = names[-1]
        shape = tuple(leaf.shape)
        if name == "idx" or len(shape) == 0:
            return P()
        # leading stack dim from the layer-group scan?
        stack = 1 if (len(names) >= 3 and "groups" in names and
                      len(shape) > _cache_rank(name)) else 0
        lead = (None,) * stack
        body = shape[stack:]
        # batch axes only when the batch dim divides (long_500k: B=1)
        if body and body[0] % max(total, 1) == 0 and batch:
            bspec = batch if len(batch) > 1 else batch[0]
        else:
            bspec = None
        if name in transformer.RING_NAMES:
            # (B, K, ring, hd) or (B, ring, lanes): ring axis → model
            mid = (None,) * (len(body) - 3)
            return P(*lead, bspec, *mid, _fits(body[-2], mesh, "model"), None)
        if name in ("xk", "xv"):          # (B, F, H, hd)
            return P(*lead, bspec, None, _fits(body[2], mesh, "model"), None)
        if name == "wkv":                 # (B, H, hd, hd)
            return P(*lead, bspec, _fits(body[1], mesh, "model"), None, None)
        if name == "shift":               # (B, D)
            return P(*lead, bspec, _fits(body[1], mesh, "model"))
        if name == "conv":                # (B, cw-1, rd)
            return P(*lead, bspec, None, _fits(body[2], mesh, "model"))
        if name == "h":                   # (B, rd)
            return P(*lead, bspec, _fits(body[1], mesh, "model"))
        return P(*((None,) * len(shape)))
    return jax.tree.map_with_path(spec, tree)


_CACHE_RANKS = {"k": 4, "v": 4, "latent": 3, "xk": 4, "xv": 4, "wkv": 4,
                "shift": 2, "conv": 3, "h": 2, "idx": 0}


def _cache_rank(name: str) -> int:
    return _CACHE_RANKS.get(name, 0)


def batch_specs(batch_tree: Any, mesh: Mesh, batch: tuple[str, ...]) -> Any:
    bspec = batch if len(batch) > 1 else (batch[0] if batch else None)

    def spec(path, leaf):
        shape = tuple(leaf.shape)
        if len(shape) == 0:
            return P()
        b = bspec
        if shape[0] == 1 or (isinstance(b, tuple) and
                             shape[0] % math.prod(_axis_size(mesh, a)
                                                  for a in batch) != 0) \
           or (isinstance(b, str) and shape[0] % _axis_size(mesh, b) != 0):
            b = None                       # long_500k: batch=1 → replicate
        return P(b, *((None,) * (len(shape) - 1)))
    return jax.tree.map_with_path(spec, batch_tree)


def to_named(spec_tree: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
