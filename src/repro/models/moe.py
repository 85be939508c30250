"""Mixture-of-Experts MLP — GShard/Switch-style dense-dispatch formulation.

TPU-native design notes (vs. the CUDA grouped-GEMM formulation):
- tokens are dispatched with one-hot combine/dispatch einsums so the whole
  layer is static-shaped and GSPMD-shardable; experts shard over the `model`
  mesh axis (expert parallelism) which lowers the dispatch einsums to
  all-to-all style collectives;
- each sequence forms a dispatch group, so the transient dispatch tensor is
  (B, S, E, C) with C = S·top_k·cf/E — bounded per layer and freed by the
  layer scan;
- capacity overflow drops tokens (standard Switch behaviour); the router
  aux load-balance loss keeps the drop rate low.

A layer may hold a share of the experts (``cfg.experts_held``, the chip's
share under expert parallelism, model-configs §4): the router keeps all
``n_experts`` outputs and its top-k, and the layer computes only experts
[0, held) for the tokens routed to them.  What the absent experts would
add is left out.  Shared experts (DeepSeek) are one dense MLP every token
passes, added in full.  The experts are frozen: ``lora_mlp`` is refused.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.models.config import ModelConfig


def init_moe(key, cfg: ModelConfig) -> dict:
    d, f, e = cfg.d_model, cfg.expert_d_ff, cfg.n_held
    keys = jax.random.split(key, 4)
    s_in, s_out = 1.0 / jnp.sqrt(d), 1.0 / jnp.sqrt(f)
    p = {"router": (jax.random.normal(keys[0], (d, cfg.n_experts))
                    * s_in).astype(jnp.float32)}
    if cfg.mlp_type == "swiglu":
        p["w_gate"] = (jax.random.normal(keys[1], (e, d, f)) * s_in).astype(cfg.dtype)
        p["w_up"] = (jax.random.normal(keys[2], (e, d, f)) * s_in).astype(cfg.dtype)
        p["w_down"] = (jax.random.normal(keys[3], (e, f, d)) * s_out).astype(cfg.dtype)
    else:
        p["w_in"] = (jax.random.normal(keys[1], (e, d, f)) * s_in).astype(cfg.dtype)
        p["w_out"] = (jax.random.normal(keys[2], (e, f, d)) * s_out).astype(cfg.dtype)
    if cfg.shared_d_ff:
        p["shared"] = layers.init_mlp(jax.random.fold_in(key, 4), d,
                                      cfg.shared_d_ff, cfg.mlp_type, cfg.dtype)
    return p


def capacity(cfg: ModelConfig, seq: int) -> int:
    c = int(seq * max(cfg.top_k, 1) * cfg.capacity_factor / cfg.n_experts)
    # MXU-friendly: round up to a multiple of 8, floor at 8 (decode: seq==1)
    return max(8, -(-c // 8) * 8) if seq > 1 else 1


def route(cfg: ModelConfig, router_w: jnp.ndarray, x: jnp.ndarray):
    """x (B,S,D) -> (dispatch (B,S,E',C) bf16, combine (B,S,E',C) f32, aux
    loss), E' the held experts.  Softmax and greedy top-k run over all E;
    the gates are renormalised over the top k only with ``norm_topk``."""
    b, s, _ = x.shape
    e, k, c = cfg.n_experts, cfg.top_k, capacity(cfg, s)
    logits = x.astype(jnp.float32) @ router_w                 # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)

    # top-k selection, one expert at a time (iteratively masked argmax)
    gates = jnp.zeros_like(probs)
    masked = probs
    sel_onehot = jnp.zeros((b, s, e), jnp.float32)
    for _ in range(k):
        idx = jnp.argmax(masked, axis=-1)                     # (B,S)
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)
        gates = gates + onehot * probs
        sel_onehot = sel_onehot + onehot
        masked = masked * (1.0 - onehot)
    if cfg.norm_topk:
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

    # position of each token inside its expert's buffer (per sequence group)
    held = sel_onehot[..., :cfg.n_held]                       # (B,S,E')
    pos_in_expert = jnp.cumsum(held, axis=1) * held - 1.0
    keep = (pos_in_expert >= 0) & (pos_in_expert < c)
    pos_clamped = jnp.clip(pos_in_expert, 0, c - 1).astype(jnp.int32)
    slot = jax.nn.one_hot(pos_clamped, c, dtype=jnp.float32)  # (B,S,E',C)
    dispatch = slot * keep[..., None]
    combine = dispatch * gates[..., :cfg.n_held, None]

    # Switch load-balance auxiliary loss
    frac_tokens = jnp.mean(sel_onehot / max(k, 1), axis=1)    # (B,E)
    frac_probs = jnp.mean(probs, axis=1)                      # (B,E)
    aux = e * jnp.mean(jnp.sum(frac_tokens * frac_probs, axis=-1))
    return dispatch, combine, aux


MOE_GROUP = 1024          # tokens per dispatch group (capacity granularity)
MOE_CHUNK_TOKENS = 16384  # max tokens in flight through the expert einsums


def moe_mlp(cfg: ModelConfig, p: dict,
            x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (out (B,S,D), aux_loss scalar): the held experts' part plus
    the shared experts, under the named scope ``moe``.  Experts are FROZEN
    in CE-LoRA fine-tuning (adapters attach to attention), so a config
    asking for MLP adapters (``lora_mlp``) is refused.

    Long sequences are split into MOE_GROUP-token dispatch groups so the
    one-hot dispatch/combine tensors stay O(group·E·C) per layer.
    """
    if cfg.lora_mlp:
        raise ValueError(f"{cfg.name}: lora_mlp asks for adapters on the "
                         f"experts, which are frozen; adapt attention only")
    with jax.named_scope("moe"):
        out, aux = _routed(cfg, p, x)
        if "shared" in p:
            out = out + layers.mlp(x, p["shared"], cfg.mlp_type)
    return out, aux


def _routed(cfg: ModelConfig, p: dict, x: jnp.ndarray):
    b, s, d = x.shape
    group = min(MOE_GROUP, s)
    pad = (-s) % group
    if pad == 0 and s <= group:
        return _moe_grouped(cfg, p, x)
    xg = jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
    nb = b * ((s + pad) // group)
    xg = xg.reshape(nb, group, d)

    # bound live expert activations: process ≤ MOE_CHUNK_TOKENS at a time,
    # lax.map + checkpoint (residuals are rematerialized per chunk)
    chunk = max(1, MOE_CHUNK_TOKENS // group)
    if nb > chunk and nb % chunk == 0:
        xg = xg.reshape(nb // chunk, chunk, group, d)

        def one(xi):
            o, a = _moe_grouped(cfg, p, xi)
            return o, a
        outs, auxs = jax.lax.map(jax.checkpoint(one), xg)
        out = outs.reshape(b, s + pad, d)[:, :s]
        return out, jnp.mean(auxs)
    out, aux = _moe_grouped(cfg, p, xg)
    out = out.reshape(b, s + pad, d)[:, :s]
    return out, aux


def _moe_grouped(cfg: ModelConfig, p: dict, x: jnp.ndarray):
    dispatch, combine, aux = route(cfg, p["router"], x)
    xin = jnp.einsum("bsec,bsd->ebcd", dispatch.astype(x.dtype), x)  # (E,B,C,D)
    if cfg.mlp_type == "swiglu":
        g = jnp.einsum("ebcd,edf->ebcf", xin, p["w_gate"])
        u = jnp.einsum("ebcd,edf->ebcf", xin, p["w_up"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        out_e = jnp.einsum("ebcf,efd->ebcd", h, p["w_down"])
    else:
        h = jnp.einsum("ebcd,edf->ebcf", xin, p["w_in"])
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
        out_e = jnp.einsum("ebcf,efd->ebcd", h, p["w_out"])
    out = jnp.einsum("bsec,ebcd->bsd", combine.astype(x.dtype), out_e)
    return out, aux.astype(jnp.float32)
