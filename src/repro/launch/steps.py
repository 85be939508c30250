"""Distributed step functions + abstract input specs for every
(architecture × input shape) combination.

Three step kinds (matching the assigned input shapes):
- train_step   : frozen-base tri-LoRA fine-tuning step (fwd + adapter grads
                 + AdamW) — `train_4k`.
- prefill_step : full-sequence forward, last-position logits — `prefill_32k`.
- serve_step   : ONE new token against a KV cache of seq_len —
                 `decode_32k`, `long_500k`.

Plus the paper's distributed signature piece:
- fed_round_step : shard_map over the `pod` axis — each pod is a federated
  client; A/B/optimizer updates stay pod-local, and the ONLY cross-pod
  collective is the all-gather + weighted combine of the r×r C matrices
  (paper Alg. 1 lines 4–9 mapped onto ICI/DCN).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import tri_lora
from repro.launch import sharding as shd
from repro.launch.mesh import batch_axes
from repro.models import model
from repro.models.config import ModelConfig
from repro.optim import adamw, apply_updates

# ---------------------------------------------------------------------------
# the four assigned input shapes
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # train | prefill | decode


SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}

SWA_VARIANT_WINDOW = 8192


def shape_variant(cfg: ModelConfig, shape_name: str) -> ModelConfig:
    """long_500k needs sub-quadratic attention: full-attention archs run
    their sliding-window variant (same weights, window=8192); natively
    sub-quadratic archs (ssm/hybrid/swa) are unchanged (DESIGN.md §4)."""
    if shape_name == "long_500k" and "attn" in cfg.layer_pattern:
        pattern = tuple("swa" if k == "attn" else k for k in cfg.layer_pattern)
        return cfg.with_overrides(layer_pattern=pattern,
                                  window=cfg.window or SWA_VARIANT_WINDOW,
                                  name=cfg.name + "+swa")
    return cfg


def _f(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ModelConfig, shape_name: str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    sh = SHAPES[shape_name]
    b, s = sh.global_batch, sh.seq_len
    i32 = jnp.int32
    if sh.kind in ("train", "prefill"):
        batch = {"tokens": _f((b, s), i32)}
        if sh.kind == "train":
            batch["labels"] = _f((b, s), i32)
        if cfg.pos_type == "mrope":
            p = cfg.vision_patches
            batch["positions"] = _f((b, s + p, 3), i32)
            batch["vision"] = _f((b, p, cfg.d_model), cfg.dtype)
        else:
            batch["positions"] = _f((b, s), i32)
        if cfg.enc_dec:
            batch["frames"] = _f((b, cfg.enc_frames, cfg.d_model), cfg.dtype)
        return batch
    # decode: one token against a seq_len cache
    pos = _f((b, 1, 3), i32) if cfg.pos_type == "mrope" else _f((b, 1), i32)
    return {"token": _f((b, 1), i32), "positions": pos}


def abstract_cache(cfg: ModelConfig, shape_name: str):
    sh = SHAPES[shape_name]
    return jax.eval_shape(
        lambda: model.init_decode_cache(cfg, sh.global_batch, sh.seq_len))


# ---------------------------------------------------------------------------
# step factories (plain functions; jitting/sharding applied by the callers)
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, lr: float = 1e-4,
                    attn_impl: str | None = None,
                    microbatches: int = 1) -> Callable:
    """``microbatches > 1`` = gradient accumulation: the global batch is
    split into k sequential microbatches (lax.scan over grads), so live
    activation/attention-backward memory scales 1/k at the cost of k×
    parameter re-reads (compute term unchanged; memory/collective terms
    trade — see EXPERIMENTS §Perf M9)."""
    opt = adamw(lr=lr)

    def train_step(params, opt_state, batch):
        def lf(adapter, mb):
            return model.loss_fn(cfg, adapter, params["base"], mb,
                                 attn_impl=attn_impl)

        if microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(
                params["adapter"], batch)
        else:
            k = microbatches
            mb_batch = jax.tree.map(
                lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]),
                batch)

            def acc_step(carry, mb):
                g_acc, l_acc = carry
                (l, m), g = jax.value_and_grad(lf, has_aux=True)(
                    params["adapter"], mb)
                g_acc = jax.tree.map(lambda a, b: a + b, g_acc, g)
                return (g_acc, l_acc + l), m

            g0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                              params["adapter"])
            (grads, loss_sum), ms = jax.lax.scan(
                acc_step, (g0, jnp.zeros((), jnp.float32)), mb_batch)
            grads = jax.tree.map(lambda g: g / k, grads)
            loss = loss_sum / k
            metrics = jax.tree.map(lambda x: jnp.mean(x), ms)
        upd, opt_state2 = opt.update(grads, opt_state, params["adapter"])
        adapter = apply_updates(params["adapter"], upd)
        return ({"base": params["base"], "adapter": adapter}, opt_state2,
                {"loss": loss, **metrics})

    train_step.optimizer = opt
    return train_step


def make_prefill_step(cfg: ModelConfig,
                      attn_impl: str | None = None) -> Callable:
    def prefill_step(params, batch):
        hidden, _, n_prefix = model.forward_hidden(
            cfg, params["base"], params["adapter"], batch,
            attn_impl=attn_impl)
        last = hidden[:, -1]                       # serving: next-token logits
        from repro.models import layers
        return layers.unembed(last, model.head_table(cfg, params["base"]),
                              cfg.vocab_size)
    return prefill_step


def make_serve_step(cfg: ModelConfig) -> Callable:
    def serve_step(params, cache, batch):
        logits, cache = model.decode_step(cfg, params["base"],
                                          params["adapter"], cache, batch,
                                          pad_vocab=True)
        return logits[:, 0], cache
    return serve_step


# ---------------------------------------------------------------------------
# federated round step over the pod axis (the paper's comm pattern)
# ---------------------------------------------------------------------------

def make_fed_round_step(cfg: ModelConfig, mesh: Mesh, lr: float = 1e-4,
                        attn_impl: str | None = None,
                        payload_dtype=None) -> Callable:
    """One federated "micro-round" on the multi-pod mesh: each pod is one
    federated client.  Adapter/optimizer leaves carry a leading pod dim
    sharded P('pod', …); the local train step is vmapped over that dim, so
    A/B/optimizer updates stay strictly pod-local.  The ONLY cross-pod
    collective is the personalized combination of the r×r C matrices
    (paper Alg. 1 lines 4–9: C̄_i = Σ_j W[i,j]·C_j) — an einsum over the
    pod-sharded leading dim whose payload is Σ r² floats per pod.
    """
    opt = adamw(lr=lr)
    n_pods = mesh.shape["pod"]

    def fed_round_step(params, adapter_p, opt_state_p, batch, agg_w):
        from repro.models import layers
        base = params["base"]

        def local(adapter, opt_state, batch_local):
            def lf(ad):
                return model.loss_fn(cfg, ad, base, batch_local,
                                     attn_impl=attn_impl)
            (loss, _), grads = jax.value_and_grad(lf, has_aux=True)(adapter)
            upd, opt_state = opt.update(grads, opt_state, adapter)
            return apply_updates(adapter, upd), opt_state, loss

        # split the global batch into per-pod (client) shards
        def split(x):
            x = x.reshape((n_pods, x.shape[0] // n_pods) + x.shape[1:])
            try:
                return jax.lax.with_sharding_constraint(
                    x, P("pod", "data", *([None] * (x.ndim - 2))))
            except (ValueError, TypeError):
                return x  # spec incompatible with the mesh — advisory
        batch_p = jax.tree.map(split, batch)

        with layers.hint_batch_axes(("data",)):
            adapter_p, opt_state_p, losses = jax.vmap(
                local, spmd_axis_name="pod")(adapter_p, opt_state_p, batch_p)

        # ---- the ONLY cross-pod communication: the C matrices -------------
        c_all = tri_lora.tree_payload(adapter_p)        # leaves (n_pods,…,r,r)
        if payload_dtype is not None:
            # beyond-paper: quantize the wire payload (halves cross-pod
            # bytes at bf16).  The weighted combine as a sharded einsum
            # would all-reduce f32 PARTIALS (XLA promotes bf16 dots), so we
            # instead all-gather the quantized C's (the wire move, bf16)
            # and combine locally in f32.
            c_all = jax.tree.map(
                lambda c: jax.lax.with_sharding_constraint(
                    c.astype(payload_dtype),
                    P(*([None] * c.ndim))),           # replicate = all-gather
                c_all)
        c_bar = jax.tree.map(
            lambda c: jnp.einsum("ij,j...->i...",
                                 agg_w.astype(jnp.float32),
                                 c.astype(jnp.float32)),
            c_all)
        adapter_p = tri_lora.tree_load_payload(adapter_p, c_bar)
        return adapter_p, opt_state_p, losses

    fed_round_step.optimizer = opt
    fed_round_step.n_pods = n_pods
    return fed_round_step


# ---------------------------------------------------------------------------
# pod-replicated → pod-stacked helpers for the federated step's inputs
# ---------------------------------------------------------------------------

def pod_stacked_adapter(cfg: ModelConfig, n_pods: int):
    """Abstract adapter with a leading pod dim (one tri-LoRA set per pod)."""
    ad = model.abstract_params(cfg)["adapter"]
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((n_pods,) + tuple(x.shape), x.dtype),
        ad)


def pod_stacked_opt_state(cfg: ModelConfig, n_pods: int, opt):
    ad = model.abstract_params(cfg)["adapter"]
    ostate = jax.eval_shape(opt.init, ad)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((n_pods,) + tuple(x.shape), x.dtype),
        ostate)
