"""Shared neural-net layers: norms, positional encodings, MLPs, adapted dense.

Conventions
-----------
- params are plain nested dicts of jnp arrays (no framework).
- matmuls run in the param dtype (bf16 on TPU) with f32 accumulation
  (XLA default on MXU); norms / softmax / rope angles in f32.
- every projection goes through :func:`dense`, which applies the tri-LoRA
  low-rank path when an adapter is attached.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import tri_lora


# ---------------------------------------------------------------------------
# dense projection with optional tri-LoRA adapter
# ---------------------------------------------------------------------------

def dense(x: jnp.ndarray, w: jnp.ndarray, *, bias: Optional[jnp.ndarray] = None,
          adapter=None, lora_scaling: float = 1.0,
          adapter_rows: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """``adapter_rows`` switches the adapter to grouped/bank mode
    (DESIGN.md §15): ``adapter`` then holds STACKED (m, …) factors and each
    batch row ``i`` applies bank row ``adapter_rows[i]`` (-1 = no delta)."""
    y = x @ w
    if bias is not None:
        y = y + bias
    if adapter is not None:
        if adapter_rows is not None:
            with jax.named_scope("tri_lora"):
                delta = tri_lora.apply_tri_lora_grouped(
                    x, adapter, lora_scaling, adapter_rows)
        else:
            delta = tri_lora.apply_tri_lora(x, adapter, lora_scaling)
        y = y + delta.astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# activation sharding hints (no-ops outside a mesh context)
# ---------------------------------------------------------------------------

def _ambient_mesh():
    """The mesh visible at trace time (what ``jax.set_mesh`` installs), or
    None when no mesh with named axes is set."""
    am = jax.sharding.get_abstract_mesh()
    return am if am.axis_names else None


def shard_hint(x: jnp.ndarray, *spec) -> jnp.ndarray:
    """with_sharding_constraint that degrades to identity when no mesh (or a
    mesh lacking the named axes) is ambient — model code stays runnable on a
    single CPU device."""
    m = _ambient_mesh()
    if m is None:
        return x
    names = set(m.axis_names)
    for s in spec:
        axes = s if isinstance(s, tuple) else ((s,) if s else ())
        if any(a not in names for a in axes):
            return x
    try:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.PartitionSpec(*spec))
    except (ValueError, TypeError):
        return x  # spec incompatible with the mesh/shape — hint is advisory


_BATCH_AXES = ("pod", "data")


import contextlib


@contextlib.contextmanager
def hint_batch_axes(axes: tuple):
    """Trace-time override of which mesh axes the batch hints use — the
    federated pod-round step vmaps over `pod`, so inner hints must only
    claim `data` (the vmapped dim carries `pod` via spmd_axis_name)."""
    global _BATCH_AXES
    prev = _BATCH_AXES
    _BATCH_AXES = tuple(axes)
    try:
        yield
    finally:
        _BATCH_AXES = prev


def batch_hint(x: jnp.ndarray, *, seq_parallel: bool = False) -> jnp.ndarray:
    """Anchor dim 0 to the batch mesh axes (pod, data) when divisible.
    With ``seq_parallel`` also shard dim 1 (sequence) over `model` — used at
    block boundaries so remat-saved activations are stored fully sharded
    (sequence parallelism); GSPMD re-gathers where attention needs full seq.
    """
    m = _ambient_mesh()
    if m is None:
        return x
    axes = tuple(a for a in _BATCH_AXES if a in m.axis_names)
    if not axes:
        return x
    total = 1
    for a in axes:
        total *= m.shape[a]
    if x.shape[0] % total != 0:
        return x
    spec = [axes] + [None] * (x.ndim - 1)
    if (seq_parallel and x.ndim >= 3 and "model" in m.axis_names
            and x.shape[1] % m.shape["model"] == 0 and x.shape[1] > 1):
        spec[1] = "model"
    try:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.PartitionSpec(*spec))
    except (ValueError, TypeError):
        return x  # spec incompatible with the mesh/shape — hint is advisory


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(x.dtype)


def layernorm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
              eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def norm(x: jnp.ndarray, params: dict, norm_type: str) -> jnp.ndarray:
    if norm_type == "rmsnorm":
        return rmsnorm(x, params["scale"])
    return layernorm(x, params["scale"], params["bias"])


def init_norm(d: int, norm_type: str, dtype) -> dict:
    if norm_type == "rmsnorm":
        return {"scale": jnp.zeros((d,), dtype)}  # (1 + scale) convention
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def group_rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, n_groups: int,
                  eps: float = 64e-5) -> jnp.ndarray:
    """Per-head GroupNorm used by RWKV's time-mix output (`ln_x`)."""
    *lead, d = x.shape
    xf = x.astype(jnp.float32).reshape(*lead, n_groups, d // n_groups)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    xf = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (xf.reshape(*lead, d) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings (RoPE and Qwen2-VL's M-RoPE)
# ---------------------------------------------------------------------------

def _rope_angles(positions: jnp.ndarray, head_dim: int, theta: float) -> jnp.ndarray:
    """positions (..., S) -> angles (..., S, head_dim//2), f32."""
    half = head_dim // 2
    inv_freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    return positions.astype(jnp.float32)[..., None] * inv_freq


def _mrope_angles(positions: jnp.ndarray, head_dim: int, theta: float,
                  sections) -> jnp.ndarray:
    """M-RoPE: positions (..., S, 3) = (t, h, w) ids; ``sections`` splits the
    head_dim//2 frequency slots among the three components (arXiv:2409.12191).
    """
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    inv_freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    comp = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                      total_repeat_length=half)               # (half,) in {0,1,2}
    pos = jnp.take_along_axis(
        positions.astype(jnp.float32),
        jnp.broadcast_to(comp, positions.shape[:-1] + (half,)).astype(jnp.int32),
        axis=-1)                                              # (..., S, half)
    return pos * inv_freq


def yarn_get_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor 0.1 m ln(s) + 1 (1 for s <= 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(dim: int, theta: float, original_max: int,
                          beta_fast: float, beta_slow: float) -> tuple:
    """The first and last frequency index of YaRN's ramp: where the
    frequencies turn ``beta_fast`` and ``beta_slow`` times over the original
    context."""
    def at(rot):
        return (dim * math.log(original_max / (rot * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(at(beta_fast)), 0),
            min(math.ceil(at(beta_slow)), dim - 1))


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> jnp.ndarray:
    """YaRN's (dim/2,) inverse frequencies: ``theta ** (-2i/dim)`` below the
    ramp, the same divided by ``factor`` above it, linear in between
    (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``)."""
    half = dim // 2
    extra = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    low, high = yarn_correction_range(dim, theta, original_max, beta_fast,
                                      beta_slow)
    high = high + 0.001 if low == high else high
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               *, sections=None, inv_freq=None) -> jnp.ndarray:
    """x: (B, S, H, hd); positions: (B, S) or (B, S, 3) for M-RoPE.
    ``inv_freq`` (hd/2,) replaces the plain frequencies (YaRN)."""
    hd = x.shape[-1]
    if sections is not None:
        ang = _mrope_angles(positions, hd, theta, sections)   # (B,S,half)
    elif inv_freq is not None:
        ang = positions.astype(jnp.float32)[..., None] * inv_freq
    else:
        ang = _rope_angles(positions, hd, theta)              # (B,S,half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, mlp_type: str, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = 1.0 / jnp.sqrt(d_model)
    s_out = 1.0 / jnp.sqrt(d_ff)
    if mlp_type == "swiglu":
        return {
            "w_gate": (jax.random.normal(k1, (d_model, d_ff)) * s_in).astype(dtype),
            "w_up": (jax.random.normal(k2, (d_model, d_ff)) * s_in).astype(dtype),
            "w_down": (jax.random.normal(k3, (d_ff, d_model)) * s_out).astype(dtype),
        }
    return {
        "w_in": (jax.random.normal(k1, (d_model, d_ff)) * s_in).astype(dtype),
        "w_out": (jax.random.normal(k2, (d_ff, d_model)) * s_out).astype(dtype),
    }


def mlp(x: jnp.ndarray, params: dict, mlp_type: str, *, adapters=None,
        lora_scaling: float = 1.0,
        adapter_rows: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    ad = adapters or {}
    kw = dict(lora_scaling=lora_scaling, adapter_rows=adapter_rows)
    if mlp_type == "swiglu":
        g = dense(x, params["w_gate"], adapter=ad.get("w_gate"), **kw)
        u = dense(x, params["w_up"], adapter=ad.get("w_up"), **kw)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        return dense(h, params["w_down"], adapter=ad.get("w_down"), **kw)
    h = dense(x, params["w_in"], adapter=ad.get("w_in"), **kw)
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return dense(h, params["w_out"], adapter=ad.get("w_out"), **kw)


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d_model: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d_model)) * 0.02).astype(dtype)


def embed(tokens: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(table, tokens, axis=0)


def unembed(x: jnp.ndarray, table: jnp.ndarray,
            true_vocab: int = 0) -> jnp.ndarray:
    """LM head through a (V, D) table; logits in f32.  If the table is
    padded beyond ``true_vocab``, pad logits are masked to -inf
    (softmax-exact)."""
    x = batch_hint(x)
    # keep operands in param dtype; accumulate f32 on the MXU — avoids
    # materializing (and GSPMD gathering) an f32 copy of the vocab table
    logits = jnp.einsum("...d,vd->...v", x, table.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    if true_vocab and table.shape[0] > true_vocab:
        vmask = jnp.arange(table.shape[0]) < true_vocab
        logits = jnp.where(vmask, logits, -1e30)
    # batch → (pod,data), vocab → model (NOT batch_hint: None dims in a
    # with_sharding_constraint mean REPLICATED — hinting (batch, …, None)
    # would force the vocab dim replicated and blow memory up)
    m = _ambient_mesh()
    if (m is not None and "model" in m.axis_names
            and logits.shape[-1] % m.shape["model"] == 0):
        axes = tuple(a for a in _BATCH_AXES if a in m.axis_names)
        total = 1
        for a in axes:
            total *= m.shape[a]
        b_ax = axes if axes and logits.shape[0] % total == 0 else None
        spec = (b_ax,) + (None,) * (logits.ndim - 2) + ("model",)
        try:
            logits = jax.lax.with_sharding_constraint(
                logits, jax.sharding.PartitionSpec(*spec))
        except (ValueError, TypeError):
            pass  # spec incompatible with the mesh/shape — hint is advisory
    return logits
