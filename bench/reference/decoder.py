"""Plain float32 dense GQA decoder with tri-LoRA adapters: the forward.

Follows the published layer equations of the benchmark's configurations:
pre-norm blocks (LayerNorm with bias, or RMSNorm with a (1 + scale) gain),
rotary embeddings on q and k (rotate-half, theta ** (-i / (hd/2))), causal
grouped-query attention (query head h reads key/value head h // (H/K)),
an optional sliding window, a tanh-GELU or SwiGLU MLP, a final norm and a
tied unembedding.  An adapted projection is y = x W + b + s ((x A) C) B with
s = alpha / r.

Weights may arrive in bfloat16; every layer is taken to float32 as it is
used and all products run at ``highest`` precision, so nothing here rounds
below float32.  ``quant`` swaps in a lower-precision matmul for the
control (see ``fp8_matmul``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG = -1e30
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def f32_matmul(x, w):
    return jnp.matmul(x.astype(F32), w.astype(F32), precision="highest")


def _fp8(x):
    """Round to float8 e4m3 under one scale for the whole tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(FP8).astype(F32) * s


def fp8_matmul(x, w):
    """The control's matmul: both operands rounded to float8 e4m3 with a
    per-tensor scale, products summed in float32."""
    return f32_matmul(_fp8(x.astype(F32)), _fp8(w.astype(F32)))


def _norm(c, x, p):
    eps = c["norm_eps"]
    if c["norm_type"] == "rmsnorm":
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * (1.0 + p["scale"].astype(F32))
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * p["scale"].astype(F32)
            + p["bias"].astype(F32))


def _dense(c, mm, x, w, b=None, ad=None):
    y = mm(x, w)
    if b is not None:
        y = y + b.astype(F32)
    if ad is not None:
        s = c["lora_alpha"] / c["lora_rank"]
        p = f32_matmul(f32_matmul(x, ad["A"]), ad["C"])
        y = y + s * f32_matmul(p, ad["B"])
    return y


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[..., None] * inv              # (B, S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(c, q, k, v, pos):
    """q (B,S,H,hd), k/v (B,S,K,hd), causal over positions ``pos`` (B,S)."""
    h, kh, hd = q.shape[2], k.shape[2], q.shape[3]
    k = jnp.repeat(k, h // kh, axis=2)
    v = jnp.repeat(v, h // kh, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / math.sqrt(hd)
    qp, kp = pos[:, None, :, None], pos[:, None, None, :]
    mask = kp <= qp
    if c.get("sliding_window"):
        mask &= kp > qp - c["sliding_window"]
    p = jax.nn.softmax(jnp.where(mask, s, NEG), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


def _block(c, mm, x, pos, lp, la):
    b, s, _ = x.shape
    h, kh, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    a, la = lp["attn"], (la or {}).get("attn", {})
    y = _norm(c, x, lp["ln1"])
    q = _dense(c, mm, y, a["wq"], a.get("bq"), la.get("wq")).reshape(b, s, h, hd)
    k = _dense(c, mm, y, a["wk"], a.get("bk"), la.get("wk")).reshape(b, s, kh, hd)
    v = _dense(c, mm, y, a["wv"], a.get("bv"), la.get("wv")).reshape(b, s, kh, hd)
    q, k = _rope(q, pos, c["rope_theta"]), _rope(k, pos, c["rope_theta"])
    o = _attention(c, q, k, v, pos).reshape(b, s, h * hd)
    x = x + _dense(c, mm, o, a["wo"], None, la.get("wo"))
    y = _norm(c, x, lp["ln2"])
    m = lp["mlp"]
    if c["mlp"] == "swiglu":
        g = mm(y, m["w_gate"])
        u = mm(y, m["w_up"])
        return x + mm(jax.nn.silu(g) * u, m["w_down"])
    z = mm(y, m["w_in"])
    z = 0.5 * z * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                  * (z + 0.044715 * z ** 3)))
    return x + mm(z, m["w_out"])


def hidden(c, base, adapter, tokens, quant=False):
    """Final-norm hidden states (B, S, D) in float32."""
    mm = fp8_matmul if quant else f32_matmul
    pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    x = jnp.take(base["embed"], tokens, axis=0).astype(F32)
    layers = base["groups"]["0"]
    ads = None if adapter is None else adapter["groups"]["0"]

    def step(x, lw):
        lp, la = lw
        return _block(c, mm, x, pos, lp, la), None

    x, _ = jax.lax.scan(step, x, (layers, ads))
    return _norm(c, x, base["final_norm"])


def logits(c, base, adapter, tokens, quant=False):
    """Float32 logits (B, S, V) through the tied table."""
    mm = fp8_matmul if quant else f32_matmul
    return mm(hidden(c, base, adapter, tokens, quant), base["embed"].T)
