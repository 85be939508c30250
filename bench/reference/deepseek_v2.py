"""Plain float32 DeepSeek-V2 decoder with tri-LoRA adapters: the forward.

Follows the published equations (arXiv:2405.04434 §2; the model's
``modeling_deepseek.py``): pre-norm RMSNorm blocks; multi-head latent
attention computed explicitly — q = x Wq = [q_nope | q_pe] per head,
[c | k_pe] = x Wkv_a, c normalised, [k_nope | v]_h = c Wkv_b,h, the one
k_pe shared by all heads, scores (q_nope.k_nope + q_pe.k_pe) times
1/sqrt(nope + rope) and YaRN's mscale(factor, mscale_all_dim)^2, causal
softmax, concat_h(p v) Wo; rotary embeddings with YaRN frequencies (the
published ramp between ``beta_fast`` and ``beta_slow`` rotations over the
original context); leading dense SwiGLU layers, then expert layers: a
float32 softmax router over all routed experts, greedy top-k, the weights
renormalised only with ``norm_topk_prob``, scaled by
``routed_scaling_factor``, the experts this chip holds (the first
``n_routed_experts``) each computed over every token and weighted by its
gate (zero where the token did not choose it), plus the shared experts as
one SwiGLU; final norm and the unembedding through ``lm_head`` (through
the embedding table where ``tie_word_embeddings``).  An adapted projection is
y = x W + s ((x A) C) B with s = alpha / r.

Departures from the published model, which the program shares: the
rotary part uses the rotate-half pairing, where the published code first
de-interleaves q_pe and k_pe (a fixed permutation of the rope columns of
Wq and Wkv_a, so the same family of models); only this chip's share of
the routed experts contributes, as the configuration's ``deployment`` states.

Weights may arrive in bfloat16; each is taken to float32 as it is used and
every product runs at ``highest`` precision.  ``quant`` swaps in the
float8 matmul of ``decoder.fp8_matmul`` for the frozen weights, the
control.  The configuration is the flat dict of
``deepseek_v2_weights.freeze``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.decoder import F32, NEG, _fp8, f32_matmul, fp8_matmul
from bench.reference.deepseek_v2_weights import routed_experts

HI = "highest"


def _rms(c, x, p):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + c["rms_norm_eps"])
            * (1.0 + p["scale"].astype(F32)))


def _dense(c, mm, x, w, ad=None):
    y = mm(x, w)
    if ad is not None:
        s = c["lora_alpha"] / c["lora_rank"]
        y = y + s * f32_matmul(f32_matmul(f32_matmul(x, ad["A"]), ad["C"]),
                               ad["B"])
    return y


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def inv_freq(c):
    """YaRN's inverse frequencies of the rotary part (rope_head/2,)."""
    dim, base = c["qk_rope_head_dim"], c["rope_theta"]
    factor, orig = c["rope_factor"], c["rope_original_max_position_embeddings"]

    def turns(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(turns(c["rope_beta_fast"])), 0)
    high = min(math.ceil(turns(c["rope_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(dim // 2, dtype=F32)
    extra = 1.0 / base ** (2.0 * i / dim)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def softmax_scale(c):
    scale = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
    return scale * _mscale(c["rope_factor"], c["rope_mscale_all_dim"]) ** 2


def _rope(c, x, pos):
    """Rotate-half rotary embedding of x (B, S, H, rope) at ``pos`` (B, S);
    cos and sin carry mscale(factor, mscale) / mscale(factor, all_dim)."""
    half = x.shape[-1] // 2
    ang = pos.astype(F32)[..., None] * inv_freq(c)
    m = (_mscale(c["rope_factor"], c["rope_mscale"])
         / _mscale(c["rope_factor"], c["rope_mscale_all_dim"]))
    cos, sin = m * jnp.cos(ang)[..., None, :], m * jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla(c, mm, x, pos, a, la):
    b, s, _ = x.shape
    h, r = c["num_attention_heads"], c["kv_lora_rank"]
    nope, vd = c["qk_nope_head_dim"], c["v_head_dim"]
    q = _dense(c, mm, x, a["wq"], la.get("wq")).reshape(b, s, h, -1)
    kv = _dense(c, mm, x, a["wkv_a"], la.get("wkv_a"))
    lat = _rms(c, kv[..., :r], a["kv_norm"])
    kvb = mm(lat, a["wkv_b"]).reshape(b, s, h, nope + vd)
    q_pe = _rope(c, q[..., nope:], pos)
    k_pe = _rope(c, kv[..., None, r:], pos)                 # (B, S, 1, rope)
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], kvb[..., :nope],
                         precision=HI)
              + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe[:, :, 0],
                           precision=HI)) * softmax_scale(c)
    mask = pos[:, None, None, :] <= pos[:, None, :, None]
    p = jax.nn.softmax(jnp.where(mask, scores, NEG), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, kvb[..., nope:], precision=HI)
    return _dense(c, mm, o.reshape(b, s, h * vd), a["wo"], la.get("wo"))


def _swiglu(mm, x, m):
    return mm(jax.nn.silu(mm(x, m["w_gate"])) * mm(x, m["w_up"]),
              m["w_down"])


def _experts(mm, spec, x, w):
    """One einsum over the held experts' weights, at ``highest``; in the
    control both operands pass through float8 as ``mm``'s do."""
    x, w = x.astype(F32), w.astype(F32)
    if mm is fp8_matmul:
        x, w = _fp8(x), _fp8(w)
    return jnp.einsum(spec, x, w, precision=HI)


def _moe(c, mm, x, m):
    routed, held = routed_experts(c)
    probs = jax.nn.softmax(mm(x, m["router"]), axis=-1)     # (B, S, E)
    _, top = jax.lax.top_k(probs, c["num_experts_per_tok"])
    gates = probs * jnp.sum(jax.nn.one_hot(top, routed, dtype=F32), axis=-2)
    if c["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    gates = gates[..., :held] * c["routed_scaling_factor"]
    g = _experts(mm, "bsd,edf->bsef", x, m["w_gate"])
    u = _experts(mm, "bsd,edf->bsef", x, m["w_up"])
    z = jax.nn.silu(g) * u * gates[..., None]
    y = _experts(mm, "bsef,efd->bsd", z, m["w_down"])
    return y + _swiglu(mm, x, m["shared"])


def _layer(c, mm, x, pos, lp, la):
    la = (la or {}).get("attn", {})
    x = x + _mla(c, mm, _rms(c, x, lp["ln1"]), pos, lp["attn"], la)
    y = _rms(c, x, lp["ln2"])
    if "moe" in lp:
        return x + _moe(c, mm, y, lp["moe"])
    return x + _swiglu(mm, y, lp["mlp"])


def hidden(c, base, adapter, tokens, quant=False):
    """Final-norm hidden states (B, S, D) in float32."""
    mm = fp8_matmul if quant else f32_matmul
    pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    x = jnp.take(base["embed"], tokens, axis=0).astype(F32)
    lead_ad = (None,) * len(base["lead"]) if adapter is None \
        else adapter["lead"]
    for lp, la in zip(base["lead"], lead_ad):
        x = _layer(c, mm, x, pos, lp, la)
    ads = None if adapter is None else adapter["groups"]["0"]

    def step(x, lw):
        lp, la = lw
        return _layer(c, mm, x, pos, lp, la), None

    x, _ = jax.lax.scan(step, x, (base["groups"]["0"], ads))
    return _rms(c, x, base["final_norm"])


def logits(c, base, adapter, tokens, quant=False):
    """Float32 logits (B, S, V) through the output head."""
    mm = fp8_matmul if quant else f32_matmul
    head = base["embed" if c["tie_word_embeddings"] else "lm_head"]
    return mm(hidden(c, base, adapter, tokens, quant), head.T)
