"""Analytic parameter / FLOP / byte model per (architecture × input shape).

XLA's ``cost_analysis`` counts while-loop bodies ONCE (scan-over-layers,
blockwise attention, chunked CE all undercount), so the roofline's compute
and memory terms are derived analytically from the config; the HLO numbers
are kept as cross-checks and the collective term is parsed from the HLO with
loop-trip-count correction (see roofline.py).
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.launch.steps import SHAPES, shape_variant  # noqa: E402
from repro.models.config import ModelConfig, get_config  # noqa: E402

BF16 = 2


# ---------------------------------------------------------------------------
# parameter counts
# ---------------------------------------------------------------------------

def _attn_params(cfg: ModelConfig, cross: bool = False) -> int:
    d, hd = cfg.d_model, cfg.hd
    h = cfg.n_heads
    k = h if cross else cfg.n_kv_heads
    n = d * h * hd + 2 * d * k * hd + h * hd * d
    if cfg.attn_bias and not cross:
        n += h * hd + 2 * k * hd
    return n


def _mlp_params(cfg: ModelConfig) -> int:
    if cfg.mlp_type == "swiglu":
        return 3 * cfg.d_model * cfg.d_ff
    return 2 * cfg.d_model * cfg.d_ff


def _moe_params(cfg: ModelConfig) -> tuple[int, int]:
    """(total, active) expert-MLP params per MoE layer incl. router."""
    per_exp = _mlp_params(cfg)
    router = cfg.d_model * cfg.n_experts
    return (cfg.n_experts * per_exp + router,
            max(cfg.top_k, 1) * per_exp + router)


def _rwkv_params(cfg: ModelConfig) -> int:
    d, f = cfg.d_model, cfg.d_ff
    tm = 5 * d * d + d * (5 * 32) + 5 * 32 * d + d * 64 + 64 * d + 4 * d
    cm = d * f + f * d + d * d
    return tm + cm


def _rglru_params(cfg: ModelConfig) -> int:
    d, rd = cfg.d_model, cfg.rnn_d
    rec = d * 2 * rd + cfg.conv1d_width * rd + 2 * rd * rd + rd * d + 3 * rd
    return rec + _mlp_params(cfg)


def layer_params(cfg: ModelConfig, kind: str) -> tuple[int, int]:
    """(total, active) params for one layer of `kind`."""
    if kind in ("attn", "swa"):
        attn = _attn_params(cfg)
        if cfg.enc_dec:
            attn += _attn_params(cfg, cross=True)
        if cfg.is_moe:
            tot, act = _moe_params(cfg)
            return attn + tot, attn + act
        m = _mlp_params(cfg)
        return attn + m, attn + m
    if kind == "rwkv6":
        n = _rwkv_params(cfg)
        return n, n
    if kind == "rglru":
        n = _rglru_params(cfg)
        return n, n
    raise ValueError(kind)


@dataclasses.dataclass
class ParamCount:
    total: int
    active: int
    embed: int

    @property
    def non_embed(self):
        return self.total - self.embed


def count_params(cfg: ModelConfig) -> ParamCount:
    tot = act = 0
    for kind in cfg.kinds():
        t, a = layer_params(cfg, kind)
        tot += t
        act += a
    if cfg.enc_dec:
        ecfg = cfg.with_overrides(n_layers=cfg.n_enc_layers,
                                  layer_pattern=("attn",),
                                  n_kv_heads=cfg.n_heads, enc_dec=False)
        for _ in range(cfg.n_enc_layers):
            t, a = layer_params(ecfg, "attn")
            tot += t
            act += a
    embed = cfg.padded_vocab * cfg.d_model
    if not cfg.tie_embeddings:                   # a separate output head
        embed += cfg.padded_vocab * cfg.d_model
    if cfg.pos_type == "learned":
        embed += cfg.max_target_positions * cfg.d_model
    tot += embed
    act += embed  # embeddings always touched
    return ParamCount(tot, act, embed)


# ---------------------------------------------------------------------------
# FLOPs / bytes per step
# ---------------------------------------------------------------------------

def _attn_flops_per_layer(cfg: ModelConfig, kind: str, seq: int,
                          causal_train: bool) -> float:
    """Attention-matrix FLOPs (QK^T + PV) per sequence, one layer."""
    if kind == "rwkv6":
        # state update + readout: ~4·T·H·hd² MACs
        return 4 * 2 * seq * cfg.n_heads * cfg.hd * cfg.hd
    if kind == "rglru":
        gates = 2 * seq * cfg.rnn_d * cfg.rnn_d * 2
        scan = 8 * seq * cfg.rnn_d
        return gates + scan
    window = cfg.window if kind == "swa" else 0
    h, hd = cfg.n_heads, cfg.hd
    if window and window < seq:
        eff = window  # each query attends ≤ window keys
        return 2 * 2 * seq * eff * h * hd
    # causal: S²/2 scores (the blockwise XLA path computes full blocks of
    # the band; we count the ideal S²/2 and note the gap in the roofline)
    return 2 * 2 * seq * seq * h * hd * (0.5 if causal_train else 1.0)


def step_flops(cfg: ModelConfig, shape_name: str) -> dict:
    """Analytic FLOPs for one global step of the given input shape."""
    sh = SHAPES[shape_name]
    pc = count_params(cfg)
    b, s = sh.global_batch, sh.seq_len
    if cfg.vision_patches and sh.kind != "decode":
        s = s + cfg.vision_patches
    matmul_flops_tok = 2 * (pc.active - pc.embed)   # fwd per token
    lm_head = 2 * cfg.padded_vocab * cfg.d_model    # unembed per token
    attn = sum(_attn_flops_per_layer(cfg, k, s, True) for k in cfg.kinds())

    if sh.kind == "train":
        tokens = b * s
        # fwd + bwd(2×) + remat fwd recompute (+1) = 4× forward matmuls
        # (LoRA-only grads do not change matmul count: dX needs both passes)
        mm = 4 * matmul_flops_tok * tokens + 2 * lm_head * tokens * 3 / 4
        at = 4 * attn * b
        return {"matmul": mm, "attention": at, "total": mm + at,
                "model_flops_6nd": 6 * (pc.active - pc.embed) * tokens}
    if sh.kind == "prefill":
        tokens = b * s
        mm = matmul_flops_tok * tokens + lm_head * b  # last-pos logits only
        at = attn * b
        return {"matmul": mm, "attention": at, "total": mm + at,
                "model_flops_6nd": 2 * (pc.active - pc.embed) * tokens}
    # decode: 1 token/seq; attention reads the whole (ring) cache
    per_tok = matmul_flops_tok + lm_head
    at = 0.0
    for kind in cfg.kinds():
        if kind in ("attn", "swa"):
            window = cfg.window if kind == "swa" else 0
            eff = min(window, s) if window else s
            at += 2 * 2 * eff * cfg.n_heads * cfg.hd
        elif kind == "rwkv6":
            at += 4 * 2 * cfg.n_heads * cfg.hd * cfg.hd
        elif kind == "rglru":
            at += 2 * cfg.rnn_d * cfg.rnn_d * 2 + 8 * cfg.rnn_d
    return {"matmul": per_tok * b, "attention": at * b,
            "total": (per_tok + at) * b,
            "model_flops_6nd": 2 * (pc.active - pc.embed) * b}


def step_bytes(cfg: ModelConfig, shape_name: str) -> dict:
    """Analytic HBM traffic per global step (bf16 params/cache)."""
    sh = SHAPES[shape_name]
    pc = count_params(cfg)
    b, s = sh.global_batch, sh.seq_len
    param_bytes = pc.total * BF16
    if sh.kind == "train":
        # params read fwd + bwd + remat ≈ 3×; adapter grads+opt negligible
        act = 3 * b * s * cfg.d_model * BF16 * cfg.n_layers  # carries etc.
        return {"params": 3 * param_bytes, "activations": act,
                "cache": 0, "total": 3 * param_bytes + act}
    if sh.kind == "prefill":
        act = b * s * cfg.d_model * BF16 * cfg.n_layers
        return {"params": param_bytes, "activations": act, "cache": 0,
                "total": param_bytes + act}
    cache = 0
    for kind in cfg.kinds():
        if kind in ("attn", "swa"):
            window = cfg.window if kind == "swa" else 0
            ring = min(window, s) if window else s
            cache += 2 * b * ring * cfg.n_kv_heads * cfg.hd * BF16
        elif kind == "rwkv6":
            cache += b * cfg.n_heads * cfg.hd * cfg.hd * 4 + b * cfg.d_model * BF16 * 2
        elif kind == "rglru":
            cache += b * cfg.rnn_d * 4 + b * cfg.conv1d_width * cfg.rnn_d * BF16
    if cfg.enc_dec:
        cache += 2 * b * cfg.enc_frames * cfg.n_heads * cfg.hd * BF16 * cfg.n_layers
    return {"params": param_bytes, "activations": 0, "cache": cache,
            "total": param_bytes + cache}


def describe(arch: str, shape_name: str) -> dict:
    cfg = shape_variant(get_config(arch), shape_name)
    pc = count_params(cfg)
    return {"arch": arch, "shape": shape_name, "variant": cfg.name,
            "params_total": pc.total, "params_active": pc.active,
            "flops": step_flops(cfg, shape_name),
            "bytes": step_bytes(cfg, shape_name)}


if __name__ == "__main__":
    import json
    for a in ("qwen2.5-14b", "grok-1-314b", "rwkv6-1.6b"):
        for s in SHAPES:
            d = describe(a, s)
            print(a, s, f"N={d['params_total']/1e9:.1f}B",
                  f"flops={d['flops']['total']:.2e}")
