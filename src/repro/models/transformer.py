"""Block assembly: init / train-forward / decode for every block kind.

Layer stacking follows the config's ``layer_pattern``: the stack is
``q = n_layers // len(pattern)`` scanned repetitions of the pattern (params
stacked on a leading group axis, ``lax.scan`` + optional remat) plus an
unrolled remainder ("tail").  This keeps HLO size O(pattern) instead of
O(n_layers) — essential for compiling 64–80-layer models against a
512-device mesh.

Caches/recurrent state mirror the same (groups, tail) structure so decode
scans params and cache together.  A config with ``n_dense_layers`` adds an
unrolled leading section ("lead", a tuple of blocks with a dense MLP) to
params, adapters and caches; other configs' trees have no such key.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import tri_lora
from repro.models import attention, layers, moe, rglru, rwkv
from repro.models.config import ModelConfig

#: The cache entries of each attention kind that are decode rings: the layer
#: scan carries them stacked and writes them in place.
RING_KEYS = {"attn": ("k", "v"), "swa": ("k", "v"), "mla": ("latent",)}
ATTN_KINDS = tuple(RING_KEYS)
RING_NAMES = frozenset(n for names in RING_KEYS.values() for n in names)

# ---------------------------------------------------------------------------
# per-block init
# ---------------------------------------------------------------------------

def _adapter_shapes(cfg: ModelConfig, kind: str, cross: bool) -> dict:
    d, hd, h, k = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv_heads
    f, rd = cfg.d_ff, cfg.rnn_d
    if kind in ATTN_KINDS:
        if kind == "mla":     # wkv_b stays frozen: decode absorbs it
            qd = cfg.qk_nope_dim + cfg.qk_rope_dim
            shapes = {"wq": (d, h * qd),
                      "wkv_a": (d, cfg.kv_lora_rank + cfg.qk_rope_dim),
                      "wo": (h * cfg.v_head_dim, d)}
        else:
            shapes = {"wq": (d, h * hd), "wk": (d, k * hd),
                      "wv": (d, k * hd), "wo": (h * hd, d)}
        out = {"attn": {t: shapes[t] for t in cfg.lora_targets if t in shapes}}
        if cross:
            xs = {"wq": (d, h * hd), "wk": (d, h * hd),
                  "wv": (d, h * hd), "wo": (h * hd, d)}
            out["xattn"] = {t: xs[t] for t in cfg.lora_targets if t in xs}
        if cfg.lora_mlp and not cfg.is_moe:
            if cfg.mlp_type == "swiglu":
                out["mlp"] = {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
            else:
                out["mlp"] = {"w_in": (d, f), "w_out": (f, d)}
        return out
    if kind == "rwkv6":
        # the paper's attention attachment point does not exist; adapt the
        # time-mix r/k/v/o projections instead (DESIGN.md §4)
        return {"tm": {t: (d, d) for t in ("wr", "wk", "wv", "wo")}}
    if kind == "rglru":
        return {"rec": {"w_in": (d, 2 * rd), "w_out": (rd, d)}}
    raise ValueError(kind)


def init_block_adapters(key, cfg: ModelConfig, kind: str, *,
                        cross: bool = False) -> dict:
    spec = _adapter_shapes(cfg, kind, cross)
    flat = [(m, t, s) for m, ts in spec.items() for t, s in ts.items()]
    ks = jax.random.split(key, max(len(flat), 1))
    out: dict = {m: {} for m in spec}
    for kk, (m, t, (din, dout)) in zip(ks, flat):
        out[m][t] = tri_lora.init_adapter(kk, din, dout, cfg.lora_rank,
                                          jnp.float32)
    return out


def init_block(key, cfg: ModelConfig, kind: str, *, cross: bool = False,
               causal: bool = True, dense: bool = False) -> dict:
    """``dense``: a leading layer, with a dense MLP even in an MoE model."""
    del causal
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    nt = cfg.norm_type
    if kind in ATTN_KINDS:
        init = attention.init_mla if kind == "mla" else attention.init_attn
        p = {"ln1": layers.init_norm(d, nt, cfg.dtype),
             "attn": init(ks[0], cfg),
             "ln2": layers.init_norm(d, nt, cfg.dtype)}
        if cross:
            p["ln_x"] = layers.init_norm(d, nt, cfg.dtype)
            p["xattn"] = attention.init_attn(ks[1], cfg, cross=True)
        if cfg.is_moe and not dense:
            p["moe"] = moe.init_moe(ks[2], cfg)
        else:
            p["mlp"] = layers.init_mlp(ks[2], d, cfg.d_ff, cfg.mlp_type, cfg.dtype)
        return p
    if kind == "rwkv6":
        return {"ln1": layers.init_norm(d, nt, cfg.dtype),
                "tm": rwkv.init_time_mix(ks[0], cfg),
                "ln2": layers.init_norm(d, nt, cfg.dtype),
                "cm": rwkv.init_channel_mix(ks[1], cfg)}
    if kind == "rglru":
        return {"ln1": layers.init_norm(d, nt, cfg.dtype),
                "rec": rglru.init_rglru_block(ks[0], cfg),
                "ln2": layers.init_norm(d, nt, cfg.dtype),
                "mlp": layers.init_mlp(ks[1], d, cfg.d_ff, cfg.mlp_type,
                                       cfg.dtype)}
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# per-block apply (train)
# ---------------------------------------------------------------------------

def _ffn(cfg: ModelConfig, p: dict, ad: dict, h: jnp.ndarray,
         adapter_rows=None):
    """The block's MLP: its experts where it has them, else dense."""
    if "moe" in p:
        return moe.moe_mlp(cfg, p["moe"], h)
    return layers.mlp(h, p["mlp"], cfg.mlp_type, adapters=ad.get("mlp"),
                      lora_scaling=cfg.lora_alpha / cfg.lora_rank,
                      adapter_rows=adapter_rows), jnp.zeros((), jnp.float32)


def block_apply(cfg: ModelConfig, kind: str, p: dict, ad: Optional[dict],
                x: jnp.ndarray, positions, *, enc_out=None, causal=True,
                attn_impl=None, use_rwkv_kernel=False):
    ad = ad or {}
    nt = cfg.norm_type
    aux = jnp.zeros((), jnp.float32)
    if kind == "mla":
        with jax.named_scope("mla"):   # the MLA attention sub-block
            h = layers.norm(x, p["ln1"], nt)
            x = x + attention.mla_attention(cfg, p["attn"], h, positions,
                                            ad.get("attn"), impl=attn_impl)
        y, aux = _ffn(cfg, p, ad, layers.norm(x, p["ln2"], nt))
        return x + y, aux
    if kind in ("attn", "swa"):
        window = cfg.window if kind == "swa" else 0
        h = layers.norm(x, p["ln1"], nt)
        if causal:
            y = attention.self_attention(cfg, p["attn"], h, positions,
                                         ad.get("attn"), window=window,
                                         impl=attn_impl)
        else:  # encoder: bidirectional
            q, k, v = attention._project_qkv(cfg, p["attn"], h, ad.get("attn"))
            o = attention.sdpa(q, k, v, causal=False)
            b, s = h.shape[:2]
            y = layers.dense(o.reshape(b, s, -1), p["attn"]["wo"],
                             adapter=(ad.get("attn") or {}).get("wo"),
                             lora_scaling=cfg.lora_alpha / cfg.lora_rank)
        x = x + y
        if "xattn" in p:
            h = layers.norm(x, p["ln_x"], nt)
            x = x + attention.cross_attention(cfg, p["xattn"], h, enc_out,
                                              ad.get("xattn"))
        y, aux = _ffn(cfg, p, ad, layers.norm(x, p["ln2"], nt))
        return x + y, aux
    if kind == "rwkv6":
        h = layers.norm(x, p["ln1"], nt)
        y, _ = rwkv.time_mix(cfg, p["tm"], h, None, ad.get("tm"),
                             use_kernel=use_rwkv_kernel)
        x = x + y
        h = layers.norm(x, p["ln2"], nt)
        y, _ = rwkv.channel_mix(cfg, p["cm"], h, None)
        return x + y, aux
    if kind == "rglru":
        h = layers.norm(x, p["ln1"], nt)
        y, _ = rglru.rglru_block(cfg, p["rec"], h, None, ad.get("rec"))
        x = x + y
        h = layers.norm(x, p["ln2"], nt)
        x = x + layers.mlp(h, p["mlp"], cfg.mlp_type)
        return x, aux
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# per-block decode (one token or a prompt chunk, carries cache/state)
# ---------------------------------------------------------------------------

def init_block_cache(cfg: ModelConfig, kind: str, batch: int, seq_len: int,
                     *, cross: bool = False) -> dict:
    if kind == "mla":
        return attention.init_latent_cache(cfg, batch, seq_len)
    if kind in ("attn", "swa"):
        window = cfg.window if kind == "swa" else 0
        c = attention.init_kv_cache(cfg, batch, seq_len, window=window)
        if cross:
            c["xk"] = jnp.zeros((batch, cfg.enc_frames, cfg.n_heads, cfg.hd),
                                cfg.dtype)
            c["xv"] = jnp.zeros((batch, cfg.enc_frames, cfg.n_heads, cfg.hd),
                                cfg.dtype)
        return c
    if kind == "rwkv6":
        return rwkv.init_state(cfg, batch)
    if kind == "rglru":
        return rglru.init_state(cfg, batch)
    raise ValueError(kind)


def _ffn_decode(cfg: ModelConfig, p: dict, ad: dict, h: jnp.ndarray,
                adapter_rows=None):
    """:func:`_ffn` for x (B, S, D) against a cache: an expert layer routes
    each of a chunk's tokens as its own dispatch group, as it routes a
    decode step's, so no expert's capacity drops a token."""
    b, s, d = h.shape
    if "moe" in p and s > 1:
        y, aux = _ffn(cfg, p, ad, h.reshape(b * s, 1, d))
        return y.reshape(b, s, d), aux
    return _ffn(cfg, p, ad, h, adapter_rows)


def block_decode(cfg: ModelConfig, kind: str, p: dict, ad: Optional[dict],
                 cache: dict, x: jnp.ndarray, positions,
                 adapter_rows: Optional[jnp.ndarray] = None,
                 layer: Optional[jnp.ndarray] = None,
                 ring_row: Optional[jnp.ndarray] = None):
    """``layer``: the scan's group index when ``cache`` holds the stacked
    rings of an attention block (see :func:`run_stack_decode`).  x (B, S,
    D) with S > 1 is a prompt chunk written to ring rows ``ring_row +
    [0, B)`` (attention blocks only; :func:`attention.decode_self_attention`)."""
    ad = ad or {}
    nt = cfg.norm_type
    if kind == "mla":
        with jax.named_scope("mla"):   # the MLA attention sub-block
            h = layers.norm(x, p["ln1"], nt)
            y, new_cache = attention.decode_mla(
                cfg, p["attn"], h, cache, positions, ad.get("attn"),
                adapter_rows=adapter_rows, layer=layer, ring_row=ring_row)
            x = x + y
        y, _ = _ffn_decode(cfg, p, ad, layers.norm(x, p["ln2"], nt),
                           adapter_rows)
        return x + y, new_cache
    if kind in ("attn", "swa"):
        window = cfg.window if kind == "swa" else 0
        h = layers.norm(x, p["ln1"], nt)
        y, kv = attention.decode_self_attention(
            cfg, p["attn"], h, {k: cache[k] for k in ("k", "v", "idx")},
            positions, ad.get("attn"), window=window,
            adapter_rows=adapter_rows, layer=layer, ring_row=ring_row)
        x = x + y
        new_cache = dict(kv)
        if "xattn" in p:
            h = layers.norm(x, p["ln_x"], nt)
            q = layers.dense(h, p["xattn"]["wq"]).reshape(
                x.shape[0], 1, cfg.n_heads, cfg.hd)
            o = attention.sdpa(q, cache["xk"], cache["xv"], causal=False)
            y = layers.dense(o.reshape(x.shape[0], 1, -1), p["xattn"]["wo"])
            x = x + y
            new_cache["xk"], new_cache["xv"] = cache["xk"], cache["xv"]
        y, _ = _ffn_decode(cfg, p, ad, layers.norm(x, p["ln2"], nt),
                           adapter_rows)
        return x + y, new_cache
    if adapter_rows is not None:
        raise NotImplementedError(
            f"grouped adapter banks (DESIGN.md §15) only support attention "
            f"blocks; got layer kind {kind!r}")
    if kind == "rwkv6":
        h = layers.norm(x, p["ln1"], nt)
        y, tm = rwkv.time_mix(cfg, p["tm"], h, cache["tm"], ad.get("tm"))
        x = x + y
        h = layers.norm(x, p["ln2"], nt)
        y, cm = rwkv.channel_mix(cfg, p["cm"], h, cache["cm"])
        return x + y, {"tm": tm, "cm": cm}
    if kind == "rglru":
        h = layers.norm(x, p["ln1"], nt)
        y, st = rglru.rglru_block(cfg, p["rec"], h, cache, ad.get("rec"))
        x = x + y
        h = layers.norm(x, p["ln2"], nt)
        x = x + layers.mlp(h, p["mlp"], cfg.mlp_type)
        return x, st
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# stack init: (groups scanned, tail unrolled)
# ---------------------------------------------------------------------------

def _stack(trees: list) -> Any:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_stack(key, cfg: ModelConfig, *, cross: bool = False) -> tuple:
    """Returns (groups_params, tail_params) following cfg.stack_plan()."""
    q, pattern, rem = cfg.stack_plan()
    n_per_group = len(pattern)
    keys = jax.random.split(key, q * n_per_group + len(rem))
    groups = []
    for gi in range(q):
        g = {str(i): init_block(keys[gi * n_per_group + i], cfg, kind,
                                cross=cross)
             for i, kind in enumerate(pattern)}
        groups.append(g)
    tail = tuple(init_block(keys[q * n_per_group + i], cfg, kind, cross=cross)
                 for i, kind in enumerate(rem))
    return (_stack(groups) if q else None), tail


def init_lead(key, cfg: ModelConfig) -> tuple:
    """The leading dense layers' params, one block each."""
    keys = jax.random.split(key, max(cfg.n_dense_layers, 1))
    return tuple(init_block(k, cfg, kind, dense=True)
                 for k, kind in zip(keys, cfg.lead_kinds))


def init_lead_adapters(key, cfg: ModelConfig) -> tuple:
    keys = jax.random.split(key, max(cfg.n_dense_layers, 1))
    return tuple(init_block_adapters(k, cfg, kind)
                 for k, kind in zip(keys, cfg.lead_kinds))


def init_lead_cache(cfg: ModelConfig, batch: int, seq_len: int) -> tuple:
    return tuple(init_block_cache(cfg, kind, batch, seq_len)
                 for kind in cfg.lead_kinds)


def init_stack_adapters(key, cfg: ModelConfig, *, cross: bool = False) -> tuple:
    q, pattern, rem = cfg.stack_plan()
    n = len(pattern)
    keys = jax.random.split(key, q * n + len(rem))
    groups = [{str(i): init_block_adapters(keys[g * n + i], cfg, kind,
                                           cross=cross)
               for i, kind in enumerate(pattern)} for g in range(q)]
    tail = tuple(init_block_adapters(keys[q * n + i], cfg, kind, cross=cross)
                 for i, kind in enumerate(rem))
    return (_stack(groups) if q else None), tail


def init_stack_cache(cfg: ModelConfig, batch: int, seq_len: int, *,
                     cross: bool = False) -> tuple:
    q, pattern, rem = cfg.stack_plan()
    groups = [{str(i): init_block_cache(cfg, kind, batch, seq_len, cross=cross)
               for i, kind in enumerate(pattern)} for _ in range(q)]
    tail = tuple(init_block_cache(cfg, kind, batch, seq_len, cross=cross)
                 for kind in rem)
    return (_stack(groups) if q else None), tail


# ---------------------------------------------------------------------------
# stack apply
# ---------------------------------------------------------------------------

def run_lead(cfg: ModelConfig, lead_p, lead_ad, x: jnp.ndarray, positions,
             *, attn_impl=None):
    """The leading dense layers, unrolled.  Returns (x, aux_sum)."""
    aux = jnp.zeros((), jnp.float32)
    for kind, p, ad in zip(cfg.lead_kinds, lead_p,
                           lead_ad or (None,) * len(lead_p)):
        x, a = block_apply(cfg, kind, p, ad, x, positions,
                           attn_impl=attn_impl)
        aux = aux + a
    return x, aux


def run_lead_decode(cfg: ModelConfig, lead_p, lead_ad, lead_cache,
                    x: jnp.ndarray, positions, adapter_rows=None,
                    ring_row=None):
    """A token (or a chunk) through the leading layers; returns (x, new
    caches)."""
    new = []
    for kind, p, ad, c in zip(cfg.lead_kinds, lead_p,
                              lead_ad or (None,) * len(lead_p), lead_cache):
        x, c = block_decode(cfg, kind, p, ad, c, x, positions,
                            adapter_rows=adapter_rows, ring_row=ring_row)
        new.append(c)
    return x, tuple(new)


def run_stack(cfg: ModelConfig, groups_p, tail_p, groups_ad, tail_ad,
              x: jnp.ndarray, positions, *, enc_out=None, causal=True,
              attn_impl=None, use_rwkv_kernel=False):
    """Train-time forward through the whole stack.  Returns (x, aux_sum).
    ``attn_impl=None`` defers the backend choice to ``cfg.attn_impl``
    (attention.select_impl)."""
    pattern = cfg.layer_pattern
    apply_kw = dict(enc_out=enc_out, causal=causal, attn_impl=attn_impl,
                    use_rwkv_kernel=use_rwkv_kernel)

    def group_fn(carry, scanned):
        h, aux = carry
        gp, gad = scanned
        for i, kind in enumerate(pattern):
            # sequence-parallel anchor: remat-saved carries stay fully sharded
            h = layers.batch_hint(h, seq_parallel=True)
            h, a = block_apply(cfg, kind, gp[str(i)], gad[str(i)], h,
                               positions, **apply_kw)
            aux = aux + a
        return (layers.batch_hint(h, seq_parallel=True), aux), None

    fn = jax.checkpoint(group_fn) if cfg.remat else group_fn
    aux = jnp.zeros((), jnp.float32)
    if groups_p is not None:
        (x, aux), _ = jax.lax.scan(fn, (x, aux), (groups_p, groups_ad))
    q, _, rem = cfg.stack_plan()
    for i, kind in enumerate(rem):
        x, a = block_apply(cfg, kind, tail_p[i], tail_ad[i], x, positions,
                           **apply_kw)
        aux = aux + a
    return x, aux


def run_stack_decode(cfg: ModelConfig, groups_p, tail_p, groups_ad, tail_ad,
                     groups_cache, tail_cache, x: jnp.ndarray, positions,
                     adapter_rows=None, ring_row=None):
    """One-token decode through the stack; returns (x, new caches).  x (B,
    S, D) with S > 1 is a prompt chunk, written to ring rows ``ring_row +
    [0, B)`` (:func:`attention.decode_self_attention`).

    The scan carries every attention block's stacked rings — (q, B, K, W,
    hd) K/V rings, or (q, B, W, lanes) latent rings of MLA blocks — with
    the group index, so each layer scatters its new token into the stacked
    ring in place and attention reads its layer straight out of it; ``idx``
    and every other state (recurrent states, cross caches) are scanned per
    group as ``xs``/``ys``.  Tail blocks keep per-block rings.

    With ``adapter_rows`` (B,) the adapter trees carry a stacked bank axis
    — groups leaves (q, m, …), tail leaves (m, …), see
    ``adapter_bank.AdapterBank.decode_tree`` — and each batch row applies
    its own bank row (DESIGN.md §15)."""
    pattern = cfg.layer_pattern
    carried = {str(i): RING_KEYS[kind] for i, kind in enumerate(pattern)
               if kind in RING_KEYS}

    def group_fn(carry, scanned):
        h, layer, rings = carry
        gp, gad, gc = scanned
        rings, new_c = dict(rings), {}
        for i, kind in enumerate(pattern):
            key = str(i)
            if key in rings:
                h, c = block_decode(cfg, kind, gp[key], gad[key],
                                    {**gc[key], **rings[key]}, h, positions,
                                    adapter_rows=adapter_rows, layer=layer,
                                    ring_row=ring_row)
                rings[key] = {n: c.pop(n) for n in carried[key]}
            else:
                h, c = block_decode(cfg, kind, gp[key], gad[key], gc[key], h,
                                    positions, adapter_rows=adapter_rows,
                                    ring_row=ring_row)
            new_c[key] = c
        return (h, layer + 1, rings), new_c

    new_groups_cache = None
    if groups_p is not None:
        rings = {i: {n: groups_cache[i][n] for n in names}
                 for i, names in carried.items()}
        rest = {i: ({n: a for n, a in c.items() if n not in carried[i]}
                    if i in carried else c) for i, c in groups_cache.items()}
        (x, _, rings), new_rest = jax.lax.scan(
            group_fn, (x, jnp.zeros((), jnp.int32), rings),
            (groups_p, groups_ad, rest))
        new_groups_cache = {i: {**c, **rings.get(i, {})}
                            for i, c in new_rest.items()}
    q, _, rem = cfg.stack_plan()
    new_tail = []
    for i, kind in enumerate(rem):
        x, c = block_decode(cfg, kind, tail_p[i], tail_ad[i], tail_cache[i],
                            x, positions, adapter_rows=adapter_rows,
                            ring_row=ring_row)
        new_tail.append(c)
    return x, new_groups_cache, tuple(new_tail)
