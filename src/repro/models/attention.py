"""Attention: GQA, optional qk-norm / bias / sliding window, train + decode.

Backends are first-class: every entry point resolves its implementation
through :func:`select_impl` (explicit ``impl=`` kwarg > ``cfg.attn_impl`` >
"auto") — the pure-jnp reference, the XLA blockwise variants, or the Pallas
flash kernel (trainable via its custom VJP); all are numerically validated
against each other in the kernel tests.  The decode path attends one new
token against a (possibly ring-buffered) KV cache.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.models.config import ModelConfig

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_attn(key, cfg: ModelConfig, *, cross: bool = False) -> dict:
    d, hd = cfg.d_model, cfg.hd
    h, k = cfg.n_heads, (cfg.n_heads if cross else cfg.n_kv_heads)
    keys = jax.random.split(key, 4)
    s = 1.0 / jnp.sqrt(d)
    p = {
        "wq": (jax.random.normal(keys[0], (d, h * hd)) * s).astype(cfg.dtype),
        "wk": (jax.random.normal(keys[1], (d, k * hd)) * s).astype(cfg.dtype),
        "wv": (jax.random.normal(keys[2], (d, k * hd)) * s).astype(cfg.dtype),
        "wo": (jax.random.normal(keys[3], (h * hd, d))
               * (1.0 / jnp.sqrt(h * hd))).astype(cfg.dtype),
    }
    if cfg.attn_bias and not cross:
        p["bq"] = jnp.zeros((h * hd,), cfg.dtype)
        p["bk"] = jnp.zeros((k * hd,), cfg.dtype)
        p["bv"] = jnp.zeros((k * hd,), cfg.dtype)
    if cfg.qk_norm and not cross:
        p["q_norm"] = {"scale": jnp.zeros((hd,), cfg.dtype)}
        p["k_norm"] = {"scale": jnp.zeros((hd,), cfg.dtype)}
    return p


def _project_qkv(cfg: ModelConfig, p: dict, x: jnp.ndarray, adapters,
                 *, kv_from: Optional[jnp.ndarray] = None, cross: bool = False,
                 adapter_rows: Optional[jnp.ndarray] = None):
    """Return q (B,S,H,hd), k,v (B,Skv,K,hd) — rope NOT yet applied."""
    ad = adapters or {}
    sc = cfg.lora_alpha / cfg.lora_rank
    b, s, _ = x.shape
    kv_x = x if kv_from is None else kv_from
    skv = kv_x.shape[1]
    h = cfg.n_heads
    k_heads = h if cross else cfg.n_kv_heads
    q = layers.dense(x, p["wq"], bias=p.get("bq"), adapter=ad.get("wq"),
                     lora_scaling=sc,
                     adapter_rows=adapter_rows).reshape(b, s, h, cfg.hd)
    k = layers.dense(kv_x, p["wk"], bias=p.get("bk"), adapter=ad.get("wk"),
                     lora_scaling=sc,
                     adapter_rows=adapter_rows).reshape(b, skv, k_heads,
                                                        cfg.hd)
    v = layers.dense(kv_x, p["wv"], bias=p.get("bv"), adapter=ad.get("wv"),
                     lora_scaling=sc,
                     adapter_rows=adapter_rows).reshape(b, skv, k_heads,
                                                        cfg.hd)
    if cfg.qk_norm and not cross:
        q = layers.rmsnorm(q, p["q_norm"]["scale"])
        k = layers.rmsnorm(k, p["k_norm"]["scale"])
    return q, k, v


def _rope(cfg: ModelConfig, x: jnp.ndarray, positions) -> jnp.ndarray:
    if cfg.pos_type == "rope":
        return layers.apply_rope(x, positions, cfg.rope_theta)
    if cfg.pos_type == "mrope":
        return layers.apply_rope(x, positions, cfg.rope_theta,
                                 sections=cfg.mrope_sections)
    return x  # learned / none: positions handled at the embedding


# ---------------------------------------------------------------------------
# reference SDPA (grouped-query, causal, optional window)
# ---------------------------------------------------------------------------

def sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
         causal: bool, window: int = 0,
         kv_valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """q (B,Sq,H,hd), k/v (B,Skv,K,hd); H % K == 0.  f32 softmax."""
    b, sq, h, hd = q.shape
    skv, kh = k.shape[1], k.shape[2]
    g = h // kh
    qg = q.reshape(b, sq, kh, g, hd)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(hd).astype(jnp.float32)
    mask = jnp.ones((sq, skv), bool)
    if causal:
        # rows are the LAST sq queries of the skv-long sequence
        qpos = jnp.arange(sq) + (skv - sq)
        kpos = jnp.arange(skv)
        mask &= kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
    if kv_valid is not None:  # (B, Skv) extra validity (ring caches, padding)
        mask = mask[None] & kv_valid[:, None, :]
        mask = mask[:, None, None]            # (B,1,1,Sq,Skv)
    else:
        mask = mask[None, None, None]         # (1,1,1,Sq,Skv)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, hd).astype(q.dtype)


def _head_parallel(q, k, v):
    """When q-heads divide the `model` axis, expand GQA KV to full heads and
    pin the head dim to `model` — attention intermediates (and their grads)
    then shard 16-way across heads instead of living replicated.  The KV
    duplication is an XLA-path cost only; the Pallas kernel uses BlockSpec
    head-indexing instead (no materialized repeat)."""
    m = layers._ambient_mesh()
    if m is None or "model" not in m.axis_names:
        return q, k, v
    msz = m.shape["model"]
    h, kh = q.shape[2], k.shape[2]
    if h % msz != 0:
        return q, k, v
    if kh != h:
        rep = h // kh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    def hint(x):
        try:
            axes = tuple(a for a in layers._BATCH_AXES if a in m.axis_names)
            total = 1
            for a in axes:
                total *= m.shape[a]
            b_ax = axes if x.shape[0] % total == 0 else None
            return jax.lax.with_sharding_constraint(
                x, jax.sharding.PartitionSpec(b_ax, None, "model", None))
        except (ValueError, TypeError):
            return x  # spec incompatible with the mesh — hint is advisory
    return hint(q), hint(k), hint(v)


# ---------------------------------------------------------------------------
# blockwise SDPA ("XLA-flash"): online-softmax over KV chunks via lax.scan.
# Used for long sequences where materializing (Sq, Skv) logits is impossible.
# For sliding-window attention the KV span per q-chunk is a STATIC-size
# dynamic slice, so compiled FLOPs are truly sub-quadratic (O(S·window)).
# ---------------------------------------------------------------------------

def blockwise_sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                   causal: bool = True, window: int = 0,
                   bq: int = 256, bk: int = 256) -> jnp.ndarray:
    """Memory: O(bq·bk) logits tiles; every tile op is rematerialized in
    backward (checkpointed q-chunks and kv-steps), so train-time residuals
    stay O(bq·hd) per step — the XLA analogue of flash attention's backward.
    For windowed attention the per-q-chunk KV span is a static-size dynamic
    slice ⇒ compiled FLOPs are O(S·window), not O(S²)."""
    b, sq, h, hd = q.shape
    skv, kh = k.shape[1], k.shape[2]
    g = h // kh
    bq = min(bq, sq)
    bk = min(bk, skv)
    scale = 1.0 / (float(hd) ** 0.5)
    pad_q = (-sq) % bq                       # e.g. VLM fused 4096+256 patches
    qg = jnp.moveaxis(q, 1, 2).reshape(b, kh, g, sq, hd)       # (B,K,G,Sq,hd)
    if pad_q:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, pad_q), (0, 0)))
    sq_p = sq + pad_q
    kt = jnp.moveaxis(k, 1, 2)                                 # (B,K,Skv,hd)
    vt = jnp.moveaxis(v, 1, 2)

    if window:
        # static-size KV span per q chunk; front-padded by `span` and
        # end-padded by pad_q so slices never clip (mask drops pad keys)
        span = (-(-(window + bq) // bk)) * bk
        span = min(span, ((skv + bk - 1) // bk) * bk)
        kt = jnp.pad(kt, ((0, 0), (0, 0), (span, pad_q), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (span, pad_q), (0, 0)))
        n_kv = span // bk
    else:
        pad_kv = (-skv) % bk
        if pad_kv:
            kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
            vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
        n_kv = (skv + pad_kv) // bk

    def _bhint(a):
        from repro.models import layers as _l
        return _l.batch_hint(a)

    def _kv_hint(a):
        # pin full-size KV (and its f32 grad carries) seq-sharded over
        # `model`; per-block dynamic slices gather only one tile
        m = layers._ambient_mesh()
        if (m is None or "model" not in m.axis_names
                or a.shape[2] % m.shape["model"] != 0):
            return _bhint(a)
        axes = tuple(x for x in layers._BATCH_AXES if x in m.axis_names)
        total = 1
        for x in axes:
            total *= m.shape[x]
        b_ax = axes if a.shape[0] % total == 0 else None
        try:
            return jax.lax.with_sharding_constraint(
                a, jax.sharding.PartitionSpec(b_ax, None, "model", None))
        except (ValueError, TypeError):
            return a  # spec incompatible with the mesh — hint is advisory
    kt = _kv_hint(kt)
    vt = _kv_hint(vt)

    def q_chunk(qi):
        q_first = qi * bq
        qc = jax.lax.dynamic_slice_in_dim(qg, q_first, bq, axis=3)
        qc = _bhint(qc.astype(jnp.float32) * scale)
        qpos = q_first + jnp.arange(bq) + (skv - sq)

        if window:
            # padded-coords slice start: ends exactly at the chunk's last row
            start = q_first + (skv - sq) + bq
            kvk = jax.lax.dynamic_slice_in_dim(kt, start, span, axis=2)
            kvv = jax.lax.dynamic_slice_in_dim(vt, start, span, axis=2)
            pos0 = start - span                     # absolute pos of slice[0]
        else:
            kvk, kvv, pos0 = kt, vt, 0
        kvk, kvv = _bhint(kvk), _bhint(kvv)

        def kv_step(carry, ki):
            m_run, l_run, acc = carry
            k_first = ki * bk
            kc = _bhint(jax.lax.dynamic_slice_in_dim(kvk, k_first, bk, axis=2))
            vc = _bhint(jax.lax.dynamic_slice_in_dim(kvv, k_first, bk, axis=2))
            s = jnp.einsum("bkgqd,bksd->bkgqs", qc, kc.astype(jnp.float32),
                           preferred_element_type=jnp.float32)
            s = _bhint(s)
            kpos = pos0 + k_first + jnp.arange(bk)
            mask = (kpos[None, :] >= 0) & (kpos[None, :] < skv)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window:
                mask &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_run - m_new)
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(mask[None, None, None], p, 0.0)
            l_new = alpha * l_run + jnp.sum(p, axis=-1)
            acc = _bhint(acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bksd->bkgqd", p, vc.astype(jnp.float32),
                preferred_element_type=jnp.float32))
            return (m_new, l_new, acc), None

        m0 = jnp.full((b, kh, g, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kh, g, bq), jnp.float32)
        a0 = jnp.zeros((b, kh, g, bq, hd), jnp.float32)
        (m_f, l_f, acc), _ = jax.lax.scan(jax.checkpoint(kv_step),
                                          (m0, l0, a0), jnp.arange(n_kv))
        return acc / jnp.maximum(l_f, 1e-30)[..., None]

    chunks = jax.lax.map(jax.checkpoint(q_chunk),
                         jnp.arange(sq_p // bq))               # (nq,B,K,G,bq,hd)
    out = jnp.moveaxis(chunks, 0, 3).reshape(b, kh, g, sq_p, hd)[:, :, :, :sq]
    return jnp.moveaxis(out.reshape(b, h, sq, hd), 1, 2).astype(q.dtype)


# ---------------------------------------------------------------------------
# backend registry: every entry point resolves its implementation here
# ---------------------------------------------------------------------------

#: Valid values for ``ModelConfig.attn_impl`` / per-call ``impl=`` overrides.
IMPLS = ("auto", "ref", "blockwise", "blockwise_hp", "blockwise_cv", "flash")

#: "auto" self-attention: materialized-logits reference up to this length,
#: blockwise (online-softmax) beyond it.
AUTO_REF_MAX_SEQ = 2048

#: cross-attention tiles its (Sq, Skv) logits once the product exceeds this
#: (4M f32 entries = 16 MiB of materialized logits per head pair).
CROSS_TILE_THRESHOLD = 4_194_304


def select_impl(cfg: Optional[ModelConfig], seq_len: int, *,
                impl: Optional[str] = None,
                kv_len: Optional[int] = None) -> str:
    """Resolve the attention backend for one call site.

    Precedence: explicit ``impl`` kwarg > ``cfg.attn_impl`` > "auto".  The
    returned name is concrete (never "auto").  ``kv_len`` marks the
    non-causal cross-attention path (tile above CROSS_TILE_THRESHOLD).
    Decode attends through :func:`ring_sdpa` and asks no backend.
    """
    chosen = impl if impl is not None else (
        cfg.attn_impl if cfg is not None else "auto")
    if chosen not in IMPLS:
        raise ValueError(
            f"unknown attn_impl {chosen!r}; valid: {', '.join(IMPLS)}")
    if kv_len is not None:      # cross-attention: non-causal, Sq != Skv
        if chosen in ("ref", "blockwise"):
            return chosen
        return ("blockwise" if seq_len * kv_len > CROSS_TILE_THRESHOLD
                else "ref")
    if chosen == "auto":
        return "ref" if seq_len <= AUTO_REF_MAX_SEQ else "blockwise"
    if chosen in ("blockwise_hp", "blockwise_cv") \
            and seq_len <= AUTO_REF_MAX_SEQ:
        return "ref"            # tiling overhead not worth it at short seq
    return chosen


# ---------------------------------------------------------------------------
# block-level entry points
# ---------------------------------------------------------------------------

def self_attention(cfg: ModelConfig, p: dict, x: jnp.ndarray, positions,
                   adapters=None, *, window: int = 0,
                   impl: Optional[str] = None) -> jnp.ndarray:
    """impl: 'ref' (materialized logits), 'blockwise' (XLA-flash, long-seq
    safe), 'flash' (Pallas kernel, trainable custom-VJP), or 'auto' (ref
    below AUTO_REF_MAX_SEQ, else blockwise).  None defers to
    ``cfg.attn_impl`` — resolution happens in :func:`select_impl`.
    """
    q, k, v = _project_qkv(cfg, p, x, adapters)
    q = _rope(cfg, q, positions)
    k = _rope(cfg, k, positions)
    out = _causal_attend(cfg, q, k, v, window=window, impl=impl)
    b, s = x.shape[:2]
    sc = cfg.lora_alpha / cfg.lora_rank
    ad = adapters or {}
    return layers.dense(out.reshape(b, s, -1), p["wo"], adapter=ad.get("wo"),
                        lora_scaling=sc)


def _causal_attend(cfg: ModelConfig, q, k, v, *, window: int = 0,
                   impl: Optional[str] = None) -> jnp.ndarray:
    """Causal attention of q (B,S,H,hd) over k/v (B,S,K,hd) through the
    backend :func:`select_impl` resolves."""
    impl = select_impl(cfg, q.shape[1], impl=impl)
    if impl == "flash":
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(q, k, v, causal=True, window=window)
    elif impl == "blockwise_cv":   # opt-in custom-VJP flash backward (M10)
        from repro.models.attention_cv import blockwise_sdpa_cv
        if q.shape[1] % 256 == 0:
            out = blockwise_sdpa_cv(q, k, v, True, window, 256, 256)
        else:
            out = blockwise_sdpa(q, k, v, causal=True, window=window)
    elif impl == "blockwise_hp":   # opt-in head-parallel variant (§Perf)
        q, k, v = _head_parallel(q, k, v)
        out = blockwise_sdpa(q, k, v, causal=True, window=window)
    elif impl == "blockwise":
        out = blockwise_sdpa(q, k, v, causal=True, window=window)
    else:
        out = sdpa(q, k, v, causal=True, window=window)
    return out


def cross_attention(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                    enc_out: jnp.ndarray, adapters=None,
                    *, impl: Optional[str] = None) -> jnp.ndarray:
    q, k, v = _project_qkv(cfg, p, x, adapters, kv_from=enc_out, cross=True)
    impl = select_impl(cfg, q.shape[1], impl=impl, kv_len=k.shape[1])
    if impl == "blockwise":                     # long decoder seq: tile it
        out = blockwise_sdpa(q, k, v, causal=False)
    else:
        out = sdpa(q, k, v, causal=False)
    b, s = x.shape[:2]
    sc = cfg.lora_alpha / cfg.lora_rank
    ad = adapters or {}
    return layers.dense(out.reshape(b, s, -1), p["wo"], adapter=ad.get("wo"),
                        lora_scaling=sc)


# ---------------------------------------------------------------------------
# decode (one token, or a prompt chunk, against a ring-buffered KV cache)
# ---------------------------------------------------------------------------

#: The K/V ring stores each head's vector padded with zeros to a multiple
#: of this lane width.  At such a width the TPU's default layout of the ring
#: is row-major, the layout its in-place scatter and the attention dots
#: share, so no decode step re-lays the ring.  (The padding costs no memory
#: there: a row-major tile pads the head dim to 128 lanes anyway.)
RING_LANES = 128


def ring_head_dim(hd: int) -> int:
    return -(-hd // RING_LANES) * RING_LANES


def _lane_pad(x: jnp.ndarray, width: int) -> jnp.ndarray:
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def ring_sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              valid: jnp.ndarray) -> jnp.ndarray:
    """Queries against head-major rings: q (B,S,H,hd), k/v
    (B,K,W,ring_head_dim(hd)), valid (B,W) for one query token, (B,S,W)
    for S.  :func:`sdpa`'s arithmetic: f32 logits (the zero lanes add
    exact zeros) and softmax, the output cast to q's dtype."""
    b, s, h, hd = q.shape
    kh = k.shape[1]
    qa, lead = ("q", (b, s)) if s > 1 else ("", (b,))  # one: no query axis
    qg = _lane_pad(q.reshape(lead + (kh, h // kh, hd)), k.shape[-1])
    logits = jnp.einsum(f"b{qa}kgd,bksd->b{qa}kgs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(hd).astype(jnp.float32)
    logits = jnp.where(valid[..., None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(f"b{qa}kgs,bksd->b{qa}kgd", probs,
                     v.astype(jnp.float32))
    return out[..., :hd].reshape(b, s, h, hd).astype(q.dtype)


def _ring_position(idx, ring: int, b: int):
    """Where a decode step writes and what it may read.  ``idx`` is the new
    token's absolute position: a scalar, or (B,) ragged per-row positions
    where -1 marks a masked batch slot.  Returns the ring slot, the batch
    row to write (``b``, out of bounds, for a masked slot, so its write is
    dropped), the next ``idx`` and the (B, ring) validity mask: slots
    [0, idx] until the ring wraps, then all of them."""
    if jnp.ndim(idx) == 0:
        valid = (jnp.arange(ring)[None, :] <= idx) | (idx >= ring)
        return (jnp.mod(idx, ring), jnp.arange(b), idx + 1,
                jnp.broadcast_to(valid, (b, ring)))
    active = idx >= 0
    valid = (jnp.arange(ring)[None, :] <= idx[:, None]) | \
        (idx[:, None] >= ring)
    return (jnp.where(active, jnp.mod(idx, ring), 0),
            jnp.where(active, jnp.arange(b), b),
            jnp.where(active, idx + 1, idx), valid)


def _chunk_position(positions, ring: int, b_ring: int, ring_row):
    """Where a chunk of S tokens per row writes and what it may read.
    ``positions`` (B, S) are the tokens' absolute positions, -1 for
    padding; rows of the chunk are ring rows ``ring_row + [0, B)``.
    Returns the ring row of each token (``b_ring``, out of bounds, for
    padding, so its write is dropped), its ring slot, and the (B, S, ring)
    causal mask: slots [0, position].  A chunk never wraps the ring."""
    b = positions.shape[0]
    rows = ring_row + jnp.arange(b, dtype=jnp.int32)[:, None]
    real = positions >= 0
    return (jnp.where(real, rows, b_ring), jnp.where(real, positions, 0),
            jnp.arange(ring)[None, None, :] <= positions[..., None])


def _ring_rows(ring: jnp.ndarray, layer, ring_row, b: int) -> jnp.ndarray:
    """The ring rows a call reads: layer ``layer`` of a stacked ring (or
    the ring itself), all rows for a decode step, rows ``ring_row + [0,
    b)`` for a chunk."""
    ring = ring if layer is None else ring[layer]
    if ring_row is None:
        return ring
    return jax.lax.dynamic_slice_in_dim(ring, ring_row, b, axis=0)


def decode_self_attention(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                          cache: dict, positions, adapters=None,
                          *, window: int = 0,
                          adapter_rows: Optional[jnp.ndarray] = None,
                          layer: Optional[jnp.ndarray] = None,
                          ring_row: Optional[jnp.ndarray] = None):
    """x: (B, 1, D).  cache: {'k','v': (B, K, W, ring_head_dim(hd)) head-
    major rings, 'idx': int32 scalar — or (B,) for RAGGED per-row positions
    (DESIGN.md §15): each sequence advances independently, and rows at idx
    -1 are masked batch slots that write nothing and attend to nothing}.

    x: (B, S, D) with S > 1 is a prompt chunk (DESIGN.md §18): token i of
    row b goes to position ``positions[b, i]`` (-1 for padding, which
    writes nothing) of ring row ``ring_row + b`` (an int32 scalar),
    attends to that row's slots [0, its position] and must not wrap the
    ring; ``idx`` is passed through unchanged.

    ``W`` is the ring size (== window for SWA blocks, == max_len otherwise).
    Keys are stored post-rope; with rotary embeddings relative offsets are
    preserved, so ring overwrite is safe for windowed attention.

    With ``layer`` (an int32 scalar) 'k'/'v' are a layer scan's stacked
    (L, B, K, W, ·) rings: the new token is scattered into layer ``layer``
    in place, attention reads that layer out of the stacked buffer, and the
    returned cache holds the whole stacked rings.

    ``adapter_rows`` switches the q/k/v/o adapters to grouped/bank mode —
    ``adapters`` then carries stacked (m, …) factors per target.
    """
    q, k_new, v_new = _project_qkv(cfg, p, x, adapters,
                                   adapter_rows=adapter_rows)
    q = _rope(cfg, q, positions)
    k_new = _rope(cfg, k_new, positions)

    b, s = x.shape[:2]
    tok = (lambda t: t[:, 0]) if s == 1 else (lambda t: t)
    kh, ring, lanes = cache["k"].shape[-3:]
    at = () if layer is None else (layer,)
    with jax.named_scope("kv_ring"):      # ring write + validity mask
        if s == 1:
            slot, wb, new_idx, valid = _ring_position(cache["idx"], ring, b)
            # one (K, hd) row per batch row, at [layer, row, :, slot]; with
            # the heads indexed too each write is one contiguous hd row, so
            # XLA keeps the ring row-major, the layout the attention dots
            # read
            where = at + (wb[:, None], jnp.arange(kh)[None, :],
                          jnp.reshape(slot, (-1, 1)))
        else:                             # (B, S, K) such rows
            wb, slot, valid = _chunk_position(positions, ring,
                                              cache["k"].shape[-4], ring_row)
            where = at + (wb[..., None], jnp.arange(kh), slot[..., None])
            new_idx = cache["idx"]
        k_all = cache["k"].at[where].set(
            _lane_pad(tok(k_new), lanes).astype(cache["k"].dtype),
            mode="drop")
        v_all = cache["v"].at[where].set(
            _lane_pad(tok(v_new), lanes).astype(cache["v"].dtype),
            mode="drop")
    with jax.named_scope("attention"):
        out = ring_sdpa(q, _ring_rows(k_all, layer, ring_row, b),
                        _ring_rows(v_all, layer, ring_row, b), valid)
    sc = cfg.lora_alpha / cfg.lora_rank
    ad = adapters or {}
    y = layers.dense(out.reshape(b, s, -1), p["wo"], adapter=ad.get("wo"),
                     lora_scaling=sc, adapter_rows=adapter_rows)
    return y, {"k": k_all, "v": v_all, "idx": new_idx}


def init_kv_cache(cfg: ModelConfig, batch: int, seq_len: int, *,
                  window: int = 0, dtype=None) -> dict:
    ring = min(window, seq_len) if window else seq_len
    kh, lanes = cfg.n_kv_heads, ring_head_dim(cfg.hd)
    dt = dtype or cfg.dtype
    return {
        "k": jnp.zeros((batch, kh, ring, lanes), dt),    # head-major
        "v": jnp.zeros((batch, kh, ring, lanes), dt),
        "idx": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1)
# ---------------------------------------------------------------------------
#
# q = x Wq = [q_nope | q_pe] per head;  [c | k_pe] = x Wkv_a;  c <- RMSNorm(c);
# [k_nope | v]_h = c Wkv_b,h;  q_pe and the one k_pe all heads share are
# rotated (rotate-half pairing, YaRN frequencies);  scores
# (q_nope.k_nope + q_pe.k_pe) * mla_softmax_scale;  y = concat_h(p v) Wo.
# Decode keeps only [c | k_pe] per token in its ring and absorbs Wkv_b into
# the query and the output (DESIGN.md §17).

def init_mla(key, cfg: ModelConfig) -> dict:
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    qd = cfg.qk_nope_dim + cfg.qk_rope_dim
    ks = jax.random.split(key, 4)

    def w(k, din, dout):
        return (jax.random.normal(k, (din, dout))
                * (1.0 / jnp.sqrt(din))).astype(cfg.dtype)
    return {"wq": w(ks[0], d, h * qd),
            "wkv_a": w(ks[1], d, r + cfg.qk_rope_dim),
            "kv_norm": {"scale": jnp.zeros((r,), cfg.dtype)},
            "wkv_b": w(ks[2], r, h * (cfg.qk_nope_dim + cfg.v_head_dim)),
            "wo": w(ks[3], h * cfg.v_head_dim, d)}


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(qk head dim), times YaRN's mscale(factor, mscale_all_dim)^2."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= layers.yarn_get_mscale(cfg.yarn_factor,
                                        cfg.yarn_mscale_all_dim) ** 2
    return scale


def _mla_rope(cfg: ModelConfig, x: jnp.ndarray, positions) -> jnp.ndarray:
    """Rotate x (B, S, H, qk_rope_dim); with YaRN the rotated vector is
    scaled by mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    if not cfg.yarn_factor:
        return layers.apply_rope(x, positions, cfg.rope_theta)
    inv = layers.yarn_inv_freq(cfg.qk_rope_dim, cfg.rope_theta,
                               cfg.yarn_factor, cfg.yarn_original_max,
                               cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    out = layers.apply_rope(x, positions, cfg.rope_theta, inv_freq=inv)
    gain = (layers.yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale)
            / layers.yarn_get_mscale(cfg.yarn_factor,
                                     cfg.yarn_mscale_all_dim))
    return out if gain == 1.0 else (out * gain).astype(out.dtype)


def _mla_project(cfg: ModelConfig, p: dict, x: jnp.ndarray, positions,
                 adapters, adapter_rows=None):
    """q_nope (B,S,H,nope), rotated q_pe (B,S,H,rope), the normalised
    latent c (B,S,r) and the rotated shared key k_pe (B,S,rope)."""
    ad = adapters or {}
    kw = dict(lora_scaling=cfg.lora_alpha / cfg.lora_rank,
              adapter_rows=adapter_rows)
    b, s, _ = x.shape
    nope, r = cfg.qk_nope_dim, cfg.kv_lora_rank
    q = layers.dense(x, p["wq"], adapter=ad.get("wq"), **kw).reshape(
        b, s, cfg.n_heads, nope + cfg.qk_rope_dim)
    kv = layers.dense(x, p["wkv_a"], adapter=ad.get("wkv_a"), **kw)
    c = layers.rmsnorm(kv[..., :r], p["kv_norm"]["scale"])
    q_pe = _mla_rope(cfg, q[..., nope:], positions)
    k_pe = _mla_rope(cfg, kv[:, :, None, r:], positions)[:, :, 0]
    return q[..., :nope], q_pe, c, k_pe


def mla_attention(cfg: ModelConfig, p: dict, x: jnp.ndarray, positions,
                  adapters=None, *, impl: Optional[str] = None) -> jnp.ndarray:
    """Full-sequence causal MLA with k and v computed explicitly.  v is
    zero-padded to the qk head dim and q pre-multiplied by the YaRN factor,
    so every backend of :func:`select_impl` applies unchanged."""
    b, s, _ = x.shape
    h, nope, vd = cfg.n_heads, cfg.qk_nope_dim, cfg.v_head_dim
    qd = nope + cfg.qk_rope_dim
    q_nope, q_pe, c, k_pe = _mla_project(cfg, p, x, positions, adapters)
    kv = (c @ p["wkv_b"]).reshape(b, s, h, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[:, :, None],
                                          (b, s, h, cfg.qk_rope_dim))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    q = (q.astype(jnp.float32) * (mla_softmax_scale(cfg) * qd ** 0.5)
         ).astype(q.dtype)
    out = _causal_attend(cfg, q, k, _lane_pad(kv[..., nope:], qd), impl=impl)
    ad = adapters or {}
    return layers.dense(out[..., :vd].reshape(b, s, h * vd), p["wo"],
                        adapter=ad.get("wo"),
                        lora_scaling=cfg.lora_alpha / cfg.lora_rank)


def latent_lanes(cfg: ModelConfig) -> int:
    return ring_head_dim(cfg.kv_lora_rank + cfg.qk_rope_dim)


def init_latent_cache(cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    """One ring row per token: [c | k_pe | zero lanes], 576 values in 640
    lanes at DeepSeek-V2's widths."""
    return {"latent": jnp.zeros((batch, seq_len, latent_lanes(cfg)),
                                cfg.dtype),
            "idx": jnp.zeros((), jnp.int32)}


def decode_mla(cfg: ModelConfig, p: dict, x: jnp.ndarray, cache: dict,
               positions, adapters=None, *,
               adapter_rows: Optional[jnp.ndarray] = None,
               layer: Optional[jnp.ndarray] = None,
               ring_row: Optional[jnp.ndarray] = None):
    """One token of MLA against the latent ring, Wkv_b absorbed:
    q_lat,h = W_UK,h q_nope,h scores against c and q_pe against k_pe, and
    o_h = W_UV,h^T (sum_s p c).  cache: {'latent': (B, W, lanes), or a
    layer scan's stacked (L, B, W, lanes) with ``layer``; 'idx'} — the
    ring's write, mask and ragged ``idx`` follow
    :func:`decode_self_attention`, and so does a chunk of S > 1 tokens a
    row (``ring_row``), by the same absorbed formula."""
    b, s = x.shape[:2]
    h, nope, vd, r = (cfg.n_heads, cfg.qk_nope_dim, cfg.v_head_dim,
                      cfg.kv_lora_rank)
    q_nope, q_pe, c, k_pe = _mla_project(cfg, p, x, positions, adapters,
                                         adapter_rows)
    qa = "q" if s > 1 else ""                  # one token: no query axis
    tok = (lambda t: t[:, 0]) if s == 1 else (lambda t: t)
    ring, lanes = cache["latent"].shape[-2:]
    at = () if layer is None else (layer,)
    with jax.named_scope("kv_ring"):
        if s == 1:
            slot, wb, new_idx, valid = _ring_position(cache["idx"], ring, b)
        else:
            wb, slot, valid = _chunk_position(
                positions, ring, cache["latent"].shape[-3], ring_row)
            new_idx = cache["idx"]
        row = _lane_pad(jnp.concatenate([tok(c), tok(k_pe)], -1), lanes)
        lat = cache["latent"].at[at + (wb, slot)].set(
            row.astype(cache["latent"].dtype), mode="drop")
    with jax.named_scope("attention"):
        kv = _ring_rows(lat, layer, ring_row, b).astype(jnp.float32)
        w = p["wkv_b"].reshape(r, h, nope + vd).astype(jnp.float32)
        q_lat = jnp.einsum(f"b{qa}hn,chn->b{qa}hc",
                           tok(q_nope).astype(jnp.float32), w[..., :nope])
        qf = _lane_pad(jnp.concatenate(
            [q_lat, tok(q_pe).astype(jnp.float32)], -1), lanes)
        logits = jnp.einsum(f"b{qa}hd,bsd->b{qa}hs", qf,
                            kv) * mla_softmax_scale(cfg)
        probs = jax.nn.softmax(
            jnp.where(valid[..., None, :], logits, NEG_INF), axis=-1)
        o_lat = jnp.einsum(f"b{qa}hs,bsd->b{qa}hd", probs, kv)[..., :r]
        o = jnp.einsum(f"b{qa}hc,chv->b{qa}hv", o_lat, w[..., nope:])
    y = layers.dense(o.reshape(b, s, h * vd).astype(x.dtype), p["wo"],
                     adapter=(adapters or {}).get("wo"),
                     lora_scaling=cfg.lora_alpha / cfg.lora_rank,
                     adapter_rows=adapter_rows)
    return y, {"latent": lat, "idx": new_idx}
