"""Jit'd wrappers: model layout (B,1,H,hd) / cache (B,R,K,hd) ⇄ kernel
layout, plus the grouped heterogeneous tri-LoRA decode composite
(DESIGN.md §15): per-row bank GEMVs for q/k/v, per-row ragged cache write,
flash-decode attention, per-row bank GEMV for the output projection."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import (
    decode_attention_kernel,
)
from repro.kernels.decode_attention.grouped import (
    grouped_tri_lora_gemv_kernel,
)
from repro.kernels.interpret import interpret_mode


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention(q, k_cache, v_cache, idx, *, bk: int = 512,
                     interpret: bool | None = None):
    """q (B,1,H,hd); k/v_cache (B,R,K,hd); idx () or (B,) int32 (ragged
    per-row newest positions; -1 = masked slot, output row exactly zero)
    → (B,1,H,hd)."""
    interpret = interpret_mode(interpret)
    ring = k_cache.shape[1]
    bk_eff = min(bk, ring)
    pad = (-ring) % bk_eff
    kt = jnp.swapaxes(k_cache, 1, 2)
    vt = jnp.swapaxes(v_cache, 1, 2)
    if pad:  # padded slots have slot-index >= ring, masked by `slot <= idx`
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        # a wrapped ring (idx >= REAL ring) must validate exactly the real
        # slots — remap to ring-1 so the kernel (which sees the PADDED ring
        # and would take its all-valid branch) never attends to the pads
        idx = jnp.where(jnp.asarray(idx, jnp.int32) >= ring, ring - 1, idx)
    qt = jnp.swapaxes(q, 1, 2)
    out = decode_attention_kernel(qt, kt, vt, idx, bk=bk_eff,
                                  interpret=interpret)
    return jnp.swapaxes(out, 1, 2)


@functools.partial(jax.jit, static_argnames=("scaling", "bn", "bk",
                                             "interpret"))
def grouped_dense(rows, x, w, a, c, b, *, scaling: float = 1.0,
                  bn: int = 256, bk: int = 256,
                  interpret: bool | None = None):
    """Per-row tri-LoRA dense: y[i] = x[i]·w + s·x[i]·A[g]·C[g]·B[g] with
    g = rows[i] (-1 = masked → exactly-zero row).  x (B,K); w (K,N); bank
    a (m,K,r) / c (m,r,r) / b (m,r,N).  Pads K and N to tile multiples
    (zero K-pads contribute nothing; N-pads are sliced off)."""
    interpret = interpret_mode(interpret)
    k, n = w.shape
    bk_eff, bn_eff = min(bk, k), min(bn, n)
    pad_k, pad_n = (-k) % bk_eff, (-n) % bn_eff
    if pad_k:
        x = jnp.pad(x, ((0, 0), (0, pad_k)))
        w = jnp.pad(w, ((0, pad_k), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad_k), (0, 0)))
    if pad_n:
        w = jnp.pad(w, ((0, 0), (0, pad_n)))
        b = jnp.pad(b, ((0, 0), (0, 0), (0, pad_n)))
    out = grouped_tri_lora_gemv_kernel(rows, x, w, a, c, b, scaling=scaling,
                                       bn=bn_eff, bk=bk_eff,
                                       interpret=interpret)
    return out[:, :n]


@functools.partial(jax.jit, static_argnames=("scaling", "interpret"))
def grouped_decode(x, weights, bank, rows, pos, k_cache, v_cache, *,
                   scaling: float = 1.0, interpret: bool | None = None):
    """One decode step for a batch of sequences, EACH applying its own
    tri-LoRA adapter row from a stacked bank (DESIGN.md §15).

    x (B,d): current-token hidden states (pre-qkv; rope is NOT applied at
    this level — the oracle contract is rope-free).  weights: {'wq','wk',
    'wv','wo'} base projections.  bank: same keys, each an {'A': (m,d,r),
    'C': (m,r,r), 'B': (m,r,·)} stacked adapter.  rows (B,) int32 bank row
    per sequence (-1 = masked slot).  pos (B,) int32 absolute position of
    the incoming token per row.  k/v_cache (B,R,KH,hd) ring caches.

    Returns (out (B,d), k_cache, v_cache).  Masked slots write nothing to
    their cache rows and their output rows are exactly zero.  Oracle:
    :func:`repro.kernels.decode_attention.ref.grouped_decode_ref`.
    """
    bsz = x.shape[0]
    ring, kh, hd = k_cache.shape[1], k_cache.shape[2], k_cache.shape[3]
    h = weights["wq"].shape[1] // hd
    rows = jnp.asarray(rows, jnp.int32)
    active = rows >= 0
    pos = jnp.where(active, jnp.asarray(pos, jnp.int32), -1)

    def gd(xin, name):
        ad = bank[name]
        return grouped_dense(rows, xin, weights[name], ad["A"], ad["C"],
                             ad["B"], scaling=scaling, interpret=interpret)

    q = gd(x, "wq").reshape(bsz, 1, h, hd)
    k_new = gd(x, "wk").reshape(bsz, kh, hd)
    v_new = gd(x, "wv").reshape(bsz, kh, hd)
    slot = jnp.where(active, jnp.mod(pos, ring), 0)
    wb = jnp.where(active, jnp.arange(bsz), bsz)      # OOB ⇒ dropped write
    k_cache = k_cache.at[wb, slot].set(k_new.astype(k_cache.dtype),
                                       mode="drop")
    v_cache = v_cache.at[wb, slot].set(v_new.astype(v_cache.dtype),
                                       mode="drop")
    attn = decode_attention(q, k_cache, v_cache, pos, interpret=interpret)
    out = gd(attn.reshape(bsz, h * hd), "wo")
    return out, k_cache, v_cache
