"""Jit'd wrapper: layout adaptation (B,S,H,hd) ⇄ (B,H,S,hd) + padding.

``pl.pallas_call`` has no autodiff rule, so the padded kernel-layout core
carries a ``jax.custom_vjp`` (the ``tri_lora.ops`` idiom): the forward runs
the online-softmax kernel with ``save_lse=True`` and keeps (q, k, v, out,
lse) as residuals; the backward recomputes probability tiles from the
logsumexp inside the Pallas dq / dk-dv kernels
(``flash_attention_bwd_kernel``).  Padding and layout swaps sit OUTSIDE the
custom VJP, so their cotangents (zero-fill / slice) come from ordinary
autodiff — padded q rows carry zero dO and therefore contribute nothing to
dk/dv.  Gradients for all three operands are checked against ``jax.grad``
of ``flash_attention_ref`` in tests/test_kernels.py (f32/bf16 ×
causal/windowed × padded/unpadded × GQA).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import (
    flash_attention_bwd_kernel, flash_attention_kernel)
from repro.kernels.interpret import interpret_mode


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_padded(qt, kt, vt, causal, window, bq, bk, interpret):
    """Kernel-layout core on block-divisible (B,H,S,hd) operands."""
    return flash_attention_kernel(qt, kt, vt, causal=causal, window=window,
                                  bq=bq, bk=bk, interpret=interpret)


def _flash_padded_fwd(qt, kt, vt, causal, window, bq, bk, interpret):
    out, lse = flash_attention_kernel(qt, kt, vt, causal=causal,
                                      window=window, bq=bq, bk=bk,
                                      interpret=interpret, save_lse=True)
    return out, (qt, kt, vt, out, lse)


def _flash_padded_bwd(causal, window, bq, bk, interpret, res, g):
    qt, kt, vt, out, lse = res
    return flash_attention_bwd_kernel(qt, kt, vt, out, lse, g, causal=causal,
                                      window=window, bq=bq, bk=bk,
                                      interpret=interpret)


_flash_padded.defvjp(_flash_padded_fwd, _flash_padded_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0, bq: int = 512,
                    bk: int = 512,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Model-layout entry point: q (B,Sq,H,hd), k/v (B,Skv,K,hd).

    Differentiable in q, k and v — the backward runs the Pallas
    recompute-from-logsumexp kernels (custom VJP above), so residual memory
    stays O(S) per head instead of the O(S²) probability matrix.
    """
    interpret = interpret_mode(interpret)
    sq = q.shape[1]
    bq = min(bq, 1 << (sq - 1).bit_length())
    bk = min(bk, bq)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    pad_q = (-qt.shape[2]) % bq
    pad_k = (-kt.shape[2]) % bk
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    # NOTE on padded causal rows: padded q rows attend to nothing real but
    # their outputs are sliced away; padded k cols are masked by causality
    # only when causal=True — for non-causal use, callers must pad-mask.
    out = _flash_padded(qt, kt, vt, causal, window, bq, bk, interpret)
    if pad_q:
        out = out[:, :, :sq]
    return jnp.swapaxes(out, 1, 2)
