"""Blockwise online-softmax attention kernels (FlashAttention on TPU).

Features: causal masking, sliding window (SWA archs + the long_500k
sliding-window variants), grouped-query attention WITHOUT materializing
repeated KV — the BlockSpec index map points each query head at its KV
group (h → h // group_size), so KV tiles are fetched once per group.

Forward grid: (batch, q_heads, Sq/bq, Skv/bk) — the KV dim is innermost
and sequential on TPU, so the (m, l, acc) running-softmax state lives in
VMEM scratch across KV iterations.  Blocks outside the causal/window band
are skipped entirely via ``pl.when`` predication (this is what makes the
SWA variant sub-quadratic in compiled FLOPs).  With ``save_lse=True`` the
forward also emits the per-row logsumexp, the only residual the backward
needs beyond the inputs and output.

Backward (DESIGN.md §14): probability tiles are RECOMPUTED from the stored
logsumexp — ``p = where(mask, exp(s·scale − lse), 0)`` — instead of being
saved, so train-time residuals stay O(S) per head like the forward.  Two
kernels mirror the forward's tiling idiom (f32 VMEM accumulators carried
across the innermost sequential grid dim, same ``pl.when`` band
predication, same GQA head→group index maps — the ``tri_lora_dx_kernel``
pattern): ``dq`` iterates KV blocks innermost and accumulates
ds@K per q tile; ``dk/dv`` iterates the flattened (group, q-block) axis
innermost and accumulates pᵀ@dO and dsᵀ@Q per KV tile, one pass for both
cotangents.  The ``where`` is applied AFTER the exp on the raw scores so a
fully-masked row (lse ≈ −1e30) yields p = 0 rather than exp(0) = 1.

Per-row statistics (the logsumexp and the backward's Σ dO·O) cross HBM
as (…, S, 128) lane-replicated blocks: the TPU lowering requires the last
two block dims to be divisible by (8, 128) or to equal the array's, which a
(1, bq) row block of a (B, H, S) array is not.  The public shapes stay
(B, H, S): the forward slices lane 0 out and the backward broadcasts it
back in.

VMEM per step ≈ bq·hd (q) + 2·bk·hd (k,v) + bq·bk (logits) + bq·hd (acc)
f32 — with bq=bk=512, hd=128: ~2.6 MB, comfortably inside one core's VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128    # lane width of the replicated per-row statistic blocks


def _band(q_first, k_first, *, causal: bool, window: int, bq: int, bk: int):
    """Block-level predicate: does (q block, k block) intersect the mask
    band?  Shared by the forward and both backward kernels so the backward
    recomputation visits exactly the blocks the forward normalized over."""
    run = jnp.bool_(True)
    if causal:
        run &= k_first <= q_first + bq - 1           # block not fully future
    if window:
        run &= k_first + bk - 1 >= q_first - window + 1   # overlaps window
    return run


def _mask(q_first, k_first, *, causal: bool, window: int, bq: int, bk: int):
    """Element-level causal/window mask for one (bq, bk) tile."""
    qpos = q_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = k_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    return mask


def _check_blocks(sq: int, skv: int, bq: int, bk: int) -> None:
    if sq % bq or skv % bk:
        raise ValueError(
            f"flash kernel needs block-divisible sequence lengths: "
            f"sq={sq} % bq={bq} = {sq % bq}, skv={skv} % bk={bk} = "
            f"{skv % bk}; pad the inputs (ops.flash_attention pads "
            f"internally and slices the result)")


def _kernel(q_ref, k_ref, v_ref, o_ref, *rest, sm_scale: float, causal: bool,
            window: int, bq: int, bk: int, n_kv: int, save_lse: bool):
    if save_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        m_ref, l_ref, acc_ref = rest
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_first = qi * bq          # absolute position of this q block's first row
    k_first = ki * bk
    run = _band(q_first, k_first, causal=causal, window=window, bq=bq, bk=bk)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)           # (bk, hd)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale

        mask = _mask(q_first, k_first, causal=causal, window=window,
                     bq=bq, bk=bk)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                           # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + \
            jnp.dot(p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)
        if save_lse:
            lse = (m_ref[...] + jnp.log(denom))[:, None]
            lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def flash_attention_kernel(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True, window: int = 0,
                           bq: int = 512, bk: int = 512,
                           interpret: bool = False, save_lse: bool = False):
    """q (B,H,Sq,hd), k/v (B,K,Skv,hd), H % K == 0.  Returns (B,H,Sq,hd),
    or (out, lse (B,H,Sq) f32) when ``save_lse`` — lse is the per-row
    logsumexp of the scaled masked logits, the backward's only residual."""
    b, h, sq, hd = q.shape
    kh, skv = k.shape[1], k.shape[2]
    g = h // kh
    bq = min(bq, sq)
    bk = min(bk, skv)
    _check_blocks(sq, skv, bq, bk)
    n_kv = skv // bk
    grid = (b, h, sq // bq, n_kv)
    sm_scale = float(hd) ** -0.5
    out_specs = [pl.BlockSpec((1, 1, bq, hd),
                              lambda bb, hh, qi, ki: (bb, hh, qi, 0))]
    out_shape = [jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype)]
    if save_lse:
        out_specs.append(pl.BlockSpec((1, 1, bq, LANES),
                                      lambda bb, hh, qi, ki: (bb, hh, qi, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, h, sq, LANES),
                                              jnp.float32))
    res = pl.pallas_call(
        functools.partial(_kernel, sm_scale=sm_scale, causal=causal,
                          window=window, bq=bq, bk=bk, n_kv=n_kv,
                          save_lse=save_lse),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
            # GQA: map query head -> kv head, no repeat materialized
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bb, hh, qi, ki, g=g: (bb, hh // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bb, hh, qi, ki, g=g: (bb, hh // g, ki, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # running max
            pltpu.VMEM((bq,), jnp.float32),       # running denom
            pltpu.VMEM((bq, hd), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    return (res[0], res[1][..., 0]) if save_lse else res[0]


# ---------------------------------------------------------------------------
# backward: dq / dk / dv via recompute from the stored logsumexp
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, sm_scale: float, causal: bool, window: int,
               bq: int, bk: int, n_kv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_first = qi * bq
    k_first = ki * bk
    run = _band(q_first, k_first, causal=causal, window=window, bq=bq, bk=bk)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)           # (bk, hd)
        do = do_ref[0, 0].astype(jnp.float32)         # (bq, hd)
        lse = lse_ref[0, 0, :, :1]                    # (bq, 1) f32
        delta = delta_ref[0, 0, :, :1]                # (bq, 1) f32  Σ dO·O
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        mask = _mask(q_first, k_first, causal=causal, window=window,
                     bq=bq, bk=bk)
        # where AFTER exp: fully-masked rows (lse ≈ NEG_INF) must give p=0,
        # not exp(NEG_INF − lse) = 1; in-band entries satisfy s ≤ lse.
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        acc_ref[...] += jnp.dot(ds, k,
                                preferred_element_type=jnp.float32) * sm_scale

    @pl.when(ki == n_kv - 1)
    def _done():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale: float,
                causal: bool, window: int, bq: int, bk: int, n_q: int,
                n_inner: int):
    ki = pl.program_id(2)
    ji = pl.program_id(3)      # flattened (query group, q block) — innermost
    qi = ji % n_q

    @pl.when(ji == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_first = qi * bq
    k_first = ki * bk
    run = _band(q_first, k_first, causal=causal, window=window, bq=bq, bk=bk)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)           # (bk, hd)
        do = do_ref[0, 0].astype(jnp.float32)         # (bq, hd)
        lse = lse_ref[0, 0, :, :1]                    # (bq, 1) f32
        delta = delta_ref[0, 0, :, :1]                # (bq, 1) f32
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        mask = _mask(q_first, k_first, causal=causal, window=window,
                     bq=bq, bk=bk)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv_acc[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_acc[...] += jnp.dot(ds.T, q,
                               preferred_element_type=jnp.float32) * sm_scale

    @pl.when(ji == n_inner - 1)
    def _done():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd_kernel(q, k, v, o, lse, do, *, causal: bool = True,
                               window: int = 0, bq: int = 512, bk: int = 512,
                               interpret: bool = False):
    """Gradients of the flash forward w.r.t. (q, k, v).

    All operands in kernel layout — q/do/o (B,H,Sq,hd), k/v (B,K,Skv,hd),
    lse (B,H,Sq) f32 from ``save_lse=True`` — with block-divisible sequence
    lengths.  delta = Σ_d dO·O (the softmax-Jacobian row correction) is a
    cheap O(S·hd) elementwise pass left to XLA; the two Pallas kernels do
    the O(S²) work.  dq is accumulated per q tile over KV blocks; dk/dv are
    accumulated per KV tile over the flattened (group, q-block) axis, which
    keeps the GQA group sum inside one sequential grid pass (no
    materialized KV repeat, no cross-block atomics).
    """
    b, h, sq, hd = q.shape
    kh, skv = k.shape[1], k.shape[2]
    g = h // kh
    bq = min(bq, sq)
    bk = min(bk, skv)
    _check_blocks(sq, skv, bq, bk)
    n_q, n_kv = sq // bq, skv // bk
    sm_scale = float(hd) ** -0.5
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    # per-row statistics cross HBM lane-replicated (see the module docstring)
    lse, delta = (jnp.broadcast_to(x[..., None], x.shape + (LANES,))
                  for x in (lse, delta))

    q_spec = pl.BlockSpec((1, 1, bq, hd), lambda bb, hh, qi, ki: (bb, hh, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, hd),
                           lambda bb, hh, qi, ki, g=g: (bb, hh // g, ki, 0))
    row_spec = pl.BlockSpec((1, 1, bq, LANES),
                            lambda bb, hh, qi, ki: (bb, hh, qi, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal,
                          window=window, bq=bq, bk=bk, n_kv=n_kv),
        grid=(b, h, n_q, n_kv),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    n_inner = g * n_q
    # q-side operands follow the flattened (group, q block) index back to
    # their query head (kv head · g + group) and q block (ji % n_q)
    qj_spec = pl.BlockSpec(
        (1, 1, bq, hd),
        lambda bb, hh, ki, ji, g=g, n_q=n_q: (bb, hh * g + ji // n_q,
                                              ji % n_q, 0))
    rowj_spec = pl.BlockSpec(
        (1, 1, bq, LANES),
        lambda bb, hh, ki, ji, g=g, n_q=n_q: (bb, hh * g + ji // n_q,
                                              ji % n_q, 0))
    kj_spec = pl.BlockSpec((1, 1, bk, hd),
                           lambda bb, hh, ki, ji: (bb, hh, ki, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          window=window, bq=bq, bk=bk, n_q=n_q,
                          n_inner=n_inner),
        grid=(b, kh, n_kv, n_inner),
        in_specs=[qj_spec, kj_spec, kj_spec, qj_spec, rowj_spec, rowj_spec],
        out_specs=[kj_spec, kj_spec],
        out_shape=[jax.ShapeDtypeStruct((b, kh, skv, hd), k.dtype),
                   jax.ShapeDtypeStruct((b, kh, skv, hd), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
