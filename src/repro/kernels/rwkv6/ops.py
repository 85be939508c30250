"""Jit'd wrapper: model layout (B,T,H,hd) ⇄ kernel layout (B·H,T,hd)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.interpret import interpret_mode
from repro.kernels.rwkv6.rwkv6 import wkv6_kernel


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, state, *, chunk: int = 32,
         interpret: bool | None = None):
    """r,k,v,w: (B,T,H,hd); u: (H,hd); state: (B,H,hd,hd) f32.
    Returns (y (B,T,H,hd) f32, new state (B,H,hd,hd) f32)."""
    interpret = interpret_mode(interpret)
    b, t, h, hd = r.shape
    eff_chunk = min(chunk, t)
    # pad time to a chunk multiple with w=1 (no decay), k=0 (no state write)
    pad = (-t) % eff_chunk
    if pad:
        zeros = ((0, 0), (0, pad), (0, 0), (0, 0))
        r = jnp.pad(r, zeros)
        k = jnp.pad(k, zeros)
        v = jnp.pad(v, zeros)
        w = jnp.pad(w, zeros, constant_values=1.0)
    def fold(x):
        return jnp.moveaxis(x, 2, 1).reshape(b * h, -1, hd)
    u_b = jnp.broadcast_to(u, (b, h, hd)).reshape(b * h, hd)
    s_b = state.reshape(b * h, hd, hd).astype(jnp.float32)
    y, s_new = wkv6_kernel(fold(r), fold(k), fold(v), fold(w), u_b, s_b,
                           chunk=eff_chunk, interpret=interpret)
    y = jnp.moveaxis(y.reshape(b, h, -1, hd), 1, 2)
    if pad:
        y = y[:, :t]
    return y, s_new.reshape(b, h, hd, hd)
