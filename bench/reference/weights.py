"""Seeded weights, made from the seed alone, so that the reference can
rebuild what a run computed on without taking anything from the run.

``init_base`` follows the key splits and scales of the program's own
``init_base(cfg, key(seed))`` (a test checks it bit for bit at a small
size); ``init_bank`` draws a bank of per-user adapters.  The serving
driver hands both to the engine.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def _norm_params(c):
    d, dtype = c["hidden_size"], DTYPES[c["torch_dtype"]]
    if c["norm_type"] == "rmsnorm":
        return {"scale": jnp.zeros((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def _layer(c, key):
    d, h, k = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd, f = c["head_dim"], c["intermediate_size"]
    dt = DTYPES[c["torch_dtype"]]
    ks = jax.random.split(key, 6)
    ka = jax.random.split(ks[0], 4)
    s = 1.0 / jnp.sqrt(d)
    attn = {"wq": _normal(ka[0], (d, h * hd), s, dt),
            "wk": _normal(ka[1], (d, k * hd), s, dt),
            "wv": _normal(ka[2], (d, k * hd), s, dt),
            "wo": _normal(ka[3], (h * hd, d), 1.0 / jnp.sqrt(h * hd), dt)}
    if c["attention_bias"]:
        attn.update(bq=jnp.zeros((h * hd,), dt), bk=jnp.zeros((k * hd,), dt),
                    bv=jnp.zeros((k * hd,), dt))
    k1, k2, k3 = jax.random.split(ks[2], 3)
    s_out = 1.0 / jnp.sqrt(f)
    if c["mlp"] == "swiglu":
        mlp = {"w_gate": _normal(k1, (d, f), s, dt),
               "w_up": _normal(k2, (d, f), s, dt),
               "w_down": _normal(k3, (f, d), s_out, dt)}
    else:
        mlp = {"w_in": _normal(k1, (d, f), s, dt),
               "w_out": _normal(k2, (f, d), s_out, dt)}
    return {"ln1": _norm_params(c), "attn": attn, "ln2": _norm_params(c),
            "mlp": mlp}


def _freeze(c: dict) -> tuple:
    """The configuration's sizes as a hashable static argument."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in c.items()
                        if isinstance(v, (int, float, str, bool, list))))


@functools.partial(jax.jit, static_argnums=0)
def _init_base(cfg_items: tuple, key) -> dict:
    c = dict(cfg_items)
    ks = jax.random.split(key, 8)
    layers = [_layer(c, lk)
              for lk in jax.random.split(ks[1], c["num_hidden_layers"])]
    return {"embed": _normal(ks[0], (c["vocab_size"], c["hidden_size"]), 0.02,
                             DTYPES[c["torch_dtype"]]),
            "final_norm": _norm_params(c),
            "groups": {"0": jax.tree.map(lambda *xs: jnp.stack(xs), *layers)},
            "tail": ()}


def init_base(c: dict, seed: int) -> dict:
    """The frozen backbone for ``key(seed)`` in the configuration's dtype,
    made in one compiled call:
    {'embed', 'final_norm', 'groups': {'0': layers stacked on axis 0},
    'tail': ()}."""
    return _init_base(_freeze(c), jax.random.key(seed))


def _adapter_shapes(c):
    d, h, k, hd = (c["hidden_size"], c["num_attention_heads"],
                   c["num_key_value_heads"], c["head_dim"])
    shapes = {"wq": (d, h * hd), "wk": (d, k * hd), "wv": (d, k * hd),
              "wo": (h * hd, d)}
    return {t: shapes[t] for t in c["lora_targets"]}


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _init_bank(cfg_items: tuple, users: int, delta_ratio: float, key) -> dict:
    c = dict(cfg_items)
    r, n_layers = c["lora_rank"], c["num_hidden_layers"]
    # (x A) C B with A ~ N(0, 1/r) and C near I puts a delta of about
    # s * std(B) on each weight, whose own scale is 1 / sqrt(d_in)
    s = c["lora_alpha"] / r
    bank = {}
    for j, (t, (din, dout)) in enumerate(_adapter_shapes(c).items()):
        ka, kb, kc = jax.random.split(jax.random.fold_in(key, j), 3)
        lead = (users, n_layers)
        bank[t] = {
            "A": jax.random.normal(ka, lead + (din, r)) / np.sqrt(r),
            "C": (jnp.eye(r) + 0.1 * jax.random.normal(kc, lead + (r, r))),
            "B": (delta_ratio / (s * np.sqrt(din)))
                 * jax.random.normal(kb, lead + (r, dout))}
    return {"groups": {"0": {"attn": bank}}, "tail": ()}


def init_bank(c: dict, users: int, seed: int, delta_ratio: float) -> dict:
    """A bank of ``users`` distinct f32 tri-LoRA trees, leaves (users,
    layers, ...): A ~ N(0, 1/r), C = I + 0.1 N(0, 1), and B drawn so that
    each user's weight delta s A C B is about ``delta_ratio`` of the
    weight's own scale (a fresh adapter has B = 0, which would make every
    user alike)."""
    return _init_bank(_freeze(c), users, float(delta_ratio),
                      jax.random.key(seed))


def user_adapter(bank: dict, row: int) -> dict:
    """One user's adapter tree out of a bank (leaves (layers, ...))."""
    return jax.tree.map(lambda x: x[row], bank)
