"""Seeded weights of the DeepSeek-V2 configuration, made from the seed
alone, in the program's tree layout: ``init_base`` follows the key splits
and scales of the program's ``init_base(cfg, key(seed))`` (a test checks it
bit for bit at a small size), ``init_bank`` draws a bank of per-user
adapters on the MLA targets.

Experts: the router has the published number of outputs
(``published.n_routed_experts``), and this chip holds the first
``n_routed_experts`` of them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.weights import DTYPES, _normal


def freeze(c: dict) -> tuple:
    """The configuration's sizes as a hashable static argument, flat: the
    router's width as ``n_router_experts`` (the published count of routed
    experts) and the YaRN parameters as ``rope_<key>``."""
    flat = {k: v for k, v in c.items()
            if isinstance(v, (int, float, str, bool))}
    flat["lora_targets"] = tuple(c["lora_targets"])
    flat["n_router_experts"] = c.get("published", {}).get(
        "n_routed_experts", c["n_routed_experts"])
    flat.update({f"rope_{k}": v for k, v in c["rope_scaling"].items()})
    return tuple(sorted(flat.items()))


def routed_experts(c: dict) -> tuple[int, int]:
    """(experts the router chooses among, experts held here), of a frozen
    configuration."""
    return c["n_router_experts"], c["n_routed_experts"]


def _rms(c, d):
    return {"scale": jnp.zeros((d,), DTYPES[c["torch_dtype"]])}


def _mlp(c, key, f):
    d, dt = c["hidden_size"], DTYPES[c["torch_dtype"]]
    k1, k2, k3 = jax.random.split(key, 3)
    s_in, s_out = 1.0 / jnp.sqrt(d), 1.0 / jnp.sqrt(f)
    return {"w_gate": _normal(k1, (d, f), s_in, dt),
            "w_up": _normal(k2, (d, f), s_in, dt),
            "w_down": _normal(k3, (f, d), s_out, dt)}


def _mla(c, key):
    d, h, r = (c["hidden_size"], c["num_attention_heads"],
               c["kv_lora_rank"])
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    dt = DTYPES[c["torch_dtype"]]
    ks = jax.random.split(key, 4)

    def w(k, din, dout):
        return _normal(k, (din, dout), 1.0 / jnp.sqrt(din), dt)
    return {"wq": w(ks[0], d, h * (nope + rope)),
            "wkv_a": w(ks[1], d, r + rope),
            "kv_norm": _rms(c, r),
            "wkv_b": w(ks[2], r, h * (nope + vd)),
            "wo": w(ks[3], h * vd, d)}


def _moe(c, key):
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    dt = DTYPES[c["torch_dtype"]]
    routed, held = routed_experts(c)
    keys = jax.random.split(key, 4)
    s_in, s_out = 1.0 / jnp.sqrt(d), 1.0 / jnp.sqrt(f)
    return {"router": _normal(keys[0], (d, routed), s_in, jnp.float32),
            "w_gate": _normal(keys[1], (held, d, f), s_in, dt),
            "w_up": _normal(keys[2], (held, d, f), s_in, dt),
            "w_down": _normal(keys[3], (held, f, d), s_out, dt),
            "shared": _mlp(c, jax.random.fold_in(key, 4),
                           c["n_shared_experts"] * f)}


def _layer(c, key, dense: bool):
    ks = jax.random.split(key, 6)
    d = c["hidden_size"]
    p = {"ln1": _rms(c, d), "attn": _mla(c, ks[0]), "ln2": _rms(c, d)}
    if dense:
        p["mlp"] = _mlp(c, ks[2], c["intermediate_size"])
    else:
        p["moe"] = _moe(c, ks[2])
    return p


@functools.partial(jax.jit, static_argnums=0)
def _init_base(cfg_items: tuple, key) -> dict:
    c = dict(cfg_items)
    n_dense = c["first_k_dense_replace"]
    ks = jax.random.split(key, 8)
    layers = [_layer(c, k, False) for k in jax.random.split(
        ks[1], c["num_hidden_layers"] - n_dense)]
    lead = tuple(_layer(c, k, True)
                 for k in jax.random.split(ks[6], max(n_dense, 1))[:n_dense])
    table = (c["vocab_size"], c["hidden_size"])
    dt = DTYPES[c["torch_dtype"]]
    base = {"embed": _normal(ks[0], table, 0.02, dt),
            "final_norm": _rms(c, c["hidden_size"]),
            "groups": {"0": jax.tree.map(lambda *xs: jnp.stack(xs), *layers)},
            "lead": lead, "tail": ()}
    if not c["tie_word_embeddings"]:
        base["lm_head"] = _normal(jax.random.fold_in(ks[0], 1), table, 0.02,
                                  dt)
    return base


def init_base(c: dict, seed: int) -> dict:
    """The frozen backbone for ``key(seed)`` in the configuration's dtype,
    made in one compiled call: {'embed', 'lm_head' unless the head is tied,
    'final_norm', 'lead': the leading dense layers, 'groups': {'0': the
    expert layers stacked on axis 0}, 'tail': ()}."""
    return _init_base(freeze(c), jax.random.key(seed))


def adapter_shapes(c: dict) -> dict:
    """The adapted projections' (d_in, d_out), by target."""
    d, h, r = (c["hidden_size"], c["num_attention_heads"],
               c["kv_lora_rank"])
    rope = c["qk_rope_head_dim"]
    shapes = {"wq": (d, h * (c["qk_nope_head_dim"] + rope)),
              "wkv_a": (d, r + rope), "wo": (h * c["v_head_dim"], d)}
    return {t: shapes[t] for t in c["lora_targets"]}


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _init_bank(cfg_items: tuple, users: int, delta_ratio: float, key) -> dict:
    c = dict(cfg_items)
    r, n_layers = c["lora_rank"], c["num_hidden_layers"]
    n_dense = c["first_k_dense_replace"]
    s = c["lora_alpha"] / r
    bank = {}
    for j, (t, (din, dout)) in enumerate(adapter_shapes(c).items()):
        ka, kb, kc = jax.random.split(jax.random.fold_in(key, j), 3)
        lead = (users, n_layers)
        bank[t] = {
            "A": jax.random.normal(ka, lead + (din, r)) / np.sqrt(r),
            "C": (jnp.eye(r) + 0.1 * jax.random.normal(kc, lead + (r, r))),
            "B": (delta_ratio / (s * np.sqrt(din)))
                 * jax.random.normal(kb, lead + (r, dout))}
    return {"groups": {"0": {"attn": jax.tree.map(
                lambda x: x[:, n_dense:], bank)}},
            "lead": tuple({"attn": jax.tree.map(lambda x, i=i: x[:, i], bank)}
                          for i in range(n_dense)),
            "tail": ()}


def init_bank(c: dict, users: int, seed: int, delta_ratio: float) -> dict:
    """A bank of ``users`` distinct f32 tri-LoRA trees drawn as
    ``weights.init_bank`` draws them, every layer's delta about
    ``delta_ratio`` of its weight's scale: leaves (users, layers, ...) in
    'groups' and (users, ...) in each 'lead' layer."""
    return _init_bank(freeze(c), users, float(delta_ratio),
                      jax.random.key(seed))
