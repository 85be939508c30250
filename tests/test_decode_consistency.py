"""Integration: token-by-token decode must reproduce the train-time forward
logits (same weights, same tokens) for every architecture family.  This
exercises KV/ring caches, recurrent state carry-over, cross-attn caches and
token-shift states end to end."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import model, transformer
from repro.models.config import get_config

from conftest import make_batch

FAMS = ["qwen2.5-14b",        # dense GQA + bias
        "h2o-danube-3-4b",    # sliding-window (ring cache exercised)
        "rwkv6-1.6b",         # SSM state
        "recurrentgemma-2b",  # hybrid RG-LRU + local attn
        "whisper-small",      # enc-dec cross attention
        "grok-1-314b",        # MoE (capacity_factor raised to avoid drops)
        "deepseek-v2-lite"]   # MLA latent ring, leading dense layer, MoE


@pytest.mark.parametrize("arch", FAMS)
def test_decode_matches_forward(arch):
    cfg = get_config(arch).reduced()
    if cfg.is_moe:
        cfg = cfg.with_overrides(capacity_factor=float(cfg.n_experts))
    b, t = 2, 12
    params = model.init_params(cfg, jax.random.key(0))
    batch = make_batch(cfg, b=b, s=t)
    full_logits, _ = model.forward(cfg, params["base"], params["adapter"],
                                   batch)

    cache = model.init_decode_cache(cfg, b, max(t, 16))
    if cfg.enc_dec:   # prefill the cross-attention cache from the encoder
        enc_out = model.encode(cfg, params["base"], batch["frames"])
        cache = _fill_cross_cache(cfg, params["base"], cache, enc_out)

    toks = np.asarray(batch["tokens"])
    step_logits = []
    for step in range(t):
        pos = (jnp.full((b, 1, 3), step, jnp.int32)
               if cfg.pos_type == "mrope" else jnp.full((b, 1), step, jnp.int32))
        sb = {"token": jnp.asarray(toks[:, step:step + 1]), "positions": pos}
        lg, cache = model.decode_step(cfg, params["base"], params["adapter"],
                                      cache, sb)
        step_logits.append(np.asarray(lg[:, 0]))
    got = np.stack(step_logits, axis=1)
    want = np.asarray(full_logits)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def _fill_cross_cache(cfg, base, cache, enc_out):
    """Precompute xk/xv from encoder output into every decoder block cache."""
    b = enc_out.shape[0]

    def fill(blk_params, blk_cache):
        if not (isinstance(blk_cache, dict) and "xk" in blk_cache):
            return blk_cache
        xp = blk_params["xattn"]
        k = (enc_out @ xp["wk"]).reshape(b, -1, cfg.n_heads, cfg.hd)
        v = (enc_out @ xp["wv"]).reshape(b, -1, cfg.n_heads, cfg.hd)
        return dict(blk_cache, xk=k.astype(blk_cache["xk"].dtype),
                    xv=v.astype(blk_cache["xv"].dtype))

    q, pattern, rem = cfg.stack_plan()
    new_groups = cache["groups"]
    if new_groups is not None:
        for i in range(len(pattern)):
            for gi in range(q):
                gp = jax.tree.map(lambda x, gi=gi: x[gi],
                                  _index_groups(cfg, i))
                blk = jax.tree.map(lambda x, gi=gi: x[gi],
                                   new_groups[str(i)])
                filled = fill(gp, blk)
                new_groups = {**new_groups, str(i): jax.tree.map(
                    lambda full, one, gi=gi: full.at[gi].set(one),
                    new_groups[str(i)], filled)}
    new_tail = tuple(fill(tp, tc) for tp, tc in
                     zip(_tail_params(cfg), cache["tail"]))
    return {"groups": new_groups, "tail": new_tail}


# helpers bound late so the test file stays self-contained
_PARAMS_CACHE = {}


def _ensure_params(cfg):
    if cfg.name not in _PARAMS_CACHE:
        _PARAMS_CACHE[cfg.name] = model.init_params(cfg, jax.random.key(0))
    return _PARAMS_CACHE[cfg.name]


def _index_groups(cfg, i):
    return _ensure_params(cfg)["base"]["groups"][str(i)]


def _tail_params(cfg):
    return _ensure_params(cfg)["base"]["tail"]
