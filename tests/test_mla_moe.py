"""DeepSeek-V2's latent attention (MLA) and held expert share against the
benchmark's float32 reference (``bench/reference/deepseek_v2.py``), at a
small size on the CPU with seeded random weights in float32.

- Served logits: prompt and then decode through the decode step with
  per-slot bank rows and ragged positions, as ``ServeEngine`` runs it,
  match the reference's full forward; the engine's greedy tokens are the
  reference's argmax, also for prompts of one and several prefill chunks.
- A prompt chunk through the decode step (the prefill path) gives the
  token-by-token logits: its expert layers route each token on its own
  and drop none, where one dispatch group of the chunk would.
- The absorbed decode against the latent ring matches ``block_apply``'s
  explicit forward, block by block.
- The parts every expert share computes, with the shared experts counted
  once, add up to the uncut layer.
- YaRN's frequencies and scales at the published sizes.
- MLP adapters on an MoE model are refused.

The fine-tuning forward is compared at capacity factor n_experts / top_k,
where the Switch dispatch keeps every token; dropping under the default
factor stays open (ROADMAP 2.4).  Decode dispatches one token per row at
capacity 1, which drops nothing; so does a prefill chunk, token by token.
"""
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.adapter_bank import AdapterBank, random_bank
from repro.launch import serve
from repro.models import attention, layers, model, moe, transformer
from repro.models.config import get_config

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.reference import deepseek_v2, deepseek_v2_weights  # noqa: E402

# float32 throughout; the absorbed decode sums the same products in
# another order (through the 32-wide latent instead of per-head keys), so
# outputs of magnitude about 1 agree to a few float32 roundings of their
# partial sums: 1e-5, absolute and relative.
TOL = 1e-5


def _tiny():
    """The reference's configuration and the program's, at small widths:
    3 layers (one dense), 4 heads, latent 32, 8 routed experts of which 4
    are held, top 3, weights not renormalised, YaRN on, an untied output
    head."""
    c = {"hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
         "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
         "v_head_dim": 16, "intermediate_size": 96,
         "moe_intermediate_size": 32, "n_routed_experts": 4,
         "n_shared_experts": 2, "num_experts_per_tok": 3,
         "first_k_dense_replace": 1, "norm_topk_prob": False,
         "routed_scaling_factor": 1, "rms_norm_eps": 1e-6,
         "tie_word_embeddings": False,
         "rope_theta": 10000.0, "vocab_size": 256, "torch_dtype": "float32",
         "lora_rank": 4, "lora_alpha": 8.0,
         "lora_targets": ["wq", "wkv_a", "wo"],
         "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                          "beta_slow": 1, "mscale": 0.707,
                          "mscale_all_dim": 0.707,
                          "original_max_position_embeddings": 4096},
         "published": {"n_routed_experts": 8}}
    cfg = get_config("deepseek-v2-lite").with_overrides(
        d_model=64, n_layers=3, n_heads=4, n_kv_heads=4, head_dim=16,
        kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=16, v_head_dim=16,
        d_ff=96, moe_d_ff=32, shared_d_ff=64, n_experts=8, experts_held=4,
        top_k=3, vocab_size=256, param_dtype="float32", lora_rank=4,
        lora_alpha=8.0, capacity_factor=8 / 3)
    return c, cfg


@pytest.fixture(scope="module")
def tiny():
    c, cfg = _tiny()
    base = model.init_base(cfg, jax.random.key(3))
    bank = deepseek_v2_weights.init_bank(c, 3, 4, 0.5)
    return c, cfg, base, bank


def _ref_logits(c, base, bank, user, toks):
    ad = jax.tree.map(lambda a: a[user], bank)
    return np.asarray(deepseek_v2.logits(
        dict(deepseek_v2_weights.freeze(c)), base, ad,
        jnp.asarray(toks)[None]))[0]


def test_served_logits_match_the_reference(tiny):
    """Three slots, each its own user, start at steps 0, 2 and 3 (ragged
    ring positions, masked rows before): every logit the decode step gives
    at every position matches the reference's full forward."""
    c, cfg, base, bank = tiny
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (3, 9)).astype(np.int32)
    start = np.asarray([0, 2, 3])
    rows = np.asarray([2, 0, 1], np.int32)
    dec = AdapterBank(tree=bank, n_clients=3, rank=4, users={}).decode_tree()
    cache = model.init_decode_cache(cfg, 3, 16)
    got = {s: [] for s in range(3)}
    with jax.default_matmul_precision("highest"):
        for t in range(12):
            pos = np.where(t >= start, t - start, -1).astype(np.int32)
            live = (pos >= 0) & (pos < toks.shape[1])
            tok = np.where(live, toks[np.arange(3), np.clip(pos, 0, 8)], 0)
            pos = np.where(live, pos, -1).astype(np.int32)
            lg, cache = model.decode_step(
                cfg, base, dec, serve._with_positions(cache, jnp.asarray(pos)),
                {"token": jnp.asarray(tok[:, None]),
                 "positions": jnp.asarray(pos[:, None])},
                adapter_rows=jnp.asarray(np.where(live, rows, -1)))
            for s in np.flatnonzero(live):
                got[s].append(np.asarray(lg[s, 0]))
    for s in range(3):
        want = _ref_logits(c, base, bank, rows[s], toks[s])
        np.testing.assert_allclose(np.stack(got[s]), want, atol=TOL,
                                   rtol=TOL)


def test_engine_tokens_are_the_references_argmax(tiny):
    """Through ``ServeEngine`` itself, more requests than slots."""
    c, cfg, base, bank = tiny
    users = {f"u{i}": i for i in range(3)}
    eng = serve.ServeEngine(cfg, base,
                            AdapterBank(tree=bank, n_clients=3, rank=4,
                                        users=users), slots=2, max_len=16)
    rng = np.random.default_rng(1)
    reqs = [serve.Request(rid=i, user_id=f"u{i % 3}",
                          prompt=rng.integers(0, cfg.vocab_size,
                                              4 + i).astype(np.int32), gen=6)
            for i in range(4)]
    with jax.default_matmul_precision("highest"):
        done = eng.run(reqs)
    assert eng._ring == "2x16x128"               # 32 + 16 in 128 lanes
    for r in reqs:
        toks = done[r.rid]
        lg = _ref_logits(c, base, bank, r.rid % 3, toks[:-1])
        p = len(r.prompt)
        np.testing.assert_array_equal(lg[p - 1:].argmax(-1), toks[p:])


def test_engine_prefills_long_prompts_to_the_references_argmax(tiny):
    """Prompts of one token, of one chunk and a token, and of several
    chunks, three users on two slots: the engine's prefill writes them into
    the latent rings and its greedy tokens are the reference's argmax."""
    c, cfg, base, bank = tiny
    chunk = serve.PREFILL_CHUNK
    users = {f"u{i}": i for i in range(3)}
    lens = [(1, 4), (chunk + 1, 3), (2 * chunk + 5, 3)]
    eng = serve.ServeEngine(cfg, base,
                            AdapterBank(tree=bank, n_clients=3, rank=4,
                                        users=users), slots=2,
                            max_len=2 * chunk + 8)
    rng = np.random.default_rng(6)
    reqs = [serve.Request(rid=i, user_id=f"u{i % 3}",
                          prompt=rng.integers(0, cfg.vocab_size,
                                              p).astype(np.int32), gen=g)
            for i, (p, g) in enumerate(lens)]
    with jax.default_matmul_precision("highest"):
        done = eng.run(reqs)
    assert eng.stats.prefill_calls == 0 + 1 + 3
    for r in reqs:
        toks = done[r.rid]
        lg = _ref_logits(c, base, bank, r.rid % 3, toks[:-1])
        p = len(r.prompt)
        np.testing.assert_array_equal(lg[p - 1:].argmax(-1), toks[p:])


def test_prefill_chunk_drops_no_routed_token(tiny):
    """At capacity factor 1, 40 tokens in one dispatch group overflow the
    busiest experts; the decode path's expert layer handed the same 40 as a
    chunk gives what it gives them one by one.  And the whole decode step,
    fed a 40-token prompt as chunks of 16 into ring row 2 of three, gives
    the logits of 40 one-token steps."""
    c, cfg, base, bank = tiny
    cfg = cfg.with_overrides(capacity_factor=1.0)
    p = jax.tree.map(lambda a: a[0], base["groups"]["0"])
    h = jax.random.normal(jax.random.key(4), (1, 40, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        grouped, _ = moe.moe_mlp(cfg, p["moe"], h)
        alone, _ = moe.moe_mlp(cfg, p["moe"], h.reshape(40, 1, -1))
        chunk, _ = transformer._ffn_decode(cfg, p, {}, h)
    alone = alone.reshape(h.shape)
    assert np.abs(np.asarray(grouped - alone)).max() > 1e-3   # it drops
    np.testing.assert_allclose(chunk, alone, atol=TOL, rtol=TOL)

    toks = np.random.default_rng(3).integers(0, cfg.vocab_size, 40)
    step = jax.jit(model.decode_step, static_argnums=0)
    dec = AdapterBank(tree=bank, n_clients=3, rank=4, users={}).decode_tree()
    with jax.default_matmul_precision("highest"):
        cache = serve._init_cache(cfg, 3, 48)
        got = []
        for start in range(0, 40, 16):
            part = toks[start:start + 16]
            n = len(part)
            lg, cache = step(
                cfg, base, dec, cache,
                {"token": np.pad(part, (0, 16 - n))[None].astype(np.int32),
                 "positions": np.where(np.arange(16) < n,
                                       start + np.arange(16), -1)[None]},
                adapter_rows=np.asarray([1], np.int32), ring_row=np.int32(2))
            got.append(np.asarray(lg[0, :n]))
        cache = serve._init_cache(cfg, 3, 48)
        want = []
        for t in range(40):
            pos = np.asarray([-1, -1, t], np.int32)
            lg, cache = step(
                cfg, base, dec, serve._with_positions(cache, pos),
                {"token": np.asarray([[0], [0], [toks[t]]], np.int32),
                 "positions": pos[:, None]},
                adapter_rows=np.asarray([-1, -1, 1], np.int32))
            want.append(np.asarray(lg[2, 0]))
    np.testing.assert_allclose(np.concatenate(got), np.stack(want),
                               atol=TOL, rtol=TOL)


@pytest.mark.parametrize("dense", [True, False])
def test_absorbed_decode_matches_block_apply(tiny, dense):
    """One block, its adapters drawn non-zero: the explicit full-sequence
    forward and the absorbed decode token by token agree."""
    c, cfg, base, _ = tiny
    p = base["lead"][0] if dense else jax.tree.map(
        lambda a: a[0], base["groups"]["0"])
    ad = jax.tree.map(lambda a: a[1, 0] if not dense else a[1],
                      random_bank(cfg, 2, jax.random.key(9)).tree[
                          "lead" if dense else "groups"])
    ad = ad[0] if dense else ad["0"]
    s = 10
    x = jax.random.normal(jax.random.key(5), (2, s, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(s), (2, s))
    with jax.default_matmul_precision("highest"):
        want, _ = transformer.block_apply(cfg, "mla", p, ad, x, pos)
        cache = attention.init_latent_cache(cfg, 2, 16)
        got = []
        for t in range(s):
            y, cache = transformer.block_decode(
                cfg, "mla", p, ad, cache, x[:, t:t + 1], pos[:, t:t + 1])
            got.append(y[:, 0])
    np.testing.assert_allclose(np.stack(got, 1), want, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("seq", [1, 12])
def test_expert_shares_add_up_to_the_uncut_layer(tiny, seq):
    """Four chips of two experts each: a share holds its experts first (the
    router's columns permuted to match, which routes the same tokens to
    the same experts), and the four parts, the shared experts once, give
    the layer that holds all eight."""
    _, cfg, _, _ = tiny
    full = cfg.with_overrides(experts_held=0)
    p = moe.init_moe(jax.random.key(7), full)
    x = jax.random.normal(jax.random.key(8), (3, seq, cfg.d_model))
    share = cfg.with_overrides(experts_held=2)
    with jax.default_matmul_precision("highest"):
        want, _ = moe.moe_mlp(full, p, x)
        shared = layers.mlp(x, p["shared"], cfg.mlp_type)
        parts = []
        for k in range(4):
            mine = np.r_[2 * k:2 * k + 2]
            perm = np.r_[mine, np.setdiff1d(np.arange(8), mine)]
            pk = dict(p, router=p["router"][:, perm],
                      **{w: p[w][mine] for w in ("w_gate", "w_up", "w_down")})
            y, _ = moe.moe_mlp(share, pk, x)
            parts.append(y - shared)
    np.testing.assert_allclose(sum(parts) + shared, want, atol=TOL, rtol=TOL)
    # and a share alone is not the layer
    assert np.abs(np.asarray(parts[0] + shared - want)).max() > 1e-3


def test_yarn_at_published_sizes():
    cfg = get_config("deepseek-v2-lite")
    assert layers.yarn_correction_range(64, 1e4, 4096, 32, 1) == (10, 23)
    inv = np.asarray(layers.yarn_inv_freq(64, 1e4, 40.0, 4096, 32.0, 1.0))
    base = 1e4 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    ramp = (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(inv[11:23], base[11:23] / 40 * ramp
                               + base[11:23] * (1 - ramp), rtol=1e-6)
    m = layers.yarn_get_mscale(40.0, 0.707)
    assert m ** 2 == pytest.approx(1.589626, abs=1e-6)
    assert attention.mla_softmax_scale(cfg) == pytest.approx(0.114721,
                                                             abs=1e-6)
    # the cos/sin factor mscale(40, 0.707) / mscale(40, 0.707) is 1: the
    # rotation is a pure rotation
    x = jax.random.normal(jax.random.key(0), (1, 5, 2, 64))
    pos = jnp.arange(5)[None] * 1000
    y = attention._mla_rope(cfg.with_overrides(param_dtype="float32"), x,
                            pos)
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)


@pytest.mark.parametrize("arch", ["deepseek-v2-lite", "grok-1-314b"])
def test_mlp_adapters_on_experts_are_refused(arch):
    cfg = get_config(arch).reduced().with_overrides(lora_mlp=True)
    params = model.init_params(cfg, jax.random.key(0))
    toks = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="lora_mlp"):
        model.forward(cfg, params["base"], params["adapter"],
                      {"tokens": toks})
