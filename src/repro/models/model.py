"""Top-level model API: init / train forward / loss / decode.

params = {'base': …frozen…, 'adapter': …tri-LoRA, trainable…}

Batch conventions
-----------------
train:   {'tokens': (B,S) i32, 'labels': (B,S) i32,
          'positions': (B,S) i32  or (B,S,3) for M-RoPE,
          ['vision': (B,P,D)]  (vlm stub embeds, prepended — early fusion),
          ['frames': (B,F,D)]  (audio stub embeds, encoder input)}
decode:  {'token': (B,S) i32, 'positions': (B,S) or (B,S,3) i32}
         + cache pytree from :func:`init_decode_cache`; S = 1 is a decode
         step, S > 1 a prompt chunk written into the rings.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import layers, transformer
from repro.models.config import ModelConfig


def _enc_cfg(cfg: ModelConfig) -> ModelConfig:
    return cfg.with_overrides(n_layers=cfg.n_enc_layers,
                              layer_pattern=("attn",), window=0,
                              n_kv_heads=cfg.n_heads)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=0)
def init_base(cfg: ModelConfig, key: jax.Array) -> dict:
    """The frozen backbone, built by one compiled program: XLA writes every
    layer straight into its stacked output, so the device never holds the
    per-layer arrays and their stack at once."""
    ks = jax.random.split(key, 8)
    base: dict = {"embed": layers.init_embedding(ks[0], cfg.padded_vocab,
                                                 cfg.d_model, cfg.dtype),
                  "final_norm": layers.init_norm(cfg.d_model, cfg.norm_type,
                                                 cfg.dtype)}
    if not cfg.tie_embeddings:
        base["lm_head"] = layers.init_embedding(
            jax.random.fold_in(ks[0], 1), cfg.padded_vocab, cfg.d_model,
            cfg.dtype)
    groups, tail = transformer.init_stack(ks[1], cfg, cross=cfg.enc_dec)
    base["groups"], base["tail"] = groups, tail
    if cfg.n_dense_layers:
        base["lead"] = transformer.init_lead(ks[6], cfg)
    if cfg.pos_type == "learned":
        base["pos_embed"] = (jax.random.normal(
            ks[2], (cfg.max_target_positions, cfg.d_model)) * 0.02
        ).astype(cfg.dtype)
    if cfg.enc_dec:
        ecfg = _enc_cfg(cfg)
        eg, et = transformer.init_stack(ks[3], ecfg)
        base["encoder"] = {
            "groups": eg, "tail": et,
            "final_norm": layers.init_norm(cfg.d_model, cfg.norm_type,
                                           cfg.dtype),
            "pos_embed": (jax.random.normal(
                ks[4], (cfg.enc_frames, cfg.d_model)) * 0.02).astype(cfg.dtype),
        }
    return base


def head_table(cfg: ModelConfig, base: dict) -> jnp.ndarray:
    """The (V, D) table the logits are read through: the embedding table
    when tied, else the model's own ``lm_head``."""
    return base["embed" if cfg.tie_embeddings else "lm_head"]


def init_adapter(cfg: ModelConfig, key: jax.Array) -> dict:
    """The trainable tri-LoRA tree that ``init_params(cfg, key)`` pairs with
    its base, drawn without building the base."""
    ks = jax.random.split(key, 8)
    ag, at = transformer.init_stack_adapters(ks[5], cfg, cross=cfg.enc_dec)
    out = {"groups": ag, "tail": at}
    if cfg.n_dense_layers:
        out["lead"] = transformer.init_lead_adapters(ks[7], cfg)
    return out


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    return {"base": init_base(cfg, key), "adapter": init_adapter(cfg, key)}


def abstract_params(cfg: ModelConfig) -> dict:
    """ShapeDtypeStruct pytree — used by the dry-run (no allocation)."""
    return jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0)))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def encode(cfg: ModelConfig, base: dict, frames: jnp.ndarray) -> jnp.ndarray:
    """Whisper-style encoder over stub frame embeddings (B,F,D)."""
    enc = base["encoder"]
    ecfg = _enc_cfg(cfg)
    x = frames.astype(cfg.dtype) + enc["pos_embed"][None, :frames.shape[1]]
    ad_g, ad_t = _none_adapters_like(ecfg, enc["groups"] is not None)
    x, _ = transformer.run_stack(ecfg, enc["groups"], enc["tail"],
                                 ad_g, ad_t, x,
                                 positions=None, causal=False)
    return layers.norm(x, enc["final_norm"], cfg.norm_type)


def _none_adapters_like(cfg: ModelConfig, has_groups: bool):
    """Adapter placeholders (all None) matching the stack structure."""
    q, pattern, rem = cfg.stack_plan()
    g = {str(i): None for i in range(len(pattern))} if has_groups else None
    # scan requires xs leaves; None per block is a valid (empty) pytree node
    groups = g
    tail = tuple(None for _ in rem)
    return groups, tail


def forward_hidden(cfg: ModelConfig, base: dict, adapter: dict, batch: dict,
                   *, attn_impl: str | None = None,
                   use_rwkv_kernel: bool = False):
    """Embeddings → stack → final norm.  Returns (hidden (B,S',D), aux).
    ``attn_impl=None`` defers to ``cfg.attn_impl`` (attention.select_impl)."""
    tokens = batch["tokens"]
    x = layers.batch_hint(layers.embed(tokens, base["embed"]))
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1], dtype=jnp.int32),
                                     tokens.shape)
    if cfg.pos_type == "learned":
        pos_idx = positions if positions.ndim == 2 else positions[..., 0]
        x = x + jnp.take(base["pos_embed"], pos_idx, axis=0)
    n_prefix = 0
    if cfg.vision_patches and "vision" in batch:
        x = jnp.concatenate([batch["vision"].astype(x.dtype), x], axis=1)
        n_prefix = batch["vision"].shape[1]
        # positions for the fused sequence must already cover P+S
    enc_out = None
    if cfg.enc_dec:
        enc_out = encode(cfg, base, batch["frames"])
    lead_aux = jnp.zeros((), jnp.float32)
    if cfg.n_dense_layers:
        x, lead_aux = transformer.run_lead(cfg, base["lead"],
                                           adapter.get("lead"), x, positions,
                                           attn_impl=attn_impl)
    x, aux = transformer.run_stack(
        cfg, base["groups"], base["tail"], adapter["groups"], adapter["tail"],
        x, positions, enc_out=enc_out, causal=True, attn_impl=attn_impl,
        use_rwkv_kernel=use_rwkv_kernel)
    aux = aux + lead_aux
    x = layers.norm(x, base["final_norm"], cfg.norm_type)
    return layers.batch_hint(x), aux, n_prefix


def forward(cfg: ModelConfig, base: dict, adapter: dict, batch: dict,
            pad_vocab: bool = False, **kw) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (logits f32 over the TEXT positions, aux loss).  Shape
    (B,S,padded_vocab) with -inf pad logits when ``pad_vocab`` (the
    distributed path — keeps the vocab dim shardable), else (B,S,vocab)."""
    x, aux, n_prefix = forward_hidden(cfg, base, adapter, batch, **kw)
    if n_prefix:
        x = x[:, n_prefix:]
    logits = layers.unembed(x, head_table(cfg, base), cfg.vocab_size)
    if not pad_vocab and cfg.padded_vocab != cfg.vocab_size:
        logits = logits[..., :cfg.vocab_size]
    return logits, aux


_CE_CHUNK = 512
_CE_CHUNK_THRESHOLD = 2 ** 28   # S·V above this → chunked loss


def _ce_stats(cfg, hidden, table, labels):
    """(Σ nll·w, Σ correct·w, Σ w) for one hidden chunk — logits transient."""
    logits = layers.unembed(hidden, table, cfg.vocab_size)     # (B, s, Vp)
    weights = (labels >= 0).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None],
                               axis=-1)[..., 0]
    correct = (jnp.argmax(logits, -1) == labels) * weights
    return (jnp.sum(nll * weights), jnp.sum(correct), jnp.sum(weights))


def loss_fn(cfg: ModelConfig, adapter: dict, base: dict, batch: dict,
            **kw) -> tuple[jnp.ndarray, dict]:
    """Causal-LM cross entropy over labels >= 0.  adapter-first so that
    ``jax.grad`` differentiates only the tri-LoRA parameters.

    For large S·V the loss runs over sequence chunks (lax.map + remat) so
    the (B, S, V) logits tensor never materializes."""
    hidden, aux, n_prefix = forward_hidden(cfg, base, adapter, batch, **kw)
    if n_prefix:
        hidden = hidden[:, n_prefix:]
    labels = batch["labels"]
    table = head_table(cfg, base)
    s = hidden.shape[1]
    if s * cfg.padded_vocab > _CE_CHUNK_THRESHOLD and s % _CE_CHUNK == 0:
        n = s // _CE_CHUNK
        h_c = hidden.reshape(hidden.shape[0], n, _CE_CHUNK, -1).swapaxes(0, 1)
        l_c = labels.reshape(labels.shape[0], n, _CE_CHUNK).swapaxes(0, 1)
        stats = jax.lax.map(
            jax.checkpoint(lambda hl: _ce_stats(cfg, hl[0], table, hl[1])),
            (h_c, l_c))
        nll_sum, corr_sum, w_sum = (jnp.sum(t) for t in stats)
    else:
        nll_sum, corr_sum, w_sum = _ce_stats(cfg, hidden, table, labels)
    denom = jnp.maximum(w_sum, 1.0)
    ce = nll_sum / denom
    loss = ce + cfg.router_aux_weight * aux
    return loss, {"ce": ce, "aux": aux, "acc": corr_sum / denom}


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def init_decode_cache(cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    """The decode cache, built by one compiled program: each stacked ring is
    written straight into its output, never stacked from per-layer ones."""
    g, t = transformer.init_stack_cache(cfg, batch, seq_len,
                                        cross=cfg.enc_dec)
    out = {"groups": g, "tail": t}
    if cfg.n_dense_layers:
        out["lead"] = transformer.init_lead_cache(cfg, batch, seq_len)
    return out


def decode_step(cfg: ModelConfig, base: dict, adapter: dict, cache: dict,
                batch: dict, pad_vocab: bool = False,
                adapter_rows=None, ring_row=None) -> tuple[jnp.ndarray, dict]:
    """S new tokens a row against the cache.  Returns (logits (B,S,V), new
    cache).  S = 1 is a decode step; S > 1 a prompt chunk whose tokens go
    into ring rows ``ring_row + [0, B)`` at ``positions`` (-1: padding,
    written nowhere) and attend causally (DESIGN.md §18).
    ``pad_vocab`` keeps the padded (shardable) vocab dim — distributed path.
    ``adapter_rows`` (B,) int32 switches ``adapter`` to a stacked bank
    (``AdapterBank.decode_tree()``): each batch row applies its own adapter
    row, and cache ``idx`` leaves must be per-row (B,) vectors (ragged
    decode, DESIGN.md §15)."""
    token = batch["token"]
    positions = batch["positions"]
    x = layers.batch_hint(layers.embed(token, base["embed"]))
    if cfg.pos_type == "learned":
        pos_idx = positions if positions.ndim == 2 else positions[..., 0]
        x = x + jnp.take(base["pos_embed"], pos_idx, axis=0)
    new = {}
    if cfg.n_dense_layers:
        x, new["lead"] = transformer.run_lead_decode(
            cfg, base["lead"], adapter.get("lead"), cache["lead"], x,
            positions, adapter_rows=adapter_rows, ring_row=ring_row)
    x, new["groups"], new["tail"] = transformer.run_stack_decode(
        cfg, base["groups"], base["tail"], adapter["groups"], adapter["tail"],
        cache["groups"], cache["tail"], x, positions,
        adapter_rows=adapter_rows, ring_row=ring_row)
    with jax.named_scope("logits"):
        x = layers.norm(x, base["final_norm"], cfg.norm_type)
        logits = layers.unembed(x, head_table(cfg, base), cfg.vocab_size)
        if not pad_vocab and cfg.padded_vocab != cfg.vocab_size:
            logits = logits[..., :cfg.vocab_size]
    return logits, new
