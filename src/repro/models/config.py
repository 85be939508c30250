"""Model configuration system.

Every assigned architecture is expressed as a :class:`ModelConfig` — a frozen,
hashable dataclass so it can be closed over by ``jax.jit`` as a static value.

The layer stack is described by ``layer_pattern``: a repeating tuple of block
kinds.  ``n_layers = q * len(pattern) + rem`` — the stack is ``q`` scanned
repetitions of the pattern followed by ``rem`` unrolled leading-pattern
layers.  Kinds:

- ``attn``   : full-attention block (+ dense or MoE MLP)
- ``swa``    : sliding-window attention block (``window`` controls size)
- ``rwkv6``  : RWKV-6 "Finch" time-mix + channel-mix (attention-free)
- ``rglru``  : RG-LRU recurrent block (RecurrentGemma)
- ``mla``    : multi-head latent attention block (DeepSeek-V2), whose decode
               cache is one ring of compressed latents and shared rotary keys

``n_dense_layers`` leading layers (DeepSeek's ``first_k_dense_replace``) run
unrolled before the scanned groups, with a dense MLP of width ``d_ff`` even
in an MoE model; the pattern then tiles the remaining layers.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

BlockKind = str  # attn | swa | rwkv6 | rglru | mla


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                 # 0 -> d_model // n_heads
    source: str = ""                  # citation for the config

    # --- attention details -------------------------------------------------
    attn_bias: bool = False           # bias on q,k,v projections (qwen2.5)
    qk_norm: bool = False             # per-head RMSNorm on q,k (qwen3)
    rope_theta: float = 1e4
    pos_type: str = "rope"            # rope | mrope | learned | none
    mrope_sections: Tuple[int, ...] = (16, 24, 24)   # qwen2-vl split of hd/2
    window: int = 0                   # sliding-window size for `swa` blocks
    local_window: int = 2048          # window for hybrid local-attn blocks
    layer_pattern: Tuple[BlockKind, ...] = ("attn",)
    attn_impl: str = "auto"           # attention backend (attention.IMPLS);
    #                                   resolved per call via select_impl()

    # --- multi-head latent attention (``mla`` blocks) ----------------------
    kv_lora_rank: int = 0             # width of the compressed KV latent
    qk_nope_dim: int = 0              # per-head query/key width without rope
    qk_rope_dim: int = 0              # rotary width, one key shared by heads
    v_head_dim: int = 0               # per-head value width
    # YaRN scaling of the rotary frequencies (arXiv:2309.00071); factor 0
    # leaves them unscaled
    yarn_factor: float = 0.0
    yarn_original_max: int = 4096     # original_max_position_embeddings
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    # --- output head -------------------------------------------------------
    tie_embeddings: bool = True       # unembed through the embedding table;
    #                                   False gives a separate ``lm_head``

    # --- MLP / norm --------------------------------------------------------
    mlp_type: str = "swiglu"          # swiglu | gelu
    norm_type: str = "rmsnorm"        # rmsnorm | layernorm

    # --- MoE ---------------------------------------------------------------
    n_experts: int = 0                # 0 -> dense MLP
    top_k: int = 0
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    moe_d_ff: int = 0                 # routed expert width (0 -> d_ff)
    shared_d_ff: int = 0              # width of the shared experts (0: none)
    norm_topk: bool = True            # renormalise the top-k gate weights
    experts_held: int = 0             # experts [0, held) live here (0: all)
    n_dense_layers: int = 0           # leading layers with a dense MLP

    # --- recurrent (rwkv6 / rglru) ----------------------------------------
    rnn_width: int = 0                # 0 -> d_model
    conv1d_width: int = 4             # RG-LRU temporal conv width

    # --- encoder-decoder (whisper) -----------------------------------------
    enc_dec: bool = False
    n_enc_layers: int = 0
    enc_frames: int = 1500            # stub frontend output length
    max_target_positions: int = 4096  # learned-pos table size

    # --- multimodal stubs --------------------------------------------------
    vision_patches: int = 0           # VLM: # of precomputed patch embeddings

    # --- tri-LoRA ----------------------------------------------------------
    lora_rank: int = 8
    lora_alpha: float = 16.0
    lora_targets: Tuple[str, ...] = ("wq", "wk", "wv", "wo")
    lora_mlp: bool = False            # also adapt MLP in/out projections

    # --- numerics ----------------------------------------------------------
    param_dtype: str = "bfloat16"
    remat: bool = True

    # ------------------------------------------------------------------ api
    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to 256 so the logits/embedding shard over any
        mesh axis (e.g. whisper's 51865 → 51968); pad logits are masked to
        -inf before softmax, so semantics are exact."""
        return -(-self.vocab_size // 256) * 256

    @property
    def dtype(self):
        return _DTYPES[self.param_dtype]

    @property
    def rnn_d(self) -> int:
        return self.rnn_width or self.d_model

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def n_held(self) -> int:
        """Routed experts this device holds: experts [0, n_held)."""
        return self.experts_held or self.n_experts

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def lead_kinds(self) -> tuple[BlockKind, ...]:
        """Kinds of the unrolled leading dense layers."""
        return self.layer_pattern[:1] * self.n_dense_layers

    def stack_plan(self) -> tuple[int, tuple[BlockKind, ...], tuple[BlockKind, ...]]:
        """Return (n_scan_groups, pattern, remainder_kinds) of the layers
        after the leading dense ones."""
        p = len(self.layer_pattern)
        q, rem = divmod(self.n_layers - self.n_dense_layers, p)
        return q, self.layer_pattern, self.layer_pattern[:rem]

    def kinds(self) -> tuple[BlockKind, ...]:
        """Flat per-layer kind list (length n_layers)."""
        q, pat, rem = self.stack_plan()
        return self.lead_kinds + pat * q + rem

    def with_overrides(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self, **kw) -> "ModelConfig":
        """Smoke-test variant of the same family: tiny but structurally equal."""
        pat = self.layer_pattern
        base = dict(
            n_layers=max(2, len(pat)) + self.n_dense_layers,
            d_model=256,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads < self.n_heads else 4,
            head_dim=64,
            d_ff=512,
            vocab_size=512,
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            experts_held=(max(1, self.experts_held * min(self.n_experts, 4)
                              // self.n_experts)
                          if self.experts_held else 0),
            n_enc_layers=2 if self.enc_dec else 0,
            enc_frames=16 if self.enc_dec else self.enc_frames,
            vision_patches=16 if self.vision_patches else 0,
            window=min(self.window, 64) if self.window else 0,
            local_window=64,
            rnn_width=256 if self.rnn_width or self.family in ("ssm", "hybrid") else 0,
            max_target_positions=256,
            lora_rank=4,
            param_dtype="float32",
            name=self.name + "-reduced",
        )
        if self.kv_lora_rank:
            # latent + rotary key fill exactly one 128-lane ring row
            base.update(kv_lora_rank=96, qk_rope_dim=32, qk_nope_dim=64,
                        v_head_dim=64)
        if self.moe_d_ff:
            base["moe_d_ff"] = 128
        if self.shared_d_ff:
            base["shared_d_ff"] = 256
        if self.pos_type == "mrope":
            half = base["head_dim"] // 2
            hw = 3 * half // 8
            base["mrope_sections"] = (half - 2 * hw, hw, hw)
        base.update(kw)
        return dataclasses.replace(self, **base)


# ----------------------------------------------------------------------------
# Registry
# ----------------------------------------------------------------------------
_REGISTRY: dict[str, ModelConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    if cfg.name in _REGISTRY:
        raise ValueError(f"duplicate config {cfg.name}")
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    # importing repro.configs populates the registry
    import repro.configs  # noqa: F401
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_configs() -> list[str]:
    import repro.configs  # noqa: F401
    return sorted(_REGISTRY)
