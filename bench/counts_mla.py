"""Operation counts of the DeepSeek-V2 configuration, from its sizes.

A FLOP is a multiply or an add; a matmul of (m, k) by (k, n) is 2 m k n.
Counted per token and layer: the MLA projections (Wq, Wkv_a, Wkv_b, Wo;
with the latent ring, Wkv_b's work is the query's and the output's
absorption, the same count), attention over the token's context in the
latent space the ring holds (scores against [c | k_pe], the sum of p c),
the router, this chip's held experts at ``top_k * held / routed`` of an
expert each (what a token routed over all experts costs here, on
average), the shared experts, the leading layers' dense MLP, the tri-LoRA
adapters, and the unembedding where a token is emitted.  Not
counted: norms, rotary embeddings, softmax and other elementwise work.
"""
from __future__ import annotations


def _experts(c: dict) -> tuple[int, int]:
    held = c["n_routed_experts"]
    return c.get("published", {}).get("n_routed_experts", held), held


def mla_matmul_params(c: dict) -> int:
    d, h, r = (c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"])
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    return (d * h * (nope + rope) + d * (r + rope) + r * h * (nope + vd)
            + h * vd * d)


def swiglu_params(c: dict, width: int) -> int:
    return 3 * c["hidden_size"] * width


def moe_matmul_params(c: dict) -> float:
    """Router, the held experts' expected share, the shared experts."""
    routed, held = _experts(c)
    f = c["moe_intermediate_size"]
    return (c["hidden_size"] * routed
            + c["num_experts_per_tok"] * held / routed * swiglu_params(c, f)
            + swiglu_params(c, c["n_shared_experts"] * f))


def adapter_fwd_flops(c: dict) -> int:
    """One token through every adapter of one layer: (x A) C B."""
    d, h, r = (c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"])
    rope, lr = c["qk_rope_head_dim"], c["lora_rank"]
    shapes = {"wq": (d, h * (c["qk_nope_head_dim"] + rope)),
              "wkv_a": (d, r + rope), "wo": (h * c["v_head_dim"], d)}
    return sum(2 * (din * lr + lr * lr + lr * dout)
               for din, dout in (shapes[t] for t in c["lora_targets"]))


def attn_fwd_flops(c: dict, keys: float) -> float:
    """One query over ``keys`` latent ring rows, all heads."""
    h, r = c["num_attention_heads"], c["kv_lora_rank"]
    return 2.0 * h * (2 * r + c["qk_rope_head_dim"]) * keys


def unembed_fwd_flops(c: dict) -> int:
    return 2 * c["vocab_size"] * c["hidden_size"]


def token_matmul_flops(c: dict) -> float:
    """One token through every layer's weights and adapters."""
    n, lead = c["num_hidden_layers"], c["first_k_dense_replace"]
    return (n * (2 * mla_matmul_params(c) + adapter_fwd_flops(c))
            + lead * 2 * swiglu_params(c, c["intermediate_size"])
            + (n - lead) * 2 * moe_matmul_params(c))


def serve_request_flops(c: dict, prompt: int, gen: int) -> float:
    """Forward work a served request needs: its prompt and all but its last
    generated token pass the stack once, each attending over the positions
    up to its own; logits are needed where a token is emitted."""
    fed = prompt + gen - 1
    keys = fed * (fed + 1) / 2.0          # token t sees t + 1 positions
    return (fed * token_matmul_flops(c)
            + c["num_hidden_layers"] * attn_fwd_flops(c, keys)
            + gen * unembed_fwd_flops(c))
