"""Operation counts of the benchmark's configurations, from their sizes.

Parameter arithmetic follows ``benchmarks/analytic.py`` (dense GQA
attention with optional q/k/v bias, a two- or three-matrix MLP, one tied
vocabulary table).  A FLOP is a multiply or an add; a matmul of (m, k) by
(k, n) is 2 m k n.  Counted: the matmuls of the frozen base, the tied
unembedding, causal attention (QK^T and PV over the keys each query may
see) and the tri-LoRA adapters.  Not counted: norms, rotary embeddings,
softmax and other elementwise work.
"""
from __future__ import annotations


def attn_matmul_params(c: dict) -> int:
    d, h, k, hd = (c["hidden_size"], c["num_attention_heads"],
                   c["num_key_value_heads"], c["head_dim"])
    return d * h * hd + 2 * d * k * hd + h * hd * d


def mlp_params(c: dict) -> int:
    n = 3 if c["mlp"] == "swiglu" else 2
    return n * c["hidden_size"] * c["intermediate_size"]


def layer_matmul_params(c: dict) -> int:
    """Weights one token multiplies through in one layer."""
    return attn_matmul_params(c) + mlp_params(c)


def adapter_fwd_flops(c: dict) -> int:
    """One token through every adapter of one layer: (x A) C B."""
    d, h, k, hd, r = (c["hidden_size"], c["num_attention_heads"],
                      c["num_key_value_heads"], c["head_dim"], c["lora_rank"])
    shapes = {"wq": (d, h * hd), "wk": (d, k * hd), "wv": (d, k * hd),
              "wo": (h * hd, d)}
    return sum(2 * (din * r + r * r + r * dout)
               for din, dout in (shapes[t] for t in c["lora_targets"]))


def attn_fwd_flops(c: dict, keys: float) -> float:
    """QK^T and PV of one query over ``keys`` keys, all heads."""
    return 4.0 * c["num_attention_heads"] * c["head_dim"] * keys


def unembed_fwd_flops(c: dict) -> int:
    return 2 * c["vocab_size"] * c["hidden_size"]


def serve_request_flops(c: dict, prompt: int, gen: int) -> float:
    """Forward work a served request needs: its prompt and all but its last
    generated token pass the stack once, each attending over the positions
    up to its own; logits are needed where a token is emitted."""
    fed = prompt + gen - 1
    per_layer = (2 * layer_matmul_params(c) + adapter_fwd_flops(c))
    keys = fed * (fed + 1) / 2.0          # token t sees t + 1 positions
    return (c["num_hidden_layers"] * (fed * per_layer + attn_fwd_flops(c, keys))
            + gen * unembed_fwd_flops(c))
